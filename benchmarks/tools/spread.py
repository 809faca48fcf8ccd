"""Spread of a cell's runs, as the builder's instructions define it.

    python benchmarks/tools/spread.py lines.jsonl [runs per set]

`lines.jsonl` holds one result line of `run.py` per run, in the order
run (set 1, then set 2 with the same seeds). For every metric: each set's
median and spread (distance between the first and third quartile of
`statistics.quantiles(values, n=4)` as a share of the median), the wider
of the two, the bound that five times it would give, and how far the
second set's median lies from the first's.
"""

import json
import statistics
import sys


def spread(values):
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)


def main() -> int:
    with open(sys.argv[1], "r", encoding="utf-8") as f:
        lines = [json.loads(row) for row in f if row.strip().startswith("{")]
    per_set = int(sys.argv[2]) if len(sys.argv) > 2 else len(lines) // 2
    sets = [lines[:per_set], lines[per_set:2 * per_set]]
    print(f"{len(lines)} runs, sets of {per_set}; correct: "
          f"{[line['correct'] for line in lines]}; failed: "
          f"{[line['failed'] for line in lines]}")
    for name in lines[0]["metrics"]:
        vals = [[line["metrics"][name]["value"] for line in s if name in line["metrics"]]
                for s in sets]
        if name == "setup_s":  # the first run of a checkout compiles
            vals[0] = vals[0][1:]
        row = [f"{name}:"]
        meds = []
        for v in vals:
            if len(v) >= 2:
                meds.append(statistics.median(v))
                row.append(f"median {meds[-1]:.6g} spread {spread(v):.4f} "
                           f"[{min(v):.6g} .. {max(v):.6g}]")
        if len(meds) == 2:
            wider = max(spread(v) for v in vals)
            row.append(f"wider {wider:.4f} -> bound {5 * wider:.3f}; "
                       f"set2/set1 median {meds[1] / meds[0] - 1:+.4f}")
        print("  ".join(row))
    return 0


if __name__ == "__main__":
    sys.exit(main())
