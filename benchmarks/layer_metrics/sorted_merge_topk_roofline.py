"""Share of the HBM roofline that the sorted-merge top-k launches of the
traced window reached: the least bytes they had to move (the benchmark's
function, from the launch shapes the trace's sort ops name) / the chip's
peak bytes per second / the seconds its programs ran on the device."""

from esbench import roofline


def read(facts):
    prefix = "trace.op_count."
    op_counts = {key[len(prefix):]: int(n) for key, n in facts.items()
                 if key.startswith(prefix)}
    if "trace.module_s" not in facts or "device.peak_hbm_bytes_per_s" not in facts:
        return None
    return roofline.roofline_share_pct(
        op_counts, facts["trace.module_s"], int(facts["request.size"]),
        facts["device.peak_hbm_bytes_per_s"])
