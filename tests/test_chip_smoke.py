"""chip_smoke.py rehearsed on the CPU mesh: `run()` passes at toy size,
fails when one launch is answered by the planner behind an HTTP 200, and
`main()` refuses a backend that is not a TPU."""

import numpy as np
import pytest

import chip_smoke
from elasticsearch_tpu.search import tpu_service

TOY = dict(seed=21, small_docs=1000, append_docs=200, n_or=8, n_and=4,
           n_small=4, n_append=3, k_large=100)


def _toy_run(tmp_path):
    return chip_smoke.run(3000, 2, data_path=str(tmp_path / "smoke"), **TOY)


@pytest.mark.streaming  # hard per-test timeout: the run serves over HTTP
def test_run_passes_at_toy_size(tmp_path):
    facts = _toy_run(tmp_path)
    assert facts["requests_sent"] == 8 + 4 + 4 + 4 + 3
    assert facts["devices"]["platform"] == "cpu"
    assert facts["devices"]["mesh_devices"] == 8
    # every device of the virtual mesh holds its part of the pack
    assert len(facts["pack_bytes_per_device"]) == 8
    assert all(n > 0 for n in facts["pack_bytes_per_device"].values())
    # toy segments are under 65,536 docs: both packs are compressed here
    # (main() pins raw/compressed for the real sizes)
    assert facts["large"]["pack"]["compressed"]
    assert set(facts["small"]["launches"]) <= {"exact,compressed",
                                               "exact,compressed_exact"}
    assert facts["append"]["deltas"]["appends"] >= 1
    assert facts["append"]["queries_returning_appended"] == 3
    assert facts["large"]["signatures"] > 0


@pytest.mark.streaming
def test_run_fails_when_one_launch_falls_back(tmp_path, monkeypatch):
    """The planner answers a failed launch with HTTP 200 and the right
    hits; only the stats say the device did not serve it."""
    fired = []

    def fail_once(mesh):
        if not fired:
            fired.append(mesh)
            raise RuntimeError("injected launch failure")

    monkeypatch.setattr(tpu_service, "DISPATCH_FAULT_HOOKS", [fail_once])
    with pytest.raises(chip_smoke.SmokeFailure,
                       match=r"not served by the kernel path.*fallback="):
        _toy_run(tmp_path)
    assert fired


def test_main_refuses_a_cpu_backend(capsys):
    assert chip_smoke.main([]) != 0
    assert capsys.readouterr().out == ""


def test_main_prints_one_result_line_with_exactly_ok_and_device(
        tmp_path, monkeypatch, capsys):
    """The driver reads the last stdout line and takes nothing but
    {"ok", "device": {"platform", "kind", "count"}}; the set-up facts go
    to stderr and a file. `run` is stubbed and jax made to report a TPU:
    only main()'s own output is under test."""
    import json
    from types import SimpleNamespace

    import jax

    fake = SimpleNamespace(platform="tpu", device_kind="TPU v5 lite", id=0,
                           client=SimpleNamespace(platform_version="x"))
    monkeypatch.setattr(jax, "devices", lambda *a: [fake])
    monkeypatch.setattr(chip_smoke, "run", lambda *a, **kw: {
        "large": {"pack": {"compressed": False}},
        "small": {"pack": {"compressed": True}}})
    monkeypatch.chdir(tmp_path)
    assert chip_smoke.main([]) == 0
    out, err = capsys.readouterr()
    lines = out.splitlines()
    assert len(lines) == 1
    assert json.loads(lines[0]) == {"ok": True, "device": {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 1}}
    facts = json.loads((tmp_path / "chiprun_out"
                        / "chip_smoke_facts.json").read_text())
    assert facts["reduced"] == [{"what": "docs", "deployment": 1105920,
                                 "run": 552960}]
    assert "facts: " in err


def _response(ids, scores, total, relation="eq"):
    return {"timed_out": False, "_shards": {"failed": 0},
            "hits": {"total": {"value": total, "relation": relation},
                     "hits": [{"_id": i, "_score": s}
                              for i, s in zip(ids, scores)]}}


class TestCompareResponse:
    # reference: b and c one ulp-ish apart (a near-tie), the rest distinct
    REF_IDS = ["a", "b", "c", "d", "e"]
    REF = np.array([9.0, 5.0, 5.0 - 2e-6, 3.0, 1.0], dtype=np.float64)

    def _ref(self, total=5):
        return total, self.REF_IDS, self.REF

    def test_equal_passes(self):
        resp = _response(self.REF_IDS[:4], self.REF[:4].tolist(), 5)
        assert chip_smoke.compare_response(resp, self._ref(), 4, "t") == 0

    def test_swap_inside_a_near_tie_is_tolerated_and_counted(self):
        resp = _response(["a", "c", "b", "d"],
                         [9.0, 5.0, 5.0 - 2e-6, 3.0], 5)
        assert chip_smoke.compare_response(resp, self._ref(), 4, "t") == 2

    def test_tie_at_the_cut_may_bring_the_doc_past_k(self):
        # k=2: the reference list runs past k through the near-tie
        resp = _response(["a", "c"], [9.0, 5.0 - 2e-6], 5)
        assert chip_smoke.compare_response(resp, self._ref(), 2, "t") == 1

    def test_swap_outside_a_near_tie_fails(self):
        resp = _response(["a", "b", "d", "c"], self.REF[:4].tolist(), 5)
        with pytest.raises(chip_smoke.SmokeFailure, match="not a near-tie"):
            chip_smoke.compare_response(resp, self._ref(), 4, "t")

    def test_score_outside_tolerance_fails(self):
        resp = _response(self.REF_IDS[:4], [9.0, 5.0, 5.0, 3.001], 5)
        with pytest.raises(chip_smoke.SmokeFailure, match="score at rank 3"):
            chip_smoke.compare_response(resp, self._ref(), 4, "t")

    def test_exact_total_must_match_and_bound_must_hold(self):
        resp = _response(self.REF_IDS[:4], self.REF[:4].tolist(), 4)
        with pytest.raises(chip_smoke.SmokeFailure, match="hits.total"):
            chip_smoke.compare_response(resp, self._ref(), 4, "t")
        resp = _response(self.REF_IDS[:4], self.REF[:4].tolist(), 4, "gte")
        assert chip_smoke.compare_response(resp, self._ref(), 4, "t") == 0
        resp = _response(self.REF_IDS[:4], self.REF[:4].tolist(), 6, "gte")
        with pytest.raises(chip_smoke.SmokeFailure, match="hits.total"):
            chip_smoke.compare_response(resp, self._ref(), 4, "t")

    def test_missing_and_duplicate_hits_fail(self):
        resp = _response(self.REF_IDS[:3], self.REF[:3].tolist(), 5)
        with pytest.raises(chip_smoke.SmokeFailure, match="3 hits"):
            chip_smoke.compare_response(resp, self._ref(), 4, "t")
        resp = _response(["a", "b", "b", "d"], self.REF[:4].tolist(), 5)
        with pytest.raises(chip_smoke.SmokeFailure, match="duplicate"):
            chip_smoke.compare_response(resp, self._ref(), 4, "t")
