"""Trace reduction: busy time as the union of op intervals, kernel time
by name, idle gaps owned by the harness span that covers them most. Run on
a synthetic trace and on a small trace recorded on a TPU v5e."""
import json
from pathlib import Path

import pytest

from chipbench import trace

FIXTURE = Path(__file__).resolve().parent / "data" / "v5e_trace.json"


def test_synthetic_reduction():
    ev = {"devices": {"/device:TPU:0": [
        ["fusion.1", 0, 100], ["_vpu_kernel", 50, 100],     # overlap
        ["_paged_kernel", 300, 50], ["copy.3", 1000, 10]]},
        "spans": [["scheduler.poll", 0, 400], ["client.sleep", 400, 700]]}
    r = trace.reduce(ev, window_s=2e-6)
    assert r["busy_s"] == pytest.approx(210e-9)
    assert r["kernel_s"] == {"gemm": pytest.approx(100e-9),
                             "attn": pytest.approx(50e-9)}
    assert r["idle_gaps"][0] == ["client.sleep", pytest.approx(650e-9)]
    assert r["idle_gaps"][1] == ["scheduler.poll", pytest.approx(150e-9)]
    assert r["top_ops"][0] == ["fusion.1", pytest.approx(100e-9)] or \
        r["top_ops"][0][1] == pytest.approx(100e-9)


def test_no_device_plane_reads_nothing():
    assert trace.reduce({"devices": {}, "spans": []}, 1.0) is None


@pytest.mark.skipif(not FIXTURE.exists(), reason="no recorded trace")
def test_recorded_v5e_trace():
    ev = json.loads(FIXTURE.read_text())
    window = ev.pop("window_s")
    r = trace.reduce(ev, window)
    assert 0 < r["busy_s"] <= window
    assert r["kernel_s"]["gemm"] > 0 and r["kernel_s"]["attn"] > 0
    assert r["kernel_s"]["gemm"] + r["kernel_s"]["attn"] <= r["busy_s"]
    gaps = [g for _, g in r["idle_gaps"]]
    assert gaps == sorted(gaps, reverse=True) and len(gaps) <= trace.TOP
    assert {o for o, _ in r["idle_gaps"]} <= set(trace.HARNESS_SPANS) | \
        {"untraced host work"}
    assert len(r["top_ops"]) == trace.TOP
