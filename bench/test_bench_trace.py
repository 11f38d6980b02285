"""The reduction from a profiler trace to the per-layer metrics, and the
operation and byte counts behind the roofline and MFU shares: on a
hand-made trace whose answers are known, and on a short trace recorded
on a TPU v5e (``testdata/``), through the committed metric readers."""
from __future__ import annotations

from pathlib import Path

import pytest

from bench import flops, harness, peaks, tracefile

TESTDATA = Path(__file__).resolve().parent / "testdata"
DIMS = {"d": 1024, "H": 16, "K": 8, "hd": 128, "ff": 3072, "V": 151936,
        "L": 28}


def test_union_busy_steps_and_gaps():
    ev = {"host_spans": [["bench.window", 0.0, 100.0],
                         ["bench.step.decode.0", 5.0, 30.0],
                         ["bench.step.mixed.1", 45.0, 25.0],
                         ["bench.idle", 70.0, 30.0]],
          "device_ops": {"/device:TPU:0": [["fusion.1", 10.0, 10.0],
                                           ["paged_attention.3", 15.0, 15.0],
                                           ["fusion.2", 50.0, 10.0],
                                           ["fusion.9", 120.0, 5.0]]}}
    tr = tracefile.Trace(ev)
    assert tr.window_s == pytest.approx(100e-9)
    assert tr.busy_s() == pytest.approx(30e-9)
    steps = tr.steps()
    assert [(s["kind"], s["index"], s["device_ns"]) for s in steps] == [
        ("decode", 0, 20.0), ("mixed", 1, 10.0)]
    assert tr.op_time_in("paged_attention", 5.0, 35.0) == 15.0
    assert tr.idle_gaps() == [["bench.idle", pytest.approx(40e-9)],
                              ["host:outside-harness-spans",
                               pytest.approx(20e-9)],
                              ["bench.step.decode", pytest.approx(10e-9)]]
    assert tr.top_ops(1) == [["paged_attention.3", pytest.approx(15e-9)]]


def test_op_names_and_containers():
    name = tracefile.op_name("%while.4 = (s32[], bf16[2]) while(%t), body=b")
    assert name == "while.4" and tracefile.is_container(name)
    assert not tracefile.is_container(tracefile.op_name(
        "%paged_attention.8 = bf16[32,8,64,128] custom-call(%a)"))


def test_flops_and_bytes_by_hand():
    # one decode row at position 99 (100 tokens of context), one prefill
    # chunk of 3 tokens at positions 16..18, emitting
    rows = [(1, 99, True), (3, 16, True), (0, 5, False)]
    H, K, hd = 16, 8, 128
    ctx = 100 + (17 + 18 + 19)
    assert flops.paged_attention_flops(DIMS, rows) == 4 * H * hd * ctx
    ps, it = 16, 2
    pages = 7 + 2                       # ceil(100/16) + ceil(19/16)
    assert flops.paged_attention_bytes(DIMS, rows, ps, it) == (
        pages * ps * K * hd * 2 * it + 4 * H * hd * 2 * it)
    d, ff, V, L = 1024, 3072, 151936, 28
    per_tok = 2 * (d * (H + 2 * K) * hd + H * hd * d + 3 * d * ff) * L
    assert flops.step_flops(DIMS, rows) == (
        4 * per_tok + L * 4 * H * hd * ctx + 2 * 2 * d * V)


def test_peaks_unknown_kind_is_an_error():
    assert peaks.peaks("TPU v5 lite")["hbm_bw"] == 819e9
    with pytest.raises(ValueError):
        peaks.peaks("TPU v9 imaginary")


@pytest.mark.parametrize("path", sorted(TESTDATA.glob("*.json.gz")),
                         ids=lambda p: p.name)
def test_recorded_chip_trace(path):
    """Every trace metric reads inside its range on a real TPU trace."""
    ev = tracefile.load(path)
    tr = tracefile.Trace(ev)
    assert 0 < tr.busy_s() <= tr.window_s
    steps = tr.steps()
    assert steps and all(0 < s["device_ns"] <= s["host_ns"] for s in steps)
    meta = ev["meta"]
    view = harness.RunView(
        records=[], seconds=0.0, counters={}, trace=tr,
        steps=[harness.Step(i, k, k, 0.0, 0.0, [tuple(r) for r in rows])
               for i, k, rows in ev["steps"]],
        dims=meta["dims"], page_size=meta["page_size"],
        kv_itemsize=meta["kv_itemsize"], peaks=peaks.peaks(meta["kind"]),
        notes=[])
    for name, lo, hi in (("paged_attention_roofline", 0.0, 100.0),
                         ("step_mfu", 0.0, 100.0),
                         ("device_idle_share", 0.0, 100.0)):
        v = harness.metric_reader(name)(view)
        assert v is not None and lo < v < hi, (name, v)
    for name in ("decode_step_ms", "mixed_step_ms"):
        v = harness.metric_reader(name)(view)
        assert v is None or v > 0
