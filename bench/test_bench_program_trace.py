"""The reductions of ``program_trace``: each device operation's program
and model scope, and the engine phase of each idle stretch.  On a
hand-made trace whose answers are known; on the traces recorded on a TPU
v5e before the program named its parts (``*.bringup-trace``), which read
exactly what ``tracefile`` reads; on traces recorded on a TPU v5e with
the names (``*.program-trace``); and on a traced run on the CPU."""
from __future__ import annotations

import json
import shutil
import tempfile
from pathlib import Path

import pytest

from bench import harness, peaks, tracefile
from bench import program_trace as pt

TESTDATA = Path(__file__).resolve().parent / "testdata"
DEC, MIX, SAMPLE = "jit_serve_decode", "jit_serve_mixed", "jit_sample_batch"


def hand_made():
    """Two steps: a decode (6-34) and a mixed one (46-68) that admits
    with a prefix-cache lookup, in a window of 100."""
    return {
        "host_spans": [["bench.window", 0.0, 100.0],
                       ["bench.step.decode.0", 5.0, 30.0],
                       ["bench.step.mixed.1", 45.0, 25.0]],
        "program_spans": [
            ["serve.step", 6.0, 28.0], ["serve.admit", 6.0, 2.0],
            ["serve.plan", 8.0, 2.0], ["serve.inputs", 10.0, 2.0],
            ["serve.dispatch.decode", 12.0, 2.0],
            ["serve.sample", 14.0, 14.0], ["serve.commit", 28.0, 5.0],
            ["serve.step", 46.0, 22.0], ["serve.admit", 46.0, 4.0],
            ["serve.prefix_lookup", 47.0, 2.0], ["serve.plan", 50.0, 2.0],
            ["serve.inputs", 52.0, 2.0],
            ["serve.dispatch.mixed", 54.0, 1.0],
            ["serve.sample", 55.0, 10.0], ["serve.commit", 65.0, 3.0]],
        "device_ops": {"/device:TPU:0": [
            ["fusion.1", 13.0, 10.0], ["copy.2", 23.0, 4.0],
            ["argmax.3", 27.0, 1.0], ["fusion.4", 55.0, 5.0],
            ["dynamic-update-slice.5", 60.0, 3.0],
            ["paged_attention.7", 64.0, 1.0], ["copy.6", 120.0, 5.0]]},
        "op_labels": {"/device:TPU:0": [
            [DEC, "mlp"], [DEC, ""], [SAMPLE, None], [MIX, "attn_kernel"],
            [MIX, ""], [MIX, "attn_kernel"], [DEC, ""]]},
    }


def test_hand_made_programs_and_scopes():
    tr = pt.ProgramTrace(hand_made())
    assert tr.program_time() == {DEC: {"mlp": 10.0, "": 4.0},
                                 SAMPLE: {None: 1.0},
                                 MIX: {"attn_kernel": 6.0, "": 3.0}}
    assert tr.step_scopes() == {"mlp": 10.0, "plumbing": 7.0,
                                "attn_kernel": 6.0}
    assert tr.plumbing_share() == pytest.approx(100.0 * 7 / 23)


def test_hand_made_idle_by_phase():
    tr = pt.ProgramTrace(hand_made())
    idle = tr.step_idle()
    assert idle["per_step_ns"] == [13.0, 13.0]
    assert idle["by_phase_ns"] == {
        "serve.admit": 4.0, "serve.prefix_lookup": 2.0, "serve.plan": 4.0,
        "serve.inputs": 4.0, "serve.dispatch.decode": 1.0,
        "serve.dispatch.mixed": 1.0, "serve.sample": 1.0,
        "serve.commit": 8.0, "serve.step": 1.0}
    assert idle["outside_steps_ns"] == 50.0
    assert tr.step_host_idle_ms() == pytest.approx(13e-6)


def test_hand_made_gaps_carry_the_phase():
    """An idle gap's label is the harness's, followed by the innermost
    program span; tracefile's own label stays its prefix."""
    ev = hand_made()
    assert pt.ProgramTrace(ev).idle_gaps() == [
        ["host:outside-harness-spans", pytest.approx(35e-9)],
        ["host:outside-harness-spans", pytest.approx(27e-9)],
        ["bench.step.decode/serve.admit", pytest.approx(13e-9)],
        ["bench.step.mixed/serve.sample", pytest.approx(1e-9)]]
    assert [g[0] for g in tracefile.Trace(ev).idle_gaps()] == [
        "host:outside-harness-spans", "host:outside-harness-spans",
        "bench.step.decode", "bench.step.mixed"]


def test_without_names_reads_none():
    ev = hand_made()
    del ev["program_spans"], ev["op_labels"]
    tr = pt.ProgramTrace(ev)
    assert tr.program_time() == {}
    assert tr.plumbing_share() is None and tr.step_idle() is None
    assert tr.step_host_idle_ms() is None and tr.report() == []
    assert tr.idle_gaps() == tracefile.Trace(ev).idle_gaps()


def test_scope_names_from_hlo():
    text = "\n".join([
        "HloModule jit_serve_decode, is_scheduled=true",
        "%fused_computation (p: f32[2]) -> f32[2] {",
        '  ROOT %mul.1 = f32[2] multiply(%p, %p), metadata={op_name='
        '"jit(serve_decode)/while/body/closed_call/mlp/mul"}',
        "}",
        "ENTRY %main (a: f32[2]) -> f32[2] {",
        "  %a = f32[2]{0} parameter(0)",
        "  %fusion.3 = f32[2]{0} fusion(%a), kind=kLoop, calls=%fused, "
        'metadata={op_name="jit(serve_decode)/while/body/dynamic_slice" '
        "stack_frame_id=3}",
        "  ROOT %copy.4 = f32[2]{0} copy(%fusion.3)",
        "}"])
    names = pt.hlo_op_names([text])
    assert names == {DEC: {
        "mul.1": "jit(serve_decode)/while/body/closed_call/mlp/mul",
        "a": "", "fusion.3": "jit(serve_decode)/while/body/dynamic_slice",
        "copy.4": ""}}
    assert [pt.scope_of(n) for n in names[DEC].values()] == [
        "mlp", "", "", ""]
    assert pt.scope_of("jit(f)/kv_write/attn_kernel/x") == "attn_kernel"
    assert pt.module_name("jit_serve_mixed(41)") == MIX


def _view(ev, tr):
    meta = ev["meta"]
    return harness.RunView(
        records=[], seconds=0.0, counters={}, trace=tr,
        steps=[harness.Step(i, k, k, 0.0, 0.0, [tuple(r) for r in rows])
               for i, k, rows in ev["steps"]],
        dims=meta["dims"], page_size=meta["page_size"],
        kv_itemsize=meta["kv_itemsize"], peaks=peaks.peaks(meta["kind"]),
        notes=[])


BRINGUP = json.loads((TESTDATA / "bringup-trace-readings.json").read_text())


@pytest.mark.parametrize("cls", [tracefile.Trace, pt.ProgramTrace],
                         ids=["tracefile", "program_trace"])
@pytest.mark.parametrize("name", sorted(BRINGUP))
def test_bringup_traces_read_as_before(name, cls):
    """On the traces recorded before the program named its parts, every
    per-layer metric, busy time, top operations and idle gaps read what
    the benchmark read when they were recorded."""
    want = BRINGUP[name]
    ev = tracefile.load(TESTDATA / name)
    tr = cls(ev)
    view = _view(ev, tr)
    got = {m: harness.metric_reader(m)(view) for m in want["metrics"]}
    assert got == want["metrics"]
    assert view.notes == want["notes"]
    assert (tr.busy_s(), tr.window_s) == (want["busy_s"], want["window_s"])
    assert tr.top_ops() == want["device_ops"]
    assert tr.idle_gaps() == want["idle_gaps"]


# what the traces recorded on a TPU v5e with ``program_trace.py --save``
# read (the first 2 s of a 10 s traced window of each cell)
PROGRAM_TRACES = {
    "qwen3-0.6b.program-trace.json.gz": {
        "step_plumbing_share": 70.18306916843866,
        "step_host_idle_ms": 4.441266470588235},
    "yi-6b-l16.program-trace.json.gz": {
        "step_plumbing_share": 71.6465929081423,
        "step_host_idle_ms": 2.594951933333333},
}


@pytest.mark.parametrize("name", sorted(PROGRAM_TRACES))
def test_program_traces_recorded_on_chip(name):
    want = PROGRAM_TRACES[name]
    ev = tracefile.load(TESTDATA / name)
    tr = pt.ProgramTrace(ev)
    assert tr.plumbing_share() == want["step_plumbing_share"]
    assert tr.step_host_idle_ms() == want["step_host_idle_ms"]
    times = tr.program_time()
    busy = sum(ns for by in times.values() for ns in by.values())
    named = busy - sum(times.get(None, {}).values())
    assert named >= 0.95 * busy
    idle = tr.step_idle()
    total = sum(idle["per_step_ns"])
    assert total - idle["by_phase_ns"].get("serve.step", 0.0) >= 0.9 * total
    inside = [g for g in tr.idle_gaps() if g[0].startswith("bench.step.")]
    assert inside and all("/serve." in g[0] for g in inside)


@pytest.fixture(scope="module")
def cpu_run(tmp_path_factory):
    """A traced window of the smoke cell on the CPU, reduced both ways."""
    from bench.test_bench_run import SECONDS, smoke_root
    root = smoke_root(tmp_path_factory.mktemp("smoke"))
    b = harness.build(harness.load_cell("smoke.chat", root), 2**31 + 11,
                      SECONDS, require_tpu=False, log=lambda m: None)
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
    try:
        b.window(trace_dir).run()
        return (pt.extract(trace_dir, pt.step_op_names(b.eng)),
                tracefile.extract(trace_dir))
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)


def test_cpu_extract_adds_to_tracefile(cpu_run):
    """``extract`` returns what ``tracefile.extract`` does, and more."""
    ev, base = cpu_run
    assert {k: ev[k] for k in base} == base
    assert [len(v) for v in ev["op_labels"].values()] == [
        len(v) for v in ev["device_ops"].values()]
    assert {s[0] for s in ev["program_spans"]} >= {
        "serve.step", "serve.admit", "serve.plan", "serve.inputs",
        "serve.sample", "serve.commit", "serve.dispatch.mixed",
        "serve.dispatch.decode", "serve.prefix_lookup"}


def test_cpu_traced_run_reads_both_metrics(cpu_run):
    """On a real profiler trace (the CPU's stand-in for the device) the
    two reductions read, every operation has a program, and the engine's
    phases hold the idle time inside its steps."""
    tr = pt.ProgramTrace(cpu_run[0])
    share = tr.plumbing_share()
    assert share is not None and 0.0 < share < 100.0
    assert set(tr.step_scopes()) >= {"plumbing", "mlp", "attn_kernel",
                                     "attn_in", "head"}
    assert tr.step_host_idle_ms() > 0.0
    times = tr.program_time()
    assert None not in times and {DEC, MIX} <= set(times)
    idle = tr.step_idle()
    total = sum(idle["per_step_ns"])
    assert total - idle["by_phase_ns"].get("serve.step", 0.0) >= 0.9 * total
    inside = [g for g in tr.idle_gaps(50) if g[0].startswith("bench.step.")]
    assert inside and all("/serve." in g[0] for g in inside)
    assert len(tr.report()) == 3
