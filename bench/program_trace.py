"""The program's own names in a profiler trace: the program and model
scope of each device operation, and the engine phase the host was in
while the device sat idle (docs/SERVING.md#tracing).

    python3 bench/program_trace.py --workload <name> [--seed <n>]
        [--seconds <s>] [--save <file.json.gz>]

serves one traced window of a cell as a ``--trace 1`` run of
``run.py`` does (set-up and ramp, then at most 10 s traced), and prints
where the window's device time went by program and scope, the host
milliseconds per step, ``step_mfu``, the idle time inside the engine's
steps by phase, and the longest idle gaps, labelled.  No check against
the reference is made.  ``--save`` keeps the first 2 s of the window,
reduced, for the tests (``testdata/``).

``tracefile.extract`` keeps the harness's spans and each operation's
name.  :func:`extract` keeps the same, and also

- ``program_spans``: the engine's host spans (``serve.*``): one
  ``serve.step`` per tick, its phases nested inside it on one thread;
- ``op_labels``: for each device, one [program, scope] per operation of
  ``device_ops``, in the same order: its program (HLO module, e.g.
  ``jit_serve_decode``) and its model scope (``mlp``, ``kv_write``, ...;
  "" for an operation under none of them, the step's plumbing; None
  where nothing names its scope).  The module comes from the operation's
  ``hlo_module`` stat where the trace has one (the CPU's), else from the
  device's ``XLA Modules`` line (a TPU's); the scope from the ``op_name``
  that the engine's compiled step programs give the instruction
  (:func:`hlo_op_names`), as a TPU trace's operations carry no stat
  that names it.

A trace without these (``testdata/*.bringup-trace.json.gz``) still loads
as a :class:`ProgramTrace`; its reductions of this module read None and
its idle gaps carry the harness's labels alone.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
import sys
from collections import defaultdict
from typing import Dict, Iterable, List, Optional

if __name__ == "__main__":
    ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path[:] = [ROOT, os.path.join(ROOT, "src")] + sys.path[1:]

from bench import tracefile  # noqa: E402
from bench.tracefile import (  # noqa: E402
    CPU_DEVICE, CPU_XLA_LINE, DEVICE_LINE, PREFIX, Interval, is_container,
    op_name)

MODULE_LINE = "XLA Modules"
PROGRAM_PREFIX = "serve."
STEP_SPAN = "serve.step"
# the model's named scopes (models/*.py); the innermost one names an op
SCOPES = ("embed", "attn_in", "kv_write", "attn_kernel", "attn_out", "mlp",
          "norm", "head")
STEP_PROGRAMS = ("jit_serve_decode", "jit_serve_mixed", "jit_serve_verify")
GROUPS = (("step programs", STEP_PROGRAMS),
          ("serve_copy", ("jit_serve_copy",)),
          ("sampler", ("jit_sample_batch", "jit_verify_batch")))
SAVE_S = 2.0
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([^\s=]+) = (.*)$")
_OP_NAME = re.compile(r'metadata=\{[^}]*op_name="([^"]*)"')


def module_name(event_name: str) -> str:
    """The HLO module's name from an ``XLA Modules`` event's name, which
    may carry the program's id (``jit_serve_decode(12)``)."""
    return event_name.split("(", 1)[0]


def scope_of(op_name_path: str) -> str:
    """The innermost model scope in an HLO ``op_name``
    (``jit(serve_decode)/while/body/closed_call/mlp/dot_general`` reads
    ``mlp``), or "" where it names none."""
    for part in reversed(op_name_path.split("/")):
        if part in SCOPES:
            return part
    return ""


def hlo_op_names(texts: Iterable[str]) -> Dict[str, Dict[str, str]]:
    """{module: {instruction: op_name}} from compiled programs' HLO text
    (``Compiled.as_text()``); an instruction without metadata reads ""."""
    out: Dict[str, Dict[str, str]] = {}
    for text in texts:
        lines = text.splitlines()
        if not lines or not lines[0].startswith("HloModule "):
            continue
        names = out.setdefault(lines[0].split()[1].rstrip(","), {})
        for line in lines[1:]:
            m = _INSTR.match(line)
            if m:
                op = _OP_NAME.search(m.group(2))
                names[m.group(1)] = op.group(1) if op else ""
    return out


def step_op_names(eng) -> Dict[str, Dict[str, str]]:
    """:func:`hlo_op_names` of the engine's compiled step programs; empty
    for a program that does not expose them."""
    compiled = getattr(eng, "compiled_steps", None)
    return hlo_op_names(e.as_text() for e in compiled()) if compiled else {}


def _scope(program: Optional[str], name: str,
           op_names: Dict[str, Dict[str, str]]) -> Optional[str]:
    known = op_names.get(program or "", {}).get(name)
    return None if known is None else scope_of(known)


def _module_at(modules: List, t: float) -> Optional[str]:
    """The module whose ``XLA Modules`` interval holds time ``t``;
    ``modules`` is sorted [(start, end, name)]."""
    i = bisect.bisect_right(modules, (t, float("inf"), "")) - 1
    return modules[i][2] if i >= 0 and t < modules[i][1] else None


def extract(logdir: str,
            op_names: Optional[Dict[str, Dict[str, str]]] = None) -> Dict:
    """What ``tracefile.extract`` returns from the newest trace under
    ``logdir``, with ``op_labels`` and ``program_spans``; ``op_names``
    (:func:`hlo_op_names`) names scopes where the trace does not."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    op_names = op_names or {}
    pd = ProfileData.from_file(paths[-1])
    dev: Dict[str, List] = {}
    labels: Dict[str, List] = {}
    host: List = []
    prog: List = []
    cpu_ops: List = []
    cpu_labels: List = []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            modules = sorted(
                (float(e.start_ns), float(e.start_ns + e.duration_ns),
                 module_name(e.name))
                for line in plane.lines if line.name == MODULE_LINE
                for e in line.events)
            ops, labs = [], []
            for line in plane.lines:
                if line.name != DEVICE_LINE:
                    continue
                for e in line.events:
                    name = op_name(e.name)
                    if is_container(name):
                        continue
                    module = dict(e.stats).get("hlo_module")
                    module = (module_name(str(module)) if module else
                              _module_at(modules, float(e.start_ns)))
                    ops.append([name, float(e.start_ns),
                                float(e.duration_ns)])
                    labs.append([module, _scope(module, name, op_names)])
            dev[plane.name] = ops
            labels[plane.name] = labs
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    span = [e.name, float(e.start_ns), float(e.duration_ns)]
                    if e.name.startswith(PREFIX):
                        host.append(span)
                    elif e.name.startswith(PROGRAM_PREFIX):
                        prog.append(span)
                    elif line.name.startswith(CPU_XLA_LINE):
                        stats = dict(e.stats)
                        if "hlo_op" in stats:
                            name = op_name(e.name)
                            module = str(stats.get("hlo_module") or "")
                            cpu_ops.append([name, span[1], span[2]])
                            cpu_labels.append([
                                module or None,
                                _scope(module, name, op_names)])
    if not dev and cpu_ops:
        # as in tracefile.extract: the CPU backend's XLA operations run on
        # host threads (a test's stand-in for the device)
        dev[CPU_DEVICE] = cpu_ops
        labels[CPU_DEVICE] = cpu_labels
    return {"device_ops": dev, "op_labels": labels, "host_spans": host,
            "program_spans": prog}


class _Cover:
    """``tracefile.covered`` in O(log n) a query, for many short ones."""

    def __init__(self, merged: List[Interval]):
        self.starts = [a for a, _ in merged]
        self.ends = [b for _, b in merged]
        self.before = [0.0]
        for a, b in merged:
            self.before.append(self.before[-1] + (b - a))

    def _upto(self, t: float) -> float:
        i = bisect.bisect_right(self.starts, t)
        if i == 0:
            return 0.0
        return (self.before[i - 1] + min(t, self.ends[i - 1])
                - self.starts[i - 1])

    def covered(self, a: float, b: float) -> float:
        return self._upto(b) - self._upto(a) if b > a else 0.0


def _innermost(spans: List, t: float) -> Optional[List]:
    """The shortest of ``spans`` that holds time ``t``."""
    inner = None
    for s in spans:
        if s[1] <= t < s[1] + s[2] and (inner is None or s[2] < inner[2]):
            inner = s
    return inner


def _group(program: Optional[str]) -> str:
    for name, members in GROUPS:
        if program in members:
            return name
    return "other" if program else "no program"


class ProgramTrace(tracefile.Trace):
    """A traced window's reductions by program, scope and engine phase.
    Everything ``tracefile.Trace`` reads, it reads the same."""

    def __init__(self, events: Dict):
        super().__init__(events)
        self.program_spans = sorted(events.get("program_spans", []),
                                    key=lambda s: s[1])
        self._starts = [s[1] for s in self.program_spans]
        # [name, start, dur, program, scope] for the window's operations
        # (the CPU's also list the loops that hold other operations)
        self.labelled = {
            d: [o + lab for o, lab in zip(ops, events["op_labels"][d])
                if o[1] < self.t1 and o[1] + o[2] > self.t0
                and not is_container(o[0])]
            for d, ops in events["device_ops"].items()
            if d in events.get("op_labels", {})}

    def phase_at(self, t: float) -> Optional[str]:
        """The innermost program span the host was in at time ``t``."""
        i = bisect.bisect_right(self._starts, t)
        inner = _innermost([s for s in self.program_spans[:i]
                            if t < s[1] + s[2]], t)
        return None if inner is None else inner[0]

    def _host_label(self, t: float) -> str:
        """The harness's label, then the innermost program span, if any
        (``bench.step.mixed/serve.commit``)."""
        label = super()._host_label(t)
        phase = self.phase_at(t)
        return label if phase is None else f"{label}/{phase}"

    def program_time(self) -> Dict[Optional[str], Dict[Optional[str], float]]:
        """Device nanoseconds in the window by program and by scope
        ({program: {scope: ns}}, averaged over the devices that ran an
        operation); empty for a trace without ``op_labels``."""
        out: Dict = defaultdict(lambda: defaultdict(float))
        for ops in self.labelled.values():
            for _, st, du, program, scope in ops:
                out[program][scope] += min(st + du, self.t1) - max(st,
                                                                   self.t0)
        n = max(1, len([m for m in self.busy.values() if m]))
        return {p: {sc: ns / n for sc, ns in by.items()}
                for p, by in out.items()}

    def step_scopes(self) -> Optional[Dict[str, float]]:
        """The step programs' device nanoseconds by scope, "plumbing"
        for an operation under none; None where the step programs are
        not in the trace or most of their time has no scope known."""
        step = [by for p, by in self.program_time().items()
                if p in STEP_PROGRAMS]
        total = sum(ns for by in step for ns in by.values())
        if total <= 0 or sum(by.get(None, 0.0) for by in step) > total / 2:
            return None
        out: Dict[str, float] = defaultdict(float)
        for by in step:
            for sc, ns in by.items():
                out["plumbing" if sc == "" else sc or "unknown"] += ns
        return dict(out)

    def plumbing_share(self) -> Optional[float]:
        """Per cent of the step programs' device time in the window that
        goes to operations under none of the model's scopes: the layer
        scan's slices of the state it carries, the update back, the
        copies the compiler adds around the loop."""
        scopes = self.step_scopes()
        if scopes is None:
            return None
        return 100.0 * scopes.get("plumbing", 0.0) / sum(scopes.values())

    def step_idle(self) -> Optional[Dict]:
        """Device idle time inside the ``serve.step`` spans wholly inside
        the window: per step, and split by the innermost program span
        (the phase; ``serve.step`` itself where no phase holds the
        host), with the window's idle time outside every ``serve.step``.
        None without such spans."""
        steps = [s for s in self.program_spans if s[0] == STEP_SPAN
                 and self.t0 <= s[1] and s[1] + s[2] <= self.t1]
        used = [_Cover(m) for m in self.busy.values() if m]
        if not steps or not used:
            return None

        def idle(a: float, b: float) -> float:
            return (b - a) - sum(c.covered(a, b) for c in used) / len(used)

        per_step, by_phase = [], defaultdict(float)
        for step in steps:
            st, en = step[1], step[1] + step[2]
            lo = bisect.bisect_left(self._starts, st)
            hi = bisect.bisect_left(self._starts, en)
            inner = [s for s in self.program_spans[lo:hi]
                     if s is not step and s[1] + s[2] <= en]
            edges = sorted({st, en} | {x for s in inner
                                       for x in (s[1], s[1] + s[2])})
            total = 0.0
            for a, b in zip(edges, edges[1:]):
                gap = idle(a, b)
                phase = _innermost(inner, (a + b) / 2)
                by_phase[STEP_SPAN if phase is None else phase[0]] += gap
                total += gap
            per_step.append(total)
        in_steps = sum(idle(max(s[1], self.t0), min(s[1] + s[2], self.t1))
                       for s in self.program_spans if s[0] == STEP_SPAN
                       and s[1] < self.t1 and s[1] + s[2] > self.t0)
        return {"per_step_ns": per_step, "by_phase_ns": dict(by_phase),
                "outside_steps_ns": idle(self.t0, self.t1) - in_steps}

    def step_host_idle_ms(self) -> Optional[float]:
        """Mean milliseconds per ``serve.step`` with no operation on the
        device."""
        idle = self.step_idle()
        if idle is None:
            return None
        return sum(idle["per_step_ns"]) / len(idle["per_step_ns"]) * 1e-6

    def report(self) -> List[str]:
        """The attribution as lines of text."""
        def fmt(d, base, scale=1e-6, unit="ms"):
            return ", ".join(f"{k} {v * scale:.3f} {unit} "
                             f"({100 * v / base:.2f} %)"
                             for k, v in sorted(d.items(),
                                                key=lambda kv: -kv[1]))
        out = []
        groups: Dict[str, float] = defaultdict(float)
        for p, by in self.program_time().items():
            groups[_group(p)] += sum(by.values())
        if groups:
            busy = sum(groups.values())
            named = busy - groups.get("no program", 0.0)
            out.append(f"device time by program: {fmt(groups, busy)}; "
                       f"{100 * named / busy:.2f} % in a named program")
        scopes = self.step_scopes()
        if scopes:
            out.append(f"step programs by scope: "
                       f"{fmt(scopes, sum(scopes.values()))}; "
                       f"step_plumbing_share {self.plumbing_share()}")
        idle = self.step_idle()
        if idle is not None:
            n = len(idle["per_step_ns"])
            total = sum(idle["per_step_ns"])
            per = {k: v / n for k, v in idle["by_phase_ns"].items()}
            named = total - idle["by_phase_ns"].get(STEP_SPAN, 0.0)
            out.append(f"idle per step over {n} serve.step spans: "
                       f"{fmt(per, total / n)}; step_host_idle_ms "
                       f"{total / n * 1e-6}; "
                       f"{100 * named / total if total else 100.0:.2f} % "
                       f"in a named phase; idle outside serve.step "
                       f"{idle['outside_steps_ns'] * 1e-6:.3f} ms")
        return out


def _clip(events: Dict, seconds: float) -> Dict:
    """The first ``seconds`` of a reduced trace's window."""
    win = next(s for s in events["host_spans"] if s[0] == PREFIX + "window")
    t0, t1 = win[1], win[1] + min(win[2], seconds * 1e9)

    def inside(s):
        return s[1] < t1 and s[1] + s[2] > t0
    spans = [s for s in events["host_spans"] if s is not win and inside(s)]
    return dict(
        events, host_spans=[[win[0], t0, t1 - t0]] + spans,
        program_spans=[s for s in events["program_spans"] if inside(s)],
        device_ops={d: [o for o in ops if inside(o)]
                    for d, ops in events["device_ops"].items()})


def main(argv=None) -> None:
    import argparse
    import shutil
    import tempfile
    import time

    import jax

    from bench import harness, peaks

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=5)
    ap.add_argument("--seconds", type=float, default=harness.TRACE_MAX_S)
    ap.add_argument("--save")
    args = ap.parse_args(argv)
    spec = harness.load_cell(args.workload)
    b = harness.build(spec, args.seed, args.seconds,
                      t_start=time.perf_counter())
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
    win = b.window(trace_dir)
    win.run()
    events = extract(trace_dir, step_op_names(b.eng))
    shutil.rmtree(trace_dir, ignore_errors=True)
    conf = b.conf
    tr = ProgramTrace(events)
    view = harness.RunView(
        win.records, win.steps, win.seconds, win.counters, tr,
        harness.family_ref(conf).dims(conf), conf["serve"]["page_size"],
        jax.numpy.dtype(conf["dtype"]).itemsize,
        peaks.peaks(b.device["kind"]) if b.device["platform"] == "tpu"
        else peaks.PEAKS["TPU v5 lite"], [])
    steps = tr.steps()
    for kind in sorted({s["kind"] for s in steps}):
        mine = [s for s in steps if s["kind"] == kind]
        host = sum(s["host_ns"] for s in mine) / len(mine) * 1e-6
        dev = sum(s["device_ns"] for s in mine) / len(mine) * 1e-6
        print(f"{kind}: {len(mine)} steps, host {host:.3f} ms, device "
              f"{dev:.3f} ms per step")
    print(f"step_mfu {harness.metric_reader('step_mfu')(view)}; idle "
          f"{100 * (1 - tr.busy_s() / tr.window_s):.3f} % of "
          f"{tr.window_s:.3f} s")
    for line in tr.report():
        print(line)
    print(f"longest idle gaps: {tr.idle_gaps()}")
    if args.save:
        tracefile.save(dict(_clip(events, SAVE_S), steps=[
            [s.index, s.kind, s.rows] for s in win.steps], meta={
            "dims": view.dims, "page_size": view.page_size,
            "kv_itemsize": view.kv_itemsize, "kind": b.device["kind"]}),
            args.save)
        print(f"saved the first {SAVE_S} s to {args.save}")


if __name__ == "__main__":
    main()
