"""From the profiler's trace to the numbers the per-layer metrics read.

``extract`` reads the ``.xplane.pb`` the JAX profiler wrote with
``jax.profiler.ProfileData`` and keeps two lists: the operations that ran
on each device (the ``XLA Ops`` line of every ``/device:`` plane, named
by their HLO instruction, e.g. ``paged_attention.8``; the ``while`` and
``conditional`` ops that contain other ops are left out, as their
bodies' ops are there) and the harness's own host spans
(TraceAnnotations named ``bench.*``).  The
reductions below work on that small dict, which is also what a recorded
trace under ``testdata/`` holds.

Host spans and device operations share the profiler's clock.  The
harness brackets the traced period with a ``bench.window`` span and each
engine step with ``bench.step.<kind>.<index>``.
"""
from __future__ import annotations

import glob
import gzip
import json
import os
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

Interval = Tuple[float, float]
DEVICE_LINE = "XLA Ops"
PREFIX = "bench."
CPU_XLA_LINE = "tf_XLA"
CPU_DEVICE = "/host:CPU/xla"
CONTAINERS = ("while", "conditional", "call")


def op_name(event_name: str) -> str:
    """The HLO instruction's name from the trace's event name, which on a
    TPU is the whole instruction text (``%fusion.3 = bf16[...] ...``)."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def is_container(name: str) -> bool:
    return name.split(".", 1)[0] in CONTAINERS


def extract(logdir: str) -> Dict:
    """{"device_ops": {device: [[name, start_ns, dur_ns], ...]},
    "host_spans": [[name, start_ns, dur_ns], ...]} from the newest trace
    under ``logdir``."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    pd = ProfileData.from_file(paths[-1])
    dev: Dict[str, List] = {}
    host: List = []
    cpu_ops: List = []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            ops = []
            for line in plane.lines:
                if line.name != DEVICE_LINE:
                    continue
                for e in line.events:
                    name = op_name(e.name)
                    if not is_container(name):
                        ops.append([name, float(e.start_ns),
                                    float(e.duration_ns)])
            dev[plane.name] = ops
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(PREFIX):
                        host.append([e.name, float(e.start_ns),
                                     float(e.duration_ns)])
                    elif line.name.startswith(CPU_XLA_LINE) and \
                            "hlo_op" in dict(e.stats):
                        cpu_ops.append([op_name(e.name), float(e.start_ns),
                                        float(e.duration_ns)])
    if not dev and cpu_ops:
        # the CPU backend has no device plane: its XLA operations run on
        # host threads (a test's stand-in for the device, never a chip
        # number)
        dev[CPU_DEVICE] = cpu_ops
    return {"device_ops": dev, "host_spans": host}


def save(events: Dict, path: str) -> None:
    with gzip.open(path, "wt") as f:
        json.dump(events, f)


def load(path: str) -> Dict:
    with gzip.open(path, "rt") as f:
        return json.load(f)


def merge(intervals: List[Interval]) -> List[Interval]:
    """Union of intervals, as sorted disjoint intervals."""
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def covered(merged: List[Interval], a: float, b: float) -> float:
    """Length of [a, b] that the disjoint ``merged`` intervals cover."""
    return sum(max(0.0, min(b, y) - max(a, x)) for x, y in merged)


class Trace:
    """The reductions of one traced window."""

    def __init__(self, events: Dict):
        spans = events["host_spans"]
        win = [s for s in spans if s[0] == PREFIX + "window"]
        if len(win) != 1:
            raise ValueError(f"expected one {PREFIX}window span, found "
                             f"{len(win)}")
        self.t0 = win[0][1]
        self.t1 = win[0][1] + win[0][2]
        self.spans = spans
        self.ops = {d: [o for o in ops
                        if o[1] < self.t1 and o[1] + o[2] > self.t0]
                    for d, ops in events["device_ops"].items()}
        if not any(self.ops.values()):
            raise ValueError("no operation ran on a device in the window")
        self.busy = {d: merge([(o[1], o[1] + o[2]) for o in ops])
                     for d, ops in self.ops.items()}

    @property
    def window_s(self) -> float:
        return (self.t1 - self.t0) * 1e-9

    def busy_s(self) -> float:
        """Seconds with an operation running, averaged over the devices
        that ran any."""
        used = [m for m in self.busy.values() if m]
        return sum(covered(m, self.t0, self.t1) for m in used) \
            / len(used) * 1e-9

    def steps(self) -> List[Dict]:
        """Every step span wholly inside the window, in order: its kind,
        index, start, host duration and the device time inside it
        (averaged over the devices)."""
        out = []
        for name, st, du in self.spans:
            if not name.startswith(PREFIX + "step."):
                continue
            if st < self.t0 or st + du > self.t1:
                continue
            _, _, kind, idx = name.split(".")
            used = [m for m in self.busy.values() if m]
            dev = sum(covered(m, st, st + du) for m in used) / len(used)
            out.append({"kind": kind, "index": int(idx), "host_ns": du,
                        "device_ns": dev, "start_ns": st})
        return sorted(out, key=lambda s: s["start_ns"])

    def op_time_in(self, pattern: str, a: float, b: float) -> float:
        """Nanoseconds of operations whose name holds ``pattern`` that
        started in [a, b), summed over devices."""
        return sum(o[2] for ops in self.ops.values() for o in ops
                   if pattern in o[0] and a <= o[1] < b)

    def top_ops(self, k: int = 10) -> List[List]:
        tot: Dict[str, float] = defaultdict(float)
        for ops in self.ops.values():
            for name, st, du in ops:
                tot[name] += (min(st + du, self.t1) - max(st, self.t0))
        n = max(1, len([m for m in self.busy.values() if m]))
        best = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
        return [[name, ns * 1e-9 / n] for name, ns in best]

    def _host_label(self, t: float) -> str:
        inner: Optional[List] = None
        for s in self.spans:
            if s[0] == PREFIX + "window" or not s[1] <= t < s[1] + s[2]:
                continue
            if inner is None or s[2] < inner[2]:
                inner = s
        if inner is None:
            return "host:outside-harness-spans"
        parts = inner[0].split(".")
        return ".".join(parts[:3]) if parts[1] == "step" else inner[0]

    def idle_gaps(self, k: int = 10) -> List[List]:
        """The longest stretches with no operation on a device, each
        labelled by the harness span the host was in at their middle."""
        gaps = []
        for m in (m for m in self.busy.values() if m):
            edges = [self.t0] + [x for iv in m for x in iv] + [self.t1]
            for a, b in zip(edges[0::2], edges[1::2]):
                a, b = max(a, self.t0), min(b, self.t1)
                if b > a:
                    gaps.append((b - a, a))
        gaps.sort(reverse=True)
        return [[self._host_label(a + g / 2), g * 1e-9] for g, a in gaps[:k]]
