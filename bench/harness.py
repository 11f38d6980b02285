"""One run of one benchmark cell: load, warm up, measure, check.

Everything is found by name.  ``BENCHMARK.json`` names the cell's
configuration and traffic mix; the configuration is
``bench/configs/<config>.json`` (published sizes, dtype, serving shape,
and the family whose code builds the system and its reference), the mix
is ``bench/traffic/<traffic>.json`` (read by ``traffic/generate.py``), and
each per-layer metric is ``bench/metrics/<metric>.py`` with a
``read(run)`` that returns a number, or None where the run holds nothing
for it to read.

The clock's 0 is the start of the measured window.  Traffic starts
``ramp_s`` earlier so that the window opens near steady state; the ramp
counts as set-up.  After the window the run goes on serving, with
arrivals still coming, until every request due in the window has shown
its first token (in a cell that reports time to first token) and, in a
mix of multi-round sessions, until a later round has finished since the
window opened, so that the check can compare one.

``run_cell`` is one run as ``run.py`` makes it.  Its pieces (``build``,
``Window``, ``measure``, ``check``) serve the tools beside this module.
"""
from __future__ import annotations

import gc
import importlib
import importlib.util
import json
import math
import os
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Callable, Dict, List, Optional

import numpy as np

ROOT = Path(__file__).resolve().parents[1]
DRAIN_MAX_S = 60.0          # stop waiting this long past the window's end
TRACE_MAX_S = 10.0          # a traced run traces at most this much window
IDLE_POLL_S = 0.002         # longest sleep between looks at the schedule


# ------------------------------------------------------------------ lookup

def load_cell(workload: str, root: Path = ROOT) -> Dict:
    """The cell ``workload`` with its configuration, mix and metrics."""
    with open(root / "BENCHMARK.json") as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"bench: no workload {workload!r}; known: "
                         f"{sorted(cells)}")
    cell = cells[workload]
    with open(root / "bench" / "configs" / f"{cell['config']}.json") as f:
        conf = json.load(f)
    with open(root / "bench" / "traffic" / f"{cell['traffic']}.json") as f:
        mix = json.load(f)

    def applies(m):
        return workload in m.get("workloads", [workload])
    return {"cell": cell, "conf": conf, "mix": mix, "root": root,
            "end_to_end": [m for m in bench["end_to_end"] if applies(m)],
            "per_layer": [m for m in bench["per_layer"] if applies(m)]}


def metric_reader(name: str, root: Path = ROOT) -> Callable:
    path = root / "bench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def family(conf: Dict):
    return importlib.import_module(f"bench.families.{conf['family']}")


def family_ref(conf: Dict):
    return importlib.import_module(f"bench.families.{conf['family']}_ref")


def prng_key(seed: int):
    """A JAX key from any whole number (more than 32 bits included)."""
    import jax
    import jax.numpy as jnp
    words = np.random.SeedSequence(abs(int(seed))).generate_state(2)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32))


def percentile(xs, q: float) -> Optional[float]:
    return float(np.percentile(np.asarray(xs, np.float64), q)) if len(xs) \
        else None


# -------------------------------------------------------------- the window

@dataclass
class Record:
    spec: object
    req: object
    submit_t: float
    admit_t: Optional[float] = None
    tok_t: List[float] = field(default_factory=list)
    done_t: Optional[float] = None


@dataclass
class Step:
    index: int
    kind: str                   # what the harness planned: decode | mixed
    ran: str                    # what the engine counted, or none
    t0: float
    t1: float
    rows: List                  # (valid tokens, first position, emits)
    slots: int = 0              # occupied slots before the step
    queued: int = 0             # requests waiting for a slot before it
    later: int = 0              # of the rows, those of a later round


def planned_kind(eng) -> str:
    """The step the engine will run: mixed while anything prefills or a
    queued request can take a free slot, decode otherwise."""
    from repro.serving.request import Status
    slots = eng.slots
    if any(r is not None and r.status is Status.PREFILLING for r in slots):
        return "mixed"
    if eng.queue and any(r is None for r in slots):
        return "mixed"
    return "decode"


class _Compiles:
    """Programs compiled, or loaded from the persistent cache, by this
    process, counted from JAX's monitoring events."""
    requests = 0
    backend_s = 0.0
    _on = False

    @classmethod
    def listen(cls) -> None:
        if cls._on:
            return
        from jax import monitoring

        def event(name, **kw):
            if name == "/jax/compilation_cache/compile_requests_use_cache":
                cls.requests += 1

        def duration(name, secs, **kw):
            if name == "/jax/core/compile/backend_compile_duration":
                cls.backend_s += secs
        monitoring.register_event_listener(event)
        monitoring.register_event_duration_secs_listener(duration)
        cls._on = True

    @classmethod
    def read(cls):
        return cls.requests, cls.backend_s


class Window:
    """Drives the engine on the wall clock and records what it shows."""

    def __init__(self, eng, traffic, seconds: float, trace_dir=None,
                 wait_first_tokens: bool = False):
        self.eng = eng
        self.wait_first_tokens = wait_first_tokens
        self.wait_later_round = traffic.max_rounds() > 1
        self.traffic = traffic
        self.seconds = seconds
        self.trace_dir = trace_dir
        self.records: List[Record] = []
        self.active: List[Record] = []
        self._of: Dict[int, Record] = {}
        self.steps: List[Step] = []
        self.counters: Dict[str, Dict] = {}
        self.compiles: Dict[str, tuple] = {}
        self.setup_s: Optional[float] = None     # set by the caller
        _Compiles.listen()

    def _submit(self, now: float) -> None:
        from repro.serving.request import Request
        for spec in self.traffic.due(now):
            req = Request(prompt=spec.prompt, max_new_tokens=spec.max_new,
                          eos_id=None, temperature=0.0)
            self.eng.submit(req)
            rec = Record(spec, req, now)
            self.records.append(rec)
            self.active.append(rec)
            self._of[id(req)] = rec

    def _step(self, clock) -> None:
        import jax
        from repro.serving.request import Status
        eng = self.eng
        before = [(r, r.status, r.prefill_pos, int(eng.pos[i]))
                  for i, r in enumerate(eng.slots) if r is not None]
        free = sum(r is None for r in eng.slots)
        queued = len(eng.queue)
        admitted = list(eng.queue)[:free]
        kind = planned_kind(eng)
        n0 = (eng.model_steps["decode_batch_steps"],
              eng.model_steps["mixed_steps"])
        idx = len(self.steps)
        t0 = clock()
        with jax.profiler.TraceAnnotation(f"bench.step.{kind}.{idx}"):
            eng.step()
        t1 = clock()
        rows, later = [], 0
        for r, st, pp, pos in before:
            if st is Status.DECODING:
                rows.append((1, pos, True))
            elif st is Status.PREFILLING and r.prefill_pos > pp:
                rows.append((r.prefill_pos - pp, pp,
                             r.status is not Status.PREFILLING))
            else:
                continue
            later += self._of[id(r)].spec.round > 1
        for r in admitted:
            if r.status is not Status.QUEUED:
                n = r.prefill_pos - r.cached_len
                if n > 0:
                    rows.append((n, r.cached_len,
                                 r.status is not Status.PREFILLING))
                    later += self._of[id(r)].spec.round > 1
        n1 = (eng.model_steps["decode_batch_steps"],
              eng.model_steps["mixed_steps"])
        ran = ("decode" if n1[0] > n0[0] else
               "mixed" if n1[1] > n0[1] else "none")
        self.steps.append(Step(idx, kind, ran, t0, t1, rows, len(before),
                               queued, later))
        still = []
        for rec in self.active:
            req = rec.req
            if rec.admit_t is None and req.status is not Status.QUEUED:
                rec.admit_t = t0
            n = len(req.output)
            if n > len(rec.tok_t):
                rec.tok_t.extend([t1] * (n - len(rec.tok_t)))
            if req.status is Status.DONE:
                rec.done_t = t1
                self.traffic.done(rec.spec, req.output, t1)
                del self._of[id(req)]
            else:
                still.append(rec)
        self.active = still

    def _pending(self) -> bool:
        """What the run still waits for past the window's end."""
        if self.wait_first_tokens and any(
                0.0 <= r.spec.due < self.seconds and not r.tok_t
                for r in self.records):
            return True
        return self.wait_later_round and not any(
            r.spec.round > 1 and r.done_t is not None and r.done_t >= 0.0
            for r in self.records)

    def _mark(self, name: str) -> None:
        self.counters[name] = self.eng.stats()
        self.compiles[name] = _Compiles.read()

    def run(self) -> float:
        """Serve the ramp, the window and the drain.  Returns the
        absolute clock reading of the window's start."""
        import jax
        eng = self.eng
        t_origin = time.perf_counter() + self.traffic.ramp_s

        def clock():
            return time.perf_counter() - t_origin

        tracing = None          # (annotation, stop time) while tracing
        traced = self.trace_dir is None
        while True:
            now = clock()
            if now >= 0.0 and "start" not in self.counters:
                self._mark("start")
            if not traced and now >= 0.0:
                jax.profiler.start_trace(self.trace_dir)
                ann = jax.profiler.TraceAnnotation("bench.window")
                ann.__enter__()
                tracing = (ann, now + min(self.seconds, TRACE_MAX_S))
                traced = True
            if tracing is not None and now >= tracing[1]:
                tracing[0].__exit__(None, None, None)
                jax.profiler.stop_trace()
                tracing = None
            if now >= self.seconds and "end" not in self.counters:
                self._mark("end")
            if now >= self.seconds and tracing is None and (
                    not self._pending()
                    or now >= self.seconds + DRAIN_MAX_S):
                break
            self._submit(now)
            if eng.queue or any(r is not None for r in eng.slots):
                self._step(clock)
                continue
            nxt = self.traffic.next_due()
            wait = IDLE_POLL_S if nxt is None else min(IDLE_POLL_S,
                                                       nxt - now)
            if wait > 0:
                with jax.profiler.TraceAnnotation("bench.idle"):
                    time.sleep(wait)
        self.drain_end = clock()
        return t_origin

    def window_compiles(self) -> tuple:
        """(programs compiled or loaded, backend compile seconds) between
        the window's start and end."""
        a, b = self.compiles["start"], self.compiles["end"]
        return b[0] - a[0], b[1] - a[1]


# ------------------------------------------------------------ end to end

def end_to_end(win: Window) -> Dict[str, Optional[float]]:
    T = win.seconds
    due = [r for r in win.records if 0.0 <= r.spec.due < T]
    ttft = [(r.tok_t[0] - r.spec.due) * 1e3 for r in due if r.tok_t]
    gaps, toks = [], 0
    for r in win.records:
        t = r.tok_t
        toks += sum(1 for x in t if 0.0 <= x < T)
        gaps += [(b - a) * 1e3 for a, b in zip(t, t[1:]) if 0.0 <= b < T]
    return {"ttft_p90_ms": percentile(ttft, 90),
            "itl_p50_ms": percentile(gaps, 50),
            "itl_p95_ms": percentile(gaps, 95),
            "output_tok_s": toks / T, "_gaps_n": len(gaps)}


@dataclass
class RunView:
    """What a per-layer metric reader may read."""
    records: List[Record]
    steps: List[Step]
    seconds: float
    counters: Dict[str, Dict]
    trace: object               # tracefile.Trace, or None
    dims: Dict[str, int]
    page_size: int
    kv_itemsize: int
    peaks: Dict[str, float]
    notes: List[str]


# ------------------------------------------------------------ correctness

def choose_sample(records: List[Record], k: int, seed: int) -> List[Record]:
    """Of the requests that finished after the window opened: the
    longest, the longest later round of a session (where the mix has
    later rounds, the window waits for one), and others drawn from the
    seed, ``k`` in all."""
    done = [r for r in records if r.req.stop_reason == "max_tokens"
            and r.done_t is not None and r.done_t >= 0.0]
    done.sort(key=lambda r: -(len(r.req.prompt) + len(r.req.output)))
    first = done[:1] + [r for r in done[1:] if r.spec.round > 1][:1]
    rest = [r for r in done if r not in first]
    rng = np.random.default_rng([abs(int(seed)), 0xc4ec])
    n = min(k - len(first), len(rest))
    pick = rng.choice(len(rest), size=n, replace=False) if n > 0 else []
    return first + [rest[i] for i in sorted(pick)]


def check_outputs(conf: Dict, seed: int, sample: List[Record],
                  control: Optional[str] = None) -> List[float]:
    """Widest gap, per sampled request, between the reference's best
    logit and that of the token served (see dense_gqa_ref.served_gaps)."""
    ref = family_ref(conf)
    import jax
    w = jax.jit(lambda k: ref.draw_weights(conf, k))(prng_key(seed))
    out = [max(ref.served_gaps(conf, w, r.req.prompt, r.req.output,
                               control=control)) for r in sample]
    del w
    return out


# ------------------------------------------------------------------ a run

def _device_info(chips: int, require_tpu: bool):
    import jax
    devs = jax.devices()
    d0 = devs[0]
    if require_tpu and d0.platform != "tpu":
        raise SystemExit(f"bench: JAX found no TPU (platform "
                         f"{d0.platform}); nothing was run")
    if len(devs) < chips:
        raise SystemExit(f"bench: the cell needs {chips} chips, JAX found "
                         f"{len(devs)}")
    return devs, {"platform": d0.platform, "kind": d0.device_kind,
                  "count": len(devs)}


def enable_compile_cache() -> str:
    """The program's persistent compilation cache (where
    JAX_COMPILATION_CACHE_DIR says, else ``.jax_cache/`` in the
    checkout), holding every program, however small or quick."""
    import jax
    from repro.launch.compile_cache import enable_compile_cache as enable
    path = enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


@dataclass
class Built:
    """A cell made ready to serve: its configuration and mix, the engine
    with its programs compiled, and the seeded traffic."""
    spec: Dict
    seed: int
    seconds: float
    devs: List
    device: Dict
    eng: object
    traffic: object
    log: Callable

    @property
    def conf(self) -> Dict:
        return self.spec["conf"]

    @property
    def mix(self) -> Dict:
        return self.spec["mix"]

    def reports_ttft(self) -> bool:
        return any(m["name"].startswith("ttft")
                   for m in self.spec["end_to_end"])

    def window(self, trace_dir=None) -> Window:
        # a cell that reports time to first token serves on past the
        # window until every request due in it has shown one
        return Window(self.eng, self.traffic, self.seconds, trace_dir,
                      wait_first_tokens=self.reports_ttft())

    def free(self, win: Optional[Window] = None) -> None:
        """Drop the program's state (the engine, here and in ``win``),
        before the reference runs."""
        self.eng = None
        if win is not None:
            win.eng = None
        gc.collect()


def build(spec: Dict, seed: int, seconds: float, require_tpu: bool = True,
          t_start: Optional[float] = None, engine_cls=None,
          log=None) -> Built:
    """The engine of ``spec`` (from :func:`load_cell`) with weights drawn
    from ``seed`` and every step program compiled, and its traffic."""
    t_start = time.perf_counter() if t_start is None else t_start
    log = log or (lambda msg: print(f"bench: {msg}", file=sys.stderr,
                                    flush=True))
    conf = spec["conf"]
    devs, device = _device_info(int(spec["cell"]["chips"]), require_tpu)
    from bench.traffic.generate import Traffic

    log(f"compile cache {enable_compile_cache()}")
    gc.collect()        # an earlier run's engine in this process (tools)
    eng, _ = family(conf).build(conf, prng_key(seed), conf["serve"],
                                engine_cls=engine_cls)
    eng.aot_compile()
    log(f"engine: attn_impl {eng.attn_impl}, {eng.pool.num_pages} pages "
        f"of {eng.scfg.page_size}, built in "
        f"{time.perf_counter() - t_start:.2f}s")
    traffic = Traffic(spec["mix"], seed, family_ref(conf).dims(conf)["V"],
                      seconds)
    if traffic.max_tokens() >= conf["serve"]["max_seq"]:
        raise SystemExit("bench: the mix's longest request does not fit "
                         "max_seq")
    return Built(spec, seed, seconds, devs, device, eng, traffic, log)


def failed_requests(b: Built, win: Window) -> int:
    """Requests due in the window that ended in an error or, where first
    tokens are waited for, never showed one.  Above the knee a request
    still queued at the end is not failed."""
    ttft = b.reports_ttft()
    return sum(1 for r in win.records if 0.0 <= r.spec.due < b.seconds and (
        (ttft and not r.tok_t)
        or r.req.stop_reason not in (None, "max_tokens")))


def measure(b: Built, win: Window, trace_dir=None) -> Dict:
    """The run's result line without ``correct`` and ``checks``: with a
    trace, the per-layer metrics, busy time and breakdown; without, the
    end-to-end metrics."""
    import jax

    from bench import peaks as P
    from bench import tracefile
    seconds, conf, device = b.seconds, b.conf, dict(b.device)
    e2e = end_to_end(win)
    mem = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in b.devs]
    device["memory_peak_bytes"] = max((m for m in mem if m), default=None)
    st = b.eng.stats()
    compiles = st["step_compiles"] - win.counters["start"]["step_compiles"]
    programs, backend_s = win.window_compiles()
    mixed = sum(s.ran == "mixed" for s in win.steps)
    off = sum(s.ran not in (s.kind, "none") for s in win.steps)
    b.log(f"window: {len(win.steps)} steps from the ramp's start ({mixed} "
          f"mixed, {off} not of the planned kind), {e2e['_gaps_n']} token "
          f"gaps, {compiles} step compiles after the ramp, {programs} "
          f"programs compiled or loaded in the window ({backend_s:.3f}s "
          f"of compiling), run ended at {win.drain_end:.2f}s, preemptions "
          f"{st['preemptions']}")

    due = [r for r in win.records if 0.0 <= r.spec.due < seconds]
    result = {"correct": None, "attempted": len(due),
              "failed": failed_requests(b, win)}
    if trace_dir is None:
        vals = dict(e2e, setup_s=win.setup_s)
        result["metrics"] = {
            m["name"]: {"value": vals[m["name"]], "unit": m["unit"]}
            for m in b.spec["end_to_end"] if vals.get(m["name"]) is not None}
        result["device"] = device
        return result
    tr = tracefile.Trace(tracefile.extract(trace_dir))
    shutil.rmtree(trace_dir, ignore_errors=True)
    view = RunView(win.records, win.steps, seconds, win.counters, tr,
                   family_ref(conf).dims(conf), conf["serve"]["page_size"],
                   jax.numpy.dtype(conf["dtype"]).itemsize,
                   P.peaks(device["kind"]) if device["platform"] == "tpu"
                   else P.PEAKS["TPU v5 lite"], [])
    metrics = {}
    for m in b.spec["per_layer"]:
        v = metric_reader(m["name"], b.spec["root"])(view)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    for note in view.notes:
        b.log(note)
    device["busy_s"] = tr.busy_s()
    device["window_s"] = tr.window_s
    result["metrics"] = metrics
    result["device"] = device
    result["breakdown"] = {"device_ops": tr.top_ops(),
                           "idle_gaps": tr.idle_gaps()}
    return result


def check(b: Built, win: Window) -> tuple:
    """(correct, checks, sample): a seeded sample of what the window
    served, compared with the reference once the program's state is
    freed.  In a mix of multi-round sessions the sample has to hold a
    later round, whose prompt the engine extended from cached pages."""
    sample = choose_sample(win.records, int(b.mix["check_requests"]), b.seed)
    served = sum(len(r.req.output) for r in sample)
    b.free(win)
    t0 = time.perf_counter()
    gaps = check_outputs(b.conf, b.seed, sample)
    limit = float(b.conf["check"]["served_gap_sd"])
    worst = max(gaps) if gaps else math.inf
    b.log(f"reference: {len(sample)} requests (rounds "
          f"{[r.spec.round for r in sample]}), {served} served tokens, "
          f"{time.perf_counter() - t0:.2f}s")
    failed = failed_requests(b, win)
    checks = {"served_gap_sd": {"value": worst, "limit": limit},
              "failed_requests": {"value": failed, "limit": 0}}
    correct = worst <= limit and failed == 0 and bool(sample)
    if win.wait_later_round:
        later = sum(r.spec.round > 1 for r in sample)
        checks["later_rounds_checked"] = {"value": later, "at_least": 1}
        correct = correct and later >= 1
    return bool(correct), checks, sample


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             root: Path = ROOT, require_tpu: bool = True,
             t_start: Optional[float] = None, engine_cls=None,
             log=None) -> Dict:
    """One run of ``workload``; returns the result line as a dict.
    ``engine_cls`` puts another engine in the program's place (the
    tests' broken timed paths)."""
    t_start = time.perf_counter() if t_start is None else t_start
    spec = load_cell(workload, root)
    b = build(spec, seed, seconds, require_tpu, t_start, engine_cls, log)
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    win = b.window(trace_dir)
    win.setup_s = win.run() - t_start
    result = measure(b, win, trace_dir)
    result["correct"], checks, _ = check(b, win)
    for k, v in checks.items():
        bound = (f"limit {v['limit']}" if "limit" in v
                 else f"at least {v['at_least']}")
        print(f"check {k}: {v['value']} ({bound})", file=sys.stderr,
              flush=True)
    result["checks"] = checks           # the line's last key
    return result
