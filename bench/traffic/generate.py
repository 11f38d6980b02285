"""The one traffic generator.  A mix is a JSON file beside this one
(``<mix>.json``) of parameters; this module turns it and a seed into
requests, due on a clock whose 0 is the start of the measured window.

Both kinds of mix are open loops: work arrives at the mix's fixed
``rate_per_s`` whether or not earlier work finished.

* ``"sessions"``: a session is 1 or more rounds.  Round 1's prompt is a
  per-domain system prompt (shared by every session of the domain, or
  unique per session) plus a task body; each later round resubmits the
  previous prompt + the previous answer + a feedback turn, and is due
  when the previous answer's last token was visible plus ``think_s``.
* ``"requests"``: single-shot requests (a prompt and an output length).

``backlog`` units are due at the start of the ramp, so that the slots
fill at once.  For ``"requests"`` their output lengths are residual
lives (what is left of a request already running), so the slots hold
requests at every stage; they are quantiles of the residual life's own
distribution, one set for every seed.

Every length and every gap is stratified in blocks of 8 consecutive
units: each block draws the same set of sizes and gaps for every seed,
in an order the seed permutes.  So seeds change which request is long,
not how much work a stretch of the run holds.

The arrival arithmetic (Pareto gaps with a mean-normalised base gap, a
sinusoidal "diurnal" rate multiplier) is copied from the program's
serving trace generator, so that the benchmark keeps its own copy.
"""
from __future__ import annotations

import heapq
import json
import math
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, List, Tuple

import numpy as np

HERE = Path(__file__).resolve().parent
DRAIN_HORIZON_S = 60.0      # arrivals keep coming this long past the window
BLOCK = 8                   # units per stratified block


def load_mix(name: str, root: Path = HERE) -> Dict:
    with open(root / f"{name}.json") as f:
        return json.load(f)


@dataclass
class Spec:
    """One request the harness submits."""
    prompt: List[int]
    max_new: int
    due: float                      # seconds from the window's start
    tag: Tuple                      # (unit index, round)
    round: int = 1
    domain: int = 0


def _strata(n: int, rng: np.random.Generator) -> np.ndarray:
    """``n`` quantiles in blocks of ``BLOCK`` consecutive units: each block
    holds the midpoints (i + 0.5) / BLOCK in an order ``rng`` permutes, so
    that any stretch of the schedule holds nearly the same sizes for
    every seed."""
    q = np.empty(n)
    mid = (np.arange(BLOCK) + 0.5) / BLOCK
    for a in range(0, n, BLOCK):
        m = min(BLOCK, n - a)
        q[a:a + m] = rng.permutation(mid)[:m]
    return q


def _ints(q: np.ndarray, lo_hi) -> np.ndarray:
    """Integers uniform on [lo, hi] at quantiles ``q``."""
    lo, hi = lo_hi
    return (lo + np.floor(q * (hi - lo + 1))).astype(np.int64)


def gaps(mix: Dict, n: int, rng: np.random.Generator) -> np.ndarray:
    """``n`` interarrival gaps at the long-run rate ``rate_per_s``:
    exponential (Poisson arrivals) or Pareto with tail index
    ``pareto_alpha``, both stratified.  A block's quantiles stop short of
    the far tail, so the gaps are scaled to keep the mean gap 1/rate."""
    alpha = mix.get("pareto_alpha")
    if alpha is not None and alpha <= 1.0:
        raise ValueError("pareto_alpha must exceed 1 (the mean diverges)")

    def gap(q):
        if alpha is None:
            return -np.log1p(-q)
        # (pareto(a) + 1) has mean a / (a - 1)
        return (1.0 - q) ** (-1.0 / alpha) * (alpha - 1.0) / alpha
    mid = (np.arange(BLOCK) + 0.5) / BLOCK
    return gap(_strata(n, rng)) / (gap(mid).mean()
                                   * float(mix["rate_per_s"]))


def arrival_times(mix: Dict, n: int, rng: np.random.Generator,
                  t0: float) -> np.ndarray:
    """Arrival times from ``t0``, the gaps stretched by the diurnal rate
    multiplier 1 + amp * sin(2 pi t / period)."""
    g = gaps(mix, n, rng)
    amp = float(mix.get("diurnal_amp", 0.0))
    period = float(mix.get("diurnal_period_s", 20.0))
    if not 0.0 <= amp < 1.0:
        raise ValueError("diurnal_amp must lie in [0, 1)")
    t, out = t0, np.empty(n)
    for i in range(n):
        mult = 1.0 + amp * math.sin(2.0 * math.pi * (t - t0) / period)
        t += g[i] / max(mult, 1e-6)
        out[i] = t
    return out


def residual_life(q: np.ndarray, lo_hi) -> np.ndarray:
    """Quantiles ``q`` of the residual life (what is left of a request
    already running, seen at a random moment) of lengths uniform on
    [lo, hi]: density P(L > r) / E[L], so F(r) = r / E for r <= lo and
    (r - (r - lo)^2 / (2 (hi - lo))) / E above."""
    lo, hi = (float(x) for x in lo_hi)
    mean, w = (lo + hi) / 2.0, hi - lo
    x = q * mean
    if w == 0:
        return x
    above = lo + w - np.sqrt(np.maximum(0.0, w * w - 2.0 * w * (x - lo)))
    return np.where(x <= lo, x, above)


def _tokens(rng: np.random.Generator, n: int, vocab: int) -> List[int]:
    return rng.integers(3, vocab, n).tolist()


@dataclass
class Traffic:
    """The schedule of one run.  ``due(now)`` hands out every request due
    by ``now``; ``done(spec, output, t)`` reports that a request showed
    its last token at ``t``, which makes a session's next round due."""
    mix: Dict
    seed: int
    vocab: int
    seconds: float
    _heap: List = field(default_factory=list)
    _n: int = 0

    def __post_init__(self):
        m = self.mix
        self.ramp_s = float(m["ramp_s"])
        if m["kind"] not in ("sessions", "requests"):
            raise ValueError(f"unknown traffic kind {m['kind']!r}")
        rng = np.random.default_rng([abs(int(self.seed)), 0x7a11])
        horizon = self.ramp_s + self.seconds + DRAIN_HORIZON_S
        backlog = int(m.get("backlog", 0))
        arrivals = max(1, int(math.ceil(float(m["rate_per_s"]) * horizon)))
        due = np.concatenate([np.full(backlog, -self.ramp_s),
                              arrival_times(m, arrivals, rng, -self.ramp_s)])
        n = len(due)
        if m["kind"] == "sessions":
            self._init_sessions(rng, n)
        else:
            self._init_requests(rng, n, backlog)
        for i in range(n):
            self._push(float(due[i]), (i, 1))

    def _init_sessions(self, rng: np.random.Generator, n: int) -> None:
        m = self.mix
        body = _ints(_strata(n, rng), m["body_tokens"])
        rounds = _ints(_strata(n, rng), m["rounds"])
        domains = int(m.get("domains", 1))
        dom = np.floor(_strata(n, rng) * domains).astype(np.int64)
        rmax = int(m["rounds"][1])
        out = _ints(_strata(n * rmax, rng), m["output_tokens"]).reshape(
            n, rmax)
        fb = _ints(_strata(n * rmax, rng), m["feedback_tokens"]).reshape(
            n, rmax)
        sys_n = int(m["system_prompt_tokens"])
        shared = bool(m["shared_system_prompt"])
        system = [_tokens(rng, sys_n, self.vocab) for _ in range(domains)]
        self.units = []
        for i in range(n):
            srng = np.random.default_rng([abs(int(self.seed)), 0x5e55, i])
            pre = system[dom[i]] if shared else _tokens(srng, sys_n,
                                                        self.vocab)
            self.units.append({
                "prompt": [1] + pre[1:] + _tokens(srng, int(body[i]),
                                                  self.vocab),
                "rounds": int(rounds[i]), "out": out[i].tolist(),
                "feedback": [_tokens(srng, int(k), self.vocab)
                             for k in fb[i]],
                "domain": int(dom[i]),
            })

    def _init_requests(self, rng: np.random.Generator, n: int,
                       backlog: int) -> None:
        m = self.mix
        prompt = _ints(_strata(n, rng), m["prompt_tokens"])
        out = _ints(_strata(n, rng), m["output_tokens"])
        if backlog:
            out[:backlog] = np.maximum(1, np.ceil(residual_life(
                _strata(backlog, rng), m["output_tokens"])))
        self.units = []
        for i in range(n):
            srng = np.random.default_rng([abs(int(self.seed)), 0x4e9, i])
            self.units.append({
                "prompt": [1] + _tokens(srng, int(prompt[i]) - 1, self.vocab),
                "rounds": 1, "out": [int(out[i])], "feedback": [],
                "domain": 0,
            })

    # -- schedule ---------------------------------------------------------

    def _push(self, due: float, tag: Tuple) -> None:
        heapq.heappush(self._heap, (due, self._n, tag))
        self._n += 1

    def next_due(self):
        return self._heap[0][0] if self._heap else None

    def due(self, now: float) -> List[Spec]:
        out = []
        while self._heap and self._heap[0][0] <= now:
            due, _, (i, r) = heapq.heappop(self._heap)
            u = self.units[i]
            out.append(Spec(list(u["prompt"]), int(u["out"][r - 1]), due,
                            (i, r), round=r, domain=u["domain"]))
        return out

    def done(self, spec: Spec, output: List[int], t: float) -> None:
        """The request ``spec`` finished with ``output`` at ``t``."""
        i, r = spec.tag
        u = self.units[i]
        if r < u["rounds"]:
            u["prompt"] = spec.prompt + list(output) + u["feedback"][r - 1]
            self._push(t + float(self.mix.get("think_s", 0.0)), (i, r + 1))

    def max_rounds(self) -> int:
        return int(self.mix["rounds"][1]) if self.mix["kind"] == \
            "sessions" else 1

    def max_tokens(self) -> int:
        """The longest prompt + output any request of this mix can hold."""
        m = self.mix
        if m["kind"] == "requests":
            return int(m["prompt_tokens"][1] + m["output_tokens"][1])
        r = int(m["rounds"][1])
        return int(m["system_prompt_tokens"] + m["body_tokens"][1]
                   + r * m["output_tokens"][1]
                   + (r - 1) * m["feedback_tokens"][1])
