"""Reflection-aware continuous-batching inference engine.

The paper's three levers are first-class here:
  * reflection rounds — requests re-enter the scheduler per round with the
    same conversation_id; prefix caching makes each round's prefill cost
    proportional to its suffix (Appendix B.4);
  * prompt caching — serving/prefix_cache.py snapshots the per-layer
    decode cache at round completion AND at page-aligned chunk boundaries
    mid-prefill;
  * budget tuning — BudgetTier caps decode steps (thinking budgets).

Scheduling is CHUNKED-PREFILL CONTINUOUS BATCHING (docs/SERVING.md):
prompts and reflection-round prefix-cache suffix extensions are split
into fixed-width chunks and interleaved with in-flight decode tokens in
a SINGLE jitted mixed step — ``model.prefill_extend(..., n_valid)`` — so
a long arriving prompt never stalls decoding rows.  A per-step token
budget (``ServeConfig.prefill_token_budget``) bounds how much prefill
work rides along with each decode step, which is what bounds tail
decode-step latency.

KV memory is a PAGED POOL by default (``ServeConfig.paged_kv``;
docs/SERVING.md): attention layers share one head-major ``[num_pages,
kv_heads, page_size, head_dim]`` pool per layer and each request owns a page table
mapping logical pages (position // page_size) to physical pages.  The
page-pool design changes what the scheduler admits against — free pages
instead of fixed ring capacity:

  * prefill chunks shrink to the pages actually allocatable this step;
  * prompt-cache snapshots PIN pages by refcount (O(1), zero-copy) — a
    full-cache memcpy in the ring engine;
  * best-of-N / judge fan-out over a shared prompt maps N page tables
    onto one physical prefix; the first write past the shared region
    triggers copy-on-write of just the boundary page;
  * on exhaustion the youngest request is PREEMPTED — its pages are
    freed and it is requeued (never dropped), replaying prompt+output on
    re-admission so generation continues where it left off.

SELF-SPECULATIVE DECODING (``ServeConfig.spec_decode``;
docs/SERVING.md#speculative-decoding): reflection-round revisions overlap
heavily with the draft they revise, so a host-side n-gram drafter
(serving/speculator.py) proposes up to ``spec_tokens`` continuation
tokens per decode row by prompt-lookup over the request's own context,
and a third compiled step shape — the VERIFY step, ``prefill_extend(...,
all_logits=True)`` at width ``[max_batch, 1 + spec_tokens]`` — scores
all lanes in one model call.  The longest accepted prefix commits
(greedy: exact match, bit-identical to non-speculative decode;
temperature: exact rejection sampling in serving/sampler.py); rejected
lanes roll back by truncating page-table tails (pool invariants hold —
``PagePool.truncate_tail``) while their KV residue stays masked by
absolute position until overwritten.  Only committed tokens are billed,
and prefix snapshots publish only at accepted watermarks.  Drafted
lanes are charged against ``prefill_token_budget`` and prefill chunks
ride the verify step at its narrow width, so mixed draft/verify/prefill
steps stay bounded.

Recurrent layers (mamba/RG-LRU) have O(1) state with no paged
representation; they keep dense per-slot state and ride along in the
same cache pytree, and hybrid-model snapshots carry that state next to
the pinned page list.  ``paged_kv=False`` restores the dense ring
caches end-to-end (A/B baseline, and models without a paged layout,
e.g. whisper's cross-attention cache).

Per-request token accounting is Bedrock-compatible so the paper's cost
analysis reproduces.
"""
from __future__ import annotations

import contextlib
import functools
import time
from collections import deque
from typing import Any, Callable, Dict, List, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.configs.base import ModelConfig, ServeConfig
from repro.core.accounting import CostModel, LatencyModel
from repro.kernels import ops
from repro.launch import sharding as SH
from repro.launch.mesh import make_serve_mesh, mesh_chips
from repro.launch.rules import serve_rules
from repro.models import layers as L
from repro.serving import sampler
from repro.serving.page_pool import PagePool, PagedSnapshot
from repro.serving.prefix_cache import (PrefixCache, config_is_recurrent)
from repro.serving.request import (DEADLINE_EPS, BudgetTier, Request,
                                   Status, TokenUsage)
from repro.serving.speculator import (NGramSpeculator, draft_corpus,
                                      external_draft_proposal)

PyTree = Any

COPY_BATCH = 8      # COW page copies applied per jitted scatter call


def _named(fn: Callable, name: str) -> Callable:
    """``fn`` under the function name ``name``, which ``jax.jit`` gives
    the program (HLO module ``jit_<name>``, and its ops' ``op_name``);
    the argument names, and so the compiled program, stay ``fn``'s."""
    @functools.wraps(fn)
    def call(*args):
        return fn(*args)
    call.__name__ = call.__qualname__ = name
    return call


class _StepFn:
    """One engine step function with explicit compile accounting and AOT
    warmup (maxtext-style ``engine.aot_compile``).

    Wraps a ``jax.jit``-ed callable and keeps one compiled EXECUTABLE per
    dynamic-argument signature (shape+dtype of everything after the fixed
    params/cache state args): ``warm()`` lowers + compiles a signature
    ahead of time — dynamic args may be ShapeDtypeStructs — and
    ``__call__`` dispatches straight to the warmed executable.  A call
    whose signature was never warmed still works (compile-on-miss, the
    legacy JIT-on-first-call behavior) but increments ``compiles``: the
    recompile tripwire Engine.stats() surfaces, so shape drift can never
    silently reintroduce mid-serve compilation stalls.

    In mesh mode every call first ``device_put``s its args onto the
    expected shardings (a no-op for already-resident state): host-side
    eager cache edits (_set_slot_cache, snapshot adoption) can therefore
    never feed an executable a mismatched layout — AOT executables,
    unlike plain jit, reject rather than reshard.  Compilation happens
    under ``with mesh`` so in-model shard_activation constraints bind.

    Each call runs inside a ``serve.dispatch.<name>`` profiler span
    (argument placement and the executable's launch), and the program
    is jitted as ``serve_<name>`` (:func:`_named`), so a trace names its
    HLO module ``jit_serve_<name>`` (docs/SERVING.md#tracing).
    """

    def __init__(self, fn, name: str, n_fixed: int, mesh=None,
                 in_shardings=None):
        self._fn = fn
        self.name = name
        self._dispatch_span = f"serve.dispatch.{name}"
        self._n_fixed = n_fixed
        self._mesh = mesh
        self._in_sh = in_shardings
        self._exe: Dict[tuple, Any] = {}
        self.warmed = 0
        self.compiles = 0
        self.compile_s: List[float] = []

    @staticmethod
    def _key(dyn) -> tuple:
        return tuple((tuple(a.shape), jnp.dtype(a.dtype).name) for a in dyn)

    def _place(self, args):
        if self._mesh is None or self._in_sh is None:
            return args
        return tuple(jax.device_put(a, s)
                     for a, s in zip(args, self._in_sh))

    def _compile(self, args):
        t0 = time.perf_counter()
        ctx = self._mesh if self._mesh is not None else (
            contextlib.nullcontext())
        with ctx:
            exe = self._fn.lower(*args).compile()
        self.compile_s.append(time.perf_counter() - t0)
        return exe

    def warm(self, *args) -> None:
        """Pre-compile one signature; dynamic args may be abstract."""
        key = self._key(args[self._n_fixed:])
        if key not in self._exe:
            self._exe[key] = self._compile(args)
            self.warmed += 1

    def __call__(self, *args):
        with TraceAnnotation(self._dispatch_span):
            args = self._place(args)
            key = self._key(args[self._n_fixed:])
            exe = self._exe.get(key)
            if exe is None:
                exe = self._compile(args)
                self._exe[key] = exe
                self.compiles += 1
            return exe(*args)


class Engine:
    def __init__(self, model, params: PyTree, scfg: ServeConfig,
                 faults=None, clock: Optional[Callable[[], float]] = None,
                 mesh=None):
        self.model = model
        self.cfg: ModelConfig = model.cfg
        self.params = params
        self.scfg = scfg
        # Device mesh (docs/SERVING.md#sharded-serving): an explicit Mesh
        # wins, else ServeConfig.mesh ("DxM") builds one, else the legacy
        # single-device engine (None — bit-identical to every prior PR).
        self.mesh = mesh if mesh is not None else (
            make_serve_mesh(scfg.mesh) if scfg.mesh else None)
        self.n_devices = mesh_chips(self.mesh) if self.mesh is not None else 1
        self._serve_rules = serve_rules() if self.mesh is not None else None
        # Deterministic fault injection (serving/faults.py).  None (the
        # default) and a rate-0 plan are both bit-identical to the
        # un-instrumented engine — pinned by tests/test_faults.py.
        self.faults = faults
        # Clock for deadline enforcement: wall time by default, the fault
        # plan's VirtualClock when one is installed (chaos tests advance
        # time explicitly instead of sleeping).
        if clock is not None:
            self.clock = clock
        elif faults is not None:
            self.clock = faults.clock
        else:
            self.clock = time.monotonic
        B, S = scfg.max_batch, scfg.max_seq

        # single source of truth shared with the prefix cache: recurrent
        # state exists iff the block pattern carries mamba/rglru stages
        self._has_state = config_is_recurrent(self.cfg)
        self.prefix_cache = (PrefixCache(scfg.page_size, model_cfg=self.cfg)
                             if scfg.prefix_cache else None)

        kinds = set(self.cfg.block_pattern)
        self.paged = bool(scfg.paged_kv
                          and hasattr(model, "cache_defs_paged"))
        # KV storage dtype: ServeConfig overrides the model default.
        # "model" keeps the PR-2 fp layout bit-identically; "int8"
        # quantizes K/V pages at write time (scale sidecars travel with
        # their pages — docs/SERVING.md#quantized-kv-cache-int8).
        self.kv_dtype = scfg.kv_dtype or self.cfg.kv_dtype
        # Paged-attention read implementation: the Pallas page-table-
        # walking kernel on one TPU device, XLA gather densify elsewhere
        # (interpret-mode Pallas is a correctness tool, not a serving
        # path).  Static per engine — it is baked into the jitted step
        # closures below.  Under a >1-device mesh auto picks the XLA
        # gather path, which GSPMD partitions along the pool's sharded
        # 'pages' axis; an explicit "pallas" there raises (the kernel has
        # no shard_map wrapper yet — kernels/ops.resolve_attn_impl).
        self.attn_impl = ops.resolve_attn_impl(scfg.attn_impl,
                                               self.n_devices)
        if self.paged:
            ps = scfg.page_size
            self.pages_per_seq = -(-S // ps)
            num_pages = scfg.num_pages or B * self.pages_per_seq
            if self.n_devices > 1:
                # round the pool up to a multiple of the 'model' axis so
                # the pages dim shards evenly (spec_for would otherwise
                # silently replicate the whole pool); extra pages only
                # ever add headroom
                m_ax = dict(zip(self.mesh.axis_names,
                                self.mesh.devices.shape)).get("model", 1)
                num_pages = -(-num_pages // m_ax) * m_ax
            if num_pages < self.pages_per_seq:
                raise ValueError(
                    f"num_pages={num_pages} cannot hold one max_seq request "
                    f"({self.pages_per_seq} pages)")
            self.pool = PagePool(num_pages, ps)
            # logical page -> physical page, per slot (-1 = unmapped)
            self.page_tables = np.full((B, self.pages_per_seq), -1, np.int64)
            defs = model.cache_defs_paged(B, num_pages, ps,
                                          kv_dtype=self.kv_dtype)
            # Paged lanes have no ring aliasing (every position is a
            # distinct page slot), so the mixed-step width is bounded only
            # by max_seq — no capacity clamp.
            self.chunk = max(1, min(scfg.prefill_chunk, S))
            # When EVERY attention-bearing layer is windowed, pages whose
            # tokens have slid out of the narrowest window can never be
            # attended again — free them as the request advances, keeping
            # resident pages O(window) instead of O(extent) (the ring
            # baseline's [B, window] footprint, without its aliasing).
            wins = []
            for k in kinds:
                if k == "rg_attn":
                    wins.append(self.cfg.local_window)
                elif k in ("attn", "moe"):
                    wins.append(self.cfg.sliding_window)
            # a page is dead only once it leaves the WIDEST window — every
            # layer shares one page table, so the narrowest layer's dead
            # tokens may still be attendable by a wider-window layer
            self._window_free = (max(wins) if wins and None not in wins
                                 else None)
        else:
            self.pool = None
            self.page_tables = None
            self._window_free = None
            defs = model.cache_defs(B, S, seq_shard=False,
                                    kv_dtype=self.kv_dtype)
            # Mixed-step lane width: besides max_seq, it must never exceed
            # the smallest attention ring capacity — with more lanes than
            # slots a chunk would overwrite ring entries BEFORE its own
            # lanes attend to them ("last-wins" aliasing), silently
            # breaking exactness.
            cap = S
            if hasattr(model, "attn_capacity"):
                cap = min(cap, model.attn_capacity(S))
            if "rg_attn" in kinds:
                cap = min(cap, self.cfg.local_window)
            self.chunk = max(1, min(scfg.prefill_chunk, cap))
            self._ring_cap = cap
        # Per-step fresh-prefill token budget.
        self.prefill_budget = max(1, scfg.prefill_token_budget)
        # Mixed-step width buckets: each mixed step runs at the smallest
        # pre-compilable width that fits its planned chunks, so prefill
        # bursts of any size hit a warmed executable.  The full chunk
        # width is always the last bucket — without scfg.prefill_buckets
        # this is exactly the legacy single-width step.
        self._mixed_buckets = sorted(
            {max(1, min(int(w), self.chunk)) for w in scfg.prefill_buckets}
            | {self.chunk})

        # SLO-aware admission (docs/SERVING.md#slo-routing): price a
        # queued request's predicted tokens against its own ceilings.
        # None = check disabled (bit-identical admission).
        self.cost_model = (CostModel.for_model(scfg.slo_price_model)
                           if scfg.slo_price_model else None)
        self.latency_model = (LatencyModel.for_model(scfg.slo_price_model)
                              if scfg.slo_price_model else None)

        # ---- self-speculative decoding (docs/SERVING.md) ------------------
        # Gates, in order: the model must expose the all-lane verify path
        # (prefill_extend(..., all_logits=True)); recurrent state (mamba/
        # RG-LRU) mutates irreversibly, so a rejected draft could not be
        # rolled back; a capacity-clamped RING cache is unsafe because a
        # rejected lane's ring write EVICTS a live in-window token (paged
        # caches have no aliasing — every position owns a distinct
        # (page, offset) slot — so the default engine supports every
        # attention/MoE arch).
        self.spec = (bool(scfg.spec_decode)
                     and getattr(model, "supports_verify", False)
                     and not self._has_state
                     and (self.paged or self._ring_cap == S))
        self.spec_tokens = max(1, min(scfg.spec_tokens, S - 1))
        self.speculator = (NGramSpeculator(scfg.spec_ngram,
                                           scfg.spec_ngram_min)
                          if self.spec else None)

        self.cache_defs = defs
        self.cache = L.init_empty_cache(defs)
        # pristine single-row cache: admission resets a slot with this so
        # no stale entries of the previous occupant survive.  In paged
        # mode only the dense (batch-axis) leaves matter — pool leaves are
        # shared and masked by the page table, so the blank uses a
        # 1-page dummy pool that _set_slot_cache skips.
        self._blank_row = L.init_empty_cache(
            model.cache_defs_paged(1, 1, 1, kv_dtype=self.kv_dtype)
            if self.paged
            else model.cache_defs(1, S, seq_shard=False,
                                  kv_dtype=self.kv_dtype))
        # Mesh placement: params get the tensor-parallel serve rules, the
        # cache its logical-axis layout (paged pool leaves shard by
        # physical page along 'model', dense per-slot state along the
        # trivial 'data' axis), and the blank row replicates — eager
        # slot resets mix it with sharded leaves, so it must live on the
        # same device set.
        if self.mesh is not None:
            params_sh, cache_sh = SH.serve_state_shardings(
                model.param_defs(), defs, self.mesh, self._serve_rules)
            rep = SH.replicated(self.mesh)
            self.params = jax.device_put(self.params, params_sh)
            self.cache = jax.device_put(self.cache, cache_sh)
            self._blank_row = jax.tree_util.tree_map(
                lambda x: jax.device_put(x, rep), self._blank_row)
            self._cache_sh = cache_sh
        else:
            params_sh = cache_sh = rep = None
            self._cache_sh = None
        # bytes of one physical page across every layer's pool (snapshot
        # accounting)
        self._page_nbytes = 0
        if self.paged:
            for leaf, d in zip(
                    jax.tree_util.tree_leaves(self.cache),
                    L.tree_defs(self.cache_defs)):
                if "pages" in d.axes:
                    self._page_nbytes += (leaf.size * leaf.dtype.itemsize
                                          // leaf.shape[d.axes.index("pages")])

        self.slots: List[Optional[Request]] = [None] * B
        self.pos = np.zeros(B, np.int64)
        self.next_token = np.zeros(B, np.int64)
        self.queue: deque[Request] = deque()
        # uid -> request for queued/in-flight only (pruned at completion
        # so a long-running server does not retain every request ever);
        # finished is a bounded notification buffer drained by poll()
        self.requests: Dict[int, Request] = {}
        self.finished: deque[Request] = deque(maxlen=max(64, 16 * B))
        self.rng = jax.random.PRNGKey(scfg.seed)
        self._ff_version = -1   # prefix-cache version at last fast-forward
        self._admit_counter = 0
        self._pending_copies: List[Tuple[int, int]] = []   # COW (src, dst)
        # Stall detector state: _progress_seq bumps on every commit /
        # prefill advance / admission / finalize; a step that moves it
        # nowhere while rows are in flight counts toward stall_limit.
        self._progress_seq = 0
        self._no_progress = 0
        self.model_steps = {"prefill_tokens": 0, "extend_tokens": 0,
                            "decode_steps": 0, "decode_batch_steps": 0,
                            "decode_tokens": 0,
                            "mixed_steps": 0, "prefill_chunks": 0,
                            "max_step_prefill_tokens": 0, "preemptions": 0,
                            "starved_mixed_steps": 0,
                            "verify_steps": 0, "spec_drafted": 0,
                            "spec_accepted": 0, "slo_rejections": 0,
                            "timeouts": 0, "stalls": 0, "errors": 0,
                            "nan_quarantines": 0, "crash_recoveries": 0,
                            "stuck_rows": 0}

        # Step executables.  Every step fn is wrapped in _StepFn: compile
        # accounting (the recompile tripwire in stats()) + per-signature
        # AOT warmup via aot_compile().  In mesh mode each carries
        # explicit in/out shardings — params/cache at their resident
        # layout, dynamic host args replicated, logits gathered
        # replicated (they go to the host for sampling anyway), and the
        # donated cache output pinned to its input layout so residency
        # never drifts across steps.
        def _mk(fn, name, n_dyn, donate):
            fn = _named(fn, f"serve_{name}")
            if self.mesh is None:
                jit = jax.jit(fn, donate_argnums=(donate,))
                return _StepFn(jit, name, n_fixed=donate + 1)
            if name == "copy":
                in_sh = (cache_sh,) + (rep,) * n_dyn
                out_sh = cache_sh
            else:
                in_sh = (params_sh, cache_sh) + (rep,) * n_dyn
                out_sh = (rep, cache_sh)
            jit = jax.jit(fn, donate_argnums=(donate,),
                          in_shardings=in_sh, out_shardings=out_sh)
            return _StepFn(jit, name, n_fixed=donate + 1, mesh=self.mesh,
                           in_shardings=in_sh)

        if self.paged:
            impl = self.attn_impl
            self._decode = _mk(
                lambda p, c, t, pos, pt: model.decode_step(
                    p, c, t, pos, page_table=pt, attn_impl=impl),
                "decode", n_dyn=3, donate=1)
            self._mixed = _mk(
                lambda p, c, t, pos0, nv, pt: model.prefill_extend(
                    p, c, t, pos0, n_valid=nv, page_table=pt,
                    attn_impl=impl),
                "mixed", n_dyn=4, donate=1)
            self._copy = _mk(self._copy_pages_fn, "copy", n_dyn=2, donate=0)
            if self.spec:
                self._verify = _mk(
                    lambda p, c, t, pos0, nv, pt: model.prefill_extend(
                        p, c, t, pos0, n_valid=nv, page_table=pt,
                        all_logits=True, attn_impl=impl),
                    "verify", n_dyn=4, donate=1)
        else:
            self._decode = _mk(
                lambda p, c, t, pos: model.decode_step(p, c, t, pos),
                "decode", n_dyn=2, donate=1)
            self._mixed = _mk(
                lambda p, c, t, pos0, nv: model.prefill_extend(
                    p, c, t, pos0, n_valid=nv),
                "mixed", n_dyn=3, donate=1)
            if self.spec:
                self._verify = _mk(
                    lambda p, c, t, pos0, nv: model.prefill_extend(
                        p, c, t, pos0, n_valid=nv, all_logits=True),
                    "verify", n_dyn=3, donate=1)

        # Startup AOT compilation (docs/SERVING.md#sharded-serving):
        # compile every reachable step shape before the first request so
        # the serve loop never JITs mid-traffic.
        self.compile_stats: Dict[str, Any] = {}
        if scfg.aot_warmup:
            self.aot_compile()

    # ------------------------------------------------------------------ API

    def submit(self, req: Request) -> int:
        """Enqueue a request (non-blocking).  Returns its uid for poll().

        Malformed requests — empty prompt, or a prompt + budget cap that
        cannot fit in max_seq — finalize immediately with stop_reason
        "error" instead of poisoning the batch: they surface through
        poll()/finished like any other completion, and the rest of the
        batch is unaffected.
        """
        req.submitted_at = self.clock()
        if not req.prompt:
            self._finalize_abnormal(req, None, "error", "empty prompt")
            return req.uid
        if len(req.prompt) + self._budget_cap(req) >= self.scfg.max_seq:
            self._finalize_abnormal(
                req, None, "error",
                f"prompt ({len(req.prompt)}) + budget cap "
                f"({self._budget_cap(req)}) would overflow "
                f"max_seq ({self.scfg.max_seq})")
            return req.uid
        self.queue.append(req)
        self.requests[req.uid] = req
        return req.uid

    def poll(self, uid: Optional[int] = None
             ) -> Union[Status, List[Request]]:
        """One cooperative scheduler tick.

        With ``uid``: advance the engine one step (if it has work) and
        return that request's status.  Without: advance one step and
        return the requests that finished during it.  Callers loop on
        poll() instead of blocking in run() — this is what lets a
        reflection controller interleave rounds of many conversations.
        """
        self.step()
        if uid is not None:
            req = self.requests.get(uid)
            # completed requests are pruned from the registry; an unknown
            # uid here was either never submitted (caller bug surfaces as
            # DONE-without-output) or already finished
            return req.status if req is not None else Status.DONE
        done = list(self.finished)
        self.finished.clear()
        return done

    def run(self, max_steps: int = 100_000) -> None:
        """Drive the scheduler until fully idle (blocking convenience)."""
        for _ in range(max_steps):
            if not self.step():
                break

    # --------------------------------------------- AOT warmup + statistics

    def _step_fns(self) -> Dict[str, _StepFn]:
        fns = {"decode": self._decode, "mixed": self._mixed}
        if self.paged:
            fns["copy"] = self._copy
        if self.spec:
            fns["verify"] = self._verify
        return fns

    def aot_compile(self) -> Dict[str, Any]:
        """Lower + compile every step executable the serve loop can reach
        (maxtext-style startup AOT): the [B, 1] decode step, the mixed
        prefill+decode step at every bucket width, the [B, 1+spec_tokens]
        verify step, and the COW page-copy scatter — plus throwaway
        executions of the host-facing sampler jits and the rng split, so
        steady-state traffic triggers ZERO compilations (the tripwire in
        stats()).  Idempotent; returns per-fn compile-second stats."""
        t0 = time.perf_counter()
        B = self.scfg.max_batch

        def sds(*shape):
            return jax.ShapeDtypeStruct(shape, jnp.int32)

        state = (self.params, self.cache)
        pt = (sds(B, self.pages_per_seq),) if self.paged else ()
        self._decode.warm(*state, sds(B, 1), sds(B), *pt)
        for w in self._mixed_buckets:
            self._mixed.warm(*state, sds(B, w), sds(B), sds(B), *pt)
        if self.spec:
            self._verify.warm(*state, sds(B, 1 + self.spec_tokens),
                              sds(B), sds(B), *pt)
        if self.paged:
            self._copy.warm(self.cache, sds(COPY_BATCH), sds(COPY_BATCH))

        # Host-facing jits outside _StepFn: the batched sampler, the
        # verify accept/reject kernel, and the per-step rng split.  Cheap
        # throwaway executions at the exact serving avals (logits arrive
        # as host arrays in mesh mode — _host_logits — and as device
        # arrays otherwise, but the aval, hence the compile cache key,
        # is identical).
        V, dt = self.cfg.vocab_size, jnp.dtype(self.cfg.dtype)
        key = jax.random.PRNGKey(0)
        _, k = jax.random.split(key)
        temps = jnp.zeros(B, jnp.float32)
        sampler.sample_batch(jnp.zeros((B, V), dt), k, temps)
        if self.spec:
            W = 1 + self.spec_tokens
            sampler.verify_batch(jnp.zeros((B, W, V), dt),
                                 jnp.zeros((B, W), jnp.int32),
                                 jnp.ones(B, jnp.int32),
                                 jnp.zeros(B, jnp.int32), k, temps)

        self.compile_stats = {
            "startup_compile_s": time.perf_counter() - t0,
            "per_fn_compile_s": {n: list(f.compile_s)
                                 for n, f in self._step_fns().items()},
            "warmed": {n: f.warmed for n, f in self._step_fns().items()},
        }
        return self.compile_stats

    def compiled_steps(self) -> List[Any]:
        """Every compiled step executable (``jit_serve_*``); the
        ``op_name`` metadata of their HLO names the model scope of each
        device op in a profiler trace (docs/SERVING.md#tracing)."""
        return [exe for f in self._step_fns().values()
                for exe in f._exe.values()]

    def _kv_stats(self) -> Dict[str, Any]:
        """Resident-KV accounting, global and per device.  Pool leaves
        count only their USED pages (the pool is a capacity, not a
        residency); dense per-slot leaves are always resident.  The
        per-device number reads each leaf's actual shard shape, so it
        reflects whatever placement the mesh rules resolved (pages
        sharded along 'model', dense state replicated)."""
        total = per_dev = 0
        used_frac = self.pool.utilization() if self.paged else 1.0
        for leaf, d in zip(jax.tree_util.tree_leaves(self.cache),
                           L.tree_defs(self.cache_defs)):
            nb = leaf.size * leaf.dtype.itemsize
            snb = (int(np.prod(leaf.sharding.shard_shape(leaf.shape)))
                   * leaf.dtype.itemsize)
            frac = used_frac if "pages" in d.axes else 1.0
            total += int(nb * frac)
            per_dev += int(snb * frac)
        out = {"resident_kv_bytes": total,
               "resident_kv_bytes_per_device": per_dev}
        if self.paged:
            out["kv_pool_pages_used"] = self.pool.used_pages
            out["kv_pool_pages"] = self.pool.num_pages
        return out

    def stats(self) -> Dict[str, Any]:
        """Serving counters + the recompile tripwire.  After
        aot_compile(), steady traffic must keep ``step_compiles`` at 0 —
        any positive value means a step shape escaped warmup (asserted
        by tests/test_engine_fuzz.py)."""
        fns = self._step_fns()
        out = dict(self.model_steps)
        out["step_compiles"] = sum(f.compiles for f in fns.values())
        out["step_compiles_by_fn"] = {n: f.compiles for n, f in fns.items()}
        out["aot_warmed"] = sum(f.warmed for f in fns.values())
        out["startup_compile_s"] = self.compile_stats.get(
            "startup_compile_s", 0.0)
        out["n_devices"] = self.n_devices
        out["mesh"] = (dict(zip(self.mesh.axis_names,
                                self.mesh.devices.shape))
                       if self.mesh is not None else None)
        out["attn_impl"] = self.attn_impl
        out.update(self._kv_stats())
        if self.prefix_cache is not None:
            out["prefix_cache"] = self.prefix_cache.stats_snapshot()
        return out

    def stats_snapshot(self) -> Dict[str, Any]:
        """Compact per-replica counters for fleet aggregation
        (serving/fleet.py): the scheduler counters that sum meaningfully
        across replicas, live occupancy, and the prefix cache's own
        snapshot.  stats() remains the full single-engine diagnostic view
        (mesh/AOT/KV accounting, recompile tripwire)."""
        out = {k: self.model_steps[k] for k in
               ("prefill_tokens", "extend_tokens", "decode_tokens",
                "preemptions", "slo_rejections", "timeouts", "stalls",
                "errors")}
        out["in_flight"] = sum(r is not None for r in self.slots)
        out["queued"] = len(self.queue)
        if self.paged:
            out["kv_pool_pages_used"] = self.pool.used_pages
            out["kv_pool_pages"] = self.pool.num_pages
        if self.prefix_cache is not None:
            out["prefix_cache"] = self.prefix_cache.stats_snapshot()
        return out

    def _host_logits(self, logits):
        """Mesh mode fetches logits to host before sampling: the sampler
        jits are plain module-level functions whose other args (rng key)
        live on device 0, and jax refuses computations whose committed
        inputs span different device sets.  out_shardings pin logits
        replicated, so this is one local copy, no cross-device gather."""
        return np.asarray(logits) if self.mesh is not None else logits

    # ----------------------------------------------------------- internals

    def _budget_cap(self, req: Request) -> int:
        caps = {BudgetTier.NONE: req.max_new_tokens,
                BudgetTier.LOW: self.scfg.max_think_tokens_low,
                BudgetTier.HIGH: self.scfg.max_think_tokens_high}
        return min(req.max_new_tokens, caps[req.budget])

    def _slot_cache(self, slot: int) -> PyTree:
        """Slice one request's PER-SLOT cache state (batch axis position
        varies per leaf: scan-stacked caches are [layers, B, ...], tail
        caches [B, ...]).  Shared page-pool leaves have no batch axis and
        come back as empty placeholders — in paged mode this function
        yields exactly the dense recurrent/conv state of the slot."""

        def take(x, d):
            if "batch" not in d.axes:
                return jnp.zeros((0,), x.dtype)        # shared pool leaf
            ax = d.axes.index("batch")
            return jax.lax.slice_in_dim(x, slot, slot + 1, axis=ax)

        return jax.tree_util.tree_map(take, self.cache, self.cache_defs)

    def _set_slot_cache(self, slot: int, c1: PyTree) -> None:
        def put(full, one, d):
            if "batch" not in d.axes:
                return full                            # shared pool leaf
            ax = d.axes.index("batch")
            idx = tuple(slice(None) for _ in range(ax)) + (slot,)
            return full.at[idx].set(jnp.squeeze(one, axis=ax))

        self.cache = jax.tree_util.tree_map(put, self.cache, c1,
                                            self.cache_defs)

    # -------------------------------------------------- page-pool plumbing

    def _copy_pages_fn(self, cache: PyTree, src: jax.Array, dst: jax.Array
                       ) -> PyTree:
        """Device-side COW: copy pool pages src -> dst in every layer.
        src/dst: [COPY_BATCH] int32; pad pairs use dst >= num_pages
        (dropped by the scatter)."""

        def cp(leaf, d):
            if "pages" not in d.axes:
                return leaf
            ax = d.axes.index("pages")                 # 0 (tail) or 1 (scan)
            taken = jnp.take(leaf, src, axis=ax)       # OOB pad src clamps
            idx = tuple(slice(None) for _ in range(ax)) + (dst,)
            return leaf.at[idx].set(taken, mode="drop")

        return jax.tree_util.tree_map(cp, cache, self.cache_defs)

    def _flush_copies(self) -> None:
        """Apply scheduled COW page copies before this step's writes."""
        P = self.pool.num_pages
        while self._pending_copies:
            batch = self._pending_copies[:COPY_BATCH]
            del self._pending_copies[:COPY_BATCH]
            src = np.zeros(COPY_BATCH, np.int32)
            dst = np.full(COPY_BATCH, P, np.int32)     # pad -> dropped
            for i, (s, t) in enumerate(batch):
                src[i], dst[i] = s, t
            self.cache = self._copy(self.cache, src, dst)

    def _release_slot_pages(self, slot: int) -> None:
        pages = [int(p) for p in self.page_tables[slot] if p >= 0]
        if pages and self._pending_copies:
            # Drop scheduled COW copies targeting this slot's pages: a COW
            # dst is solely owned, so release frees it — and a freed page
            # can be re-allocated as another slot's COW dst within the
            # same tick, which would otherwise put duplicate dst indices
            # into one scatter batch (undefined ordering = silent KV
            # corruption of the new owner).
            mine = set(pages)
            self._pending_copies = [(s, d) for (s, d) in self._pending_copies
                                    if d not in mine]
        if pages:
            self.pool.decref(pages)
        self.page_tables[slot, :] = -1

    def _alloc_page(self, protect: int) -> Optional[int]:
        """One free page, reclaiming under pressure: first evict prompt-
        cache entries (cheap to lose — recomputable), then preempt the
        youngest-admitted request (requeued, never dropped).  ``protect``
        is the slot asking — it is never its own victim."""
        while True:
            pg = self.pool.alloc()
            if pg is not None:
                return pg
            if self.prefix_cache is not None and self.prefix_cache.evict_lru():
                continue
            if self._preempt_one(protect):
                continue
            return None

    def _preempt_one(self, protect: int) -> bool:
        """Preempt the youngest request that is YOUNGER than the one
        asking for pages (strict FIFO: a late arrival never steals pages
        from an earlier request — it waits for them to free instead).
        This also guarantees a slot already planned this step is never
        yanked out from under the plan: planning runs oldest-first."""
        asking = self.slots[protect]
        pseq = asking.admit_seq if asking is not None else -1
        cands = [i for i, r in enumerate(self.slots)
                 if r is not None and i != protect and r.admit_seq > pseq]
        if not cands:
            return False
        victim = max(cands, key=lambda i: self.slots[i].admit_seq)
        self._preempt_slot(victim)
        return True

    def _preempt_slot(self, slot: int) -> None:
        """Evict a request from the pool and requeue it at the FRONT of
        the admission queue.  Its generated tokens survive: re-admission
        replays prompt+output, restoring the decode state exactly."""
        req = self.slots[slot]
        if self.paged:
            # ring mode reaches here only via NaN quarantine; the slot's
            # dense cache is reset at re-admission
            self._release_slot_pages(slot)
        if req.status is Status.DECODING:
            # decode positions were billed as output; the replay must not
            # re-bill them as input (prefilling victims keep their mark:
            # positions past it were never billed at all)
            req.billed_prefill = max(req.billed_prefill,
                                     len(req.prompt) + len(req.output))
        req.status = Status.QUEUED
        req.prefill_pos = 0
        req.cached_len = 0
        req.prefill_target = None
        req.preemptions += 1
        self.model_steps["preemptions"] += 1
        self.queue.appendleft(req)
        self.slots[slot] = None

    def _ensure_range(self, slot: int, p0: int, n: int) -> int:
        """Map (alloc / copy-on-write) every logical page the token range
        [p0, p0+n) touches.  Returns how many of the n tokens are actually
        backed by writable pages — the planner shrinks the chunk to this."""
        ps = self.pool.page_size
        first, last = p0 // ps, (p0 + n - 1) // ps
        for lpage in range(first, last + 1):
            pg = int(self.page_tables[slot, lpage])
            if pg >= 0 and not self.pool.needs_cow(pg):
                continue
            new = self._alloc_page(protect=slot)
            if new is None:
                return max(0, lpage * ps - p0)
            if pg >= 0:
                # copy-on-write: the boundary page is shared (prefix-cache
                # pin or a fan-out sibling) — divergent writes get a copy
                self._pending_copies.append((pg, new))
                self.pool.stats["cow_copies"] += 1
                self.pool.decref([pg])
            self.page_tables[slot, lpage] = new
        return n

    def _free_out_of_window(self, slot: int, extent: int) -> None:
        """Release pages that can never be attended again: with every
        attention layer windowed, future queries sit at positions >=
        ``extent`` and attend only tokens > extent - window."""
        if self._window_free is None:
            return
        ps = self.pool.page_size
        nfree = max(0, extent - self._window_free + 1) // ps
        for lpage in range(min(nfree, self.pages_per_seq)):
            pg = int(self.page_tables[slot, lpage])
            if pg >= 0:
                self.pool.decref([pg])
                self.page_tables[slot, lpage] = -1

    def _ensure_decode_pages(self, drafts: Optional[Dict[int, List[int]]]
                             = None) -> None:
        """Every DECODING row writes one token this step — plus its
        drafted continuation when speculating; make those pages writable
        first (a fresh page at each page boundary, a COW copy at the
        first write past a shared prefix).  Oldest rows first so pool
        pressure preempts the youngest.  Under pressure a row's DRAFT
        shrinks to the tokens its pages can actually back — the
        committed-token lane always comes first, so speculation degrades
        to plain decode before anyone is preempted for draft pages."""
        rows = sorted(
            (i for i, r in enumerate(self.slots)
             if r is not None and r.status is Status.DECODING),
            key=lambda i: self.slots[i].admit_seq)
        for slot in rows:
            if self.slots[slot] is None:               # preempted meanwhile
                continue
            d = drafts.get(slot) if drafts else None
            want = 1 + (len(d) if d else 0)
            got = self._ensure_range(slot, int(self.pos[slot]), want)
            if got == 0:
                # nothing reclaimable: this row itself must wait its turn
                self._preempt_slot(slot)
                if drafts:
                    drafts.pop(slot, None)
            elif d and got < want:
                if got <= 1:
                    drafts.pop(slot)
                else:
                    drafts[slot] = d[:got - 1]

    # ---------------------------------------------- snapshots (paged+ring)

    def _make_snapshot(self, slot: int, n_tokens: int) -> PagedSnapshot:
        ps = self.pool.page_size
        npages = -(-n_tokens // ps)
        pages = [int(p) for p in self.page_tables[slot, :npages]]
        # windowed models free slid-out pages (-1 entries): the snapshot
        # stays usable — an adopter's queries can never attend them either
        live = [p for p in pages if p >= 0]
        assert self._window_free is not None or len(live) == npages, \
            "snapshot of unmapped pages"
        self.pool.incref(live)
        rec = self._slot_cache(slot) if self._has_state else None
        rec_nbytes = 0
        if rec is not None:
            rec_nbytes = sum(x.size * x.dtype.itemsize
                             for x in jax.tree_util.tree_leaves(rec))
        return PagedSnapshot(pages=pages, n_tokens=n_tokens, recurrent=rec,
                             nbytes=len(live) * self._page_nbytes + rec_nbytes,
                             meta={"page_nbytes": self._page_nbytes,
                                   "rec_nbytes": rec_nbytes})

    def _insert_snapshot(self, tokens: List[int], slot: int,
                         boundary: bool = False) -> None:
        """Publish a prefix snapshot: page pins in paged mode (O(1)), a
        cache copy in ring mode."""
        if not tokens:
            return
        if self.paged:
            snap = self._make_snapshot(slot, len(tokens))
            on_evict = (lambda pages=tuple(p for p in snap.pages if p >= 0):
                        self.pool.decref(pages))
            if boundary:
                self.prefix_cache.insert_boundary(list(tokens), snap,
                                                  on_evict)
            else:
                self.prefix_cache.insert(list(tokens), snap, on_evict)
        else:
            cache1 = self._slot_cache(slot)
            if boundary:
                self.prefix_cache.insert_boundary(list(tokens), cache1)
            else:
                self.prefix_cache.insert(list(tokens), cache1)

    def _adopt_snapshot(self, slot: int, snap: PagedSnapshot,
                        cached: int) -> None:
        """Map a snapshot's physical pages into this slot's table (shared,
        refcounted) and restore dense recurrent state for hybrid models."""
        ps = self.pool.page_size
        npages = -(-cached // ps)
        pages = snap.pages[:npages]
        self.pool.incref([p for p in pages if p >= 0])
        self.page_tables[slot, :npages] = pages
        if snap.recurrent is not None:
            # recurrent state summarizes exactly n_tokens; the lookup
            # rules guarantee untrimmed full hits for stateful models
            assert cached == snap.n_tokens, (cached, snap.n_tokens)
            self._set_slot_cache(slot, snap.recurrent)

    # ------------------------------------------------------------ admission

    def _slo_reject(self, req: Request) -> bool:
        """Deadline/cost-aware admission: finalize a fresh request whose
        ceilings cannot fund its own predicted tokens (prefill at the
        prefix-cache hit length it would get right now, decode at its
        full budget cap — the worst case it may bill), freeing pages and
        step budget for requests that can still finish inside their
        SLOs.  Only fresh requests are checked: a preempted replay's
        work already happened and must be resumed, and the reflection
        controller re-prices each ROUND as its own request, so the check
        is exactly the paper's per-round funding decision."""
        if self.cost_model is None or req.preemptions or req.output:
            return False
        if req.max_cost_usd is None and req.max_latency_s is None:
            return False
        cached = 0
        if self.prefix_cache is not None:
            # peek: a pure length estimate — the admission check must not
            # inflate hit stats or refresh LRU order (the real lookup
            # happens at _admit for requests that pass)
            res = self.prefix_cache.lookup(list(req.prompt),
                                           record_miss=False, peek=True)
            cached = min(res.cached_len, len(req.prompt) - 1)
        fresh = len(req.prompt) - cached
        pred = TokenUsage(input_tokens=fresh, cache_read_tokens=cached,
                          cache_write_tokens=fresh,
                          output_tokens=self._budget_cap(req))
        cost = self.cost_model.cost(pred)
        lat = self.latency_model.latency(pred)
        if ((req.max_cost_usd is None or cost <= req.max_cost_usd + 1e-12)
                and (req.max_latency_s is None
                     or lat <= req.max_latency_s + DEADLINE_EPS)):
            return False
        req.status = Status.DONE
        req.stop_reason = "slo"
        req.decision_trace.append(
            {"action": "finalize", "reason": "slo",
             "pred_cost_usd": cost, "pred_latency_s": lat,
             "max_cost_usd": req.max_cost_usd,
             "max_latency_s": req.max_latency_s})
        self.model_steps["slo_rejections"] += 1
        self._progress_seq += 1
        self.finished.append(req)
        self.requests.pop(req.uid, None)
        return True

    # ------------------------------------------- reliability (faults.py)

    def _finalize_abnormal(self, req: Request, slot: Optional[int],
                           reason: str, detail: Optional[str] = None) -> None:
        """Terminal finalize outside the normal eos/budget path: billing
        stays frozen at the committed watermark (nothing here touches
        usage), pages are refcount-released, and the caller sees a
        definite stop_reason ("timeout" / "stalled" / "error")."""
        req.status = Status.DONE
        req.stop_reason = reason
        if detail is not None:
            req.error = detail
        rec = {"action": "finalize", "reason": reason}
        if detail is not None:
            rec["detail"] = detail
        req.decision_trace.append(rec)
        self.model_steps[{"timeout": "timeouts", "stalled": "stalls",
                          "error": "errors"}[reason]] += 1
        self._progress_seq += 1
        self.finished.append(req)
        self.requests.pop(req.uid, None)
        if slot is not None:
            if self.paged:
                self._release_slot_pages(slot)
            self.slots[slot] = None

    def _enforce_deadlines(self) -> None:
        """Finalize every queued or in-flight request whose max_latency_s
        has elapsed (stop_reason "timeout").  Partial output survives;
        billing was only ever advanced at committed watermarks, so a
        timed-out request is billed exactly the work it received."""
        now = self.clock()

        def expired(r: Request) -> bool:
            # same epsilon as admission (DEADLINE_EPS): a request accepted
            # exactly at its deadline must not time out on its first tick
            return (r.max_latency_s is not None
                    and r.submitted_at is not None
                    and now - r.submitted_at > r.max_latency_s + DEADLINE_EPS)

        if any(expired(r) for r in self.queue):
            keep: deque[Request] = deque()
            while self.queue:
                r = self.queue.popleft()
                if expired(r):
                    self._finalize_abnormal(r, None, "timeout")
                else:
                    keep.append(r)
            self.queue = keep
        for slot, r in enumerate(self.slots):
            if r is not None and expired(r):
                self._finalize_abnormal(r, slot, "timeout")

    def _nonfinite_rows(self, logits, rows: List[int],
                        nv: Optional[np.ndarray] = None,
                        ndraft: Optional[np.ndarray] = None) -> List[int]:
        """Rows (among ``rows``) whose CONSUMED logit lanes are not
        finite.  Lanes that are never consumed — nv=0 no-op lanes,
        verify-step padding past nv — are excluded: fully masked
        attention can legitimately produce NaN there."""
        if not rows or not self.scfg.nan_quarantine:
            return []
        fin = np.asarray(jnp.all(jnp.isfinite(logits), axis=-1))
        bad = []
        for s in rows:
            if fin.ndim == 1:
                ok = bool(fin[s])
            elif ndraft is not None and ndraft[s] > 0:
                # verify window: lanes [0, nv) are scored for acceptance
                ok = bool(fin[s, :nv[s]].all())
            else:
                ok = bool(fin[s, nv[s] - 1])
            if not ok:
                bad.append(s)
        return bad

    def _quarantine_rows(self, bad: List[int]) -> None:
        """Non-finite logits: skip the row's commit this step and replay
        it through the PR-2 preemption path (prefix-cache snapshots +
        billed_prefill watermark mean no recomputed token is ever billed
        twice).  Bounded per request by nan_retry_limit, after which the
        request finalizes with stop_reason "error"."""
        for slot in bad:
            req = self.slots[slot]
            if req is None:
                continue
            req.nan_retries += 1
            self.model_steps["nan_quarantines"] += 1
            req.decision_trace.append(
                {"action": "fault", "kind": "nan_quarantine",
                 "retries": req.nan_retries})
            if req.nan_retries > self.scfg.nan_retry_limit:
                self._finalize_abnormal(
                    req, slot, "error",
                    "non-finite logits persisted past nan_retry_limit")
            else:
                self._preempt_slot(slot)

    def _mark_stuck(self) -> None:
        """Fault hook ("engine.stuck"): one decoding row stops committing
        tokens — its lane still runs, nothing lands.  Reaped by the stall
        detector (or its own deadline)."""
        rows = [i for i, r in enumerate(self.slots)
                if r is not None and r.status is Status.DECODING
                and not r.stuck]
        if not rows:
            return
        req = self.slots[rows[self.faults.pick(len(rows))]]
        req.stuck = True
        self.model_steps["stuck_rows"] += 1
        req.decision_trace.append({"action": "fault", "kind": "stuck"})

    def _crash_recover(self) -> None:
        """Simulated mid-run crash ("engine.crash"): in-flight device
        state is lost at a step boundary.  Recovery preempts every
        occupied slot — replay re-adopts prefix-cache snapshots where
        they exist and recomputes the rest, while billed_prefill
        watermarks guarantee no token is billed twice.  Queue order is
        preserved: victims requeue at the front, oldest first."""
        self.model_steps["crash_recoveries"] += 1
        occupied = [i for i, r in enumerate(self.slots) if r is not None]
        # appendleft per victim: preempt youngest-first so the oldest
        # admission ends up at the head of the queue
        for slot in sorted(occupied,
                           key=lambda i: -self.slots[i].admit_seq):
            self._preempt_slot(slot)
        self._no_progress = 0

    def _admit(self, req: Request, slot: int) -> None:
        """Assign a queued request to a free slot.  No model work happens
        here — prefill is chunked into subsequent mixed steps.  After a
        preemption the request replays prompt+output (prefill_target)."""
        req.prefill_target = list(req.prompt) + list(req.output)
        target = req.prefill_target
        assert len(req.prompt) + self._budget_cap(req) < self.scfg.max_seq, \
            "request would overflow max_seq"
        res = None
        cached_len = 0
        if self.prefix_cache is not None:
            with TraceAnnotation("serve.prefix_lookup"):
                res = self.prefix_cache.lookup(target)
            # a full-prompt hit still needs >=1 suffix token for logits
            cached_len = min(res.cached_len, len(target) - 1)
        if self.paged:
            self._set_slot_cache(slot, self._blank_row)   # dense leaves only
            if cached_len > 0 and res.cache is not None:
                self._adopt_snapshot(slot, res.cache, cached_len)
                req.usage += TokenUsage(cache_read_tokens=max(
                    0, cached_len - req.billed_prefill))
                req.billed_prefill = max(req.billed_prefill, cached_len)
            else:
                cached_len = 0
        else:
            if cached_len > 0 and res is not None and res.cache is not None:
                self._set_slot_cache(slot, res.cache)
                req.usage += TokenUsage(cache_read_tokens=max(
                    0, cached_len - req.billed_prefill))
                req.billed_prefill = max(req.billed_prefill, cached_len)
            else:
                cached_len = 0
                self._set_slot_cache(slot, self._blank_row)
        req.prefill_pos = cached_len
        req.cached_len = cached_len
        req.status = Status.PREFILLING
        self._admit_counter += 1
        req.admit_seq = self._admit_counter
        self.slots[slot] = req
        self._progress_seq += 1

    def _maybe_finish(self, slot: int) -> None:
        req = self.slots[slot]
        if req is None:
            return
        cap = self._budget_cap(req)
        if req.eos_id is not None and req.output and req.output[-1] == req.eos_id:
            req.stop_reason = "eos"
        elif len(req.output) >= cap:
            req.stop_reason = ("budget" if cap < req.max_new_tokens
                               else "max_tokens")
        else:
            return
        req.status = Status.DONE
        self.finished.append(req)
        self.requests.pop(req.uid, None)
        if self.prefix_cache is not None:
            # snapshot the conversation INCLUDING the token just produced:
            # its KV was written during the decode step that produced the
            # next logits... the last sampled token is NOT yet in the cache,
            # so snapshot prompt+output[:-1].
            convo = list(req.prompt) + req.output[:-1]
            if len(convo) > 0:
                self._insert_snapshot(convo, slot)
        if self.paged:
            self._release_slot_pages(slot)
        self.slots[slot] = None

    def _sample_rows(self, logits: jax.Array) -> np.ndarray:
        """One batched sampling call for every row (greedy rows ignore
        the rng; rows without a request are discarded by the caller)."""
        temps = np.zeros(len(self.slots), np.float32)
        for i, r in enumerate(self.slots):
            if r is not None:
                temps[i] = r.temperature
        self.rng, k = jax.random.split(self.rng)
        return np.asarray(sampler.sample_batch(logits, k, jnp.asarray(temps)))

    def _fast_forward(self) -> None:
        """In-flight prefix sharing: a PREFILLING slot jumps ahead when a
        longer usable prefix snapshot has appeared since its admission —
        e.g. a concurrent identical-prompt request (best-of-N, judge
        fan-out) publishing chunk-boundary snapshots mid-flight.  In paged
        mode the jump is pure metadata: drop the slot's pages, map the
        snapshot's (incref).  Skipped entirely when no insert happened
        since the last scan, keeping the hot step path free of
        O(entries x prompt) prefix scans."""
        if self.prefix_cache is None:
            return
        if self.prefix_cache.version == self._ff_version:
            return
        self._ff_version = self.prefix_cache.version
        for slot, req in enumerate(self.slots):
            if req is None or req.status is not Status.PREFILLING:
                continue
            target = req.prefill_target
            if req.prefill_pos >= len(target) - 1:
                continue                  # last token must be processed live
            res = self.prefix_cache.lookup(target,
                                           min_len=req.prefill_pos,
                                           record_miss=False)
            cached = min(res.cached_len, len(target) - 1)
            if res.cache is None or cached <= req.prefill_pos:
                continue
            if self.paged:
                self._release_slot_pages(slot)
                self._adopt_snapshot(slot, res.cache, cached)
            else:
                self._set_slot_cache(slot, res.cache)
            req.usage += TokenUsage(cache_read_tokens=max(
                0, cached - max(req.billed_prefill, req.prefill_pos)))
            req.billed_prefill = max(req.billed_prefill, cached)
            req.prefill_pos = cached
            req.cached_len = cached

    def _make_drafts(self) -> Dict[int, List[int]]:
        """Prompt-lookup drafting for every DECODING row (host-side, no
        device work).  Per-row draft length is clamped so speculation can
        never overshoot the row's output budget (a too-long draft would
        emit tokens past the cap — billing corruption), nor write past
        max_seq.  Drafted lanes count against the per-step token budget
        (the planner sees the remainder), bounding verify-step work the
        same way prefill chunks are bounded."""
        drafts: Dict[int, List[int]] = {}
        for slot, req in enumerate(self.slots):
            if req is None or req.status is not Status.DECODING or req.stuck:
                continue
            # rem bounds the draft so at most one lane is wasted at the
            # cap (emission stops exactly at the cap — _postprocess_verify
            # discards, and never bills, tokens past a mid-step finish)
            rem = self._budget_cap(req) - len(req.output)
            kmax = min(self.spec_tokens, rem,
                       self.scfg.max_seq - 1 - int(self.pos[slot]))
            if kmax <= 0:
                continue
            # cascade handoff: a row carrying another model's committed
            # answer (Request.external_draft) drafts from it positionally
            # while the output is still a prefix of the draft; n-gram
            # lookup takes over once the models diverge
            d = None
            if req.external_draft is not None:
                d = external_draft_proposal(req.external_draft, req.output,
                                            kmax)
            if d is None:
                d = self.speculator.propose(
                    draft_corpus(req.prompt, req.output, req.spec_context),
                    kmax)
            if d:
                drafts[slot] = d
        return drafts

    def _clamp_drafts_to_budget(self, drafts: Dict[int, List[int]]) -> None:
        """Shrink drafted lanes so the step token budget is never fully
        consumed by speculation while a request is PREFILLING: at least
        one budget token must survive for the planner, preserving the
        non-speculative guarantee that a prefilling row makes >= 1 token
        of progress per step (youngest drafted rows lose lanes first —
        the same age order preemption uses)."""
        cap = self.prefill_budget
        if any(r is not None and r.status is Status.PREFILLING
               for r in self.slots):
            cap -= 1
        total = sum(len(d) for d in drafts.values())
        if total <= cap:
            return
        for slot in sorted(drafts,
                           key=lambda s: -self.slots[s].admit_seq):
            cut = min(len(drafts[slot]), total - cap)
            total -= cut
            if cut == len(drafts[slot]):
                del drafts[slot]
            else:
                drafts[slot] = drafts[slot][:len(drafts[slot]) - cut]
            if total <= cap:
                return

    def _plan_chunks(self, width: Optional[int] = None,
                     budget: Optional[int] = None) -> Dict[int, int]:
        """Token-budget admission of prefill work into this step: each
        PREFILLING slot gets min(chunk, remaining, budget-left) lanes,
        oldest admission first — so a request can never be starved by
        newer arrivals landing in lower-numbered slots.  In paged mode
        each chunk additionally shrinks to the tokens whose pages are
        actually allocatable right now (free-page admission control);
        allocation itself may evict snapshots or preempt younger rows.
        ``width``/``budget`` override the defaults when prefill rides a
        VERIFY step: chunks are clamped to the narrow verify width and
        to the budget left after drafted lanes."""
        plan: Dict[int, int] = {}
        width = self.chunk if width is None else width
        budget = self.prefill_budget if budget is None else budget
        waiting = sorted(
            (i for i, r in enumerate(self.slots)
             if r is not None and r.status is Status.PREFILLING),
            key=lambda i: self.slots[i].admit_seq)
        for slot in waiting:
            if budget <= 0:
                break
            req = self.slots[slot]
            if req is None or req.status is not Status.PREFILLING:
                continue                  # preempted during an earlier alloc
            n = min(width, req.prefill_remaining, budget)
            if n > 0 and self.paged:
                n = self._ensure_range(slot, req.prefill_pos, n)
            if n > 0:
                plan[slot] = n
                budget -= n
        # FIFO preemption never targets an already-planned (older) slot;
        # this filter is a defensive invariant, not a code path
        return {s: n for s, n in plan.items() if self.slots[s] is not None}

    def _postprocess_prefill(self, slot: int, n: int,
                             sampled: np.ndarray) -> None:
        req = self.slots[slot]
        target = req.prefill_target
        self._progress_seq += 1
        req.prefill_pos += n
        req.prefill_chunks += 1
        req.prefill_steps += 1
        self.model_steps["prefill_chunks"] += 1
        if req.cached_len > 0:
            self.model_steps["extend_tokens"] += n
        else:
            self.model_steps["prefill_tokens"] += n
        # bill only positions never billed before: a preemption replay
        # recomputes tokens the user already paid for (as input or output)
        billable = max(0, req.prefill_pos - max(req.billed_prefill,
                                                req.prefill_pos - n))
        req.usage += TokenUsage(input_tokens=billable,
                                cache_write_tokens=billable)
        req.billed_prefill = max(req.billed_prefill, req.prefill_pos)
        if req.prefill_remaining == 0:
            # prompt fully in cache: the mixed step's last-valid logits
            # are the next-token distribution — sample the first token
            tok = int(sampled[slot])
            req.output.append(tok)
            req.usage.output_tokens += 1
            req.status = Status.DECODING
            self.pos[slot] = len(target)
            self.next_token[slot] = tok
            if self.paged:
                self._free_out_of_window(slot, len(target))
            if self.prefix_cache is not None:
                self._insert_snapshot(list(target), slot)
            self._maybe_finish(slot)
        else:
            if self.paged:
                self._free_out_of_window(slot, req.prefill_pos)
            if (self.prefix_cache is not None
                    and self.scfg.cache_prefill_chunks
                    and self.prefix_cache.wants_boundary(
                        target[:req.prefill_pos])):
                self._insert_snapshot(list(target[:req.prefill_pos]), slot,
                                      boundary=True)

    def _postprocess_decode(self, slot: int, sampled: np.ndarray) -> None:
        req = self.slots[slot]
        self._progress_seq += 1
        tok = int(sampled[slot])
        req.output.append(tok)
        req.usage.output_tokens += 1
        req.decode_steps += 1
        self.model_steps["decode_tokens"] += 1
        self.pos[slot] += 1
        self.next_token[slot] = tok
        if self.paged and self.slots[slot] is not None:
            self._free_out_of_window(slot, int(self.pos[slot]))
        self._maybe_finish(slot)

    def _postprocess_verify(self, slot: int, n_emit: int,
                            emit_row: np.ndarray, drafted: int) -> None:
        """Commit one decode row's verify-step outcome: the accepted
        draft prefix plus the model-sampled bonus/corrected token, then
        ROLL BACK everything the step wrote past the committed frontier.

        Billing: only committed tokens touch TokenUsage.  Rejected
        drafts were model work, not user output — they appear in
        spec_drafted/spec_accepted stats, never in output_tokens (the
        paper's cost axis is accepted-token billing).  Emission stops
        early at eos or the output cap, so a long accepted draft can
        never overshoot the row's budget.

        Rollback: the KV written for rejected lanes sits at positions
        strictly beyond the new committed frontier ``pos``.  Every read
        path masks by absolute position (tok <= pos ring / t <= pos
        paged) and every future step rewrites positions from ``pos``
        forward BEFORE attending, so stale entries are unobservable
        (models/attention.py).  The only durable residue is page-table
        tail pages mapped for rejected positions — truncated here via
        PagePool.truncate_tail so pool accounting reflects committed
        tokens only.  Prefix-cache snapshots are published exclusively
        at accepted watermarks (_maybe_finish covers prompt+output[:-1],
        all committed), so no snapshot can ever pin a rolled-back
        position as reusable content."""
        req = self.slots[slot]
        self._progress_seq += 1
        P = int(self.pos[slot])
        req.spec_drafted += drafted
        req.spec_accepted += n_emit - 1
        req.decode_steps += 1
        self.model_steps["spec_drafted"] += drafted
        self.model_steps["spec_accepted"] += n_emit - 1
        for i in range(n_emit):
            tok = int(emit_row[i])
            req.output.append(tok)
            req.usage.output_tokens += 1
            self.model_steps["decode_tokens"] += 1
            self.pos[slot] = P + i + 1
            self.next_token[slot] = tok
            self._maybe_finish(slot)
            if self.slots[slot] is None:      # finished (eos / cap) — the
                return                        # pages are already released
        if self.paged:
            # free tail pages holding ONLY rejected draft positions; the
            # page containing the committed frontier stays (next step's
            # write lands there, and it may hold committed tokens)
            ps = self.pool.page_size
            keep = int(self.pos[slot]) // ps + 1
            if (P + drafted) // ps >= keep:
                self.pool.truncate_tail(self.page_tables[slot], keep)
            self._free_out_of_window(slot, int(self.pos[slot]))

    def step(self) -> bool:
        """One scheduler tick.  Returns False when fully idle.

        Reliability wrapper around the scheduling core (_step_inner):
        fault hooks fire at the step boundary (crash, stuck-row, latency
        spikes via the plan's virtual clock), expired deadlines finalize
        before any new work is planned, and the stall detector reaps
        in-flight rows after stall_limit consecutive no-progress steps —
        all gated off by default (docs/SERVING.md#reliability).

        The tick is the profiler span ``serve.step``; its phases are the
        spans nested in it (docs/SERVING.md#tracing)."""
        with TraceAnnotation("serve.step"):
            if self.faults is not None:
                self.faults.on_step()
                if self.faults.fire("engine.crash") is not None:
                    self._crash_recover()
                    return (bool(self.queue)
                            or any(r is not None for r in self.slots))
                if self.faults.fire("engine.stuck") is not None:
                    self._mark_stuck()
            if self.scfg.enforce_deadlines:
                self._enforce_deadlines()
            p0 = self._progress_seq
            busy = self._step_inner()
            if self.scfg.stall_limit > 0:
                if (self._progress_seq == p0
                        and any(r is not None for r in self.slots)):
                    self._no_progress += 1
                    if self._no_progress >= self.scfg.stall_limit:
                        for slot, r in enumerate(self.slots):
                            if r is not None:
                                self._finalize_abnormal(r, slot, "stalled")
                        self._no_progress = 0
                        busy = (bool(self.queue)
                                or any(r is not None for r in self.slots))
                else:
                    self._no_progress = 0
            return busy

    def _page_table_arg(self) -> tuple:
        """The page table as the paged step programs take it; nothing in
        ring mode."""
        return (self.page_tables.astype(np.int32),) if self.paged else ()

    def _step_inner(self) -> bool:
        """The scheduling core: admission, planning, one model call."""
        # admit queued requests into free slots (no model work yet);
        # SLO-unfundable requests finalize without consuming a slot
        with TraceAnnotation("serve.admit"):
            for slot in range(len(self.slots)):
                while self.slots[slot] is None and self.queue:
                    req = self.queue.popleft()
                    if not self._slo_reject(req):
                        self._admit(req, slot)
        if not any(r is not None for r in self.slots):
            return bool(self.queue)

        with TraceAnnotation("serve.plan"):
            self._fast_forward()
            # speculative drafts first: decode rows outrank prefill for
            # both pages and the step token budget (same decode-first
            # policy as _ensure_decode_pages) — but drafts never eat the
            # WHOLE budget while someone is prefilling
            # (_clamp_drafts_to_budget), so a prefilling row keeps the
            # non-spec guarantee of >=1 token of progress per step and
            # can never be starved by speculation
            drafts = self._make_drafts() if self.spec else {}
            if drafts:
                self._clamp_drafts_to_budget(drafts)
            if self.paged:
                # page admission control: decode rows first (they always
                # get their committed-token page — drafts shrink before
                # anyone is preempted), then prefill chunks sized to
                # allocatable pages
                self._ensure_decode_pages(drafts)
            plan = self._plan_chunks(
                width=min(self.chunk, 1 + self.spec_tokens) if drafts
                else None,
                budget=(self.prefill_budget
                        - sum(len(d) for d in drafts.values()))
                if drafts else None)
            decode_rows = [i for i, r in enumerate(self.slots)
                           if r is not None and r.status is Status.DECODING]
            drafts = {s: d for s, d in drafts.items()
                      if self.slots[s] is not None
                      and self.slots[s].status is Status.DECODING}
        if self._pending_copies:
            with TraceAnnotation("serve.copy_pages"):
                self._flush_copies()
        if not plan and not decode_rows:
            # pool pressure can leave a step with nothing runnable (all
            # rows preempted or waiting on pages freed next tick)
            return bool(self.queue) or any(r is not None for r in self.slots)
        starved = any(r is not None and r.status is Status.PREFILLING
                      for r in self.slots) and not plan
        if starved:
            self.model_steps["starved_mixed_steps"] += 1

        if drafts:
            # VERIFY step: the engine's third compiled shape
            # [B, 1 + spec_tokens] with per-lane logits.  Decode rows
            # carry [committed token, draft...] lanes; prefill rows ride
            # with chunks clamped to the verify width (planned above
            # under the shared token budget); starved prefill rows ride
            # as nv=0 no-op lanes exactly as in the mixed step.
            return self._verify_step(plan, decode_rows, drafts)

        if not plan and not starved:
            # decode fast path: dedicated [B, 1] step, no masked lanes.
            # Taken only when NO row is PREFILLING: a page-starved
            # prefilling row (empty plan) must ride the mixed step as an
            # nv=0 no-op — the decode step has no validity mask, so it
            # would scatter a stale (pos, next_token) into pages the row
            # already prefilled or shares copy-on-write.
            with TraceAnnotation("serve.inputs"):
                tokens = self.next_token[:, None].astype(np.int32)
                pos = self.pos.astype(np.int32)
                args = (self.params, self.cache, tokens, pos,
                        *self._page_table_arg())
            logits, self.cache = self._decode(*args)
            with TraceAnnotation("serve.sample"):
                logits = self._host_logits(logits)
                self.model_steps["decode_batch_steps"] += 1
                self.model_steps["decode_steps"] += len(decode_rows)
                if self.faults is not None:
                    logits = self.faults.corrupt_logits("engine.logits",
                                                        logits, decode_rows)
                self._quarantine_rows(self._nonfinite_rows(logits,
                                                           decode_rows))
                sampled = self._sample_rows(logits)
            with TraceAnnotation("serve.commit"):
                for slot in decode_rows:
                    req = self.slots[slot]
                    if req is None or req.stuck:  # quarantined / fault-stuck
                        continue
                    self._postprocess_decode(slot, sampled)
            return True

        # mixed step: decode rows ride in lane 0; prefill rows get chunks.
        # Width = the smallest pre-compiled bucket that fits this step's
        # chunks (defaults to the single full-chunk bucket).
        with TraceAnnotation("serve.inputs"):
            B = len(self.slots)
            need = max(plan.values()) if plan else 1
            W = next(w for w in self._mixed_buckets if w >= need)
            toks = np.zeros((B, W), np.int32)
            pos0 = np.zeros(B, np.int32)
            nv = np.zeros(B, np.int32)
            for slot in decode_rows:
                toks[slot, 0] = self.next_token[slot]
                pos0[slot] = self.pos[slot]
                nv[slot] = 1
            for slot, n in plan.items():
                req = self.slots[slot]
                target = req.prefill_target
                toks[slot, :n] = target[req.prefill_pos:req.prefill_pos + n]
                pos0[slot] = req.prefill_pos
                nv[slot] = n
            args = (self.params, self.cache, toks, pos0, nv,
                    *self._page_table_arg())
        logits, self.cache = self._mixed(*args)
        with TraceAnnotation("serve.sample"):
            logits = self._host_logits(logits)
            self.model_steps["mixed_steps"] += 1
            self.model_steps["decode_steps"] += len(decode_rows)
            self.model_steps["max_step_prefill_tokens"] = max(
                self.model_steps["max_step_prefill_tokens"],
                int(sum(plan.values())))
            consumed = decode_rows + list(plan)
            if self.faults is not None:
                logits = self.faults.corrupt_logits("engine.logits", logits,
                                                    consumed)
            self._quarantine_rows(self._nonfinite_rows(logits, consumed))
            sampled = self._sample_rows(logits)
        with TraceAnnotation("serve.commit"):
            for slot, n in plan.items():
                if self.slots[slot] is None:       # quarantined this step
                    continue
                self._postprocess_prefill(slot, n, sampled)
            for slot in decode_rows:
                req = self.slots[slot]
                if req is None or req.stuck:
                    continue
                self._postprocess_decode(slot, sampled)
        return True

    def _verify_step(self, plan: Dict[int, int], decode_rows: List[int],
                     drafts: Dict[int, List[int]]) -> bool:
        """One speculative verify step (docs/SERVING.md#speculative-decoding):
        score every row's committed token + drafted continuation in a
        single masked multi-token model call, then commit the longest
        accepted prefix per row.  Decode rows without a draft ride as
        nv=1 (plain decode with verify-lane logits — same argmax), and
        prefill rows consume their planned chunks; the call returns
        logits for EVERY lane so acceptance is decided host-side from
        one device round-trip."""
        with TraceAnnotation("serve.inputs"):
            B, W = len(self.slots), 1 + self.spec_tokens
            toks = np.zeros((B, W), np.int32)
            pos0 = np.zeros(B, np.int32)
            nv = np.zeros(B, np.int32)
            ndraft = np.zeros(B, np.int32)
            for slot in decode_rows:
                d = drafts.get(slot, [])
                toks[slot, 0] = self.next_token[slot]
                if d:
                    toks[slot, 1:1 + len(d)] = d
                pos0[slot] = self.pos[slot]
                nv[slot] = 1 + len(d)
                ndraft[slot] = len(d)
            for slot, n in plan.items():
                req = self.slots[slot]
                target = req.prefill_target
                toks[slot, :n] = target[req.prefill_pos:req.prefill_pos + n]
                pos0[slot] = req.prefill_pos
                nv[slot] = n
            args = (self.params, self.cache, toks, pos0, nv,
                    *self._page_table_arg())
        logits_all, self.cache = self._verify(*args)
        with TraceAnnotation("serve.sample"):
            logits_all = self._host_logits(logits_all)
            self.model_steps["verify_steps"] += 1
            self.model_steps["decode_steps"] += len(decode_rows)
            self.model_steps["max_step_prefill_tokens"] = max(
                self.model_steps["max_step_prefill_tokens"],
                int(sum(plan.values())))
            consumed = decode_rows + list(plan)
            if self.faults is not None:
                logits_all = self.faults.corrupt_logits("engine.logits",
                                                        logits_all, consumed)
            self._quarantine_rows(self._nonfinite_rows(
                logits_all, consumed, nv=nv, ndraft=ndraft))
            temps = np.zeros(B, np.float32)
            for i, r in enumerate(self.slots):
                if r is not None:
                    temps[i] = r.temperature
            self.rng, k = jax.random.split(self.rng)
            n_emit, emit = sampler.verify_batch(
                logits_all, jnp.asarray(toks), jnp.asarray(nv),
                jnp.asarray(ndraft), k, jnp.asarray(temps))
            n_emit = np.asarray(n_emit)
            emit = np.asarray(emit)
        with TraceAnnotation("serve.commit"):
            # prefill rows: emit[:, 0] is the sample at their last valid
            # lane (n_draft=0 rows verify nothing), exactly _sample_rows'
            # output
            for slot, n in plan.items():
                if self.slots[slot] is None:       # quarantined this step
                    continue
                self._postprocess_prefill(slot, n, emit[:, 0])
            for slot in decode_rows:
                req = self.slots[slot]
                if req is None or req.stuck:
                    continue
                self._postprocess_verify(slot, int(n_emit[slot]), emit[slot],
                                         int(ndraft[slot]))
        return True
