"""Paged KV-cache subsystem correctness (docs/SERVING.md).

Covers the contracts of serving/page_pool.py + the paged engine mode:
  * parity — paged decode reproduces ring-cache decode across attention,
    MoE and hybrid-recurrent architectures, to fp32 rounding at the model
    level (logits) and exactly at the engine level (greedy tokens);
  * allocator — refcount-correct eviction (a pinned page is never
    reallocated), pool invariants hold through a full serving run;
  * preemption — pool exhaustion requeues the youngest request (never
    drops it) and its generated tokens survive the replay;
  * sharing — best-of-N over a shared prompt allocates the prefix pages
    once; divergence past a shared boundary page copy-on-writes exactly
    that page;
  * prefix-cache recurrent semantics — the flag is derived from the model
    config, and exact-length entries are never replayed into recurrent
    state (the PR-1 regression, now pinned at the PrefixCache level).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ServeConfig
from repro.kernels import ops, ref
from repro.models import layers as L
from repro.models.registry import build_model, get_smoke_config
from repro.serving.engine import Engine
from repro.serving.page_pool import PagePool
from repro.serving.prefix_cache import PrefixCache
from repro.serving.request import Request, Status

# jit-compile-heavy end-to-end module: deselected by `make test-fast`
pytestmark = pytest.mark.slow

PARITY_ARCHS = ["qwen3_0_6b", "granite_moe_1b_a400m", "recurrentgemma_9b"]


def _f32(a):
    return np.asarray(a, dtype=np.float32)


def make_engine(arch="qwen3_0_6b", **kw):
    cfg = get_smoke_config(arch).replace(dtype="float32")
    m = build_model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    scfg = ServeConfig(**{**dict(max_batch=3, max_seq=160, page_size=8), **kw})
    return Engine(m, params, scfg), m, params


# ---------------------------------------------------------------------------
# model-level parity: paged extends/decode == ring prefill/decode
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("arch", PARITY_ARCHS)
def test_paged_decode_matches_ring(arch):
    """fp32 logits parity: chunked paged prefill + paged decode must
    reproduce monolithic ring prefill + ring decode to fp32 rounding.
    Not bit-for-bit: XLA's CPU dot kernels sum in an order that depends
    on the contraction shape ([B, 5]-lane chunks against a gathered page
    view vs one [B, 13] prefill against the ring), so the two walks
    differ in the last ulps (~1e-6 here); windowed hybrid layers add
    ring-rotation vs linear-page summation order on top."""
    cfg = get_smoke_config(arch).replace(dtype="float32", capacity_factor=8.0)
    m = build_model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    B, S, max_seq, ps = 2, 13, 32, 4
    NP = max_seq // ps
    tokens = jax.random.randint(jax.random.PRNGKey(3), (B, S), 3,
                                cfg.vocab_size)
    lg_ring, cache_ring = m.prefill(params, tokens, max_seq=max_seq)

    pt = jnp.asarray(np.stack([np.arange(NP) + b * NP for b in range(B)])
                     .astype(np.int32))
    cache = L.init_empty_cache(m.cache_defs_paged(B, B * NP, ps))
    sizes, prog = [5, 3], [0, 0]
    lg = np.zeros((B, cfg.vocab_size), np.float32)
    while min(prog) < S:
        blk = np.zeros((B, 5), np.int32)
        nv = np.zeros(B, np.int32)
        p0 = np.zeros(B, np.int32)
        for b in range(B):
            n = min(sizes[b], S - prog[b])
            blk[b, :n] = np.asarray(tokens)[b, prog[b]:prog[b] + n]
            nv[b], p0[b] = n, prog[b]
            prog[b] += n
        lg_new, cache = m.prefill_extend(params, cache, jnp.asarray(blk),
                                         jnp.asarray(p0), jnp.asarray(nv),
                                         page_table=pt)
        for b in range(B):
            if prog[b] == S and nv[b] > 0:
                lg[b] = _f32(lg_new)[b]
    np.testing.assert_allclose(lg, _f32(lg_ring), atol=1e-4, rtol=1e-4)

    nxt = jnp.argmax(lg_ring, -1)[:, None].astype(jnp.int32)
    pos = jnp.full((B,), S, jnp.int32)
    d_ring, _ = m.decode_step(params, cache_ring, nxt, pos)
    d_paged, _ = m.decode_step(params, cache, nxt, pos, page_table=pt)
    np.testing.assert_allclose(_f32(d_paged), _f32(d_ring), atol=1e-4,
                               rtol=1e-4)
    assert (np.argmax(_f32(d_paged), -1) == np.argmax(_f32(d_ring), -1)).all()


@pytest.mark.parametrize("arch", PARITY_ARCHS + ["falcon_mamba_7b"])
def test_engine_paged_matches_ring_tokens(arch):
    """End-to-end: the paged engine emits exactly the ring engine's
    tokens, with prefix caching on (snapshots = page pins vs copies)."""
    prompts = [[1] + list(range(10, 40)),
               [1] + list(range(50, 63)),
               [1] + list(range(10, 40))]               # dup: shares pages
    outs = {}
    for paged in (True, False):
        eng, _, _ = make_engine(arch, paged_kv=paged, max_batch=3,
                                max_seq=160, page_size=8)
        reqs = [Request(prompt=list(p), max_new_tokens=6, eos_id=None)
                for p in prompts]
        for r in reqs:
            eng.submit(r)
        eng.run()
        assert all(r.status is Status.DONE for r in reqs)
        outs[paged] = [r.output for r in reqs]
        if paged:
            eng.pool.check()
    assert outs[True] == outs[False]


# ---------------------------------------------------------------------------
# kernel parity: Pallas page-table walk == gather reference == dense ring
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("window", [None, 10])
def test_paged_attention_kernel_parity(window):
    rng = np.random.default_rng(0)
    B, K, G, hd, P, ps, NP = 3, 2, 2, 32, 16, 8, 5
    q = jnp.asarray(rng.standard_normal((B, K, G, hd)), jnp.float32)
    kp = jnp.asarray(rng.standard_normal((P, K, ps, hd)), jnp.float32)
    vp = jnp.asarray(rng.standard_normal((P, K, ps, hd)), jnp.float32)
    pos = jnp.asarray([3, 17, 38], jnp.int32)
    pt = np.full((B, NP), -1, np.int32)
    perm, u = rng.permutation(P), 0
    for b in range(B):
        n = int(pos[b]) // ps + 1
        pt[b, :n] = perm[u:u + n]
        u += n
    pt = jnp.asarray(pt)
    got = ops.paged_decode_attention(q, kp, vp, pt, pos, window=window)
    want = ref.paged_decode_attention_ref(q, kp, vp, pt, pos, window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5,
                               rtol=2e-5)

    # same attention through the dense ring oracle: scatter the pages into
    # a [B, C] cache with explicit tok indices
    C = NP * ps
    kd = np.zeros((B, C, K, hd), np.float32)
    vd = np.zeros((B, C, K, hd), np.float32)
    tok = np.full((B, C), -1, np.int32)
    for b in range(B):
        for lp in range(NP):
            if int(pt[b, lp]) < 0:
                continue
            for o in range(ps):
                t = lp * ps + o
                if t > int(pos[b]):
                    continue
                kd[b, t] = np.asarray(kp)[int(pt[b, lp]), :, o]
                vd[b, t] = np.asarray(vp)[int(pt[b, lp]), :, o]
                tok[b, t] = t
    dense = ref.decode_attention_ref(q, jnp.asarray(kd), jnp.asarray(vd),
                                     jnp.asarray(tok), pos, window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(dense), atol=2e-5,
                               rtol=2e-5)


# ---------------------------------------------------------------------------
# allocator: refcounts, pinning, invariants
# ---------------------------------------------------------------------------

def test_pool_pinned_page_never_reallocated():
    pool = PagePool(num_pages=4, page_size=8)
    a = pool.alloc()
    pool.incref([a])                # snapshot pin
    pool.decref([a])                # owning request leaves
    assert pool.refcount[a] == 1    # pin still holds it
    others = [pool.alloc() for _ in range(3)]
    assert None not in others and a not in others
    assert pool.alloc() is None     # pinned page must NOT be handed out
    pool.check()
    pool.decref([a])                # pin released -> reusable
    assert pool.alloc() == a
    pool.check()


def test_pool_cow_bookkeeping():
    pool = PagePool(num_pages=2, page_size=4)
    a = pool.alloc()
    pool.incref([a])
    assert pool.needs_cow(a)
    b = pool.alloc()
    assert not pool.needs_cow(b)
    pool.decref([a])
    assert not pool.needs_cow(a)
    pool.check()


def test_engine_pool_drains_without_prefix_cache():
    """With snapshots disabled every page must return to the free list
    once all requests finish (no leaks, no double frees)."""
    eng, _, _ = make_engine(prefix_cache=False, max_batch=2, max_seq=64,
                            page_size=8)
    for i in range(4):
        eng.submit(Request(prompt=[1] + list(range(10 + i, 30 + i)),
                           max_new_tokens=4, eos_id=None))
    eng.run()
    eng.pool.check()
    assert eng.pool.used_pages == 0
    assert eng.pool.stats["allocs"] == eng.pool.stats["frees"]


# ---------------------------------------------------------------------------
# pool exhaustion: preemption + requeue (never dropped)
# ---------------------------------------------------------------------------

def test_pool_exhaustion_preempts_and_requeues():
    """Two long requests cannot fit a minimum-size pool together: the
    younger is preempted (pages freed, requeued at the queue front) and
    still completes with exactly the tokens of an uncontended run."""
    long_prompts = [[1] + list(range(10, 50)),          # 41 tokens = 6 pages
                    [2] + list(range(60, 100))]
    solo = []
    for p in long_prompts:
        eng, _, _ = make_engine(prefix_cache=False, max_batch=1,
                                max_seq=64, page_size=8)
        r = Request(prompt=list(p), max_new_tokens=6, eos_id=None)
        eng.submit(r)
        eng.run()
        solo.append(r.output)

    # 8 pages = exactly one max_seq request; two admitted rows must fight
    eng, _, _ = make_engine(prefix_cache=False, max_batch=2, max_seq=64,
                            page_size=8, num_pages=8)
    reqs = [Request(prompt=list(p), max_new_tokens=6, eos_id=None)
            for p in long_prompts]
    for r in reqs:
        eng.submit(r)
    eng.run()
    assert all(r.status is Status.DONE for r in reqs), "request dropped"
    assert eng.model_steps["preemptions"] >= 1
    assert sum(r.preemptions for r in reqs) >= 1
    assert [r.output for r in reqs] == solo
    for r in reqs:
        # replay recomputes tokens but must not re-BILL them: the billed
        # input is exactly the prompt, decode tokens bill as output once
        assert (r.usage.input_tokens + r.usage.cache_read_tokens
                == len(r.prompt))
        assert r.usage.output_tokens == len(r.output)
    eng.pool.check()
    assert eng.pool.used_pages == 0


def test_preempted_decode_output_survives_replay():
    """A request preempted mid-DECODE keeps its generated tokens: the
    replay prefills prompt+output and continues from there."""
    solo_eng, _, _ = make_engine(prefix_cache=False, max_batch=1,
                                 max_seq=64, page_size=8)
    solo = Request(prompt=[1] + list(range(10, 30)), max_new_tokens=8,
                   eos_id=None)
    solo_eng.submit(solo)
    solo_eng.run()

    eng, _, _ = make_engine(prefix_cache=False, max_batch=2, max_seq=64,
                            page_size=8, num_pages=8)
    r1 = Request(prompt=[1] + list(range(10, 30)), max_new_tokens=8,
                 eos_id=None)
    eng.submit(r1)
    # let r1 decode a few tokens before the page-hungry rival arrives
    for _ in range(40):
        eng.step()
        if len(r1.output) >= 3:
            break
    assert r1.status is Status.DECODING
    r2 = Request(prompt=[2] + list(range(60, 100)), max_new_tokens=4,
                 eos_id=None)
    eng.submit(r2)
    eng.run()
    assert r1.status is Status.DONE and r2.status is Status.DONE
    assert r1.output == solo.output, "preemption replay changed tokens"
    for r in (r1, r2):
        assert (r.usage.input_tokens + r.usage.cache_read_tokens
                == len(r.prompt))
        assert r.usage.output_tokens == len(r.output)
    eng.pool.check()


# ---------------------------------------------------------------------------
# sharing: best-of-N maps one physical prefix; divergence copy-on-writes
# ---------------------------------------------------------------------------

def test_best_of_n_allocates_prefix_once():
    """8 requests over one 32-token prompt: followers adopt the leader's
    snapshot pages — fresh prefill is 1 token each, and total allocations
    stay far below 8 full prefixes."""
    eng, _, _ = make_engine(max_batch=8, max_seq=64, page_size=8)
    prompt = [1] + list(range(10, 41))                  # 32 tokens = 4 pages
    leader = Request(prompt=list(prompt), max_new_tokens=4, eos_id=None)
    eng.submit(leader)
    for _ in range(100):
        eng.step()
        if leader.status is Status.DECODING:
            break
    assert leader.status is Status.DECODING
    allocs_prefix = eng.pool.stats["allocs"]
    assert allocs_prefix >= 4                           # the one real prefix

    followers = [Request(prompt=list(prompt), max_new_tokens=4, eos_id=None)
                 for _ in range(7)]
    for r in followers:
        eng.submit(r)
    eng.run()
    assert all(r.status is Status.DONE for r in followers)
    for r in followers:
        assert r.usage.cache_read_tokens == len(prompt) - 1
        assert r.usage.input_tokens == 1                # only the live token
        assert r.output == leader.output
    follower_allocs = eng.pool.stats["allocs"] - allocs_prefix
    # each follower: COW of the shared boundary page + its decode page(s),
    # never the 4-page prefix again
    assert follower_allocs < 7 * 4
    assert eng.pool.stats["cow_copies"] >= 1
    eng.pool.check()


def test_cow_divergence_is_exact():
    """A request extending a cached conversation diverges inside the
    snapshot's partially-filled last page: the write must copy that page
    (leaving the snapshot intact) and produce uncached-identical tokens."""
    prompt = [1] + list(range(10, 30))                  # 21 tokens, ps=8
    outs = {}
    for pc in (True, False):
        eng, _, _ = make_engine(prefix_cache=pc, max_batch=2, max_seq=96,
                                page_size=8)
        r1 = Request(prompt=list(prompt), max_new_tokens=4, eos_id=None)
        eng.submit(r1)
        eng.run()
        r2 = Request(prompt=list(prompt) + r1.output + [70, 71],
                     max_new_tokens=4, eos_id=None)
        eng.submit(r2)
        eng.run()
        outs[pc] = (r1.output, r2.output)
        if pc:
            assert r2.usage.cache_read_tokens > 0
            assert eng.pool.stats["cow_copies"] >= 1
            eng.pool.check()
    assert outs[True] == outs[False]


def test_starved_prefill_row_never_sees_decode_fast_path():
    """A page-starved PREFILLING row (chunk shrunk to 0, too young to
    preempt) must ride mixed steps as an nv=0 no-op — the decode fast
    path has no validity mask and would scatter a stale (pos, next_token)
    KV into pages the row already prefilled.  Slot 0 is primed with a
    stale pos by a short finished request, then contested by a long
    decoding request while the victim prefills."""
    def outputs(shared: bool):
        if shared:
            eng, _, _ = make_engine(prefix_cache=False, max_batch=2,
                                    max_seq=80, page_size=8, num_pages=10)
        reqs = {}
        for name, prompt, new in (("C", [1] + list(range(10, 21)), 4),
                                  ("A", [2] + list(range(30, 62)), 24),
                                  ("B", [3] + list(range(70, 110)), 4)):
            if not shared:
                eng, _, _ = make_engine(prefix_cache=False, max_batch=1,
                                        max_seq=80, page_size=8)
            r = Request(prompt=list(prompt), max_new_tokens=new, eos_id=None)
            reqs[name] = r
            if not shared:
                eng.submit(r)
                eng.run()
        if shared:
            eng.submit(reqs["C"])          # slot 0: leaves a stale pos
            eng.run()
            eng.submit(reqs["A"])          # slot 0 again, long decode
            while len(reqs["A"].output) < 2:
                eng.step()
            eng.submit(reqs["B"])          # slot 1; starves under A
            eng.run()
            # the hazard must actually have been exercised: steps where a
            # starved PREFILLING row rode along as an nv=0 mixed lane
            assert eng.model_steps["starved_mixed_steps"] >= 1
            eng.pool.check()
            assert eng.pool.used_pages == 0
        return {k: r.output for k, r in reqs.items()}

    contested, solo = outputs(shared=True), outputs(shared=False)
    assert contested == solo, "starved prefill row was corrupted"


def test_windowed_layers_free_out_of_window_pages():
    """When every attention layer is windowed (recurrentgemma's rg_attn),
    pages that slid out of the window are released as the request
    advances: resident pages stay O(window), not O(extent) — matching
    the ring baseline's [B, window] footprint — and tokens still match
    the ring engine exactly."""
    eng, _, _ = make_engine("recurrentgemma_9b", prefix_cache=False,
                            max_batch=1, max_seq=160, page_size=8)
    assert eng._window_free == 32
    prompt = [1] + list(range(10, 60))                  # 51 tokens
    r = Request(prompt=list(prompt), max_new_tokens=30, eos_id=None)
    eng.submit(r)
    eng.run()                                           # extent reaches 81
    extent_pages = -(-81 // 8)
    window_pages = 32 // 8
    # transient worst case: window + one in-flight chunk still mapped
    assert eng.pool.stats["peak_in_use"] < extent_pages
    assert eng.pool.stats["peak_in_use"] <= window_pages + eng.chunk // 8 + 1
    eng.pool.check()
    assert eng.pool.used_pages == 0

    eng2, _, _ = make_engine("recurrentgemma_9b", prefix_cache=False,
                             paged_kv=False, max_batch=1, max_seq=160)
    r2 = Request(prompt=list(prompt), max_new_tokens=30, eos_id=None)
    eng2.submit(r2)
    eng2.run()
    assert r.output == r2.output


def test_paged_nbytes_counts_shared_pages_once():
    """Boundary snapshots of one prompt pin nested page lists; the cache
    must report each physical page once, not once per entry."""
    eng, _, _ = make_engine(max_batch=1, max_seq=160, page_size=8,
                            prefill_chunk=8, prefill_token_budget=8)
    r = Request(prompt=[1] + list(range(10, 41)), max_new_tokens=2,
                eos_id=None)                            # 32 tokens = 4 pages
    eng.submit(r)
    eng.run()
    assert len(eng.prefix_cache.entries) >= 3           # boundaries + full
    unique_pages = {p for e in eng.prefix_cache.entries.values()
                    for p in e.cache.pages if p >= 0}
    assert eng.prefix_cache.nbytes <= (
        len(unique_pages) * eng._page_nbytes
        + sum(e.cache.meta.get("rec_nbytes", 0)
              for e in eng.prefix_cache.entries.values()))
    # and strictly less than the naive per-entry sum
    assert eng.prefix_cache.nbytes < sum(
        e.nbytes for e in eng.prefix_cache.entries.values())


# ---------------------------------------------------------------------------
# prefix-cache recurrent semantics (PR-1 regression, satellite)
# ---------------------------------------------------------------------------

def test_prefix_cache_recurrent_derived_from_config():
    assert PrefixCache(model_cfg=get_smoke_config("falcon_mamba_7b")).recurrent
    assert PrefixCache(model_cfg=get_smoke_config("recurrentgemma_9b")).recurrent
    assert not PrefixCache(model_cfg=get_smoke_config("qwen3_0_6b")).recurrent
    assert not PrefixCache(model_cfg=get_smoke_config("granite_moe_1b_a400m")).recurrent
    # engines inherit the derivation
    eng, _, _ = make_engine("falcon_mamba_7b")
    assert eng.prefix_cache.recurrent
    eng, _, _ = make_engine("qwen3_0_6b")
    assert not eng.prefix_cache.recurrent


def test_exact_length_hit_replay_rule():
    """THE PR-1 regression, pinned at the PrefixCache level: an entry
    whose tokens exactly equal the prompt must not be served to recurrent
    models (its state already summarizes the last token, which generation
    must process live — replaying would double-count it in the
    recurrence).  Attention models may reuse it: the KV rewrite at the
    same position is idempotent."""
    toks = [1, 2, 3, 4]
    payload = {"x": jnp.zeros(2)}

    rc = PrefixCache(page_size=2, recurrent=True)
    rc.insert(list(toks), payload)
    assert rc.lookup(list(toks)).kind == "miss"
    # a strictly longer prompt may reuse the whole entry
    res = rc.lookup(toks + [9])
    assert res.kind == "full" and res.cached_len == len(toks)

    ac = PrefixCache(page_size=2, recurrent=False)
    ac.insert(list(toks), payload)
    res = ac.lookup(list(toks))
    assert res.kind == "full" and res.cached_len == len(toks)
