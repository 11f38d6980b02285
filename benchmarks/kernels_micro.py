"""Kernel microbenchmarks.  Kernels run the way the backend runs them
(kernels/ops.interpret_mode): compiled through Mosaic on a TPU, in the
Pallas interpreter elsewhere — so a CPU run gives correctness-scale
timings only, never device numbers.

Paged-attention rows time BOTH the Pallas kernel and its XLA oracle
(jitted), fp and int8-quantized: a kernel regression shows up here as a
kernel/oracle ratio shift in the bench trajectory, without waiting for
an end-to-end number to move.

Modes (argv):
  (none)    full row set (what benchmarks/run.py records)
  --smoke   kernel==oracle parity gates only (exit 1 on mismatch) — the
            scripts/verify.sh fast gate
  --tune    sweep block-size candidates for flash/decode/paged-extend
            and commit the winners to kernels/tuning_table.json (see
            docs/SERVING.md#block-autotuning)
"""
from __future__ import annotations

import sys
import time

import jax
import jax.numpy as jnp

from repro.kernels import kv_quant as Q
from repro.kernels import ops, ref, tuning
from repro.launch.mesh import TARGET_KIND, chip_peaks


def _time(fn, *args, iters=3, **kw):
    out = fn(*args, **kw)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args, **kw)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e6


def _extend_inputs(B=2, Sx=8, K=2, G=2, hd=64, P=64, ps=16, NP=16,
                   quant=False):
    """Verify/prefill-chunk-shaped inputs: Sx lanes ending at the last
    slot of an NP-page logical context, pages scattered over the pool."""
    ks = jax.random.split(jax.random.PRNGKey(7), 4)
    q = jax.random.normal(ks[0], (B, Sx, K, G, hd), jnp.float32)
    kp = jax.random.normal(ks[1], (P, K, ps, hd), jnp.float32)
    vp = jax.random.normal(ks[2], (P, K, ps, hd), jnp.float32)
    perm = jax.random.permutation(ks[3], P)[: B * NP]
    pt = perm.reshape(B, NP).astype(jnp.int32)
    pos0 = jnp.full((B,), NP * ps - Sx, jnp.int32)
    extra = {}
    if quant:
        kq, ksc, kz = Q.quantize_k(kp)
        vq, vsc = Q.quantize_v(vp)
        kp, vp = kq, vq
        extra = {"k_scale": ksc, "k_zero": kz, "v_scale": vsc}
    return q, kp, vp, pt, pos0, extra


def run(verbose: bool = True):
    ks = jax.random.split(jax.random.PRNGKey(0), 8)
    rows = []

    q = jax.random.normal(ks[0], (1, 4, 512, 64), jnp.float32)
    k = jax.random.normal(ks[1], (1, 2, 512, 64), jnp.float32)
    v = jax.random.normal(ks[2], (1, 2, 512, 64), jnp.float32)
    us = _time(ops.flash_attention, q, k, v)
    rows.append(("kernel_flash_attention_512", us, "B1H4S512d64"))

    qd = jax.random.normal(ks[3], (2, 2, 2, 64), jnp.float32)
    kd = jax.random.normal(ks[4], (2, 256, 2, 64), jnp.float32)
    vd = jax.random.normal(ks[5], (2, 256, 2, 64), jnp.float32)
    tok = jnp.broadcast_to(jnp.arange(256)[None], (2, 256)).astype(jnp.int32)
    pos = jnp.array([255, 255], jnp.int32)
    us = _time(ops.decode_attention, qd, kd, vd, tok, pos)
    rows.append(("kernel_decode_attention_256", us, "B2C256"))

    # paged decode: same logical 256 tokens scattered over a 64-page pool
    kp = jax.random.normal(ks[4], (64, 2, 16, 64), jnp.float32)
    vp = jax.random.normal(ks[5], (64, 2, 16, 64), jnp.float32)
    pt = jnp.stack([jnp.arange(16, dtype=jnp.int32),
                    jnp.arange(16, 32, dtype=jnp.int32)])
    us = _time(ops.paged_decode_attention, qd, kp, vp, pt, pos)
    rows.append(("kernel_paged_decode_attention_256", us, "B2P64ps16"))
    us = _time(jax.jit(ref.paged_decode_attention_ref), qd, kp, vp, pt, pos)
    rows.append(("oracle_paged_decode_attention_256", us, "B2P64ps16"))

    # int8-quantized pools + scale sidecars: fused-dequant kernel vs the
    # XLA-gather oracle (the engine's read path is the factored XLA
    # equivalent; the kernel is the TPU path)
    kq, ksc, kz = Q.quantize_k(kp)
    vq, vsc = Q.quantize_v(vp)
    us = _time(ops.paged_decode_attention, qd, kq, vq, pt, pos,
               k_scale=ksc, k_zero=kz, v_scale=vsc)
    rows.append(("kernel_quant_paged_decode_attention_256", us,
                 "B2P64ps16int8"))
    us = _time(jax.jit(ref.paged_decode_attention_ref),
               qd, kq, vq, pt, pos, k_scale=ksc, k_zero=kz, v_scale=vsc)
    rows.append(("oracle_quant_paged_decode_attention_256", us,
                 "B2P64ps16int8"))

    # quantized dense-ring decode kernel
    kqd, ksd, kzd = Q.quantize_k(kd)
    vqd, vsd = Q.quantize_v(vd)
    us = _time(ops.decode_attention, qd, kqd, vqd, tok, pos, k_scale=ksd,
               k_zero=kzd, v_scale=vsd)
    rows.append(("kernel_quant_decode_attention_256", us, "B2C256int8"))

    # paged extend/verify: 8 lanes (1 + spec_tokens-shaped) over the same
    # 256-token paged context — the kernel vs the XLA _gather_pages
    # densify path (which the jitted oracle reproduces exactly)
    qe, kpe, vpe, pte, pos0, _ = _extend_inputs()
    us = _time(ops.paged_extend_attention, qe, kpe, vpe, pte, pos0)
    rows.append(("kernel_paged_extend_attention_256", us, "B2Sx8P64ps16"))
    us = _time(jax.jit(ref.paged_extend_attention_ref), qe, kpe, vpe, pte,
               pos0)
    rows.append(("oracle_paged_extend_attention_256", us, "B2Sx8P64ps16"))

    qe, kqe, vqe, pte, pos0, sc = _extend_inputs(quant=True)
    us = _time(ops.paged_extend_attention, qe, kqe, vqe, pte, pos0, **sc)
    rows.append(("kernel_quant_paged_extend_attention_256", us,
                 "B2Sx8P64ps16int8"))
    us = _time(jax.jit(ref.paged_extend_attention_ref), qe, kqe, vqe, pte,
               pos0, **sc)
    rows.append(("oracle_quant_paged_extend_attention_256", us,
                 "B2Sx8P64ps16int8"))

    # tuned vs default blocks for the extend kernel (the autotuner's
    # committed win; equal-or-better by construction on the backend the
    # table was swept on — tuning_table.json, `--tune` to regenerate)
    tuned = tuning.lookup("paged_extend", r=16, hd=64, ctx=256)
    us_d = _time(ops.paged_extend_attention, qe, kqe, vqe, pte, pos0,
                 bq=128, pages_per_block=1, **sc)
    rows.append(("kernel_paged_extend_default_blocks", us_d,
                 "bq128ppb1"))
    us_t = _time(ops.paged_extend_attention, qe, kqe, vqe, pte, pos0,
                 bq=tuned["bq"], pages_per_block=tuned["pages_per_block"], **sc)
    rows.append(("kernel_paged_extend_tuned_blocks", us_t,
                 f"bq{tuned['bq']}ppb{tuned['pages_per_block']};"
                 f"vs_default={us_d / max(us_t, 1e-9):.2f}x"))

    # long-context read-traffic model (roofline, not a timer): one verify
    # step at 4k context — the gather path densifies the pool (read pool
    # + write copy + read copy = 3 passes over KV) where the kernel reads
    # each page once.  Interpret-mode CPU timings cannot show this; the
    # model row tracks the contract the TPU pipeline realizes.
    kern_us = tuning.extend_cost_model_us(B=8, Sx=8, K=2, G=2, hd=64,
                                          ctx=4096, kind=TARGET_KIND)
    kv_bytes = 2 * 8 * 4096 * 2 * 64 * 4
    gather_us = max(kern_us, 3 * kv_bytes
                    / chip_peaks(TARGET_KIND)["hbm_bw"] * 1e6)
    rows.append(("model_paged_extend_vs_gather_4k",
                 0.0, f"{gather_us / kern_us:.2f}x_less_read_time"))

    B, S, D, N = 1, 64, 128, 8
    dt = jax.nn.softplus(jax.random.normal(ks[6], (B, S, D))) * 0.1
    Bm = jax.random.normal(ks[7], (B, S, N))
    us = _time(ops.mamba_scan, dt, Bm, Bm, dt, -jnp.ones((D, N)),
               jnp.ones((D,)), jnp.zeros((B, D, N)))
    rows.append(("kernel_mamba_scan_64", us, f"S{S}D{D}N{N}"))

    a = jax.nn.sigmoid(jax.random.normal(ks[0], (2, 64, 256)))
    b = jax.random.normal(ks[1], (2, 64, 256))
    us = _time(ops.rglru_scan, a, b, jnp.zeros((2, 256)))
    rows.append(("kernel_rglru_scan_64", us, "S64W256"))

    if verbose:
        for n, us, d in rows:
            print(f"{n}: {us:.0f} us/call ({d})")
    return rows


def tune(verbose: bool = True):
    """Sweep block candidates for the three autotuned kernels and commit
    the winners (measured us + roofline estimate) to
    kernels/tuning_table.json for this backend.  Selection is by
    measured time; the recorded ``model_us`` roofline floor
    (tuning.extend_cost_model_us) marks whether the winner is
    bandwidth-credible or timer noise."""
    be = tuning.backend_key()
    # the roofline floor needs the live chip's peaks: an untabled device
    # (the CPU among them) raises here, before any sweep
    kind = jax.devices()[0].device_kind
    chip_peaks(kind)
    if verbose:
        print(f"== autotune (backend={be}) ==")

    # paged extend: verify-shaped (narrow) and prefill-chunk (wide) rows
    for Sx, NP in ((8, 16), (32, 16)):
        q, kp, vp, pt, pos0, _ = _extend_inputs(Sx=Sx, NP=NP)
        R, ctx = Sx * 2, NP * 16
        best = None
        for bq in sorted({16, 32, 64, R}):
            if bq > R:
                continue
            for ppb in (1, 2, 4):
                us = _time(ops.paged_extend_attention, q, kp, vp, pt, pos0,
                           bq=bq, pages_per_block=ppb,
                           iters=2)
                if verbose:
                    print(f"  paged_extend Sx{Sx} bq{bq} ppb{ppb}: "
                          f"{us:.0f} us")
                if best is None or us < best[0]:
                    best = (us, {"bq": bq, "pages_per_block": ppb})
        model_us = tuning.extend_cost_model_us(B=2, Sx=Sx, K=2, G=2,
                                               hd=64, ctx=ctx, kind=kind)
        tuning.record("paged_extend", tuning.shape_key(r=R, hd=64, ctx=ctx),
                      best[1], us=best[0], model_us=model_us, backend=be)

    # flash: causal self-attention tile sweep
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (1, 4, 512, 64), jnp.float32)
    k = jax.random.normal(ks[1], (1, 2, 512, 64), jnp.float32)
    v = jax.random.normal(ks[2], (1, 2, 512, 64), jnp.float32)
    best = None
    for bq in (64, 128, 256):
        for bk in (64, 128, 256):
            us = _time(ops.flash_attention, q, k, v, bq=bq, bk=bk, iters=2)
            if verbose:
                print(f"  flash bq{bq} bk{bk}: {us:.0f} us")
            if best is None or us < best[0]:
                best = (us, {"bq": bq, "bk": bk})
    model_us = tuning.extend_cost_model_us(B=1, Sx=512, K=2, G=2, hd=64,
                                           ctx=512, kind=kind) / 2   # causal
    tuning.record("flash", tuning.shape_key(s=512, hd=64), best[1],
                  us=best[0], model_us=model_us, backend=be)

    # dense-ring decode: kv-tile sweep
    qd = jax.random.normal(ks[0], (2, 2, 2, 64), jnp.float32)
    kd = jax.random.normal(ks[1], (2, 256, 2, 64), jnp.float32)
    vd = jax.random.normal(ks[2], (2, 256, 2, 64), jnp.float32)
    tok = jnp.broadcast_to(jnp.arange(256)[None], (2, 256)).astype(jnp.int32)
    pos = jnp.array([255, 255], jnp.int32)
    best = None
    for bk in (64, 128, 256):
        us = _time(ops.decode_attention, qd, kd, vd, tok, pos, bk=bk, iters=2)
        if verbose:
            print(f"  decode bk{bk}: {us:.0f} us")
        if best is None or us < best[0]:
            best = (us, {"bk": bk})
    model_us = tuning.extend_cost_model_us(B=2, Sx=1, K=2, G=1, hd=64,
                                           ctx=256, kind=kind)
    tuning.record("decode", tuning.shape_key(ctx=256, hd=64), best[1],
                  us=best[0], model_us=model_us, backend=be)
    if verbose:
        print(f"wrote {tuning.TABLE_PATH}")


def smoke():
    """Fast kernel==oracle parity gates (exit 1 on drift) — run by
    scripts/verify.sh.  Covers the extend kernel fp + int8 + windowed
    and the tuned-block configuration actually served from the table."""
    t0 = time.time()
    checks = []
    for quant in (False, True):
        q, kp, vp, pt, pos0, sc = _extend_inputs(quant=quant)
        for window in (None, 48):
            got = ops.paged_extend_attention(q, kp, vp, pt, pos0,
                                             window=window, **sc)
            want = ref.paged_extend_attention_ref(q, kp, vp, pt, pos0,
                                                  window=window, **sc)
            err = float(jnp.max(jnp.abs(got - want)))
            checks.append((f"extend_{'int8' if quant else 'fp'}"
                           f"_{'win' if window else 'full'}", err))
    # tuned blocks must agree with the oracle too (a bad table entry that
    # broke shapes would surface here, not in production)
    q, kp, vp, pt, pos0, _ = _extend_inputs()
    tuned = tuning.lookup("paged_extend", r=16, hd=64, ctx=256)
    got = ops.paged_extend_attention(
        q, kp, vp, pt, pos0, bq=tuned["bq"],
        pages_per_block=tuned["pages_per_block"])
    want = ref.paged_extend_attention_ref(q, kp, vp, pt, pos0)
    checks.append(("extend_tuned_blocks",
                   float(jnp.max(jnp.abs(got - want)))))
    ok = True
    for name, err in checks:
        good = err < 1e-4
        ok &= good
        print(f"kernel_smoke_{name},0.0,{err:.2e}{'' if good else ' FAIL'}")
    print(f"kernels_micro --smoke: {'OK' if ok else 'FAIL'} "
          f"({time.time() - t0:.1f}s)")
    if not ok:
        raise SystemExit(1)


if __name__ == "__main__":
    if "--tune" in sys.argv:
        tune()
    elif "--smoke" in sys.argv:
        smoke()
    else:
        for r in run():
            print(",".join(map(str, r)))
