"""The paged Pallas attention kernel, and the serving step programs that
run it, compile for a TPU v5e.

No chip is needed: the TPU compiler installed with jaxlib compiles for a
DESCRIBED v5e:2x2 topology, and refuses what the chip would refuse —
block shapes off the (8, 128) tiling, too much VMEM — none of which the
interpret-mode parity tests can see.  Kernel shapes are the engine's at
Qwen3-0.6B widths (8 kv heads x 2 query heads each, head_dim 128, bf16,
page_size 16, max_batch 8, max_seq 2048): decode (one lane), the
speculative verify step (1 + spec_tokens lanes) and a mixed-step chunk
(prefill_chunk lanes), each with the fp and the int8 pool.

The step programs (``decode_step`` / ``prefill_extend`` of a 4-layer
model at the same widths, the kernel through Mosaic, the cache donated
as the engine donates it) must keep the paged pool in place through the
layer scan: no instruction but parameters, aliases and the in-place
scatter may produce a pool-sized or layer-pool-sized buffer, and the
program's temporaries stay below one layer's pool.

The TPU library is loaded in a child process of its own, one for the
module, never in the test process: only one process may load it, each
xdist worker imports every test file, and once loaded it adds a device
plane to every later profiler trace of its process, where the
benchmark's CPU traced runs read the host threads only when there is
none (bench/tracefile.py).
"""
import concurrent.futures
import functools
import math
import multiprocessing
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import qwen3_0_6b
from repro.configs.base import ServeConfig, dense_pattern
from repro.kernels import ops
from repro.kernels.paged_extend import paged_extend_attention
from repro.models import attention as A
from repro.models import layers as L
from repro.models.registry import build_model

K, G, HD = 8, 2, 128                    # qwen3-0.6b: 8 kv x 2 q heads, hd 128
B, MAX_SEQ, PS = 8, 2048, 16
NP = MAX_SEQ // PS
P = B * NP
_SCFG = ServeConfig()
WIDTHS = {"decode": 1, "verify": 1 + _SCFG.spec_tokens,
          "chunk": _SCFG.prefill_chunk}


@functools.lru_cache(maxsize=None)
def _one_chip():
    """In the child: one device of a described v5e:2x2, with the kernel
    lowered through Mosaic.  Raises where none can be described."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a compile for a described chip cannot be read back from the
    # persistent cache without one: keep it out of the cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    ops.interpret_mode = lambda: False      # the engine's read on a TPU
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    return SingleDeviceSharding(topo.devices[0])


def _describe():
    try:
        _one_chip()
    except Exception as e:  # noqa: BLE001 — any failure means "no TPU compiler"
        return f"no v5e:2x2 topology can be described here: {e}"
    return None


@pytest.fixture(scope="module")
def chip():
    """A child process that compiles for the described v5e."""
    ctx = multiprocessing.get_context("spawn")
    with concurrent.futures.ProcessPoolExecutor(1, mp_context=ctx) as ex:
        why = ex.submit(_describe).result()
        if why:
            pytest.skip(why)
        yield ex


def _compile_kernel(step, quant):
    """In the child: the kernel's HLO at one step shape."""
    one_chip = _one_chip()
    sx = WIDTHS[step]

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    # the layer scan's stack of two layers' pools, read at a traced layer
    pool = jnp.int8 if quant else jnp.bfloat16
    args = [sds((B, sx, K, G, HD), jnp.bfloat16),
            sds((2, P, K, PS, HD), pool), sds((2, P, K, PS, HD), pool),
            sds((B, NP), jnp.int32), sds((B,), jnp.int32),
            sds((), jnp.int32)]
    if quant:
        args += [sds((2, P, K, PS), jnp.float32)] * 3

    def step_fn(q, kp, vp, pt, pos0, layer, *scales):
        sc = dict(zip(("k_scale", "k_zero", "v_scale"), scales))
        return paged_extend_attention(q, kp, vp, pt, pos0, layer,
                                      interpret=False, **sc)

    return jax.jit(step_fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("quant", [False, True], ids=["bf16", "int8"])
@pytest.mark.parametrize("step", sorted(WIDTHS))
def test_paged_attention_compiles_for_v5e(chip, step, quant):
    hlo = chip.submit(_compile_kernel, step, quant).result()
    assert "tpu_custom_call" in hlo


# A pool of a deployment's size at the benchmark's page size: the int8
# scale sidecars of the 4-layer stack (67 MB) and every K/V leaf are
# too large for the compiler to stage through the chip's 128 MiB of VMEM,
# as a deployment's are.  A prime page count, so that no other array of
# the step shares a pool leaf's element count.
STEP_PAGES, STEP_PS = 2053, 256
STEP_NP = MAX_SEQ // STEP_PS
STEP_MODELS = {
    "scanned": dict(num_layers=4, block_pattern=dense_pattern(4)),
    # a windowed layer after the scan: an unscanned, per-layer pool
    "tail": dict(num_layers=5, block_pattern=dense_pattern(4) + ("rg_attn",),
                 local_window=64),
}
# what may hold a pool-sized result: aliases of the donated pool, and
# the in-place writes of the new rows (a scatter, a dynamic-update-slice,
# or a fusion whose root is one)
_IN_PLACE = {"parameter", "get-tuple-element", "bitcast", "tuple", "while",
             "scatter", "dynamic-update-slice"}
# an instruction: its name, its result (an array, or a tuple of them —
# an async copy's, a multi-output fusion's) and its opcode
_INST = re.compile(r"\s*(?:ROOT )?%(\S+) = (.+?) ([\w-]+)\(")
_ARRAY = re.compile(r"\w+\[([\d,]*)\]")


def _in_place_fusions(hlo: str) -> set:
    """Names of the fused computations whose root writes in place."""
    out, comp = set(), None
    for line in hlo.splitlines():
        m = re.match(r"%(\S+) \(", line)
        if m:
            comp = m.group(1)
        elif line.lstrip().startswith("ROOT ") and re.search(
                r" (scatter|dynamic-update-slice)\(", line):
            out.add(comp)
    return out


def _compile_step(step, model, quant):
    """In the child: compile one step program of a model; return whether
    it runs the kernel, its pool-sized results but aliases and in-place
    writes, its temp bytes and one layer's pool bytes."""
    one_chip = _one_chip()
    cfg = qwen3_0_6b.CONFIG.replace(dtype="bfloat16", param_dtype="bfloat16",
                                    **STEP_MODELS[model])
    m = build_model(cfg)

    def sds(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)

    params = jax.tree_util.tree_map(
        sds, jax.eval_shape(m.init, jax.random.PRNGKey(0)))
    cache = jax.tree_util.tree_map(
        sds, m.cache_defs_paged(B, STEP_PAGES, STEP_PS,
                                kv_dtype="int8" if quant else None),
        is_leaf=L.is_def)
    layer_defs = A.paged_kv_cache_def(cfg, STEP_PAGES, STEP_PS, m.dtype,
                                      "int8" if quant else None)
    layer_pool = sum(math.prod(d.shape) * jnp.dtype(d.dtype).itemsize
                     for d in layer_defs.values())
    # every pool leaf, stacked and one layer's.  In the tail model a
    # single layer's sidecar is the tail layer's own (17 MB), which the
    # compiler stages through VMEM as it did before the pool was carried;
    # its slices out of the stack are the scanned model's to catch.
    pool_sizes = {n * math.prod(d.shape) for name, d in layer_defs.items()
                  for n in (1, m.repeats)
                  if n > 1 or name in ("kp", "vp") or not m.tail}

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

    sx = WIDTHS["chunk" if step == "mixed" else step]
    if step == "decode":
        fn = lambda p, c, t, pos, pt: m.decode_step(      # noqa: E731
            p, c, t, pos, page_table=pt, attn_impl="pallas")
        dyn = (i32(B, 1), i32(B), i32(B, STEP_NP))
    else:
        fn = lambda p, c, t, pos0, nv, pt: m.prefill_extend(  # noqa: E731
            p, c, t, pos0, n_valid=nv, page_table=pt,
            all_logits=step == "verify", attn_impl="pallas")
        dyn = (i32(B, sx), i32(B), i32(B), i32(B, STEP_NP))
    compiled = jax.jit(fn, donate_argnums=1).lower(params, cache,
                                                   *dyn).compile()
    hlo = compiled.as_text()
    in_place = _in_place_fusions(hlo)
    moved = []
    for line in hlo.splitlines():
        mt = _INST.match(line)
        if not mt:
            continue
        name, result, op = mt.groups()
        sizes = {math.prod(int(d) for d in dims.split(",") if d)
                 for dims in _ARRAY.findall(result)}
        if not sizes & pool_sizes or op in _IN_PLACE:
            continue
        if op == "fusion" and re.search(r"calls=%(\S+?),", line).group(1) \
                in in_place:
            continue
        moved.append(f"{op} {name} {result}")
    return ("tpu_custom_call" in hlo, moved,
            compiled.memory_analysis().temp_size_in_bytes, layer_pool)


@pytest.mark.parametrize("quant", [False, True], ids=["bf16", "int8"])
@pytest.mark.parametrize("model", sorted(STEP_MODELS))
@pytest.mark.parametrize("step", ["decode", "mixed", "verify"])
def test_step_programs_keep_the_pool_in_place(chip, step, model, quant):
    kernel, moved, temp, layer_pool = chip.submit(
        _compile_step, step, model, quant).result()
    assert kernel
    assert not moved, f"pool-sized buffers in the {step} program: {moved}"
    assert temp < layer_pool
