"""The paged Pallas attention kernel compiles for a TPU v5e.

No chip is needed: the TPU compiler installed with jaxlib compiles for a
DESCRIBED v5e:2x2 topology, and refuses what the chip would refuse —
block shapes off the (8, 128) tiling, too much VMEM — none of which the
interpret-mode parity tests can see.  Shapes are the engine's at
Qwen3-0.6B widths (8 kv heads x 2 query heads each, head_dim 128, bf16,
page_size 16, max_batch 8, max_seq 2048): decode (one lane), the
speculative verify step (1 + spec_tokens lanes) and a mixed-step chunk
(prefill_chunk lanes), each with the fp and the int8 pool.

The topology is described inside a module fixture, never at import:
only one process may load the TPU library, and each xdist worker
imports every test file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.base import ServeConfig
from repro.kernels.paged_extend import paged_extend_attention

K, G, HD = 8, 2, 128                    # qwen3-0.6b: 8 kv x 2 q heads, hd 128
B, MAX_SEQ, PS = 8, 2048, 16
NP = MAX_SEQ // PS
P = B * NP
_SCFG = ServeConfig()
WIDTHS = {"decode": 1, "verify": 1 + _SCFG.spec_tokens,
          "chunk": _SCFG.prefill_chunk}


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a compile for a described chip cannot be read back from the
    # persistent cache without one: keep it out of the cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "no TPU compiler"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.mark.parametrize("quant", [False, True], ids=["bf16", "int8"])
@pytest.mark.parametrize("step", sorted(WIDTHS))
def test_paged_attention_compiles_for_v5e(one_chip, step, quant):
    sx = WIDTHS[step]

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pool = jnp.int8 if quant else jnp.bfloat16
    args = [sds((B, sx, K, G, HD), jnp.bfloat16),
            sds((P, K, PS, HD), pool), sds((P, K, PS, HD), pool),
            sds((B, NP), jnp.int32), sds((B,), jnp.int32)]
    if quant:
        args += [sds((P, K, PS), jnp.float32)] * 3

    def step_fn(q, kp, vp, pt, pos0, *scales):
        sc = dict(zip(("k_scale", "k_zero", "v_scale"), scales))
        return paged_extend_attention(q, kp, vp, pt, pos0, interpret=False,
                                      **sc)

    compiled = jax.jit(step_fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
