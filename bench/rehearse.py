"""Compile a configuration's step programs for a described TPU v5e,
without a chip, and print what each needs of the device's memory.

    JAX_PLATFORMS=cpu python3 bench/rehearse.py qwen3-0.6b [yi-6b-l16 ...]

For each configuration: the weights' bytes, the paged pool's bytes at
the file's ``num_pages``, and ``memory_analysis()`` of the decode
``[B, 1]`` and mixed ``[B, prefill_chunk]`` programs with the Pallas
kernel, the pool donated as the engine donates it.  This sizes the pool:
16 GB less weights, the largest temporaries and a margin.
"""
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:] = [ROOT, os.path.join(ROOT, "src")] + sys.path[1:]
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def main(names):
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from bench.families import dense_gqa as fam
    from repro.configs.base import ServeConfig
    from repro.models import layers as L
    from repro.models.registry import build_model

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    dev = SingleDeviceSharding(topo.devices[0])
    for name in names:
        with open(os.path.join(ROOT, "bench", "configs", f"{name}.json")) as f:
            conf = json.load(f)
        cfg = fam.model_config(conf)
        model = build_model(cfg)
        sv = conf["serve"]
        B, ps, P = sv["max_batch"], sv["page_size"], sv["num_pages"]
        NP = -(-sv["max_seq"] // ps)
        W = ServeConfig().prefill_chunk

        def sds(x):
            return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=dev)
        params = jax.tree_util.tree_map(
            sds, jax.eval_shape(model.init, jax.random.PRNGKey(0)))
        cache = jax.tree_util.tree_map(
            lambda d: jax.ShapeDtypeStruct(d.shape, d.dtype, sharding=dev),
            model.cache_defs_paged(B, P, ps), is_leaf=L.is_def)
        i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=dev)
        wbytes = sum(x.size * x.dtype.itemsize
                     for x in jax.tree_util.tree_leaves(params))
        pbytes = sum(x.size * x.dtype.itemsize
                     for x in jax.tree_util.tree_leaves(cache))
        print(f"{name}: weights {wbytes} B, pool {pbytes} B "
              f"({P} pages of {ps} tokens)", flush=True)
        decode = jax.jit(lambda p, c, t, pos, pt: model.decode_step(
            p, c, t, pos, page_table=pt, attn_impl="pallas"),
            donate_argnums=1)
        mixed = jax.jit(lambda p, c, t, pos0, nv, pt: model.prefill_extend(
            p, c, t, pos0, n_valid=nv, page_table=pt, attn_impl="pallas"),
            donate_argnums=1)
        for label, fn, args in (
                ("decode", decode, (i32(B, 1), i32(B), i32(B, NP))),
                ("mixed", mixed, (i32(B, W), i32(B), i32(B), i32(B, NP)))):
            ma = fn.lower(params, cache, *args).compile().memory_analysis()
            print(f"  {label}: temp {ma.temp_size_in_bytes} B, argument "
                  f"{ma.argument_size_in_bytes} B, output "
                  f"{ma.output_size_in_bytes} B, alias "
                  f"{ma.alias_size_in_bytes} B", flush=True)


if __name__ == "__main__":
    main(sys.argv[1:])
