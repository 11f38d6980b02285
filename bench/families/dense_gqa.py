"""The system under test for the dense GQA family: the program's
``TransformerLM`` served by its ``Engine``, built from a configuration
file's published keys, with the weights that ``dense_gqa_ref.draw_weights``
draws from the seed, placed into the program's parameter tree.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

from bench.families import dense_gqa_ref as ref


def model_config(conf: Dict):
    """The program's ModelConfig for the configuration file ``conf``."""
    from repro.configs.base import ModelConfig, dense_pattern

    s = ref.dims(conf)
    return ModelConfig(
        name=conf["name"], arch_type="dense", num_layers=s["L"],
        d_model=s["d"], num_heads=s["H"], num_kv_heads=s["K"],
        head_dim=s["hd"], d_ff=s["ff"], vocab_size=s["V"],
        block_pattern=dense_pattern(s["L"]),
        qk_norm=conf["model_type"] in ref.QK_NORM_TYPES,
        rope_theta=float(conf["rope_theta"]), sliding_window=None,
        mlp_act="swiglu", norm_eps=float(conf["rms_norm_eps"]),
        dtype=conf["dtype"], param_dtype=conf["dtype"],
        tie_embeddings=bool(conf.get("tie_word_embeddings", False)),
        source=conf["source"])


def to_program(conf: Dict, w: Dict[str, jax.Array]) -> Dict:
    """Canonical weights -> the program's parameter tree: reshapes, and a
    cast to the configuration's dtype."""
    w = {k: v.astype(conf["dtype"]) for k, v in w.items()}
    s = ref.dims(conf)
    L, d, H, K, hd = s["L"], s["d"], s["H"], s["K"], s["hd"]
    attn = {"wq": w["wq"].reshape(L, d, H, hd),
            "wk": w["wk"].reshape(L, d, K, hd),
            "wv": w["wv"].reshape(L, d, K, hd),
            "wo": w["wo"].reshape(L, H, hd, d)}
    if "q_norm" in w:
        attn["q_norm"] = w["q_norm"]
        attn["k_norm"] = w["k_norm"]
    block = {"ln1": {"scale": w["ln1"]}, "attn": attn,
             "ln2": {"scale": w["ln2"]},
             "mlp": {"wi": w["w_up"], "wg": w["w_gate"],
                     "wo": w["w_down"]}}
    p = {"embed": w["embed"], "scan": (block,), "tail": (),
         "ln_f": {"scale": w["final_norm"]}}
    if "head" in w:
        p["unembed"] = w["head"]
    return p


def make_weights(conf: Dict, key: jax.Array):
    """Draw the canonical weights on the device in one jitted call."""
    return jax.jit(lambda k: ref.draw_weights(conf, k))(key)


def build(conf: Dict, key: jax.Array, serve: Dict, engine_cls=None):
    """(engine, model) serving the configuration, weights from ``key``.
    ``serve`` holds the ServeConfig fields the configuration pins."""
    from repro.configs.base import ServeConfig
    from repro.models.registry import build_model
    from repro.serving.engine import Engine

    cfg = model_config(conf)
    model = build_model(cfg)
    if model.repeats != cfg.num_layers:
        raise ValueError("expected one scanned stage over all layers")
    canon = make_weights(conf, key)
    params = jax.jit(lambda w: to_program(conf, w),
                     donate_argnums=0)(canon)
    want = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    got = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), params)
    if (jax.tree_util.tree_structure(want)
            != jax.tree_util.tree_structure(got) or want != got):
        raise ValueError("drawn weights do not fit the program's tree")
    scfg = ServeConfig(**serve, aot_warmup=False)
    eng = (engine_cls or Engine)(model, params, scfg)
    return eng, model


def kv_bytes_per_token(conf: Dict) -> int:
    s = ref.dims(conf)
    return 2 * s["L"] * s["K"] * s["hd"] * jnp.dtype(conf["dtype"]).itemsize
