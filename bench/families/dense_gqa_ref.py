"""Plain float32 reference for the dense GQA decoder family (Qwen3, Yi,
Llama-style): RMSNorm, rotary embedding (rotate-half), optional per-head
qk-norm, grouped-query attention, SwiGLU MLP, tied or untied head.

It follows the published architectures (the Hugging Face ``config.json``
keys the configuration file holds), imports nothing of the program under
test, and takes nothing the program made: it draws the weights itself
from the seed with :func:`draw_weights`, the same function the harness
uses to make the served weights.

Everything runs in float32 with ``jax.default_matmul_precision
("highest")`` from the bfloat16 weights, one layer per call, attention in
blocks of query rows, so it fits beside a 7 GB model on one chip.

The control (:data:`CONTROL`) is the same pass computed one step below
the precision the configuration states: every projection, MLP and head
matmul takes both operands rounded to bfloat16 (for a float32
configuration) or to float8_e4m3fn (for a bfloat16 one; weights scaled
per output column, activations per row).
"""
from __future__ import annotations

import functools
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

Q_BLOCK = 512            # query rows per attention block
BUCKETS = (1024, 2048, 4096, 8192, 16384)
FP8_MAX = 448.0          # largest finite float8_e4m3fn


QK_NORM_TYPES = ("qwen3",)   # model types with per-head q/k RMSNorm
# the precision one step below each stated dtype
CONTROL = {"float32": "bf16", "bfloat16": "fp8"}


def dims(hf: Dict) -> Dict[str, int]:
    """Sizes of the architecture from its ``config.json`` keys."""
    d = hf["hidden_size"]
    h = hf["num_attention_heads"]
    return {
        "d": d, "H": h, "K": hf.get("num_key_value_heads", h),
        "hd": hf.get("head_dim") or d // h,
        "ff": hf["intermediate_size"], "V": hf["vocab_size"],
        "L": hf["num_hidden_layers"],
    }


def weight_shapes(hf: Dict) -> Dict[str, tuple]:
    """Canonical weight layout: stacked over layers, matmuls as [in, out]."""
    s = dims(hf)
    d, H, K, hd, ff, V, L = (s[k] for k in ("d", "H", "K", "hd", "ff",
                                             "V", "L"))
    shapes = {
        "embed": (V, d), "final_norm": (d,),
        "ln1": (L, d), "ln2": (L, d),
        "wq": (L, d, H * hd), "wk": (L, d, K * hd), "wv": (L, d, K * hd),
        "wo": (L, H * hd, d),
        "w_gate": (L, d, ff), "w_up": (L, d, ff), "w_down": (L, ff, d),
    }
    if hf["model_type"] in QK_NORM_TYPES:
        shapes["q_norm"] = (L, hd)
        shapes["k_norm"] = (L, hd)
    if not hf.get("tie_word_embeddings", False):
        shapes["head"] = (d, V)
    return shapes


def draw_weights(hf: Dict, key: jax.Array) -> Dict[str, jax.Array]:
    """Seeded random weights in bfloat16, traced under the caller's jit.

    Matmul weights are N(0, 1/fan_in) so activations keep unit scale.
    Embeddings are N(0, 1/hidden_size): small against what each layer
    adds to the residual stream, so that a tied head does not just echo
    the input token and the logits depend on attention over the context.
    Norm scales are 1 + N(0, 0.1^2), so that a norm that is skipped or
    applied twice shows in the logits."""
    shapes = weight_shapes(hf)
    keys = jax.random.split(key, len(shapes))
    out = {}
    for (name, shape), k in zip(sorted(shapes.items()), keys):
        if name in ("final_norm", "ln1", "ln2", "q_norm", "k_norm"):
            w = 1.0 + 0.1 * jax.random.normal(k, shape, jnp.float32)
        elif name == "embed":
            w = jax.random.normal(k, shape, jnp.float32) * shape[1] ** -0.5
        else:
            w = jax.random.normal(k, shape, jnp.float32) * shape[-2] ** -0.5
        out[name] = w.astype(jnp.bfloat16)
    return out


def bucket(n: int) -> int:
    """Padded sequence length for ``n`` tokens (few shapes, few compiles)."""
    for b in BUCKETS:
        if n <= b:
            return b
    raise ValueError(f"sequence of {n} tokens exceeds the largest bucket")


def _f8(x: jax.Array, axis: int) -> jax.Array:
    """Round to float8_e4m3fn with one scale per slice along ``axis``."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / FP8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(x: jax.Array, w: jax.Array, precision: str) -> jax.Array:
    """x [..., in] @ w [in, out] in float32, from operands rounded to
    ``precision``."""
    w = w.astype(jnp.float32)
    if precision == "fp8":
        x = _f8(x, axis=-1)
        w = _f8(w, axis=0)
    elif precision == "bf16":
        x = x.astype(jnp.bfloat16).astype(jnp.float32)
    return jnp.einsum("...i,io->...o", x, w)


def _rmsnorm(x: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)


def _rope(x: jax.Array, pos: jax.Array, theta: float) -> jax.Array:
    """Rotate-half rotary embedding (the Hugging Face convention).
    x: [S, heads, hd]; pos: [S]."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]      # [S, hd/2]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, axis=-1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, axis=-1)[:, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    rot = jnp.concatenate([-x2, x1], axis=-1)
    return x * cos + rot * sin


def _layer(hf: Dict, precision: str, x: jax.Array, w: Dict, i: jax.Array,
           n: jax.Array) -> jax.Array:
    """One decoder layer over x [S, d] (rows >= n are padding)."""
    s = dims(hf)
    H, K, hd = s["H"], s["K"], s["hd"]
    G = H // K
    eps = hf["rms_norm_eps"]
    S = x.shape[0]
    lw = {k: jax.lax.dynamic_index_in_dim(v, i, 0, keepdims=False)
          for k, v in w.items()
          if k not in ("embed", "final_norm", "head")}
    pos = jnp.arange(S)
    a = _rmsnorm(x, lw["ln1"], eps)
    q = _mm(a, lw["wq"], precision).reshape(S, H, hd)
    k = _mm(a, lw["wk"], precision).reshape(S, K, hd)
    v = _mm(a, lw["wv"], precision).reshape(S, K, hd)
    if "q_norm" in lw:
        q = _rmsnorm(q, lw["q_norm"], eps)
        k = _rmsnorm(k, lw["k_norm"], eps)
    q = _rope(q, pos, hf["rope_theta"])
    k = _rope(k, pos, hf["rope_theta"])
    # query head h reads kv head h // G
    qg = q.reshape(S // Q_BLOCK, Q_BLOCK, K, G, hd)
    t = jnp.arange(S)

    def block(args):
        qb, b = args
        rows = b * Q_BLOCK + jnp.arange(Q_BLOCK)
        sc = jnp.einsum("qkgd,tkd->kgqt", qb, k) * hd ** -0.5
        ok = (t[None, :] <= rows[:, None]) & (t[None, :] < n)
        sc = jnp.where(ok[None, None], sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        return jnp.einsum("kgqt,tkd->qkgd", p, v)

    o = jax.lax.map(block, (qg, jnp.arange(S // Q_BLOCK)))
    o = o.reshape(S, H * hd)
    x = x + _mm(o, lw["wo"], precision)
    m = _rmsnorm(x, lw["ln2"], eps)
    g = _mm(m, lw["w_gate"], precision)
    u = _mm(m, lw["w_up"], precision)
    return x + _mm(jax.nn.silu(g) * u, lw["w_down"], precision)


def _logits(hf: Dict, precision: str, x: jax.Array, w: Dict,
            rows: jax.Array) -> jax.Array:
    """Final norm and head at the positions ``rows`` of x [S, d]."""
    h = _rmsnorm(x[rows], w["final_norm"], hf["rms_norm_eps"])
    head = w["head"] if "head" in w else w["embed"].T
    return _mm(h, head, precision)


@functools.lru_cache(maxsize=None)
def _programs(hf_items: tuple, precision: str):
    hf = dict(hf_items)
    hp = "highest"

    @jax.jit
    def embed(w, toks):
        with jax.default_matmul_precision(hp):
            return w["embed"][toks].astype(jnp.float32)

    @jax.jit
    def layer(x, w, i, n):
        with jax.default_matmul_precision(hp):
            return _layer(hf, precision, x, w, i, n)

    @jax.jit
    def logits(x, w, rows):
        with jax.default_matmul_precision(hp):
            return _logits(hf, precision, x, w, rows)

    return embed, layer, logits


def _freeze(hf: Dict) -> tuple:
    return tuple(sorted((k, v) for k, v in hf.items()
                        if isinstance(v, (int, float, bool, str))
                        or v is None))


def next_token_logits(hf: Dict, w: Dict, tokens: Sequence[int],
                      rows: Sequence[int], precision: str = "float32"
                      ) -> np.ndarray:
    """Logits [len(rows), V] predicting the token after each position in
    ``rows`` of the sequence ``tokens``, by the full forward pass."""
    n = len(tokens)
    S = bucket(n)
    R = bucket(len(rows))
    embed, layer, logits = _programs(_freeze(hf), precision)
    toks = np.zeros(S, np.int32)
    toks[:n] = tokens
    x = embed(w, jnp.asarray(toks))
    nn = jnp.asarray(n, jnp.int32)
    for i in range(dims(hf)["L"]):
        x = layer(x, w, jnp.asarray(i, jnp.int32), nn)
    r = np.zeros(R, np.int32)
    r[:len(rows)] = rows
    out = logits(x, w, jnp.asarray(r))
    return np.asarray(out[:len(rows)], np.float32)


def served_gaps(hf: Dict, w: Dict, prompt: Sequence[int],
                output: Sequence[int], control: Optional[str] = None
                ) -> List[float]:
    """For each served token, how far its reference logit lies below the
    reference's best at that position, in units of that row's standard
    deviation (0 where the served token is the reference's argmax).

    With ``control`` set, the served tokens are replaced by the argmax of
    the reference computed in that precision, at the same positions of
    the same sequence: the reading a lower-precision program would give.
    """
    seq = list(prompt) + list(output[:-1])
    rows = list(range(len(prompt) - 1, len(seq)))
    ref = next_token_logits(hf, w, seq, rows)
    if control is None:
        chosen = np.asarray(output, np.int64)
    else:
        chosen = next_token_logits(hf, w, seq, rows, control).argmax(-1)
    best = ref.max(-1)
    got = ref[np.arange(len(rows)), chosen]
    return ((best - got) / ref.std(-1)).tolist()
