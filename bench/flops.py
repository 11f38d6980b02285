"""Operations and bytes the work of a serving step needs, from shapes.

A step is described by its rows: ``(n, p0, emits)`` is a request that
ran ``n`` valid query tokens at positions ``p0 .. p0 + n - 1`` (each
attending causally to every earlier position of its sequence), and
``emits`` says whether the step sampled a token for it (the head's
work).  Padding lanes and empty slots are not counted: these are the
operations the result needs, not the ones the program happens to run.
"""
from __future__ import annotations

from typing import Dict, Iterable, Tuple

Row = Tuple[int, int, bool]


def _ctx_sum(n: int, p0: int) -> int:
    """Sum over the n query tokens of how many positions each attends."""
    return n * p0 + n * (n + 1) // 2


def paged_attention_flops(dims: Dict[str, int], rows: Iterable[Row]) -> int:
    """One call (one layer) of the paged kernel: q.k and p.v products."""
    H, hd = dims["H"], dims["hd"]
    return sum(4 * H * hd * _ctx_sum(n, p0) for n, p0, _ in rows if n > 0)


def paged_attention_bytes(dims: Dict[str, int], rows: Iterable[Row],
                          page_size: int, itemsize: int) -> int:
    """One call (one layer): every K and V page a row attends, read once,
    plus its queries read and its outputs written."""
    H, K, hd = dims["H"], dims["K"], dims["hd"]
    total = 0
    for n, p0, _ in rows:
        if n <= 0:
            continue
        pages = -(-(p0 + n) // page_size)
        total += pages * page_size * K * hd * 2 * itemsize
        total += n * H * hd * 2 * itemsize
    return total


def step_flops(dims: Dict[str, int], rows: Iterable[Row]) -> int:
    """The whole model step: projections, attention and MLP of every
    layer for each valid token, and the head for each emitting row."""
    d, H, K, hd, ff, V, L = (dims[k] for k in ("d", "H", "K", "hd", "ff",
                                               "V", "L"))
    per_token = 2 * (d * (H + 2 * K) * hd + H * hd * d + 3 * d * ff) * L
    rows = list(rows)
    tokens = sum(n for n, _, _ in rows if n > 0)
    emitting = sum(1 for n, _, e in rows if n > 0 and e)
    return (tokens * per_token
            + L * paged_attention_flops(dims, rows)
            + emitting * 2 * d * V)
