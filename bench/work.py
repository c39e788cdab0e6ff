"""Operations and bytes the algorithm needs, from the configuration and the
token counts of the work.  Counted at the model's dtype (bf16 weights,
activations and KV, two bytes an element), whatever dtype the program
keeps its KV in, so that the same work reads the same count whatever
implements it.  A FLOP is a multiply or an add: one multiply-accumulate
is two.

`m` is a configuration's ``model`` block (`configs/<name>.json`).
"""
from __future__ import annotations

from typing import Iterable, Tuple

BYTES = 2  # bf16


def matmul_params(m: dict) -> int:
    """Weights that multiply every token, over all layers: Q, K, V and
    output projections and the three SwiGLU matrices (norms, biases and
    the embedding lookup do no matrix work)."""
    d, h, kvh, hd, f = (m["d_model"], m["n_heads"], m["n_kv_heads"],
                        m["d_head"], m["d_ff"])
    per_layer = d * h * hd + 2 * d * kvh * hd + h * hd * d + 3 * d * f
    return m["n_layers"] * per_layer


def attn_pairs(ctx: int, new: int) -> int:
    """(query, key) pairs a causal step attends: ``new`` queries after
    ``ctx`` cached tokens, each seeing the cache and itself and the new
    tokens before it."""
    return new * ctx + new * (new + 1) // 2


def attn_flops(m: dict, pairs: int) -> int:
    """QK^T and PV over ``pairs`` (query, key) pairs, all heads and
    layers."""
    return 4 * m["n_heads"] * m["d_head"] * pairs * m["n_layers"]


def step_flops(m: dict, rows: Iterable[Tuple[int, int]], sampled: int) -> int:
    """Model FLOPs of one engine step: ``rows`` are (ctx, new) per request,
    ``sampled`` the rows unembedded (one per request that samples)."""
    rows = list(rows)
    tokens = sum(new for _, new in rows)
    pairs = sum(attn_pairs(c, n) for c, n in rows)
    return (2 * matmul_params(m) * tokens + attn_flops(m, pairs)
            + 2 * m["d_model"] * m["vocab_size"] * sampled)


def prefill_attn(m: dict, ctx: int, new: int) -> Tuple[int, int]:
    """(FLOPs, bytes) of the prefill attention kernel for one request over
    all layers: read q, k, v and write the output once each."""
    h, kvh, hd = m["n_heads"], m["n_kv_heads"], m["d_head"]
    flops = attn_flops(m, attn_pairs(ctx, new))
    per_layer = (2 * new * h * hd + 2 * (ctx + new) * kvh * hd) * BYTES
    return flops, per_layer * m["n_layers"]


def decode_attn(m: dict, ctx: int) -> Tuple[int, int]:
    """(FLOPs, bytes) of the paged decode kernel for one request over all
    layers: one query against ``ctx`` cached tokens (the new token's own
    key is merged outside the kernel).  The kernel's redundant head-match
    work is not the algorithm's and is not counted."""
    h, kvh, hd = m["n_heads"], m["n_kv_heads"], m["d_head"]
    flops = 4 * h * hd * ctx * m["n_layers"]
    per_layer = (2 * ctx * kvh * hd + 2 * h * hd) * BYTES
    return flops, per_layer * m["n_layers"]


def least_time(flops: float, nbytes: float, peak: dict) -> float:
    """Roofline: the larger of compute time and memory time at peak."""
    return max(flops / peak["bf16_flops"], nbytes / peak["hbm_bytes_per_s"])
