"""Plain float32 reference of the dense decoder the two configurations run.

It follows the published architecture of both GLM-4 and Qwen1.5 as the
configuration files set it: token embedding; per layer an RMSNorm, grouped
query attention with QKV biases and rotary embedding on the first
``rope_fraction`` of each head (half-split pairs), an output projection, a
second RMSNorm and a SwiGLU feed-forward block, each with a residual add;
a final RMSNorm and an untied head.  It imports nothing of the program:
it reads the weight tree that the benchmark made from the seed
(`bench/weights.py`) and computes everything itself, in float32 with
every matrix product at "highest" precision.

The forward runs one layer at a time (one compiled program per padded
length, shared by all layers), attention over query blocks and the
feed-forward block over row blocks, so that a 32k-token prompt fits beside
the weights.  ``mode="fp8"`` is the control: the same forward with the
inputs of every linear layer rounded to float8 e4m3 (per token for
activations, per output channel for weights), the step below the
configurations' bf16 that a serving change would be tempted by.
"""
from __future__ import annotations

import functools
from typing import Any, Dict

import numpy as np

_Q_BLOCK = 256


def pad_len(n: int) -> int:
    """Padded length: powers of two and their 3/4 points, at least 512,
    so a run compiles a handful of reference programs."""
    p = 512
    while p < n:
        p *= 2
    return p * 3 // 4 if n <= p * 3 // 4 and p * 3 // 4 >= 512 else p


def _linear(x, w, mode: str):
    """x [..., in] @ w [in, out...] in float32 at highest precision; under
    ``fp8`` both operands are first rounded to float8 levels."""
    import jax
    import jax.numpy as jnp

    w = w.astype(jnp.float32).reshape(w.shape[0], -1)
    if mode != "f32":
        x = _quantize(x, -1, mode)
        w = _quantize(w, 0, mode)
    return jnp.matmul(x, w, precision=jax.lax.Precision.HIGHEST)


def _quantize(a, axis: int, mode: str):
    """``a`` rounded to float8 e4m3 levels, scaled per slice along
    ``axis`` so that its largest magnitude maps to the format's (448)."""
    import jax.numpy as jnp

    s = jnp.max(jnp.abs(a), axis=axis, keepdims=True) / 448.0
    s = jnp.where(s > 0, s, 1.0)
    return (a / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _rms(x, scale, eps):
    import jax.numpy as jnp

    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x / jnp.sqrt(var + eps) * scale.astype(jnp.float32)


def _rope(x, pos, d_rot: int, theta: float):
    """Rotary embedding on the first ``d_rot`` features of each head, the
    first half of those paired with the second half."""
    import jax.numpy as jnp

    if d_rot == 0:
        return x
    inv = 1.0 / theta ** (jnp.arange(0, d_rot, 2, dtype=jnp.float32) / d_rot)
    ang = pos.astype(jnp.float32)[:, None] * inv  # [n, d_rot/2]
    c, s = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    half = d_rot // 2
    x1, x2, rest = x[..., :half], x[..., half:d_rot], x[..., d_rot:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s, rest], axis=-1)


@functools.lru_cache(maxsize=None)
def _layer_fn(m_items: tuple, npad: int, mode: str):
    import jax
    import jax.numpy as jnp

    m = dict(m_items)
    h, kvh, hd = m["n_heads"], m["n_kv_heads"], m["d_head"]
    g = h // kvh
    eps = m["norm_eps"]
    d_rot = int(hd * m["rope_fraction"]) // 2 * 2
    hi = jax.lax.Precision.HIGHEST

    def layer(x, layers, li):
        lp = jax.tree.map(lambda a: jax.lax.dynamic_index_in_dim(
            a, li, keepdims=False), layers)
        a = lp["attn"]
        pos = jnp.arange(npad)
        y = _rms(x, lp["norm1"]["scale"], eps)
        q = _linear(y, a["wq"], mode).reshape(npad, h, hd)
        k = _linear(y, a["wk"], mode).reshape(npad, kvh, hd)
        v = _linear(y, a["wv"], mode).reshape(npad, kvh, hd)
        if "bq" in a:
            q = q + a["bq"].astype(jnp.float32)
            k = k + a["bk"].astype(jnp.float32)
            v = v + a["bv"].astype(jnp.float32)
        q = _rope(q, pos, d_rot, m["rope_theta"])
        k = _rope(k, pos, d_rot, m["rope_theta"])
        qb = q.reshape(npad // _Q_BLOCK, _Q_BLOCK, kvh, g, hd)

        def attend(args):
            qi, i = args
            s = jnp.einsum("qkgd,tkd->kgqt", qi, k, precision=hi) / np.sqrt(hd)
            qpos = i * _Q_BLOCK + jnp.arange(_Q_BLOCK)
            s = jnp.where(qpos[:, None] >= pos[None, :], s, -jnp.inf)
            p = jax.nn.softmax(s, axis=-1)
            return jnp.einsum("kgqt,tkd->qkgd", p, v, precision=hi)

        o = jax.lax.map(attend, (qb, jnp.arange(npad // _Q_BLOCK)))
        o = o.reshape(npad, h * hd)
        x = x + _linear(o, a["wo"].reshape(h * hd, -1), mode)
        f = lp["ffn"]

        def ffn(xr):
            yr = _rms(xr, lp["norm2"]["scale"], eps)
            gate = _linear(yr, f["w_gate"], mode)
            up = _linear(yr, f["w_up"], mode)
            return _linear(jax.nn.silu(gate) * up, f["w_down"], mode)

        rows = 1024 if npad % 1024 == 0 else _Q_BLOCK
        out = jax.lax.map(ffn, x.reshape(npad // rows, rows, -1))
        return x + out.reshape(npad, -1)

    return jax.jit(layer)


@functools.lru_cache(maxsize=None)
def _head_fn(m_items: tuple, mode: str):
    import jax
    import jax.numpy as jnp

    m = dict(m_items)

    def head(x_rows, final_scale, lm_head):
        y = _rms(x_rows, final_scale, m["norm_eps"])
        return _linear(y, lm_head, mode)

    return jax.jit(head)


def logits(params: Dict[str, Any], m: Dict[str, Any], tokens: np.ndarray,
           rows: np.ndarray, mode: str = "f32") -> np.ndarray:
    """Float32 logits [len(rows), vocab] of the causal forward over
    ``tokens`` at positions ``rows``."""
    import jax.numpy as jnp

    if mode not in ("f32", "fp8"):
        raise ValueError(f"mode {mode!r}: expected f32 or fp8")
    n = len(tokens)
    npad = pad_len(n)
    items = tuple(sorted((k, v) for k, v in m.items()
                         if not isinstance(v, (dict, list))))
    ids = np.zeros(npad, np.int32)
    ids[:n] = tokens
    x = jnp.take(params["embed"], jnp.asarray(ids), axis=0).astype(jnp.float32)
    layer = _layer_fn(items, npad, mode)
    for li in range(m["n_layers"]):
        x = layer(x, params["layers"], jnp.int32(li))
    rows = np.asarray(rows, np.int32)
    rpad = max(8, 1 << max(len(rows) - 1, 0).bit_length())
    sel = np.zeros(rpad, np.int32)
    sel[: len(rows)] = rows
    head = params["embed"].T if m["tie_embeddings"] else params["lm_head"]
    out = _head_fn(items, mode)(jnp.take(x, jnp.asarray(sel), axis=0),
                                params["final_norm"]["scale"], head)
    return np.asarray(out)[: len(rows)]
