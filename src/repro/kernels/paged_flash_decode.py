"""Pallas TPU kernel: batched ragged flash-decode over the paged KV pool.

One launch serves EVERY decode request of an instance: the grid runs over
``(request, kv_head_group, page)`` and each program streams one page of the
pool's paged storage through VMEM, routed by a scalar-prefetched per-request
block table (the page index is known before the DMA is issued, the classic
paged-attention trick).  This replaces O(batch) per-request
`flash_decode_partial` launches fed by dense host-side gathers — the pool is
attended *in place*.

Contract (mirrors `repro.kvcache.pool.KVPool` layout):
  * ``k_pages``/``v_pages``: [n_pages, P, KVH, D] — one attention
    application's storage, shared by all requests;
  * ``block_table``: [B, max_pages] int32 — request b's local token j lives
    in page ``block_table[b, j // P]`` at offset ``j % P`` (padding pages are
    ignored via the length mask);
  * ``lengths``: [B] int32 — number of valid local tokens per request
    (ragged; zero-length requests yield m=-inf, l=0 like any fully-masked
    shard, which the multi-master combine treats as a no-op);
  * masked tail pages: the last page of each request is partially valid.

Window semantics (shared repo convention — see striped_attention.py and
flash_decode.py): a query at global position ``qp`` attends keys with
``0 <= qp - kp < window``, self-inclusive.  The decode query's own KV is NOT
in the pool (it rides separately through the multi-master combine), so the
kernel takes explicit ``query_pos`` and per-slot global positions
(``page_pos``) and applies ``query_pos - page_pos < window``.  Causality
needs no mask here: every pooled token precedes the query by construction.

Block layout (what Mosaic accepts at real widths): a TPU block's last two
dims must be multiples of (8, 128) or span the whole array, so a per-head
page block ``(1, P, 1, D)`` is refused.  The kernel instead views the page
storage as ``[n_pages, P*KVH, D]`` — the same bytes whenever KVH is a
multiple of the sublane tile, so the view is free — and streams one WHOLE
page (all heads) per grid step.  Every query head scores every row of the
page in one ``[H, D] x [D, P*KVH]`` matmul and a head-match mask (column
``c`` is token ``c // KVH`` of KV head ``c % KVH``) keeps each query head
on its own KV head; ``p @ v`` then sums only the matching rows.  The
matmul does KVH-fold redundant MXU work, which decode (bound by reading
the page) hides.  Stats leave as ``[B, H, 1]`` columns.

Emits the unnormalized Partial(o, m, l) for ALL requests in one launch; the
ESP multi-master combine (attention.merge_partial) merges partials across
instances exactly as before — scaling migration stays zero-copy.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.models.attention import Partial, empty_partial

NEG_INF = -1e30


def _kernel(
    # scalar-prefetch refs, inputs (pos only when windowed), outputs, scratch
    bt_ref, len_ref, qp_ref, q_ref, k_ref, v_ref, *rest,
    scale: float,
    window: Optional[int],
    softcap: Optional[float],
    page_size: int,
    kvh: int,
    q_per_kv: int,
    n_page_blocks: int,
):
    if window is not None:
        pos_ref, o_ref, m_out_ref, l_out_ref, acc_ref, m_ref, l_ref = rest
    else:
        o_ref, m_out_ref, l_out_ref, acc_ref, m_ref, l_ref = rest
    b = pl.program_id(0)
    ip = pl.program_id(1)

    @pl.when(ip == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)

    qb = q_ref[0, 0].astype(jnp.float32)  # [H, D]
    kb = k_ref[0].astype(jnp.float32)  # [P*KVH, D] one whole page
    vb = v_ref[0].astype(jnp.float32)
    s = jax.lax.dot_general(
        qb, kb, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale  # [H, P*KVH]
    if softcap is not None:
        s = softcap * jnp.tanh(s / softcap)

    shape = s.shape
    col = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    row = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    head = col % kvh  # column c = (token c // KVH, kv head c % KVH)
    mask = (row >= head * q_per_kv) & (row < (head + 1) * q_per_kv)
    # masked tail page (+ padding pages entirely)
    mask &= ip * page_size + col // kvh < len_ref[b]
    if window is not None:
        kp = pos_ref[0]  # [1, P*KVH] global position per column
        mask &= (qp_ref[b] - kp) < window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]  # [H, 1]
    l_prev = l_ref[...]
    m_blk = jnp.max(s, axis=1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_blk)
    m_safe = jnp.maximum(m_new, -1e29)  # fully-masked-row guard
    p = jnp.where(mask, jnp.exp(s - m_safe), 0.0)
    alpha = jnp.where(m_prev <= NEG_INF / 2, 0.0, jnp.exp(m_prev - m_safe))
    l_ref[...] = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p, vb, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    m_ref[...] = jnp.where(m_blk <= NEG_INF / 2, m_prev, m_new)

    @pl.when(ip == n_page_blocks - 1)
    def _emit():
        o_ref[0, 0] = acc_ref[...]
        mm = m_ref[...]
        m_out_ref[0] = jnp.where(mm <= NEG_INF / 2, -jnp.inf, mm)
        l_out_ref[0] = l_ref[...]


def paged_flash_decode_partial(
    q: jnp.ndarray,  # [B, 1, H, D]
    k_pages: jnp.ndarray,  # [n_pages, P, KVH, D] pool storage (one layer)
    v_pages: jnp.ndarray,
    block_table: jnp.ndarray,  # [B, max_pages] int32 page ids
    lengths: jnp.ndarray,  # [B] int32 valid local tokens per request
    page_pos: Optional[jnp.ndarray] = None,  # [n_pages, P] int32 global pos
    *,
    query_pos: Optional[jnp.ndarray] = None,  # [B] int32, required w/ window
    window: Optional[int] = None,
    softcap: Optional[float] = None,
    interpret: bool = False,
) -> Partial:
    """One ragged batched launch over the paged pool; returns the
    unnormalized Partial over this instance's KV shard for every request."""
    b, sq, h, d = q.shape
    assert sq == 1, "decode kernel: one query token per request"
    n_pages, page_size, kvh = k_pages.shape[0], k_pages.shape[1], k_pages.shape[2]
    rows = page_size * kvh
    max_pages = block_table.shape[1]
    if max_pages == 0:
        return empty_partial(b, sq, h, d)
    if window is not None:
        assert page_pos is not None and query_pos is not None, (
            "window masking needs per-slot global positions + query positions"
        )
    if query_pos is None:
        query_pos = jnp.zeros((b,), jnp.int32)

    kernel = functools.partial(
        _kernel, scale=1.0 / math.sqrt(d), window=window, softcap=softcap,
        page_size=page_size, kvh=kvh, q_per_kv=h // kvh,
        n_page_blocks=max_pages,
    )
    page_spec = pl.BlockSpec(
        (1, rows, d), lambda b_, ip, bt, ln, qp: (bt[b_, ip], 0, 0)
    )
    in_specs = [
        pl.BlockSpec((1, 1, h, d), lambda b_, ip, bt, ln, qp: (b_, 0, 0, 0)),
        page_spec,
        page_spec,
    ]
    operands = [
        q, k_pages.reshape(n_pages, rows, d), v_pages.reshape(n_pages, rows, d)
    ]
    if window is not None:
        # per-slot positions ride along ONLY when windowed — unwindowed
        # decode skips the O(capacity) pos upload/DMA entirely; repeated per
        # KV head to match the page view's columns
        in_specs.append(pl.BlockSpec(
            (1, 1, rows), lambda b_, ip, bt, ln, qp: (bt[b_, ip], 0, 0),
        ))
        operands.append(jnp.repeat(
            jnp.asarray(page_pos, jnp.int32), kvh, axis=1
        ).reshape(n_pages, 1, rows))
    stat_spec = pl.BlockSpec((1, h, 1), lambda b_, ip, bt, ln, qp: (b_, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,  # block_table, lengths, query_pos
        grid=(b, max_pages),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, 1, h, d), lambda b_, ip, bt, ln, qp: (b_, 0, 0, 0)),
            stat_spec,
            stat_spec,
        ],
        scratch_shapes=[
            pltpu.VMEM((h, d), jnp.float32),
            pltpu.VMEM((h, 1), jnp.float32),
            pltpu.VMEM((h, 1), jnp.float32),
        ],
    )
    o, m, l = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((b, 1, h, d), jnp.float32),
            jax.ShapeDtypeStruct((b, h, 1), jnp.float32),
            jax.ShapeDtypeStruct((b, h, 1), jnp.float32),
        ],
        interpret=interpret,
        name="paged_decode_attn",
        metadata={"kernel": "paged_decode_attn"},
    )(
        jnp.asarray(block_table, jnp.int32),
        jnp.asarray(lengths, jnp.int32),
        jnp.asarray(query_pos, jnp.int32),
        *operands,
    )
    return Partial(o=o, m=m.reshape(b, 1, h), l=l.reshape(b, 1, h))
