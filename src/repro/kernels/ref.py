"""Pure-jnp oracles for the Pallas kernels (allclose targets in tests)."""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.models import attention as A

BAND = 128  # the banded prefill fallbacks' q block (their band's unit)


def serial_decode_oracle(model, params, prompt, n_decode: int) -> list:
    """Greedy token oracle for engine parity tests/demos: one serial
    prefill over `prompt` followed by ``n_decode`` dense-cache decode steps
    (argmax sampling, KV appended in place).  Returns the ``n_decode + 1``
    emitted token ids — what a real-mode engine must reproduce exactly."""
    import numpy as np

    toks = jnp.asarray(np.asarray(prompt)[None], jnp.int32)
    logits, cache = model.prefill(params, {"tokens": toks})
    nxt = int(np.argmax(np.asarray(logits[0, -1])))
    out = [nxt]
    n_in = len(prompt)
    s_max = n_in + n_decode + 2
    k_pad = jnp.zeros((cache.k.shape[0], 1, s_max) + cache.k.shape[3:],
                      cache.k.dtype).at[:, :, :n_in].set(cache.k)
    v_pad = jnp.zeros_like(k_pad).at[:, :, :n_in].set(cache.v)
    cache = cache._replace(k=k_pad, v=v_pad)
    for _ in range(n_decode):
        logits, cache, kvs = model.decode(
            params, jnp.asarray([nxt], jnp.int32), cache
        )
        pos = int(cache.length[0]) - 1
        cache = cache._replace(
            k=cache.k.at[:, :, pos : pos + 1].set(kvs[0]),
            v=cache.v.at[:, :, pos : pos + 1].set(kvs[1]),
        )
        nxt = int(np.argmax(np.asarray(logits[0])))
        out.append(nxt)
    return out


def striped_flash_attention_ref(
    q, k, v, q_pos, k_pos, *, causal=True, window=None, softcap=None
):
    return A.full_attention(
        q, k, v, q_pos=jnp.asarray(q_pos), k_pos=jnp.asarray(k_pos),
        causal=causal, window=window, softcap=softcap,
    )


def flash_decode_partial_ref(
    q, k, v, lengths, *, k_pos_offset=0, window=None, softcap=None
) -> A.Partial:
    b, s = k.shape[0], k.shape[1]
    pos = k_pos_offset + jnp.arange(s)
    cl = jnp.asarray(lengths)
    valid = pos[None, :] < cl[:, None]
    if window is not None:
        # repo window convention (see striped_attention.py): the query sits at
        # global position `lengths` (its own KV is not in the shard), so
        # qp - kp < window  <=>  kp > lengths - window
        valid &= pos[None, :] > (cl[:, None] - window)
    mask = jnp.broadcast_to(valid[:, None, :], (b, q.shape[1], s))
    return A.partial_attention(q, k, v, mask, softcap=softcap)


def packed_prefill_ref(
    q, k, v, seq_offsets, *, window=None, softcap=None
):
    """Dense segment-mask oracle for packed ragged prefill (tests only:
    O(T^2) score matrix).  Causality/window are evaluated in packed
    coordinates — within a segment the packed order IS the local order."""
    t = q.shape[0]
    ti = jnp.arange(t, dtype=jnp.int32)
    seg = A.packed_segment_ids(seq_offsets, t)
    mask = (seg[:, None] == seg[None, :]) & (ti[:, None] >= ti[None, :])
    if window is not None:
        mask &= (ti[:, None] - ti[None, :]) < window
    out = A.finalize_partial(
        A.partial_attention(q[None], k[None], v[None], mask[None],
                            softcap=softcap)
    )
    return out[0]


def packed_prefill_banded(
    q, k, v, seq_offsets, *, window=None, softcap=None, block_q=BAND,
    max_seq_len=None,
):
    """Production XLA fallback for packed ragged prefill.

    Scans over q blocks; each block attends a banded K/V window that is
    guaranteed to cover its segments' prefixes (a segment reaches back at
    most ``max_seq_len - 1`` packed positions, less under sliding window),
    with the segment mask killing cross-request pairs inside the band.
    Work is O(T * band) instead of the oracle's O(T^2) — the XLA analogue
    of the Pallas kernel's tile skipping.  ``max_seq_len`` must be a static
    upper bound on the longest segment (None = no bound, full reach).
    """
    t, h, d = q.shape
    blk = min(block_q, t)
    while t % blk:  # defensive: engine buckets t to powers of two
        blk //= 2
    nb = t // blk
    reach = t if max_seq_len is None else min(int(max_seq_len), t)
    if window is not None:
        reach = min(reach, window)
    w = min(-(-max(reach - 1, 0) // blk) + 1, nb)  # band width in blocks
    ti = jnp.arange(t, dtype=jnp.int32)
    seg = A.packed_segment_ids(seq_offsets, t)
    pad = (w - 1) * blk
    kp = jnp.pad(k, ((pad, 0), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((pad, 0), (0, 0), (0, 0)))
    segp = jnp.pad(seg, (pad, 0), constant_values=-1)  # pad rows never match
    tkp = jnp.pad(ti, (pad, 0), constant_values=-1)

    def body(_, i):
        s0 = i * blk  # band [s0, s0 + w*blk) of the padded axis ends at the
        # q block's end: global keys [s0 - pad, (i+1)*blk)
        qb = jax.lax.dynamic_slice_in_dim(q, s0, blk)
        tqb = jax.lax.dynamic_slice_in_dim(ti, s0, blk)
        sqb = jax.lax.dynamic_slice_in_dim(seg, s0, blk)
        kb = jax.lax.dynamic_slice_in_dim(kp, s0, w * blk)
        vb = jax.lax.dynamic_slice_in_dim(vp, s0, w * blk)
        tkb = jax.lax.dynamic_slice_in_dim(tkp, s0, w * blk)
        skb = jax.lax.dynamic_slice_in_dim(segp, s0, w * blk)
        mask = (sqb[:, None] == skb[None, :]) & (tqb[:, None] >= tkb[None, :])
        if window is not None:
            mask &= (tqb[:, None] - tkb[None, :]) < window
        out = A.finalize_partial(
            A.partial_attention(qb[None], kb[None], vb[None], mask[None],
                                softcap=softcap)
        )[0]
        return None, out

    _, outs = jax.lax.scan(body, None, jnp.arange(nb))
    return outs.reshape(t, h, d)


def _ring_chunk_mask(
    tl: int, q_shard, k_shard, n_shards: int, seq_offsets, *, window=None
):
    """[Tl, Tl] mask for one striped ring chunk: shard r's local slot j is
    global packed index ``j * n + r``; segment ids derive from the per-shard
    offsets, causal/window from the global striped positions."""
    j = jnp.arange(tl, dtype=jnp.int32)
    gq = j * n_shards + q_shard
    gk = j * n_shards + k_shard
    off = jnp.asarray(seq_offsets, jnp.int32)
    seg_q = jnp.sum(gq[:, None] >= off[None, 1:], axis=1)
    seg_k = jnp.sum(gk[:, None] >= off[None, 1:], axis=1)
    mask = (seg_q[:, None] == seg_k[None, :]) & (gq[:, None] >= gk[None, :])
    if window is not None:
        mask &= (gq[:, None] - gk[None, :]) < window
    return mask


def packed_prefill_ring_chunk_ref(
    q, k, v, seq_offsets, carry, *, q_shard, k_shard, n_shards,
    window=None, softcap=None,
):
    """Dense oracle for one ring step (tests only: O(Tl^2) scores): fold one
    striped KV chunk into the carried unnormalized (o, m, l) flash state.
    ``seq_offsets`` are the GLOBAL packed offsets; positions are global
    striped (``j * n + shard``).  Finalize with ``o / l`` after the last
    step."""
    tl = q.shape[0]
    mask = _ring_chunk_mask(
        tl, q_shard, k_shard, n_shards, seq_offsets, window=window
    )
    part = A.partial_attention(
        q[None], k[None], v[None], mask[None], softcap=softcap
    )
    o, m, l = A.merge_partial(
        A.Partial(carry[0][None], carry[1][None], carry[2][None]), part
    )
    return o[0], m[0], l[0]


def packed_prefill_ring_chunk_banded(
    q, k, v, q_offsets, k_offsets, carry, *, q_shard, k_shard, n_shards,
    window=None, softcap=None, block_q=BAND, max_seq_len=None,
):
    """Production XLA fallback for one ring step of the striped packed
    prefill (the chunked analogue of `packed_prefill_banded`).

    Scans over local q blocks; each block attends a banded window of the KV
    chunk guaranteed to cover its segments' global reach — a segment spans at
    most ``max_seq_len`` GLOBAL positions, i.e. ``ceil(max_seq_len / n)``
    local slots of any one shard (less under sliding window) — with the
    per-shard segment mask killing cross-request pairs inside the band.
    ``q_offsets``/``k_offsets`` are the per-shard offsets
    (`striped.shard_offsets`); global positions rebuild as ``j * n + shard``.
    Returns the updated unnormalized (o, m, l) carry."""
    tl, h, d = q.shape
    n = n_shards
    blk = min(block_q, tl)
    while tl % blk:  # defensive: engine buckets the shard length
        blk //= 2
    nb = tl // blk
    reach_g = None if max_seq_len is None else int(max_seq_len)
    if window is not None:
        reach_g = window if reach_g is None else min(reach_g, window)
    # local band reach: global reach divided across the n stripes (+1 slack
    # for shard phase rounding)
    reach_l = tl if reach_g is None else min(-(-reach_g // n) + 1, tl)
    w = min(-(-max(reach_l - 1, 0) // blk) + 1, nb)  # band width in blocks
    j = jnp.arange(tl, dtype=jnp.int32)
    gq = j * n + q_shard
    gk = j * n + k_shard
    qo = jnp.asarray(q_offsets, jnp.int32)
    ko = jnp.asarray(k_offsets, jnp.int32)
    seg_q = jnp.sum(j[:, None] >= qo[None, 1:], axis=1)
    seg_k = jnp.sum(j[:, None] >= ko[None, 1:], axis=1)
    pad = (w - 1) * blk
    kp = jnp.pad(k, ((pad, 0), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((pad, 0), (0, 0), (0, 0)))
    segkp = jnp.pad(seg_k, (pad, 0), constant_values=-1)  # pad never matches
    gkp = jnp.pad(gk, (pad, 0), constant_values=-1)

    def body(_, i):
        s0 = i * blk  # band [s0, s0 + w*blk) of the padded local axis
        qb = jax.lax.dynamic_slice_in_dim(q, s0, blk)
        gqb = jax.lax.dynamic_slice_in_dim(gq, s0, blk)
        sqb = jax.lax.dynamic_slice_in_dim(seg_q, s0, blk)
        kb = jax.lax.dynamic_slice_in_dim(kp, s0, w * blk)
        vb = jax.lax.dynamic_slice_in_dim(vp, s0, w * blk)
        gkb = jax.lax.dynamic_slice_in_dim(gkp, s0, w * blk)
        skb = jax.lax.dynamic_slice_in_dim(segkp, s0, w * blk)
        mask = (sqb[:, None] == skb[None, :]) & (gqb[:, None] >= gkb[None, :])
        if window is not None:
            mask &= (gqb[:, None] - gkb[None, :]) < window
        part = A.partial_attention(
            qb[None], kb[None], vb[None], mask[None], softcap=softcap
        )
        return None, (part.o[0], part.m[0], part.l[0])

    _, (o_b, m_b, l_b) = jax.lax.scan(body, None, jnp.arange(nb))
    part = A.Partial(
        o_b.reshape(tl, h, d), m_b.reshape(tl, h), l_b.reshape(tl, h)
    )
    o, m, l = A.merge_partial(A.Partial(*carry), part)
    return o, m, l


def paged_decode_merge_ref(
    q, k_new, v_new, shards, *, query_pos=None, window=None, softcap=None,
):
    """Dense multi-shard oracle for the distributed decode merge (SPMD or
    per-shard loop): the new token's own KV partial LSE-merged with one
    paged partial per shard, finalized.  ``shards`` is an iterable of
    ``(k_pages, v_pages, block_table, lengths, page_pos)`` tuples — the
    per-instance pool views; merge order matches the executor's (new-token
    partial first, shards in instance order), though the merge is
    order-insensitive up to float rounding."""
    part = A.partial_attention(q, k_new, v_new, None, softcap=softcap)
    for kp, vp, bt, lens, pos in shards:
        p = paged_flash_decode_partial_ref(
            q, kp, vp, bt, lens, pos, query_pos=query_pos, window=window,
            softcap=softcap,
        )
        part = A.merge_partial(part, p)
    return A.finalize_partial(part)


def paged_decode_batch_sharded_ref(
    q, k_new, v_new, shards, *, query_pos=None, window=None, softcap=None,
):
    """Dense oracle for the BATCH-SHARDED multi-master decode boundary
    (`core.esp.paged_decode_attn_sharded`): emulates the collective
    schedule in plain jnp with ``n = len(shards)`` virtual ranks.

    Rank i holds shard i's paged KV and owns batch rows
    ``[i*B/n, (i+1)*B/n)``.  The all_gather of the q-slices reconstitutes
    the full-batch q (identical to ``q`` here), each rank's full-batch
    partial is computed over its local shard, the psum_scatter is a
    weighted sum over ranks followed by slicing each rank's own rows, and
    every rank merges its slice with ITS batch slice of the new-token
    partial.  Concatenating the per-rank slices gives the full [B,1,H,D]
    output — the structural reference the shard_map program must match."""
    n = len(shards)
    b = q.shape[0]
    assert b % n == 0, (b, n)
    b_l = b // n
    parts = [
        paged_flash_decode_partial_ref(
            q, kp, vp, bt, lens, pos, query_pos=query_pos, window=window,
            softcap=softcap,
        )
        for kp, vp, bt, lens, pos in shards
    ]
    m_g = jnp.max(jnp.stack([p.m for p in parts]), axis=0)
    m_safe = jnp.where(jnp.isinf(m_g), 0.0, m_g)
    w = [jnp.where(jnp.isinf(p.m), 0.0, jnp.exp(p.m - m_safe)) for p in parts]
    o_sum = sum(p.o * wi[..., None] for p, wi in zip(parts, w))
    l_sum = sum(p.l * wi for p, wi in zip(parts, w))
    outs = []
    for r in range(n):
        sl = slice(r * b_l, (r + 1) * b_l)
        p_new = A.partial_attention(
            q[sl], k_new[sl], v_new[sl], None, softcap=softcap
        )
        merged = A.merge_partial(
            A.Partial(o_sum[sl], m_g[sl], l_sum[sl]), p_new
        )
        outs.append(A.finalize_partial(merged))
    return jnp.concatenate(outs, axis=0)


def paged_flash_decode_partial_ref(
    q,  # [B, 1, H, D]
    k_pages,  # [n_pages, P, KVH, D]
    v_pages,
    block_table,  # [B, max_pages] int32
    lengths,  # [B] int32 valid local tokens
    page_pos=None,  # [n_pages, P] int32 global positions
    *,
    query_pos=None,  # [B] int32 (required with window)
    window=None,
    softcap=None,
) -> A.Partial:
    """XLA `take`-based oracle for the paged decode kernel (CPU parity)."""
    bt = jnp.asarray(block_table, jnp.int32)
    b, max_pages = bt.shape
    page = k_pages.shape[1]
    if max_pages == 0:
        return A.empty_partial(b, q.shape[1], q.shape[2], q.shape[3])
    s = max_pages * page
    flat = bt.reshape(-1)
    k = jnp.take(k_pages, flat, axis=0).reshape((b, s) + k_pages.shape[2:])
    v = jnp.take(v_pages, flat, axis=0).reshape((b, s) + v_pages.shape[2:])
    j = jnp.arange(s)
    valid = j[None, :] < jnp.asarray(lengths)[:, None]
    if window is not None:
        kp = jnp.take(jnp.asarray(page_pos), flat, axis=0).reshape(b, s)
        valid &= (jnp.asarray(query_pos)[:, None] - kp) < window
    mask = jnp.broadcast_to(valid[:, None, :], (b, q.shape[1], s))
    return A.partial_attention(q, k, v, mask, softcap=softcap)
