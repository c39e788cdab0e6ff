"""Pallas TPU kernel: packed ragged causal flash prefill over one token axis.

One launch serves EVERY prefill request of a batch: the prompts are
concatenated ("packed") along a single token axis of bucketed length T, and
the grid runs over ``(q_block, kv_head_group, k_block)``.  Per-sequence
boundaries ride in through a scalar-prefetched offsets array — available in
SMEM before the kernel body runs — so each program derives segment ids for
its q/k tiles and (a) skips tiles whose segment ranges cannot interact and
(b) masks cross-request attention inside mixed tiles.  This replaces
O(batch) per-request `model.prefill` launches (one XLA program per distinct
prompt length) with ONE program per bucket.

Contract:
  * ``q``: [T, H, D]; ``k``/``v``: [T, KVH, D] — the packed batch, padded to
    a bucketed T (the engine buckets to powers of two so O(log max_tokens)
    programs cover every batch);
  * ``seq_offsets``: [B+1] int32 — request b occupies packed positions
    ``[seq_offsets[b], seq_offsets[b+1])``.  Trailing entries may repeat the
    total (empty segments from batch-count bucketing); padding tokens past
    ``seq_offsets[-1]`` form their own segment and never reach real rows.
  * causality is evaluated in PACKED coordinates: within one segment the
    packed order equals the local order, so ``tq >= tk`` (and the window
    predicate ``tq - tk < window`` — repo convention, self-inclusive) need
    no per-token local positions.  RoPE uses local positions outside the
    kernel, so the striped/packed layout stays transparent to the model.

Emits the NORMALIZED output (prefill is local to the packed batch — no
cross-instance combine is needed).

Ring fusion (DoP>1 ESP prefill)
-------------------------------
``packed_flash_prefill_ring_chunk`` is the online-softmax accumulator variant
of the same kernel for the striped ESP ring: the packed token axis is striped
across the n instances of an elastic group (global packed index ``g`` lives
on shard ``g % n`` at local slot ``g // n``), and at every ring step each
instance runs ONE launch of this kernel over (its local query shard) x (the
remote KV chunk it currently holds), carrying the unnormalized
``(acc, m, l)`` flash state across steps.  Segment ids come from
scalar-prefetched PER-SHARD offsets (``striped.shard_offsets``), while the
causal/window predicates are evaluated on GLOBAL striped positions
reconstructed as ``j * n + shard`` — so tile skipping still works: a q/k tile
pair is skipped when its global causal reach, segment ranges, or window reach
cannot interact.  After n steps the carried state finalizes to exactly the
single-launch packed result (same math, chunked).

Block layout (what Mosaic accepts at real widths)
-------------------------------------------------
A TPU block's last two dims must be multiples of (8, 128) or span the
whole array, so a per-head block of a ``[T, H, D]`` operand (second-minor
dim 1 or ``q_per_kv``) is refused.  The kernels therefore view the
operands as ``[T, H*D]`` / ``[T, KVH*D]`` (a free reshape of the
projections' output) and block one KV group's columns: q and the output
``(block_q, q_per_kv*D)``, k/v ``(block_k, D)`` — lane-aligned whenever
``q_per_kv*D`` and ``D`` are multiples of 128.  The per-row softmax stats
of ALL heads live in one ``(block_q, H)`` VMEM tile (full last dim); the
grid runs ``(q_block, kv_group, k_block)`` so that tile, and the ring
variant's ``(block_q, H)`` stat blocks of the carried state, stay resident
across the groups of one q block.  A group's columns are read and written
through one-hot lane masks, so no dynamic lane slicing is needed.

Tiles are chosen from the operands' shapes (`choose_tiles`), not fixed.
Every grid step pays a cost that does not grow with its tile: the
``q_per_kv`` heads of a group update their stat columns one after the
other (select, cross-lane reduce, select), the pipeline's per-step
overhead, and a K/V block DMA even for a causally skipped tile.  At
128x128 a 16-head group spends about 3.6x its MXU time on that (a census
of the compiled kernel's bundles, PERF.md).  So the chooser takes the
first tile of `TILE_ORDER`, an order over {128, 256, 512}^2 ranked by
that census (a static count, not a timing), whose blocks divide the shard
length and whose reckoned VMEM (`tile_vmem_bytes`) fits `VMEM_BUDGET`;
wide GQA groups get smaller tiles than MHA's 128-lane groups.  Where the
tile needs more than Mosaic's default scoped VMEM the launch asks for the
reckoned amount.  An explicit ``block_q``/``block_k`` wins (the
interpret-mode tests pass 4-16 for multi-tile coverage).

Deployment note: the in-process replay (LocalExecutor) passes static shard
ids, so this Pallas kernel applies directly.  The shard_map mesh path
(`core.esp.ring_packed_prefill_spmd`) has TRACED shard ids
(lax.axis_index); it recovers static ids with the same ``lax.switch``
static-branch trick the SPMD decode path uses: `esp.switched_ring_chunk`
enumerates one branch per rank (the ring step is a python loop constant),
each baking ``q_shard=rank, k_shard=(rank-step) % n`` as the compile-time
constants the tile-skip predicates need.  Under ``impl="xla"`` the banded
variant (`ref.packed_prefill_ring_chunk_banded`, shard ids as jnp values)
still dispatches directly with no switch.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _block(n: int, want: int) -> int:
    """Largest power-of-two fraction of ``want`` dividing ``n`` (or ``n``
    itself when it is smaller): the 3/4-point token buckets (3*2^j) halve
    to a divisor."""
    blk = min(want, n)
    while n % blk:
        blk //= 2
    assert blk >= 1, (n, want)
    return blk


# (block_q, block_k) candidates, best first: fewest VLIW bundles per
# 128x128 unit of a 16-head group in the compiled kernel on a v5e (the
# census of PERF.md §5, a static count); the chooser takes the first that
# fits VMEM_BUDGET
TILE_ORDER = ((512, 512), (256, 512), (512, 256), (128, 256), (256, 256),
              (128, 512), (512, 128), (256, 128), (128, 128))
VMEM_BUDGET = 32 << 20  # bytes one launch's reckoned tiles may take
SCOPED_VMEM = 16 << 20  # Mosaic's default scoped VMEM limit on a v5e


def tile_vmem_bytes(block_q: int, block_k: int, *, q_per_kv: int, d: int,
                    h: int, carry: bool, itemsize: int) -> int:
    """VMEM one launch needs at a tile, as Mosaic allocates it on a v5e:
    the double-buffered q, k, v and output blocks (and the carried o/m/l in
    and out), the f32 scratch, twelve f32 ``(block_q, block_k)`` tiles for
    the body's temporaries (scores, probabilities and masks of consecutive
    heads of the unrolled group loop live at once), and 1 MiB for Mosaic's
    own.  Stat columns pad to 128 lanes; ``itemsize`` is q/k/v's.  Read off
    the least limit each tile compiles under, by bisection (PERF.md)."""
    grp = q_per_kv * d
    stat = block_q * (-(-h // 128) * 128) * 4  # one (block_q, H) f32 tile
    out = block_q * grp * 4
    blocks = 2 * (block_q * grp * itemsize + 2 * block_k * d * itemsize + out)
    if carry:
        blocks += 2 * (out + 4 * stat)
    scratch = out + 2 * stat
    return blocks + scratch + 12 * block_q * block_k * 4 + (1 << 20)


def choose_tiles(t: int, *, q_per_kv: int, d: int, h: int, carry: bool,
                 itemsize: int) -> tuple:
    """(block_q, block_k) for a launch over a token axis of length ``t``:
    the first of `TILE_ORDER`, each side halved to a divisor of ``t``
    (`_block`), whose `tile_vmem_bytes` fits `VMEM_BUDGET`."""
    for want_q, want_k in TILE_ORDER:
        bq, bk = _block(t, want_q), _block(t, want_k)
        if tile_vmem_bytes(bq, bk, q_per_kv=q_per_kv, d=d, h=h, carry=carry,
                           itemsize=itemsize) <= VMEM_BUDGET:
            return bq, bk
    return _block(t, 128), _block(t, 128)


def _seg_scalar(j, off_ref, n_seqs: int):
    """Segment id of one (scalar) local index from prefetched offsets."""
    return jax.lax.fori_loop(
        0, n_seqs,
        lambda b, acc: acc + (j >= off_ref[b + 1]).astype(jnp.int32),
        jnp.int32(0),
    )


def _seg_vector(j, off_ref, n_seqs: int):
    """Segment id per element of an int32 index tile (monotone in j)."""
    return jax.lax.fori_loop(
        0, n_seqs,
        lambda b, acc: acc + jnp.where(j >= off_ref[b + 1], 1, 0),
        jnp.zeros_like(j),
    )


def _kernel(*refs, scale: float, window: Optional[int],
            softcap: Optional[float], q_shard: int, k_shard: int,
            n_shards: int, block_q: int, block_k: int, n_seqs: int,
            n_k_blocks: int, q_per_kv: int, d: int, carry: bool):
    """Shared body of the packed kernel (``carry=False``: empty start state,
    normalized output) and the ring chunk (``carry=True``: resume and emit
    the unnormalized (acc, m, l) state).  Grid: (q block, kv group, k block).
    Shard ids / count are static; the packed kernel is shard 0 of 1."""
    if carry:
        (qoff_ref, koff_ref, q_ref, k_ref, v_ref, o_in_ref, m_in_ref,
         l_in_ref, o_ref, m_out_ref, l_out_ref, acc_ref, m_ref, l_ref) = refs
    else:
        (qoff_ref, koff_ref, q_ref, k_ref, v_ref, o_ref,
         acc_ref, m_ref, l_ref) = refs
    iq = pl.program_id(0)
    g = pl.program_id(1)
    ik = pl.program_id(2)
    n = n_shards

    @pl.when((g == 0) & (ik == 0))
    def _init_stats():  # stats of all heads, resident across the groups
        if carry:
            m_ref[...] = m_in_ref[...]
            l_ref[...] = l_in_ref[...]
        else:
            m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
            l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(ik == 0)
    def _init_acc():
        if carry:
            acc_ref[...] = o_in_ref[...]
        else:
            acc_ref[...] = jnp.zeros_like(acc_ref)

    # tile-level skip in GLOBAL striped coordinates (shard r's local slot j
    # is packed index j*n + r): causal reach, segment-range overlap (the
    # per-shard seg ids are monotone in the local index), window reach
    q_lo, k_lo = iq * block_q, ik * block_k
    q_hi, k_hi = q_lo + block_q - 1, k_lo + block_k - 1
    run = k_lo * n + k_shard <= q_hi * n + q_shard
    run &= _seg_scalar(k_lo, koff_ref, n_seqs) <= _seg_scalar(
        q_hi, qoff_ref, n_seqs)
    run &= _seg_scalar(q_lo, qoff_ref, n_seqs) <= _seg_scalar(
        k_hi, koff_ref, n_seqs)
    if window is not None:
        run &= (q_lo * n + q_shard) - (k_hi * n + k_shard) < window

    @pl.when(run)
    def _update():
        jq = q_lo + jax.lax.broadcasted_iota(jnp.int32, (block_q, 1), 0)
        jk = k_lo + jax.lax.broadcasted_iota(jnp.int32, (1, block_k), 1)
        gq, gk = jq * n + q_shard, jk * n + k_shard
        mask = (_seg_vector(jq, qoff_ref, n_seqs)
                == _seg_vector(jk, koff_ref, n_seqs)) & (gq >= gk)
        if window is not None:
            mask &= (gq - gk) < window
        kb = k_ref[...].astype(jnp.float32)  # [block_k, D]
        vb = v_ref[...].astype(jnp.float32)
        m_all, l_all = m_ref[...], l_ref[...]  # [block_q, H]
        lane = jax.lax.broadcasted_iota(jnp.int32, m_all.shape, 1)
        for hh in range(q_per_kv):
            sel = lane == g * q_per_kv + hh  # this head's stat column
            cols = slice(hh * d, (hh + 1) * d)
            qh = q_ref[:, cols].astype(jnp.float32)  # [block_q, D]
            s = jax.lax.dot_general(
                qh, kb, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale  # [block_q, block_k]
            if softcap is not None:
                s = softcap * jnp.tanh(s / softcap)
            s = jnp.where(mask, s, NEG_INF)
            m_prev = jnp.max(jnp.where(sel, m_all, -jnp.inf), axis=1,
                             keepdims=True)
            l_prev = jnp.sum(jnp.where(sel, l_all, 0.0), axis=1,
                             keepdims=True)
            m_blk = jnp.max(s, axis=1, keepdims=True)
            m_new = jnp.maximum(m_prev, m_blk)
            m_safe = jnp.maximum(m_new, -1e29)  # fully-masked-row guard
            p = jnp.where(mask, jnp.exp(s - m_safe), 0.0)
            alpha = jnp.where(m_prev <= NEG_INF / 2, 0.0,
                              jnp.exp(m_prev - m_safe))
            l_new = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
            acc_ref[:, cols] = acc_ref[:, cols] * alpha + jax.lax.dot_general(
                p, vb, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            m_keep = jnp.where(m_blk <= NEG_INF / 2, m_prev, m_new)
            m_all = jnp.where(sel, m_keep, m_all)
            l_all = jnp.where(sel, l_new, l_all)
        m_ref[...] = m_all
        l_ref[...] = l_all

    @pl.when(ik == n_k_blocks - 1)
    def _emit():
        if carry:  # UNNORMALIZED: the state continues to the next ring step
            o_ref[...] = acc_ref[...]
            m_out_ref[...] = m_ref[...]
            l_out_ref[...] = l_ref[...]
            return
        l_all = l_ref[...]
        lane = jax.lax.broadcasted_iota(jnp.int32, l_all.shape, 1)
        for hh in range(q_per_kv):
            l_h = jnp.sum(jnp.where(lane == g * q_per_kv + hh, l_all, 0.0),
                          axis=1, keepdims=True)
            cols = slice(hh * d, (hh + 1) * d)
            o_ref[:, cols] = acc_ref[:, cols] / jnp.where(l_h == 0.0, 1.0, l_h)


def _call(q, k, v, q_offsets, k_offsets, carry, *, name, q_shard, k_shard,
          n_shards, window, softcap, block_q, block_k, interpret):
    """Shared pallas_call for both entry points (see `_kernel`); ``name``
    is the kernel's name in the compiled program and the profiler's trace
    (the custom call's ``kernel_metadata``)."""
    t, h, d = q.shape
    kvh = k.shape[1]
    q_per_kv = h // kvh
    shape = dict(q_per_kv=q_per_kv, d=d, h=h, carry=carry is not None,
                 itemsize=max(q.dtype.itemsize, k.dtype.itemsize))
    auto_q, auto_k = choose_tiles(t, **shape)
    block_q = auto_q if block_q is None else _block(t, block_q)
    block_k = auto_k if block_k is None else _block(t, block_k)
    vmem = tile_vmem_bytes(block_q, block_k, **shape)
    nq, nk = t // block_q, t // block_k
    n_seqs = int(q_offsets.shape[0]) - 1
    kernel = functools.partial(
        _kernel, scale=1.0 / math.sqrt(d), window=window, softcap=softcap,
        q_shard=q_shard, k_shard=k_shard, n_shards=n_shards,
        block_q=block_q, block_k=block_k, n_seqs=n_seqs, n_k_blocks=nk,
        q_per_kv=q_per_kv, d=d, carry=carry is not None,
    )
    grp = q_per_kv * d
    q_spec = pl.BlockSpec((block_q, grp), lambda iq, g, ik, qo, ko: (iq, g))
    kv_spec = pl.BlockSpec((block_k, d), lambda iq, g, ik, qo, ko: (ik, g))
    stat_spec = pl.BlockSpec((block_q, h), lambda iq, g, ik, qo, ko: (iq, 0))
    in_specs = [q_spec, kv_spec, kv_spec]
    operands = [q.reshape(t, h * d), k.reshape(t, kvh * d),
                v.reshape(t, kvh * d)]
    out_shape = [jax.ShapeDtypeStruct((t, h * d), jnp.float32)]
    out_specs = [q_spec]
    if carry is not None:
        o_c, m_c, l_c = carry
        in_specs += [q_spec, stat_spec, stat_spec]
        operands += [jnp.asarray(o_c, jnp.float32).reshape(t, h * d),
                     jnp.asarray(m_c, jnp.float32),
                     jnp.asarray(l_c, jnp.float32)]
        out_shape += [jax.ShapeDtypeStruct((t, h), jnp.float32)] * 2
        out_specs += [stat_spec, stat_spec]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,  # q_offsets, k_offsets
        grid=(nq, kvh, nk),
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=[
            pltpu.VMEM((block_q, grp), jnp.float32),
            pltpu.VMEM((block_q, h), jnp.float32),
            pltpu.VMEM((block_q, h), jnp.float32),
        ],
    )
    outs = pl.pallas_call(
        kernel, grid_spec=grid_spec, out_shape=out_shape, interpret=interpret,
        name=name, metadata={"kernel": name},
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=vmem)
        if vmem > SCOPED_VMEM else None,
    )(jnp.asarray(q_offsets, jnp.int32), jnp.asarray(k_offsets, jnp.int32),
      *operands)
    return (outs[0].reshape(t, h, d),) + tuple(outs[1:])


def packed_flash_prefill(
    q: jnp.ndarray,  # [T, H, D] packed batch
    k: jnp.ndarray,  # [T, KVH, D]
    v: jnp.ndarray,
    seq_offsets: jnp.ndarray,  # [B+1] int32 segment boundaries
    *,
    window: Optional[int] = None,
    softcap: Optional[float] = None,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    interpret: bool = False,
) -> jnp.ndarray:
    """One ragged batched launch over the packed token axis; returns the
    normalized attention output [T, H, D] (f32)."""
    return _call(
        q, k, v, seq_offsets, seq_offsets, None, name="prefill_packed_attn",
        q_shard=0, k_shard=0,
        n_shards=1, window=window, softcap=softcap, block_q=block_q,
        block_k=block_k, interpret=interpret,
    )[0]


# ===================================================== ring-fused chunk step


def packed_flash_prefill_ring_chunk(
    q: jnp.ndarray,  # [Tl, H, D] striped local query shard (shard q_shard)
    k: jnp.ndarray,  # [Tl, KVH, D] the KV chunk held this ring step
    v: jnp.ndarray,
    q_offsets: jnp.ndarray,  # [B+1] int32 per-shard offsets of the q shard
    k_offsets: jnp.ndarray,  # [B+1] int32 per-shard offsets of the KV chunk
    carry,  # (o [Tl,H,D], m [Tl,H], l [Tl,H]) f32 flash state, NEG_INF-empty
    *,
    q_shard: int,
    k_shard: int,
    n_shards: int,
    window: Optional[int] = None,
    softcap: Optional[float] = None,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    interpret: bool = False,
):
    """One ring step: fold one striped KV chunk into the carried flash state
    with a single ragged launch.  Returns the updated (o, m, l) — finalize
    with ``o / l`` after the last step (empty rows keep m=-inf, l=0)."""
    return _call(
        q, k, v, q_offsets, k_offsets, carry, name="prefill_ring_chunk_attn",
        q_shard=q_shard,
        k_shard=k_shard, n_shards=n_shards, window=window, softcap=softcap,
        block_q=block_q, block_k=block_k, interpret=interpret,
    )
