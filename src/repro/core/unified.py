"""Unified continuous-batching attention: chunked prefill + in-flight decode
on ONE packed ragged token axis (the LoongServe unified iteration).

Key identity: decode IS chunked prefill with chunk == 1.  Per layer, every
packed token row's attention output is

    finalize( merge( paged PREFIX partial over the pool storage,
                     packed causal CHUNK partial over this iteration's axis ) )

The prefix partial is the SAME primitive the paged decode path uses
(`ops.paged_decode_partial`) with per-TOKEN expanded operands — each packed
token carries its request's page table and the length of the FILLED prefix
(`KVPool.prefix_block_table`), so a mid-prefill request attends exactly the
chunks it has already written.  The chunk partial is the SAME primitive the
ESP ring prefill uses (`ops.prefill_ring_chunk` — ``n_shards=1`` in-process,
the full striped ppermute ring under shard_map) with the prefix partial passed
in as the carried flash state.  A decode row is a length-1 segment: its chunk
partial degenerates to the new token's self-attention partial, so the math is
bit-identical to the dedicated decode step's merge.

No attention FLOPs are duplicated across chunks: a (query, key) pair is
computed exactly once, in the iteration whose chunk contains the query — the
paged pool IS the carried (acc, m, l) flash state, materialized as KV instead
of statistics (and therefore failure-tolerant: a crashed iteration re-runs
from the pool, no stats to checkpoint).

Masking correctness on the packed axis: a prefill chunk occupies contiguous
packed slots AND contiguous positions, so packed-coordinate causality/window
inside `prefill_ring_chunk` equals position-based masking; every prefix
position is < the chunk's first position, so the prefix partial needs no
causal mask beyond slot validity (+ the per-token window predicate on global
positions).  Bucket-padding tokens form a trailing segment that attends only
itself causally and is never sampled or scattered.

Hole-filling chunk schedules (elastic fault recovery): nothing above assumes
a chunk starts at the request's prefill frontier — only that the pool holds
every position BELOW the chunk's start (`prefix_block_table`'s coverage
contract).  So when an instance failure loses a token span whose higher
positions survive on other instances, the recovery chain replays the lost
span as ordinary chunks: the PREFIX partial reads the salvaged pages, the
chunk partial recomputes only the hole, and the engine schedules holes
strictly ascending and before the frontier so the coverage contract holds at
every link.  A decode-phase request re-feeds its already-emitted tokens over
the hole (they are inputs now, not samples) and resumes decode at its
cursor.  Recovery is therefore the SAME unified iteration — no dedicated
recovery kernel, and the bit-exactness argument above applies unchanged.
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Sequence

import jax.numpy as jnp
from jax import lax

from repro.models import attention as A
from repro.models.transformer import DefaultAttnImpl


class UnifiedShard(NamedTuple):
    """One instance's pool view for a unified step, with PER-TOKEN paged
    operands: row t of ``table``/``lengths`` is packed token t's page table
    and filled-prefix length in THIS pool (0 where the pool holds nothing
    for that token's request)."""

    k_pages: jnp.ndarray  # [L, n_pages, P, KVH, D]
    v_pages: jnp.ndarray  # [L, n_pages, P, KVH, D]
    page_pos: Optional[jnp.ndarray]  # [n_pages, P] (window masking only)
    table: jnp.ndarray  # [T, max_pages] int32
    lengths: jnp.ndarray  # [T] int32


def unified_chunk_attention(
    q, k, v, seq_offsets, positions, prefix_shards, *,
    max_seq_len: Optional[int] = None,
    window: Optional[int] = None,
    softcap: Optional[float] = None,
    impl: Optional[str] = None,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
):
    """One layer of unified attention, single-process form.

    q/k/v [T, H|KVH, D]: this iteration's packed token axis (prefill chunks
    then decode rows); ``seq_offsets`` [S+1] its segment boundaries;
    ``positions`` [T] global positions; ``prefix_shards``: iterable of
    per-layer pool views ``(k_pages [n_pages,P,KVH,D], v_pages, table
    [T,max_pages], lengths [T], page_pos)``.  Returns the normalized
    [T, H, D] f32 output."""
    from repro.kernels import ops

    carry = None
    qt = q[:, None]  # [T, 1, H, D] — token axis as the partial's batch axis
    for kp, vp, tbl, lens, pos in prefix_shards:
        p = ops.paged_decode_partial(
            qt, kp, vp, tbl, lens, pos, query_pos=positions,
            window=window, softcap=softcap, impl=impl,
        )
        part = p if carry is None else A.merge_partial(carry, p)
        carry = A.Partial(*part)
    if carry is not None:
        carry = (carry.o[:, 0], carry.m[:, 0], carry.l[:, 0])
    o, m, l = ops.prefill_ring_chunk(
        q, k, v, seq_offsets, seq_offsets, carry,
        q_shard=0, k_shard=0, n_shards=1, window=window, softcap=softcap,
        max_seq_len=max_seq_len, impl=impl, block_q=block_q, block_k=block_k,
    )
    denom = jnp.where(l == 0.0, 1.0, l)  # l==0 rows are bucket padding
    return o / denom[..., None]


class UnifiedAttnImpl(DefaultAttnImpl):
    """Attention impl for the unified iteration, armed per engine step.

    Drives `model.prefill_packed(..., unroll=True)`: the static python layer
    loop calls `prefill_attn` once per layer and the impl keeps a layer
    cursor into the per-layer pool planes (the same begin/end contract as
    `core.paged_decode.PagedDecodeAttnImpl`).

    Two modes:
      * loop (LocalExecutor): ``shards`` is a list of `UnifiedShard`, one per
        instance holding prefix KV; each layer merges one prefix partial per
        shard into the n_shards=1 chunk fold.
      * axis (inside a shard_map body, `esp.unified_iteration_spmd`): the
        token axis is STRIPED over ``n_ranks``; each layer all_gathers the
        q stripes, computes this rank's prefix partial over its own pool
        plane, LSE-merges with pmax + psum_scatter back to the stripes, and
        folds the chunk-internal attention with the SAME ppermute ring the
        SPMD prefill uses — prefix merge (decode plane) and ring fold
        (prefill plane) live inside one layer of one program.
    """

    def __init__(self, impl: Optional[str] = None):
        self.impl = impl
        self._armed = False

    def begin_step(
        self, seq_offsets, positions, *,
        max_seq_len: Optional[int] = None,
        shards: Optional[Sequence[UnifiedShard]] = None,
        axis_name: Optional[str] = None,
        n_ranks: int = 1,
        double_buffer: bool = True,
        block_q: Optional[int] = None,
        block_k: Optional[int] = None,
    ) -> None:
        """Arm one step.  ``positions`` is the FULL packed-axis position
        vector ([T]; striped order in axis mode) — the per-token query_pos of
        the prefix partial.  In axis mode ``shards`` holds ONE `UnifiedShard`
        with this rank's pool plane and per-token operands over the full
        (gathered) axis."""
        assert not self._armed, "unified step already armed"
        self._offsets = jnp.asarray(seq_offsets, jnp.int32)
        self._positions = jnp.asarray(positions, jnp.int32)
        self._max_seq_len = max_seq_len
        self._shards = list(shards) if shards else []
        self._axis = axis_name
        self._n_ranks = n_ranks
        self._double_buffer = double_buffer
        self._block_q, self._block_k = block_q, block_k
        self._li = 0
        self._n_layers = (
            int(self._shards[0].k_pages.shape[0]) if self._shards else None
        )
        self._armed = True

    def end_step(self) -> None:
        assert self._armed
        li, n = self._li, self._n_layers
        self._armed = False
        self._shards = []
        import sys

        if sys.exc_info()[0] is None and n is not None:
            assert li == n, (li, n)

    # ------------------------------------------------------------- per layer
    def prefill_attn(self, q, k, v, q_pos, k_pos, *, causal, window, softcap):
        if not self._armed:
            return super().prefill_attn(
                q, k, v, q_pos, k_pos, causal=causal, window=window,
                softcap=softcap,
            )
        assert causal and q.shape[0] == 1, (causal, q.shape)
        li = self._li
        self._li += 1
        if self._axis is not None:
            out = self._attn_axis(li, q, k, v, window, softcap)
        else:
            shards_li = [
                (s.k_pages[li], s.v_pages[li], s.table, s.lengths, s.page_pos)
                for s in self._shards
            ]
            out = unified_chunk_attention(
                q[0], k[0], v[0], self._offsets, self._positions, shards_li,
                max_seq_len=self._max_seq_len, window=window, softcap=softcap,
                impl=self.impl, block_q=self._block_q, block_k=self._block_k,
            )
        return out[None].astype(q.dtype)

    def _attn_axis(self, li, q, k, v, window, softcap):
        """One layer boundary inside the shard_map body: decode-style prefix
        merge + prefill-style ring fold, on this rank's token stripe."""
        from repro.core import esp, striped
        from repro.kernels import ops

        sp, n = self._axis, self._n_ranks
        (sh,) = self._shards
        tl = q.shape[1]
        r = lax.axis_index(sp)
        # --- prefix plane: all_gather(q) -> local paged partial over this
        # rank's pool plane -> LSE psum_scatter back to the stripes (exactly
        # the batch-sharded decode boundary, with T for B) ---
        qg = ops.all_gather(q[0][:, None], sp, axis=0)  # [T, 1, H, D]
        part = esp._switched_paged_partial(
            sp, n, qg, sh.k_pages[li], sh.v_pages[li], sh.table, sh.lengths,
            sh.page_pos, query_pos=self._positions, window=window,
            softcap=softcap, impl=self.impl,
        )
        m_g = ops.pmax(part.m, sp)
        m_safe = jnp.where(jnp.isinf(m_g), 0.0, m_g)
        w = jnp.where(jnp.isinf(part.m), 0.0, jnp.exp(part.m - m_safe))
        o_s, l_s = ops.psum_scatter(
            (part.o * w[..., None], part.l * w), sp, scatter_dimension=0,
        )
        m_s = lax.dynamic_slice_in_dim(m_g, r * tl, tl, axis=0)
        carry = (o_s[:, 0], m_s[:, 0], l_s[:, 0])
        # --- chunk plane: the striped ppermute ring over this iteration's
        # packed KV, folded into the prefix carry (double-buffered like
        # `esp.ring_packed_prefill_spmd`) ---
        pairs = striped.ring_pairs(n)
        qb, kk, vv = q[0], k[0], v[0]
        ob = self._offsets
        for step in range(n):
            if step < n - 1 and self._double_buffer:
                nxt = ops.ring_ppermute((kk, vv), sp, pairs)
            carry = esp.switched_ring_chunk(
                sp, n, step, qb, kk, vv, ob, carry, window=window,
                softcap=softcap, max_seq_len=self._max_seq_len,
                impl=self.impl, block_q=self._block_q, block_k=self._block_k,
            )
            if step < n - 1:
                if self._double_buffer:
                    kk, vv = nxt
                else:
                    kk, vv, carry = lax.optimization_barrier((kk, vv, carry))
                    kk, vv = ops.ring_ppermute((kk, vv), sp, pairs)
        o, m, l = carry
        denom = jnp.where(l == 0.0, 1.0, l)
        return o / denom[..., None]
