"""Elastic Sequence Parallelism: the SPMD production path (LoongServe §4).

`ESPAttnImpl` plugs into the model builders and replaces local attention with:

  * prefill: striped-attention ring over the `sp` mesh axis (between elastic
    instances). Each rank holds one sequence stripe; at every ring step it
    computes a flash-style *partial* against the KV stripe it currently holds
    and `ppermute`s the stripe to its ring neighbour — n steps make every
    query meet every key with zero redundant compute. Masks/RoPE are
    position-based so the striped permutation is exact.
  * decode: multi-master distributed decode. The KV cache is sharded across
    instances at token granularity; masters (batch shards over `sp`) compute
    q and the new token's KV locally, q is all-gathered (the paper's "send
    query tensors"), every rank computes a partial over its local KV shard,
    and partials are combined with an LSE-weighted reduce-scatter back to the
    masters — which then run their own FFN shard (multi-master == batch-
    sharded local layers).

Two head-sharding modes per DESIGN.md §3:
  * heads mode (n_heads % tp == 0): q heads shard over `tp`; KV heads shard
    too when divisible, otherwise each rank dynamic-slices the KV heads its
    q-head block needs (GQA group-aligned).
  * batch mode (odd head counts: qwen 20H, arctic 56H, whisper 6H): the
    attention batch shards over `tp` instead; heads stay whole.

The ring degree (DoP) can be the whole `sp` axis or disjoint subgroups of it
(`dop=`), matching LoongServe's iteration-level ESP groups.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.core import striped
from repro.core.shmap import shmap as _shmap
from repro.models import attention as A
from repro.models import ssm, xlstm
from repro.models.transformer import DefaultAttnImpl


def _slice_kv_heads(k, v, tp_idx, h_local: int, q_per_kv: int):
    """Select the KV heads a rank's q-head block needs when KV is replicated
    across tp. Requires blocks not to straddle KV groups (q_per_kv % h_local
    == 0 or h_local % q_per_kv == 0) — true for every assigned arch."""
    if h_local >= q_per_kv:
        n_loc = h_local // q_per_kv
        start = tp_idx * n_loc
    else:
        n_loc = 1
        start = (tp_idx * h_local) // q_per_kv
    k = lax.dynamic_slice_in_dim(k, start, n_loc, axis=2)
    v = lax.dynamic_slice_in_dim(v, start, n_loc, axis=2)
    return k, v


def ring_packed_prefill(
    q, k, v, seq_offsets, n_shards: int, *,
    window: Optional[int] = None,
    softcap: Optional[float] = None,
    max_seq_len: Optional[int] = None,
    impl: Optional[str] = None,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
):
    """Ring-fused packed ragged prefill for one DoP>1 ESP group (single-
    process simulation of the striped ppermute ring).

    The packed token axis [T] is striped across the group's ``n_shards``
    instances (global packed index ``g`` -> shard ``g % n``, local slot
    ``g // n``).  Every instance starts holding its own KV stripe; the ring
    then replays `striped.ring_chunk_schedule` — the exact chunk rotation the
    SPMD `ring_pairs` ppermute produces — and at each step each instance
    folds the chunk it currently holds into its carried (acc, m, l) flash
    state with ONE packed ragged `ops.prefill_ring_chunk` launch.  n steps
    make every query meet every key exactly once (zero redundant compute);
    the per-instance states then finalize LSE-style (the same
    max/sum-exp-weighted merge decode's multi-master combine uses, folded
    into the carry) and un-stripe back to the packed order.

    q [T,H,D], k/v [T,KVH,D] in PACKED order; returns the normalized
    [T,H,D] f32 output, numerically equal to `ops.prefill_packed`."""
    from repro.kernels import ops

    t = q.shape[0]
    n = int(n_shards)
    assert n >= 1 and t % n == 0, (t, n)
    if n == 1:
        return ops.prefill_packed(
            q, k, v, seq_offsets, window=window, softcap=softcap,
            max_seq_len=max_seq_len, impl=impl, block_q=block_q,
            block_k=block_k,
        )
    # counted so mesh-executor tests can assert the in-process replay is
    # NEVER reached when the shard_map ring is armed
    ops.dispatch_counts["prefill_ring_replay"] += 1
    qs = [q[r::n] for r in range(n)]
    ks = [k[r::n] for r in range(n)]
    vs = [v[r::n] for r in range(n)]
    offs = list(striped.all_shard_offsets(seq_offsets, n))
    sched = striped.ring_chunk_schedule(n)
    carries: list = [None] * n
    for step in range(n):
        for r in range(n):
            c = sched[step][r]
            carries[r] = ops.prefill_ring_chunk(
                qs[r], ks[c], vs[c], offs[r], offs[c], carries[r],
                q_shard=r, k_shard=c, n_shards=n, window=window,
                softcap=softcap, max_seq_len=max_seq_len, impl=impl,
                block_q=block_q, block_k=block_k,
            )
    outs = []
    for r in range(n):
        o, m, l = carries[r]
        denom = jnp.where(l == 0.0, 1.0, l)  # l==0 rows are bucket padding
        outs.append(o / denom[..., None])
    return striped.unstripe(jnp.concatenate(outs, axis=0), n, axis=0)


def switched_ring_chunk(
    sp: str, n: int, step: int, q, k, v, seq_offsets, carry, *,
    window: Optional[int] = None,
    softcap: Optional[float] = None,
    max_seq_len: Optional[int] = None,
    impl: Optional[str] = None,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
):
    """One ring-chunk fold inside a shard_map body, dispatching the
    CONFIGURED kernel impl instead of forcing the banded XLA fallback.

    The shard ids of ring step ``step`` are rank-derived (`lax.axis_index`),
    so — exactly like `_switched_paged_partial` on the decode side — non-XLA
    impls go through `lax.switch` over ``n`` statically-specialized branches:
    branch ``r`` bakes ``q_shard=r, k_shard=(r-step) % n`` (``step`` is a
    python loop constant) as the compile-time constants the Pallas kernel's
    tile-skip predicates need.  The XLA banded fallback accepts traced shard
    ids and dispatches directly.  ``seq_offsets`` are the GLOBAL packed
    offsets; per-shard offsets derive in place (`striped.shard_offsets`)."""
    from repro.kernels import ops

    eff = impl or ops.get_default_impl()
    if eff == "xla":
        r = lax.axis_index(sp)
        k_shard = (r - step) % n
        return ops.prefill_ring_chunk(
            q, k, v,
            striped.shard_offsets(seq_offsets, n, r),
            striped.shard_offsets(seq_offsets, n, k_shard),
            carry, q_shard=r, k_shard=k_shard, n_shards=n, window=window,
            softcap=softcap, max_seq_len=max_seq_len, impl="xla",
            block_q=block_q, block_k=block_k,
        )
    if carry is None:
        tl, h, d = q.shape
        carry = (
            jnp.zeros((tl, h, d), jnp.float32),
            jnp.full((tl, h), -jnp.inf, jnp.float32),
            jnp.zeros((tl, h), jnp.float32),
        )

    def branch(rank: int):
        k_shard = (rank - step) % n

        def run(operands):
            qb, kb, vb, cb = operands
            return ops.prefill_ring_chunk(
                qb, kb, vb,
                striped.shard_offsets(seq_offsets, n, rank),
                striped.shard_offsets(seq_offsets, n, k_shard),
                cb, q_shard=rank, k_shard=k_shard, n_shards=n, window=window,
                softcap=softcap, max_seq_len=max_seq_len, impl=eff,
                block_q=block_q, block_k=block_k,
            )

        return run

    return lax.switch(
        lax.axis_index(sp), [branch(r) for r in range(n)], (q, k, v, carry)
    )


def ring_packed_prefill_spmd(
    mesh: Mesh, q, k, v, seq_offsets, *,
    sp_axis: str = "data",
    window: Optional[int] = None,
    softcap: Optional[float] = None,
    max_seq_len: Optional[int] = None,
    impl: Optional[str] = None,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    double_buffer: bool = True,
):
    """Mesh-native ring-fused packed ragged prefill: ONE shard_map program
    over the mesh's ``sp_axis`` in which each data rank physically owns its
    stripe of the packed token axis and the KV stripes rotate between
    devices with `lax.ppermute`.

    The packed axis [T] is striped over the ``n = mesh.shape[sp_axis]``
    ranks (global packed index ``g`` -> rank ``g % n``, local slot
    ``g // n``); rank r starts holding its own KV stripe.  At ring step s it
    folds the chunk it currently holds — provenance ``(r - s) mod n``,
    `striped.chunk_provenance` — into its carried (acc, m, l) flash state
    with one `ops.prefill_ring_chunk` launch, while (``double_buffer=True``)
    the NEXT stripe's ppermute is issued BEFORE the fold so the transfer
    overlaps the chunk compute; ``double_buffer=False`` pins the permute
    behind the fold with an optimization barrier (the sequential baseline
    the benchmark compares against).  Every ring leg goes through
    `ops.ring_ppermute` (dispatch + per-leg byte counters).

    The per-shard segment offsets are static metadata derived from the
    REPLICATED global ``seq_offsets`` inside the body (`striped
    .shard_offsets` with the traced rank / chunk provenance) rather than fed
    as a data-sharded [n, B+1] array: the offsets are a few bytes every
    rank can derive for any shard, and the ring leg then only needs to
    move KV bytes.

    Shard ids reach the chunk kernel rank-derived, so the real (Pallas)
    kernel dispatches through `switched_ring_chunk`'s statically-specialized
    `lax.switch` branches — the same trick the decode path uses
    (`_switched_paged_partial`); the XLA banded fallback keeps its direct
    traced-shard-id dispatch.

    q [T,H,D], k/v [T,KVH,D] in PACKED order (T % n == 0); returns the
    normalized [T,H,D] f32 output, numerically equal to
    `ops.prefill_packed`."""
    from repro.kernels import ops

    n = int(mesh.shape[sp_axis])
    t = q.shape[0]
    assert n >= 1 and t % n == 0, (t, n)
    if n == 1:
        return ops.prefill_packed(
            q, k, v, seq_offsets, window=window, softcap=softcap,
            max_seq_len=max_seq_len, impl=impl, block_q=block_q,
            block_k=block_k,
        )
    ops.dispatch_counts["prefill_ring_spmd"] += 1
    pairs = striped.ring_pairs(n)
    sp = sp_axis

    def body(qb, kb, vb, ob):
        # qb/kb/vb: [Tl, ...] this rank's stripe; ob: [B+1] global offsets
        kk, vv = kb, vb
        carry = None
        for step in range(n):
            if step < n - 1 and double_buffer:
                # issue the NEXT stripe's transfer before folding this one:
                # no data dependency on the fold, so XLA/ICI can overlap the
                # ppermute with the chunk compute
                nxt = ops.ring_ppermute((kk, vv), sp, pairs)
            carry = switched_ring_chunk(
                sp, n, step, qb, kk, vv, ob, carry,
                window=window, softcap=softcap, max_seq_len=max_seq_len,
                impl=impl, block_q=block_q, block_k=block_k,
            )
            if step < n - 1:
                if double_buffer:
                    kk, vv = nxt
                else:
                    # sequential baseline: the barrier makes the transfer
                    # depend on the fold, so it cannot start early
                    kk, vv, carry = lax.optimization_barrier((kk, vv, carry))
                    kk, vv = ops.ring_ppermute((kk, vv), sp, pairs)
        o, m, l = carry
        denom = jnp.where(l == 0.0, 1.0, l)  # l==0 rows are bucket padding
        return o / denom[..., None]

    fn = _shmap(
        body, mesh,
        in_specs=(
            P(sp, None, None), P(sp, None, None), P(sp, None, None),
            P(None),
        ),
        out_specs=P(sp, None, None),
    )
    # striped layout = concat of per-rank stripes, so block-sharding the
    # leading axis over `sp` hands rank r exactly stripe r
    out = fn(
        striped.stripe(q, n, axis=0),
        striped.stripe(k, n, axis=0),
        striped.stripe(v, n, axis=0),
        jnp.asarray(seq_offsets, jnp.int32),
    )
    return striped.unstripe(out, n, axis=0)


def _switched_paged_partial(
    sp: str, n: int, q, k_pages, v_pages, table, lengths, page_pos, *,
    query_pos, window, softcap, impl: Optional[str],
):
    """Per-rank paged-decode partial inside a shard_map body, dispatching
    the CONFIGURED kernel impl instead of forcing the XLA fallback.

    The rank is only available as a traced value (`lax.axis_index`), but a
    `pallas_call` needs its grid/scalar-prefetch metadata static — so for
    non-XLA impls the launch goes through `lax.switch` over ``n``
    STATICALLY-specialized variants: branch ``r`` is traced with the rank as
    a compile-time constant, which is where any rank-derived static
    parameters (e.g. global-position bases for window masking on TPU) get
    baked into the kernel instead of reaching Pallas as tracers.  The block
    tables / lengths already arrive pre-sharded, so today's branches differ
    only by that static context; the XLA reference path needs none of this
    and dispatches directly."""
    from repro.kernels import ops

    eff = impl or ops.get_default_impl()
    if eff == "xla":
        return ops.paged_decode_partial(
            q, k_pages, v_pages, table, lengths, page_pos,
            query_pos=query_pos, window=window, softcap=softcap, impl="xla",
        )

    def branch(rank: int):  # noqa: ARG001 — today's branches differ only
        # by the static trace context `rank` pins (see docstring)
        def run(qb):
            return ops.paged_decode_partial(
                qb, k_pages, v_pages, table, lengths, page_pos,
                query_pos=query_pos, window=window, softcap=softcap,
                impl=eff,
            )
        return run

    return lax.switch(lax.axis_index(sp), [branch(r) for r in range(n)], q)


def paged_decode_spmd(
    mesh: Mesh, q, k_new, v_new, query_pos,
    k_pages, v_pages, table, lengths, page_pos=None, *,
    sp_axis: str = "data",
    window: Optional[int] = None,
    softcap: Optional[float] = None,
    overlap: bool = True,
    impl: Optional[str] = None,
):
    """One decode layer's multi-master paged attention as ONE shard_map
    region over the mesh's ``sp_axis``: each data rank computes its
    `ops.paged_decode_partial` over the pool mirror it physically holds (the
    sharded ``k_pages``/``v_pages`` operand IS the per-rank mirror — no KV
    ever moves), and the LSE-merge of the per-instance partials is a
    collective on the weighted running accumulator:

        M   = pmax(m)                       (tiny [B, 1, H])
        o_s = psum(o · exp(m - M))          (the paper's "send back partial
        l_s = psum(l · exp(m - M))           results", §4.2, as ONE reduce)

    The query rides in replicated (``in_specs=P(None)``): the q broadcast is
    compiled into the program instead of a per-shard `device_put` loop.  The
    new token's own KV partial (computed master-side, outside the manual
    region) is data-independent of the reduce, so with ``overlap=True``
    (default, no barriers anywhere) XLA's scheduler is free to run the
    all-reduce asynchronously against it — and, because the whole decode
    iteration is one program, against any other independent compute in the
    layer stack (e.g. the next layer's weight loads feeding its QKV dot).
    ``overlap=False`` pins the collective with an `optimization_barrier`
    threading both the merge results and the new-token partial's inputs —
    nothing can be scheduled across the reduce (the sequential baseline the
    benchmark compares against, mirroring the prefill ring's
    ``double_buffer=False`` arm).

    q [B, 1, H, D]; k_new/v_new [B, 1, KVH, D]; query_pos [B] (the token's
    global position == cached length); k_pages/v_pages
    [n, n_pages, P, KVH, D] — one LAYER's paged storage, sharded over
    ``sp_axis`` (leading axis = rank); table [n, B, max_pages];
    lengths [n, B]; page_pos [n, n_pages, P] (only with window).  Returns
    the finalized merged output [B, 1, H, D] f32."""
    from repro.kernels import ops

    n = int(mesh.shape[sp_axis])
    assert int(k_pages.shape[0]) == n, (k_pages.shape, n)
    ops.dispatch_counts["paged_decode_spmd"] += 1
    sp = sp_axis
    has_pos = page_pos is not None

    def body(qb, qp, kb, vb, tb, lb, *pb):
        # kb/vb/tb/lb/pb: this rank's mirror view, leading shard dim 1
        part = _switched_paged_partial(
            sp, n, qb, kb[0], vb[0], tb[0], lb[0],
            pb[0][0] if has_pos else None,
            query_pos=qp, window=window, softcap=softcap, impl=impl,
        )
        m_g = ops.pmax(part.m, sp)
        m_safe = jnp.where(jnp.isinf(m_g), 0.0, m_g)
        w = jnp.where(jnp.isinf(part.m), 0.0, jnp.exp(part.m - m_safe))
        o_s, l_s = ops.psum((part.o * w[..., None], part.l * w), sp)
        return o_s, m_g, l_s

    specs = [P(None), P(None), P(sp), P(sp), P(sp), P(sp)]
    args = [q, jnp.asarray(query_pos, jnp.int32), k_pages, v_pages,
            table, lengths]
    if has_pos:
        specs.append(P(sp))
        args.append(page_pos)
    fn = _shmap(
        body, mesh, in_specs=tuple(specs),
        out_specs=(P(None), P(None), P(None)),
    )
    o_s, m_s, l_s = fn(*args)
    if not overlap:
        # barriered baseline: the reduce is pinned on the critical path —
        # even the new-token partial (whose inputs are threaded through the
        # barrier) must wait for it
        o_s, m_s, l_s, q, k_new, v_new = lax.optimization_barrier(
            (o_s, m_s, l_s, q, k_new, v_new)
        )
    p_new = A.partial_attention(q, k_new, v_new, None, softcap=softcap)
    merged = A.merge_partial(A.Partial(o_s, m_s, l_s), p_new)
    return A.finalize_partial(merged)


def paged_decode_attn_sharded(
    sp: str, n: int, q, k_new, v_new, query_pos_full,
    k_pages, v_pages, table, lengths, page_pos=None, *,
    window: Optional[int] = None,
    softcap: Optional[float] = None,
    overlap: bool = True,
    impl: Optional[str] = None,
):
    """One decode layer's BATCH-SHARDED multi-master paged attention
    boundary, called INSIDE an enclosing shard_map body (no region of its
    own — the whole iteration is one manual region, see
    `paged_decode_iteration_spmd`).

    Each rank owns a ``B/n`` batch slice of the non-attention stack, so the
    layer boundary is exactly LoongServe §4.2's collective schedule:

        qg  = all_gather(q-slice)            (the paper's "send query
                                              tensors": full-B q per rank)
        part = paged partial over LOCAL KV   (full B vs this rank's pool
                                              mirror — exactly as before)
        M   = pmax(m)                        (tiny [B, 1, H])
        o_s, l_s = psum_scatter(o·exp(m-M),  ("send back partial results"
                                l·exp(m-M))   addressed to the masters: the
                                              reduce RETURNS batch shards)
        merge with the rank-LOCAL new-token partial, finalize

    replacing PR 5's replicated pmax+psum: per-rank FLOPs for everything
    outside this boundary drop to ~1/n while the attention partial (already
    1/n via the KV sharding) is unchanged.  ``overlap=False`` pins the
    scatter behind an optimization barrier threading the new-token
    partial's inputs (sequential benchmark baseline); the default leaves
    XLA free to schedule the collectives against the stack's independent
    compute, preserving PR 5's overlap property.

    q/k_new/v_new: this rank's batch slice [B/n, 1, ...];
    query_pos_full [B] REPLICATED (every rank masks the full-B partial);
    k_pages/v_pages/table/lengths/page_pos: this rank's local pool-mirror
    plane (no leading rank axis).  Returns the rank's finalized output
    slice [B/n, 1, H, D] f32."""
    from repro.kernels import ops

    ops.dispatch_counts["paged_decode_sharded"] += 1
    b_l = q.shape[0]
    qg = ops.all_gather(q, sp, axis=0)  # [B, 1, H, D]
    part = _switched_paged_partial(
        sp, n, qg, k_pages, v_pages, table, lengths, page_pos,
        query_pos=query_pos_full, window=window, softcap=softcap, impl=impl,
    )
    m_g = ops.pmax(part.m, sp)
    m_safe = jnp.where(jnp.isinf(m_g), 0.0, m_g)
    w = jnp.where(jnp.isinf(part.m), 0.0, jnp.exp(part.m - m_safe))
    o_s, l_s = ops.psum_scatter(
        (part.o * w[..., None], part.l * w), sp, scatter_dimension=0,
    )
    m_s = lax.dynamic_slice_in_dim(m_g, lax.axis_index(sp) * b_l, b_l, axis=0)
    if not overlap:
        o_s, m_s, l_s, q, k_new, v_new = lax.optimization_barrier(
            (o_s, m_s, l_s, q, k_new, v_new)
        )
    p_new = A.partial_attention(q, k_new, v_new, None, softcap=softcap)
    merged = A.merge_partial(A.Partial(o_s, m_s, l_s), p_new)
    return A.finalize_partial(merged)


def paged_decode_iteration_spmd(
    mesh: Mesh, model, impl, params, toks, n_cached_full,
    k_pages, v_pages, table, lengths, page_pos, route, *,
    sp_axis: str = "data",
    overlap: bool = True,
):
    """The WHOLE batch-sharded decode iteration as ONE shard_map program:
    embed, QKV, FFN, norms, unembed and greedy sampling all run on each
    rank's ``B/n`` batch slice; only the per-layer attention boundary
    (`paged_decode_attn_sharded`, armed through ``impl``) and the final
    exchanges are collectives.

    In-program epilogue (nothing batch-wide ever leaves the device mesh
    replicated except tiny ids):

      * sampling: each rank argmaxes its OWN logits slice
        (`model.decode_sampled` — bit-identical to the engine's host
        `_sample_token`) and the sampled ids are all_gathered so every rank
        sees the full next-token vector — the in-program token exchange
        that lets each master route its own KV appends;
      * per-master KV-append routing: the step's new per-layer KV rows are
        all_gathered over the batch axis and each rank `take`s the rows of
        the requests IT masters (``route``, built by the executor from
        `DecodeBatch.masters`) — the routed output lands master-major, each
        master's rows physically on its own device, instead of the host
        re-slicing a replicated tensor.

    toks [B] int32 sharded over ``sp_axis`` (B % n == 0, bucket-padded);
    n_cached_full [B] REPLICATED (ranks slice their own view and window
    masking needs the full vector); k_pages/v_pages
    [n, L, n_pages, P, KVH, D], table [n, B, max_pages], lengths [n, B],
    page_pos [n, n_pages, P] (window only) — sharded over the leading rank
    axis; route [n, R] int32 batch indices (R = bucketed max
    requests-per-master, padding rows point at index 0 and are never read).
    Returns (sampled ids [B] replicated, k_routed, v_routed
    [L, n*R, 1, KVH, D] sharded master-major on the row axis)."""
    from repro.core.paged_decode import SpmdPagedShards
    from repro.kernels import ops
    from repro.models.transformer import Cache

    n = int(mesh.shape[sp_axis])
    bb = int(toks.shape[0])
    assert bb % n == 0 and int(k_pages.shape[0]) == n, (bb, k_pages.shape, n)
    b_l = bb // n
    ops.dispatch_counts["decode_iteration_spmd"] += 1
    sp = sp_axis
    has_pos = page_pos is not None

    def body(prm, tk, ncf, kb, vb, tb, lb, rt, *pb):
        # tk: this rank's batch slice [B/n]; kb/vb/tb/lb/pb: its pool-mirror
        # view (leading shard dim 1); ncf: full replicated cached lengths
        r = lax.axis_index(sp)
        ncl = lax.dynamic_slice_in_dim(ncf, r * b_l, b_l, axis=0)
        shards = SpmdPagedShards(kb, vb, tb, lb, pb[0] if has_pos else None)
        impl.begin_step(
            shards, axis_name=sp, n_ranks=n, query_pos=ncf, overlap=overlap,
        )
        try:
            nxt, _, kvs = model.decode_sampled(prm, tk, Cache(length=ncl))
        finally:
            impl.end_step()
        nxt_all = ops.all_gather(nxt, sp, axis=0)  # [B] tiny ids
        k_all = ops.all_gather(kvs[0], sp, axis=1)  # [L, B, 1, KVH, D]
        v_all = ops.all_gather(kvs[1], sp, axis=1)
        k_rt = jnp.take(k_all, rt[0], axis=1)  # this master's rows [L, R,...]
        v_rt = jnp.take(v_all, rt[0], axis=1)
        return nxt_all, k_rt, v_rt

    specs = [P(), P(sp), P(None), P(sp), P(sp), P(sp), P(sp), P(sp)]
    args = [params, toks, n_cached_full, k_pages, v_pages, table, lengths,
            route]
    if has_pos:
        specs.append(P(sp))
        args.append(page_pos)
    fn = _shmap(
        body, mesh, in_specs=tuple(specs),
        out_specs=(P(None), P(None, sp), P(None, sp)),
    )
    return fn(*args)


def unified_iteration_spmd(
    mesh: Mesh, model, impl, params, toks, positions, seq_offsets, last_idx,
    k_pages, v_pages, table, lengths, page_pos, *,
    sp_axis: str = "data",
    max_seq_len: Optional[int] = None,
    double_buffer: bool = True,
):
    """ONE shard_map program for a whole UNIFIED engine iteration: a bounded
    chunk of every admitted prompt's prefill tokens AND all in-flight decode
    tokens packed on a single ragged token axis, STRIPED over the group's
    data ranks.

    Each rank runs the full stack (embed, QKV, FFN, norms) on its token
    stripe; at every layer boundary the armed `core.unified.UnifiedAttnImpl`
    executes BOTH compute planes inside the same layer:

      * prefix plane (the decode-path schedule): all_gather(q stripes) ->
        per-rank paged partial over its OWN pool plane with per-token tables
        and filled-prefix lengths (`_switched_paged_partial`) -> pmax +
        psum_scatter LSE-merge addressed back to the stripes;
      * chunk plane (the prefill-path schedule): the striped `lax.ppermute`
        KV ring folded into the prefix carry (`switched_ring_chunk`, real
        kernel under `lax.switch`), double-buffered.

    A decode row is a length-1 segment whose prefix is its whole cache —
    the merge is bit-identical to `paged_decode_iteration_spmd`'s; a prefill
    chunk's prefix is the part of its prompt already written through
    `fill_packed`, so the pool IS the carried (acc, m, l) flash state across
    engine iterations.

    In-program epilogue: the final hidden stripes are all_gathered, each
    SEGMENT's last token row is unembedded and greedily argmaxed (bit-equal
    to the engine's host `_sample_token`), and the packed per-layer KV comes
    back token-sharded for write-through scatter.  Like the decode routed
    path, the SPMD program has no host NaN guard — chaos NaN injection is a
    LocalExecutor concern (documented degradation gap).

    The chunk schedule is position-agnostic: a segment may start ANYWHERE in
    its request as long as the pools cover every lower position (the
    fault-recovery hole-filling schedule — see `core.unified` — rides this
    same program; the engine marks hole segments non-final so their rows are
    never sampled).

    toks [T] int32 STRIPED order, sharded over ``sp_axis`` (T % n == 0);
    positions [T] int32 replicated, striped order (prefix query_pos; ranks
    slice their own stripe for RoPE); seq_offsets [S+1] replicated GLOBAL
    packed offsets; last_idx [S] replicated striped-coordinate indices of
    each segment's sampling row (bucket-pad rows point at 0, never read);
    k_pages/v_pages [n, L, n_pages, P, KVH, D], table [n, T, max_pages],
    lengths [n, T], page_pos [n, n_pages, P] (window only) — leading axis =
    rank.  Returns (ids [S] replicated, k_packed, v_packed [L, T, KVH, D]
    sharded on the striped token axis)."""
    from repro.core.unified import UnifiedShard
    from repro.kernels import ops

    n = int(mesh.shape[sp_axis])
    t = int(toks.shape[0])
    assert t % n == 0 and int(k_pages.shape[0]) == n, (t, k_pages.shape, n)
    t_l = t // n
    ops.dispatch_counts["unified_iteration_spmd"] += 1
    sp = sp_axis
    has_pos = page_pos is not None

    def body(prm, tk, posf, ob, li_, kb, vb, tb, lb, *pb):
        # tk: this rank's token stripe [T/n]; kb/vb/tb/lb/pb: its pool plane
        # + per-token paged operands over the FULL striped axis (leading
        # shard dim 1); posf/ob/li_: replicated
        r = lax.axis_index(sp)
        posl = lax.dynamic_slice_in_dim(posf, r * t_l, t_l, axis=0)
        shard = UnifiedShard(
            kb[0], vb[0], pb[0][0] if has_pos else None, tb[0], lb[0]
        )
        impl.begin_step(
            ob, posf, max_seq_len=max_seq_len, shards=[shard], axis_name=sp,
            n_ranks=n, double_buffer=double_buffer,
        )
        try:
            x, kv = model.prefill_packed_hidden(
                prm, {"tokens": tk[None]}, posl, unroll=True
            )
        finally:
            impl.end_step()
        xg = ops.all_gather(x[0], sp, axis=0)  # [T, d]
        sel = jnp.take(xg, li_, axis=0)
        logits = model.unembed(prm, sel[None])[0]  # [S, V]
        ids = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return ids, kv[0], kv[1]

    specs = [P(), P(sp), P(None), P(None), P(None), P(sp), P(sp), P(sp),
             P(sp)]
    args = [params, toks, positions, jnp.asarray(seq_offsets, jnp.int32),
            jnp.asarray(last_idx, jnp.int32), k_pages, v_pages, table,
            lengths]
    if has_pos:
        specs.append(P(sp))
        args.append(page_pos)
    fn = _shmap(
        body, mesh, in_specs=tuple(specs),
        out_specs=(P(None), P(None, sp), P(None, sp)),
    )
    return fn(*args)


class ESPAttnImpl(DefaultAttnImpl):
    def __init__(
        self,
        mesh: Mesh,
        cfg: ModelConfig,
        *,
        sp_axis: str = "data",
        tp_axis: Optional[str] = "model",
        dop: Optional[int] = None,
        force_batch_mode: bool = False,
        ring_slice_tp: bool = False,
        interpret: bool = False,
    ):
        self.mesh = mesh
        self.cfg = cfg
        self.sp = sp_axis
        self.tp = tp_axis if (tp_axis and tp_axis in mesh.axis_names) else None
        self.n_sp = mesh.shape[sp_axis]
        self.n_tp = mesh.shape[self.tp] if self.tp else 1
        self.dop = dop or self.n_sp
        assert self.n_sp % self.dop == 0
        # prefill head sharding mode. Hybrid/ssm archs force batch mode so
        # attention sharding matches the recurrent layers' (batch-over-tp)
        # activation layout with no per-layer reshard.
        self.heads_mode = (
            not force_batch_mode
            and (self.n_tp == 1 or cfg.n_heads % self.n_tp == 0)
        )
        self.kv_div = cfg.n_kv_heads % self.n_tp == 0 if self.n_tp > 1 else True
        # decode KV sharding mode (mode1: heads over tp; mode2: seq over both)
        self.decode_heads_mode = (
            not force_batch_mode
            and (
                self.n_tp == 1
                or (cfg.n_kv_heads % self.n_tp == 0 and cfg.n_heads % self.n_tp == 0)
            )
        )
        # beyond-paper (§Perf A2): when KV heads are replicated across tp
        # (GQA kv < tp), the naive ring circulates the SAME stripe on every
        # tp rank (tp-fold redundant ICI traffic). slice-ring sends each tp
        # rank 1/tp of the stripe's tokens and all-gathers locally after
        # receive — ring-leg traffic drops by tp.
        self.ring_slice_tp = ring_slice_tp
        self.interpret = interpret

    # ---------------------------------------------------------------- prefill
    def prefill_attn(self, q, k, v, q_pos, k_pos, *, causal, window, softcap):
        """q [B,S,H,D] in the (striped) layout matching q_pos; S shards over
        sp as the stripes. Returns [B,S,H,D]."""
        n_sp, tp = self.n_sp, self.tp
        if n_sp == 1:
            return super().prefill_attn(
                q, k, v, q_pos, k_pos, causal=causal, window=window, softcap=softcap
            )
        h_local = self.cfg.n_heads // self.n_tp if (self.heads_mode and tp) else self.cfg.n_heads
        q_per_kv = self.cfg.q_per_kv
        slice_kv = self.heads_mode and tp and not self.kv_div
        pairs = striped.ring_pairs(n_sp, self.dop)
        ring_len = self.dop
        sp = self.sp

        slice_ring = (
            self.ring_slice_tp and tp and self.n_tp > 1
            and (not self.kv_div or not self.heads_mode)
        )
        n_tp = self.n_tp
        # ranks holding IDENTICAL kv tensors form the de-dup group: all tp
        # ranks in batch mode; the q_per_kv/h_local block in heads mode
        if slice_ring and self.heads_mode and slice_kv:
            ring_group = max(q_per_kv // h_local, 1)
        else:
            ring_group = n_tp
        if slice_ring and ring_group < 2:
            slice_ring = False
        ag_groups = [
            [b * ring_group + i for i in range(ring_group)]
            for b in range(n_tp // ring_group)
        ] if slice_ring else None

        def body(qb, kb, vb, qp, kp):
            if slice_kv:
                kb, vb = _slice_kv_heads(
                    kb, vb, lax.axis_index(tp), h_local, q_per_kv
                )
            if qp.ndim > 1:  # squeeze leading sharded dummy dims
                qp, kp = qp.reshape(-1), kp.reshape(-1)
            acc = None
            kv_pos = kp
            kk, vv = kb, vb
            s_l = kb.shape[1]
            for step in range(ring_len):
                mask = A.mask_from_positions(
                    qp, kv_pos, causal=causal, window=window
                )
                part = A.partial_attention(qb, kk, vv, mask, softcap=softcap)
                acc = part if acc is None else A.merge_partial(acc, part)
                if step < ring_len - 1:
                    if slice_ring:
                        # A2 slice-ring: each rank of the de-dup group
                        # forwards only its 1/g token slice; receivers
                        # re-gather within the group.
                        tidx = lax.axis_index(tp) % ring_group
                        per = s_l // ring_group
                        ks = lax.dynamic_slice_in_dim(kk, tidx * per, per, 1)
                        vs = lax.dynamic_slice_in_dim(vv, tidx * per, per, 1)
                        ks, vs, kv_pos = lax.ppermute((ks, vs, kv_pos), sp, pairs)
                        kk = lax.all_gather(
                            ks, tp, axis=1, tiled=True,
                            axis_index_groups=ag_groups,
                        )
                        vv = lax.all_gather(
                            vs, tp, axis=1, tiled=True,
                            axis_index_groups=ag_groups,
                        )
                    else:
                        kk, vv, kv_pos = lax.ppermute(
                            (kk, vv, kv_pos), sp, pairs
                        )
            return A.finalize_partial(acc).astype(qb.dtype)

        if self.heads_mode:
            q_spec = P(None, sp, tp, None)
            kv_spec = P(None, sp, tp if (tp and self.kv_div) else None, None)
        else:  # batch mode: batch over tp (replicated if not divisible)
            btp = tp if (tp and q.shape[0] % self.n_tp == 0) else None
            q_spec = P(btp, sp, None, None)
            kv_spec = P(btp, sp, None, None)
        pos_spec = P(sp)
        fn = _shmap(
            body,
            self.mesh,
            in_specs=(q_spec, kv_spec, kv_spec, pos_spec, pos_spec),
            out_specs=q_spec,
        )
        q_pos = jnp.broadcast_to(jnp.asarray(q_pos), (q.shape[1],))
        k_pos = jnp.broadcast_to(jnp.asarray(k_pos), (k.shape[1],))
        return fn(q, k, v, q_pos, k_pos)

    # ---------------------------------------------------------------- decode
    def decode_attn(self, q, k_cache, v_cache, k_new, v_new, cache_len, *,
                    window, softcap):
        """Multi-master distributed decode (LoongServe §4.2).

        q [B,1,H,D]; caches [B,S,KVH,D] sharded over sp (and tp in mode2) on
        the sequence dim; k_new/v_new [B,1,KVH,D] live with the masters."""
        n_sp, tp, sp = self.n_sp, self.tp, self.sp
        if n_sp == 1 and self.n_tp == 1:
            return super().decode_attn(
                q, k_cache, v_cache, k_new, v_new, cache_len,
                window=window, softcap=softcap,
            )
        b = q.shape[0]
        multi_master = b % n_sp == 0 and b >= n_sp
        heads_mode = self.decode_heads_mode
        h_local = self.cfg.n_heads // self.n_tp if (heads_mode and tp) else self.cfg.n_heads
        n_tp = self.n_tp

        def body(qb, kb, vb, knb, vnb, cl):
            # --- local KV shard positions ---
            s_l = kb.shape[1]
            if heads_mode:
                lin = lax.axis_index(sp)
            else:
                lin = lax.axis_index(sp) * n_tp + (lax.axis_index(tp) if tp else 0)
            off = lin * s_l
            pos = off + jnp.arange(s_l)
            # --- gather queries from masters (the q broadcast) ---
            if multi_master:
                qg = lax.all_gather(qb, sp, axis=0, tiled=True)  # [B,1,h,D]
            else:
                qg = qb
            valid = pos[None, :] < cl[:, None]
            qpos = cl[:, None]
            mask = A.mask_from_positions(
                qpos, jnp.broadcast_to(pos, (b, s_l)), causal=True,
                window=window, k_valid=valid,
            )
            part = A.partial_attention(qg, kb, vb, mask, softcap=softcap)
            # --- LSE-weighted combine across KV shards ---
            axes = (sp,) if heads_mode else ((sp, tp) if tp else (sp,))
            m_g = lax.pmax(part.m, axes)
            m_safe = jnp.where(jnp.isinf(m_g), 0.0, m_g)
            w = jnp.where(jnp.isinf(part.m), 0.0, jnp.exp(part.m - m_safe))
            o_w = part.o * w[..., None]
            l_w = part.l * w
            if not heads_mode and tp:
                o_w = lax.psum(o_w, tp)
                l_w = lax.psum(l_w, tp)
            if multi_master:
                # reduce-scatter back to masters (batch shards over sp)
                o_s = lax.psum_scatter(o_w, sp, scatter_dimension=0, tiled=True)
                l_s = lax.psum_scatter(l_w, sp, scatter_dimension=0, tiled=True)
                b_l = b // n_sp
                m_s = lax.dynamic_slice_in_dim(
                    m_g, lax.axis_index(sp) * b_l, b_l, axis=0
                )
            else:
                o_s = lax.psum(o_w, sp)
                l_s = lax.psum(l_w, sp)
                m_s = m_g
            # --- merge the master-local new-token KV partial ---
            if heads_mode and tp and not self.kv_div:
                knb, vnb = _slice_kv_heads(
                    knb, vnb, lax.axis_index(tp), h_local, self.cfg.q_per_kv
                )
            p_new = A.partial_attention(qb, knb, vnb, None, softcap=softcap)
            merged = A.merge_partial(A.Partial(o_s, m_s, l_s), p_new)
            return A.finalize_partial(merged).astype(qb.dtype)

        bspec = sp if multi_master else None
        if heads_mode:
            q_spec = P(bspec, None, tp, None)
            kv_spec = P(None, sp, tp, None)
            new_spec = P(bspec, None, tp if self.kv_div else None, None)
        else:
            q_spec = P(bspec, None, None, None)
            kv_spec = P(None, (sp, tp) if tp else sp, None, None)
            new_spec = P(bspec, None, None, None)
        fn = _shmap(
            body,
            self.mesh,
            in_specs=(q_spec, kv_spec, kv_spec, new_spec, new_spec, P(None)),
            out_specs=q_spec,
        )
        cl = jnp.broadcast_to(jnp.asarray(cache_len), (b,))
        return fn(q, k_cache, v_cache, k_new, v_new, cl)

    # ------------------------------------------------------------ recurrent
    def ssm_scan(self, kind, p, x, cfg, state):
        """Sequence-parallel recurrent layers (hybrid/ssm archs).

        Mamba2/mLSTM use the 3-phase chunk-state handoff (local state-only
        fold -> log-step exclusive device scan -> local pass with the true
        incoming state). sLSTM is inherently sequential (xLSTM paper §2.3):
        we all-gather its input and scan redundantly, slicing the local part.
        These run on the *contiguous* (non-striped) layout; see
        DESIGN.md §Arch-applicability.
        """
        if self.n_sp == 1:
            return super().ssm_scan(kind, p, x, cfg, state)
        from repro.core import ssm_sp

        fns = {
            "mamba": ssm_sp.mamba2_forward_sp,
            "mlstm": ssm_sp.mlstm_forward_sp,
            "slstm": ssm_sp.slstm_forward_sp,
        }
        return fns[kind](
            self.mesh, self.sp, p, x, cfg, state, tp=self.tp,
            interpret=self.interpret,
        )
