"""Pluggable compute executors: every real-mode kernel-dispatch body lives
here, behind one seam.

`engine/server.py` owns the control plane — clock, events, scheduling,
request lifecycle, pool *accounting*; an Executor owns the compute plane:
how a scheduled PrefillBatch / DecodeBatch actually turns into model steps,
kernel launches and KV writes.  The engine calls exactly four entry points
(`prefill`, `decode`, plus the `prefill_packed`/`decode_paged` fast paths it
never invokes directly but benchmarks do), so policies and executors evolve
independently:

  * `LocalExecutor` — today's in-process paths, moved verbatim from the
    engine: ONE jitted packed model step per prefill batch (DoP>1 groups
    replay the striped ppermute ring in-process, one ring-chunk launch per
    instance per ring step), batched paged decode with per-instance
    partials, and the per-request serial fallbacks for recurrent/moe
    families.
  * `MeshExecutor` — the SPMD production shape: the SAME packed prefill
    step, but the DoP>1 ring runs as ONE `shard_map` program over a real
    ``("data", "model")`` mesh (`core.esp.ring_packed_prefill_spmd`): each
    elastic instance physically owns its stripe of the packed token axis on
    its own device, KV stripes rotate between devices with `lax.ppermute`,
    and the next stripe's transfer is double-buffered against the current
    chunk's compute.  Each instance's KV-pool device mirror is bound to its
    own data-shard device, so `fill_packed` write-through lands every
    reserved placement column on the device that owns it — ESP scale-down
    stays zero-migration *physically*, not just in the bookkeeping.

Exactness is anchored to the dense oracle in `kernels/ref.py`: both
executors produce bit-identical token sequences to the serial per-request
path (tests/test_ring_prefill.py, tests/mesh_exec_cases.py).
"""
from __future__ import annotations

from collections import OrderedDict
from typing import Any, Dict, List, NamedTuple, Tuple

import numpy as np
from jax.profiler import TraceAnnotation


class _USeg(NamedTuple):
    """One segment of a unified iteration's packed token axis."""

    r: Any  # the Request
    decode: bool  # decode row (ln == 1) vs prefill chunk
    start: int  # first global position this iteration
    ln: int  # token count this iteration
    limit: int  # filled-prefix length: positions < limit are in the pool
    final: bool  # sample a token from this segment's last row


def _token_span(r, start: int, ln: int) -> np.ndarray:
    """Token ids at positions [start, start+ln): prompt ids below
    `input_len`, generated tokens above (token at position p >= input_len
    is output_tokens[p - input_len] — what a decode-resume recovery hole
    re-feeds when the lost stripe covers generated positions)."""
    end = start + ln
    out = list(r.prompt[start:min(end, r.input_len)])
    if end > r.input_len:
        lo = max(start, r.input_len) - r.input_len
        out += list(r.output_tokens[lo:end - r.input_len])
    return np.asarray(out, np.int32)


class LocalExecutor:
    """In-process executor: one device, ring replayed as a chunk schedule."""

    def __init__(self, engine):
        self.eng = engine
        # batched paged decode: the multi-master paged attention impl is
        # swapped in only around a batched decode step (the model object is
        # caller-owned and may be shared between engines).  Pure-attention
        # families only: hybrids/ssm keep the serial per-request path, and
        # moe stays serial because expert-capacity dropping is batch-size
        # dependent (batching would change generated tokens).
        self._paged_impl = None
        # packed ragged prefill: one jitted model step per bucketed
        # (total_tokens, batch, max_len, dop) shape — O(log max_tokens)
        # programs per DoP instead of one per distinct prompt length.  DoP>1
        # ESP groups run the SAME packed step with the token axis striped
        # across the group and attention ring-fused — no serial fallback for
        # scaled-up groups.  Same family gating as the paged decode path.
        self._packed_prefill_impl = None
        self._unified_impl = None
        # ONE iteration-program cache for every compiled variant the
        # executor dispatches — prefill, decode and unified steps share it,
        # keyed by (kind, bucket tuple..., mesh) with LRU eviction so a
        # long-lived engine cycling many bucket/mesh shapes cannot grow the
        # compiled-program set without bound.
        self._programs: "OrderedDict[Tuple, Any]" = OrderedDict()
        if engine.cfg.family in ("dense", "vlm"):
            from repro.core.paged_decode import PagedDecodeAttnImpl
            from repro.core.paged_prefill import PackedPrefillAttnImpl
            from repro.core.unified import UnifiedAttnImpl
            from repro.models.transformer import DefaultAttnImpl

            if type(getattr(engine.model, "attn_impl", None)) is DefaultAttnImpl:
                self._paged_impl = PagedDecodeAttnImpl()
                self._packed_prefill_impl = PackedPrefillAttnImpl()
                self._unified_impl = UnifiedAttnImpl()

    # --------------------------------------------------- program LRU cache
    _program_cache_cap = 64

    def _program_get(self, key):
        fn = self._programs.get(key)
        if fn is not None:
            self._programs.move_to_end(key)
        return fn

    def _program_put(self, key, fn):
        self._programs[key] = fn
        self._programs.move_to_end(key)
        while len(self._programs) > self._program_cache_cap:
            self._programs.popitem(last=False)
        return fn

    @property
    def _prefill_programs(self) -> Dict[Tuple, Any]:
        """Cached packed-prefill programs, keyed without the kind prefix
        (compat view over the merged cache for tests/benchmarks)."""
        return {k[1:]: v for k, v in self._programs.items() if k[0] == "prefill"}

    @property
    def _decode_programs(self) -> Dict[Tuple, Any]:
        return {k[1:]: v for k, v in self._programs.items() if k[0] == "decode"}

    def on_instance_failed(self, inst: int) -> None:
        """Failure notification from the engine. The in-process executor
        holds no per-instance compiled state (programs are keyed by bucket
        shape only), so there is nothing to purge; the mesh executor
        overrides this to drop sub-meshes containing the dead rank."""

    # ------------------------------------------------------------ NaN guard
    def _guard_logits(self, r, row):
        """Value guard on one request's logits row: a NaN/inf row quarantines
        ONLY that request (`engine._quarantine` — the completion handler
        requeues it for recompute) instead of finishing it with a garbage
        argmax or poisoning the batch.  Chaos injection (`_logit_poison`)
        overwrites the row BEFORE the finite check, so the guard is
        exercised by value exactly as a real kernel fault would present.
        Returns the row, or None when the request was quarantined (caller
        skips its token emission and KV stash)."""
        eng = self.eng
        if r.rid in eng._logit_poison:
            eng._logit_poison.discard(r.rid)
            row = np.full_like(row, np.nan)
        if not np.isfinite(row).all():
            eng._quarantine.add(r.rid)
            return None
        return row

    # ------------------------------------------------------------- buckets
    @staticmethod
    def _bucket(n: int, lo: int = 16) -> int:
        """Power-of-two padding bucket: O(log max) compiled shapes (shared
        formula with the pool's scatter-index bucketing)."""
        from repro.kvcache.pool import _pad_bucket

        return max(lo, _pad_bucket(n))

    @classmethod
    def _token_bucket(cls, n: int, lo: int = 16) -> int:
        """Packed-token-axis bucket: powers of two plus their 3/4 points
        (16, 24, 32, 48, 64, ...).  Still O(log max_tokens) compiled shapes
        — 2x the constant — but worst-case padding waste drops from ~2x to
        ~4/3 on the axis every attention launch scans."""
        b = cls._bucket(n, lo)
        mid = (b * 3) // 4
        return mid if (n <= mid and mid >= lo) else b

    # ------------------------------------------------------------- prefill
    def prefill(self, batch) -> None:
        """Dispatch one prefill batch: packed fast path when armed and every
        prompt is materialized, per-request serial otherwise.

        Fast-path guard: every instance holding a request's reserved
        placement must still be alive — scattering would silently skip the
        dead shard and leave partial KV on EITHER path, so such requests
        are pruned and requeued for recompute (normally _on_prefill_done
        already did this; the re-check covers direct callers) while the
        rest of the batch keeps packed speed."""
        eng = self.eng
        lost = [r for r in batch.requests if eng._placement_lost(batch, r)]
        if lost:
            batch.requests = [r for r in batch.requests if r not in lost]
            batch.instances = [
                i for i in batch.instances if i not in eng.failed
            ]
            for r in lost:
                eng.pool.free_request(r.rid)
                eng._requeue_for_recompute(r)
                if r not in eng.pending:
                    eng.pending.append(r)
            if not batch.requests:
                return
        if self._packed_prefill_impl is not None and all(
            r.prompt is not None and len(r.prompt) == r.input_len
            for r in batch.requests
        ):
            return self.prefill_packed(batch)
        return self.prefill_serial(batch)

    def _arm_packed_step(self, impl, offsets, max_len_b: int, dop: int):
        """Arm the packed attention impl for one jitted step (the mesh
        executor overrides this to hand the impl its shard_map mesh)."""
        impl.begin_step(offsets, max_len_b, dop=dop)

    def _program_key(self, tb: int, bb: int, max_len_b: int, dop: int):
        return (tb, bb, max_len_b, dop)

    def _packed_prefill_step(self, tb: int, bb: int, max_len_b: int, dop: int):
        """Jitted packed prefill program for one bucket tuple; cached so
        the compile count stays O(log max_tokens) per DoP (the mesh executor
        additionally keys by mesh shape)."""
        key = ("prefill",) + self._program_key(tb, bb, max_len_b, dop)
        fn = self._program_get(key)
        if fn is None:
            import jax

            model, impl = self.eng.model, self._packed_prefill_impl
            arm = self._arm_packed_step

            def prefill_packed_step(params, tokens, positions, offsets,
                                    last_idx):
                arm(impl, offsets, max_len_b, dop)
                try:
                    return model.prefill_packed(
                        params, {"tokens": tokens[None]}, positions, last_idx
                    )
                finally:
                    impl.end_step()

            fn = self._program_put(key, jax.jit(prefill_packed_step))
        return fn

    def prefill_packed(self, batch) -> None:
        """One packed model step for the WHOLE prefill batch: prompts are
        concatenated on a single (bucketed) token axis, attention is
        segment-masked by one ragged kernel launch per layer (DoP>1 groups:
        one ring-chunk launch per instance per ring step), first tokens are
        sampled from the packed logits, and the per-layer KV output is
        scattered straight into paged device storage at the slots the
        scheduler reserved (`pool.fill_packed` write-through — the decode
        mirror never re-uploads prefill KV).

        Host spans: ``loong.prefill`` around it all, and inside it
        ``.pack`` (host arrays and their upload), ``.launch`` (the jitted
        call, a compile included), ``.wait`` (the host blocked on the
        logits), ``.sample`` and ``loong.kv.write``."""
        import jax.numpy as jnp

        eng = self.eng
        reqs = batch.requests
        lens = [len(r.prompt) for r in reqs]
        total = sum(lens)
        # ring degree = the (alive) ESP group driving this batch; the token
        # bucket is a bucketed SHARD length x dop so the striped shards stay
        # block-aligned (dop=1 degenerates to plain token bucketing)
        dop = max(len([i for i in batch.instances if i not in eng.failed]), 1)
        tb = self._token_bucket(-(-total // dop)) * dop
        bb = self._bucket(len(reqs), lo=1)
        max_len_b = self._bucket(max(lens))
        with TraceAnnotation(
            "loong.prefill", n_req=len(reqs), rid0=reqs[0].rid,
            rid1=reqs[-1].rid, tokens=total, bucket=tb, dop=dop,
        ):
            with TraceAnnotation("loong.prefill.pack"):
                tokens = np.zeros(tb, np.int32)
                positions = np.zeros(tb, np.int32)
                offsets = np.full(bb + 1, total, np.int32)
                offsets[0] = 0
                last_idx = np.zeros(bb, np.int32)
                c = 0
                for b, r in enumerate(reqs):
                    n = lens[b]
                    tokens[c : c + n] = np.asarray(r.prompt, np.int32)
                    positions[c : c + n] = np.arange(n)
                    c += n
                    offsets[b + 1] = c
                    last_idx[b] = c - 1
                args = (jnp.asarray(tokens), jnp.asarray(positions),
                        jnp.asarray(offsets), jnp.asarray(last_idx))
            with TraceAnnotation("loong.prefill.launch"):
                fn = self._packed_prefill_step(tb, bb, max_len_b, dop)
                prev_impl = eng.model.attn_impl
                eng.model.attn_impl = self._packed_prefill_impl
                try:
                    logits, (k_packed, v_packed) = fn(eng.params, *args)
                finally:
                    eng.model.attn_impl = prev_impl
            with TraceAnnotation("loong.prefill.wait"):
                logits = np.asarray(logits)
            with TraceAnnotation("loong.prefill.sample"):
                for b, r in enumerate(reqs):
                    row = self._guard_logits(r, logits[b])
                    if row is None:
                        continue  # quarantined: no first token (requeued)
                    r.output_tokens.append(eng._sample_token(row))
            if not eng.pool.pools[0].store_values:
                return
            # direct-to-pool paged KV writes: per instance, gather the packed
            # columns this instance retains (striped placement from
            # batch.placement — ESP scale-down stays zero-migration) and
            # write-through into its mirror at the reserved block-table slots
            # (per-data-shard mirrors under the mesh executor: the columns
            # land on the instance's OWN device)
            with TraceAnnotation("loong.kv.write") as span:
                starts = np.concatenate([[0], np.cumsum(lens)])
                per_inst: Dict[int, Tuple[List[np.ndarray],
                                          List[np.ndarray]]] = {}
                for b, r in enumerate(reqs):
                    placed = batch.placement.get(r.rid, {})
                    for inst, pos_list in placed.items():
                        if not pos_list or inst in eng.failed:
                            continue
                        p = np.asarray(pos_list, np.int64)
                        cols, slots = per_inst.setdefault(inst, ([], []))
                        cols.append(starts[b] + p)
                        slots.append(eng.pool.pools[inst].slots_for(r.rid, p))
                n = self._write_through(per_inst, k_packed, v_packed)
                span.set_metadata(instances=len(per_inst), slots=n)

    def _write_through(self, per_inst, k_packed, v_packed) -> int:
        """Scatter each instance's packed KV columns into its pool mirror
        (`KVPool.fill_packed`); ``per_inst`` maps an instance to (packed
        column arrays, slot arrays).  Returns the slots written."""
        import jax.numpy as jnp

        n = 0
        for inst, (cols, slots) in per_inst.items():
            cidx = jnp.asarray(np.concatenate(cols))
            slots = np.concatenate(slots)
            n += len(slots)
            self.eng.pool.pools[inst].fill_packed(
                slots,
                jnp.take(k_packed, cidx, axis=1),
                jnp.take(v_packed, cidx, axis=1),
            )
        return n

    def prefill_serial(self, batch) -> None:
        """Per-request fallback (recurrent/hybrid state, moe capacity), in
        one ``loong.prefill`` span."""
        import jax.numpy as jnp

        from repro.kernels import ops

        eng = self.eng
        reqs = batch.requests
        with TraceAnnotation(
            "loong.prefill", n_req=len(reqs), rid0=reqs[0].rid,
            rid1=reqs[-1].rid, tokens=sum(r.input_len for r in reqs),
            bucket=0, dop=batch.dop,
        ):
            for r in reqs:
                # dispatch-counted so tests/benches can assert the packed
                # paths (incl. DoP>1 ring fusion) never fall back to serial
                # prefill
                ops.dispatch_counts["prefill_serial_model"] += 1
                toks = jnp.asarray(np.asarray(r.prompt, np.int32)[None])
                logits, cache = eng.model.prefill(eng.params, {"tokens": toks})
                row = self._guard_logits(r, np.asarray(logits[0, -1]))
                if row is None:
                    continue  # quarantined: no first token, engine requeues
                r.output_tokens.append(eng._sample_token(row))
                if cache.k is not None:
                    k = np.asarray(cache.k[:, 0], np.float32)  # [L, T, KVH, D]
                    v = np.asarray(cache.v[:, 0], np.float32)
                    assign = batch.placement[r.rid]
                    for inst, positions in assign.items():
                        if positions and inst not in eng.failed:
                            eng.pool.pools[inst].fill(
                                r.rid, positions, k[:, positions],
                                v[:, positions]
                            )
                if cache.ssm is not None:
                    eng._real_cache[r.rid] = cache.ssm

    # -------------------------------------------------------------- decode
    def decode(self, g) -> None:
        if self._paged_impl is not None and self.eng.pool.pools[0].store_values:
            return self.decode_paged(g)
        return self.decode_serial(g)

    def decode_paged(self, g) -> None:
        """Gather-free batched decode: ONE model step for the whole group;
        per layer, one paged-kernel launch per instance over the pool storage
        in place (block tables), partials LSE-merged multi-master style.

        Host spans: ``loong.decode`` and inside it ``.pack``, ``.launch``,
        ``.wait`` and ``.sample``, as for prefill."""
        with TraceAnnotation(
            "loong.decode", n_req=len(g.requests),
            tokens=sum(r.seq_len - 1 for r in g.requests), dop=g.dop,
        ):
            self._decode_paged(g)

    def _decode_paged(self, g) -> None:
        import jax.numpy as jnp

        from repro.core.paged_decode import PagedShard
        from repro.models.transformer import Cache

        eng = self.eng
        with TraceAnnotation("loong.decode.pack"):
            rids = [r.rid for r in g.requests]
            n_cached = np.array([r.seq_len - 1 for r in g.requests], np.int32)
            shards, covered = [], np.zeros(len(rids), np.int64)
            for pool in eng.pool.pools:
                if pool.instance_id in eng.failed:
                    continue
                table, lengths = pool.block_table(rids)
                if not lengths.any():
                    continue
                covered += lengths
                # pool-owned incrementally-synced mirror: steady-state decode
                # uploads one slot per request; packed-prefill slots upload 0
                kdev, vdev, posdev = pool.device_paged_kv()
                shards.append(PagedShard(
                    # block tables ride with the mirror's device so the whole
                    # per-shard partial computes where the stripe lives
                    k_pages=kdev,
                    v_pages=vdev,
                    table=pool._dev_put(table),
                    lengths=pool._dev_put(lengths),
                    # per-slot positions are only consumed by window masking
                    pos=(posdev if eng.cfg.sliding_window else None),
                ))
            # cache holds tokens 0..seq_len-2; the processed token's KV is
            # produced by this step and appended at the master afterwards
            assert (covered == n_cached).all(), (covered, n_cached)
            toks = jnp.asarray([r.output_tokens[-1] for r in g.requests],
                               jnp.int32)
            cache = Cache(length=jnp.asarray(n_cached))
        with TraceAnnotation("loong.decode.launch"):
            prev_impl = eng.model.attn_impl
            eng.model.attn_impl = self._paged_impl
            self._paged_impl.begin_step(shards)
            try:
                logits, _, kvs = eng.model.decode(eng.params, toks, cache)
            finally:
                self._paged_impl.end_step()
                eng.model.attn_impl = prev_impl
        self._emit_decoded(g, logits, kvs)

    def _emit_decoded(self, g, logits, kvs) -> None:
        """Shared batched-decode epilogue: sample one token per request and
        stash the step's new per-layer KV; _on_decode_done fills it once the
        slot is allocated.  logits [>=B, V]; kvs [L, >=B, 1, KVH, D] (rows
        past len(g.requests) are bucket padding)."""
        eng = self.eng
        with TraceAnnotation("loong.decode.wait"):
            logits = np.asarray(logits)
        with TraceAnnotation("loong.decode.sample"):
            emitted = []
            for b, r in enumerate(g.requests):
                row = self._guard_logits(r, logits[b])
                if row is None:
                    continue  # quarantined: no token, no KV stash
                r.output_tokens.append(eng._sample_token(row))
                emitted.append((b, r))
        if kvs is not None:
            for b, r in emitted:
                eng._pending_kv[r.rid] = (
                    np.asarray(kvs[0][:, b], np.float32),  # [L, 1, KVH, D]
                    np.asarray(kvs[1][:, b], np.float32),
                )

    def decode_serial(self, g) -> None:
        """Per-request fallback (recurrent/hybrid state or custom impls)."""
        import jax.numpy as jnp

        from repro.models.transformer import Cache

        eng = self.eng
        for r in g.requests:
            positions, k, v = eng.pool.gather_request(r.rid)
            # cache holds tokens 0..seq_len-2; the processed token's KV is
            # produced by this step and appended at the master afterwards
            n_cached = r.seq_len - 1
            if k is not None:
                assert len(positions) == n_cached, (len(positions), n_cached)
            cache = Cache(
                k=jnp.asarray(k[:, None].astype(eng.model.dtype)) if k is not None else None,
                v=jnp.asarray(v[:, None].astype(eng.model.dtype)) if v is not None else None,
                length=jnp.asarray([n_cached], jnp.int32),
                ssm=eng._real_cache.get(r.rid),
            )
            last_tok = r.output_tokens[-1]
            logits, new_cache, kvs = eng.model.decode(
                eng.params, jnp.asarray([last_tok], jnp.int32), cache
            )
            row = self._guard_logits(r, np.asarray(logits[0]))
            if row is None:
                continue  # quarantined: no token, no cache/KV update
            r.output_tokens.append(eng._sample_token(row))
            if new_cache.ssm is not None:
                eng._real_cache[r.rid] = new_cache.ssm
            if kvs is not None:
                # stash; _on_decode_done fills it once the slot is allocated
                eng._pending_kv[r.rid] = (
                    np.asarray(kvs[0][:, 0], np.float32),  # [L, 1, KVH, D]
                    np.asarray(kvs[1][:, 0], np.float32),
                )

    # ------------------------------------------------------------- unified
    @property
    def supports_unified(self) -> bool:
        """The fused chunked-prefill+decode iteration needs the packed attn
        impls (dense/vlm family) and real paged KV storage for the prefix
        partials to read from."""
        return (
            self._unified_impl is not None
            and self.eng.pool.pools[0].store_values
        )

    def _unified_segments(self, work) -> List[_USeg]:
        """Packed-axis layout of one unified iteration: every admitted
        prompt's prefill chunk (batch order), then one decode row per
        in-flight request.  A prefill segment's filled prefix is everything
        before its chunk cursor; a decode row's is its whole cache (tokens
        0..seq_len-2 — the processed token's KV is produced by this step)."""
        segs: List[_USeg] = []
        recovering = getattr(self.eng, "_recovering", {})
        for r in work.batch.requests:
            if r.rid not in work.chunks:
                continue  # out of chunk budget this iteration
            start, ln = work.chunks[r.rid]
            # a decode-resume recovery hole may cover generated positions
            # (up to seq_len - 2), not just the prompt
            hi = max(r.input_len, r.seq_len - 1)
            assert ln > 0 and start + ln <= hi, (start, ln, r.input_len, hi)
            rec = recovering.get(r.rid)
            # hole chunks of a decode-resume recovery NEVER sample: the
            # request's tokens already exist — it re-enters decode at its
            # cursor once coverage is whole (a hole ending exactly at
            # input_len must not re-emit the first generated token)
            final = start + ln == r.input_len and (
                rec is None or not rec.resume_decode
            )
            segs.append(_USeg(r, False, start, ln, start, final))
        for g in work.groups:
            for r in g.requests:
                segs.append(_USeg(r, True, r.seq_len - 1, 1, r.seq_len - 1, True))
        return segs

    def _unified_pack(self, segs, tb: int = None):
        """Host-side packing: (tokens [tb], positions [tb], offsets [bb+1],
        last_idx [bb]) — exactly `prefill_packed`'s layout, with decode rows
        as length-1 segments carrying their request's last sampled token.
        ``tb`` overrides the token bucket (the SPMD path needs a multiple of
        the rank count)."""
        total = sum(s.ln for s in segs)
        if tb is None:
            tb = self._token_bucket(total)
        bb = self._bucket(len(segs), lo=1)
        tokens = np.zeros(tb, np.int32)
        positions = np.zeros(tb, np.int32)
        offsets = np.full(bb + 1, total, np.int32)
        offsets[0] = 0
        last_idx = np.zeros(bb, np.int32)
        c = 0
        for b, s in enumerate(segs):
            if s.decode:
                tokens[c] = s.r.output_tokens[-1]
            else:
                tokens[c : c + s.ln] = _token_span(s.r, s.start, s.ln)
            positions[c : c + s.ln] = np.arange(s.start, s.start + s.ln)
            c += s.ln
            offsets[b + 1] = c
            last_idx[b] = c - 1
        return tokens, positions, offsets, last_idx

    def _unified_count(self, segs) -> None:
        from repro.kernels import ops

        n_pre = sum(s.ln for s in segs if not s.decode)
        ops.dispatch_counts["unified_step"] += 1
        ops.dispatch_counts["unified_prefill_tokens"] += n_pre
        ops.dispatch_counts["unified_decode_tokens"] += sum(
            s.ln for s in segs if s.decode
        )

    def _unified_shards(self, segs, tb: int):
        """Per-pool `core.unified.UnifiedShard`s with PER-TOKEN paged prefix
        operands: one `prefix_block_table` row per segment (clipped to the
        filled prefix), expanded to the packed token axis.  Returns
        (shards, covered); covered[b] sums segment b's prefix length over
        every pool and must equal its limit — no filled slot unreachable,
        none double-counted."""
        from repro.core.unified import UnifiedShard

        eng = self.eng
        rids = [s.r.rid for s in segs]
        limits = np.array([s.limit for s in segs], np.int64)
        infos = []
        for pool in eng.pool.pools:
            if pool.instance_id in eng.failed:
                continue
            table, lengths = pool.prefix_block_table(rids, limits)
            if lengths.any():
                infos.append((pool, table, lengths))
        covered = (
            np.sum([lg for _, _, lg in infos], axis=0)
            if infos
            else np.zeros(len(segs), np.int64)
        )
        mpb = self._bucket(
            max((t.shape[1] for _, t, _ in infos), default=1), lo=1
        )
        shards = []
        for pool, table, lengths in infos:
            tbl_t = np.zeros((tb, mpb), np.int32)
            len_t = np.zeros(tb, np.int32)
            c = 0
            for b, s in enumerate(segs):
                tbl_t[c : c + s.ln, : table.shape[1]] = table[b]
                len_t[c : c + s.ln] = lengths[b]
                c += s.ln
            kdev, vdev, posdev = pool.device_paged_kv()
            shards.append(UnifiedShard(
                k_pages=kdev,
                v_pages=vdev,
                page_pos=(posdev if eng.cfg.sliding_window else None),
                table=pool._dev_put(tbl_t),
                lengths=pool._dev_put(len_t),
            ))
        return shards, covered

    def _unified_step(self, tb: int, bb: int, max_len_b: int, n_shards: int):
        """Jitted in-process unified program for one bucket tuple: one
        packed model step with `UnifiedAttnImpl` merging the paged prefix
        partials into the chunk attention at every layer (static python
        layer loop — `unroll=True` — so the impl can keep a layer cursor)."""
        key = ("unified", tb, bb, max_len_b, n_shards)
        fn = self._program_get(key)
        if fn is None:
            import jax

            model, impl = self.eng.model, self._unified_impl

            def unified_step(params, tokens, positions, offsets, last_idx,
                             shards):
                impl.begin_step(
                    offsets, positions, max_seq_len=max_len_b, shards=shards
                )
                try:
                    return model.prefill_packed(
                        params, {"tokens": tokens[None]}, positions, last_idx,
                        unroll=True,
                    )
                finally:
                    impl.end_step()

            fn = self._program_put(key, jax.jit(unified_step))
        return fn

    def _unified_span(self, work, segs) -> TraceAnnotation:
        """The ``loong.unified`` span of one iteration."""
        return TraceAnnotation(
            "loong.unified", n_req=len(segs), rid0=segs[0].r.rid,
            rid1=segs[-1].r.rid, tokens=sum(s.ln for s in segs),
            dop=len(work.alive_instances(self.eng.failed)),
        )

    def unified(self, work) -> None:
        """ONE packed model step for a whole unified iteration: a bounded
        chunk of each admitted prompt's prefill tokens AND every in-flight
        decode token share one ragged token axis; per layer the chunk
        attention folds on top of the paged prefix partials
        (`core.unified`).  First/next tokens are sampled from the packed
        logits, prefill chunk KV write-throughs at the reserved slots, and
        decode KV is stashed exactly like `decode_paged`.  Host spans:
        ``loong.unified`` with ``.pack``, ``.launch``, ``.wait``, ``.sample``
        and ``loong.kv.write``."""
        segs = self._unified_segments(work)
        with self._unified_span(work, segs):
            self._unified_local(work, segs)

    def _unified_local(self, work, segs) -> None:
        import jax.numpy as jnp

        eng = self.eng
        with TraceAnnotation("loong.unified.pack"):
            tokens, positions, offsets, last_idx = self._unified_pack(segs)
            tb, bb = len(tokens), len(last_idx)
            max_len_b = self._bucket(max(s.ln for s in segs))
            shards, covered = self._unified_shards(segs, tb)
            limits = np.array([s.limit for s in segs], np.int64)
            assert (covered == limits).all(), (covered, limits)
            args = (jnp.asarray(tokens), jnp.asarray(positions),
                    jnp.asarray(offsets), jnp.asarray(last_idx), tuple(shards))
        self._unified_count(segs)
        with TraceAnnotation("loong.unified.launch"):
            fn = self._unified_step(tb, bb, max_len_b, len(shards))
            prev_impl = eng.model.attn_impl
            eng.model.attn_impl = self._unified_impl
            try:
                logits, (k_packed, v_packed) = fn(eng.params, *args)
            finally:
                eng.model.attn_impl = prev_impl
        with TraceAnnotation("loong.unified.wait"):
            logits = np.asarray(logits)
        self._unified_emit(work, segs, logits, None, k_packed, v_packed, None)

    def _unified_emit(
        self, work, segs, logits, ids, k_packed, v_packed, colmap
    ) -> None:
        """Shared unified epilogue.  Host-sampling path: ``logits`` [>=S, V]
        rows pass the NaN guard then argmax (``ids`` None); SPMD path:
        ``ids`` [>=S] were sampled in-program (logits never leave the
        program, so no value guard — same documented gap as
        `_emit_decoded_routed`).  ``colmap`` maps a packed column to its row
        on the KV output's token axis (striped order under SPMD; None =
        identity).  Prefill chunk KV scatters write-through at the chunk's
        reserved placement slots; decode KV is stashed for
        `_on_unified_done` to fill once the slot is allocated."""
        import jax.numpy as jnp

        eng = self.eng
        starts = np.concatenate([[0], np.cumsum([s.ln for s in segs])])
        col_of = (lambda c: c) if colmap is None else (lambda c: colmap[c])
        emitted = set()
        with TraceAnnotation("loong.unified.sample"):
            for b, s in enumerate(segs):
                if not s.final:
                    continue
                if ids is None:
                    row = self._guard_logits(s.r, logits[b])
                    if row is None:
                        continue  # quarantined: no token, engine requeues
                    s.r.output_tokens.append(eng._sample_token(row))
                else:
                    s.r.output_tokens.append(int(ids[b]))
                emitted.add(s.r.rid)
        if not eng.pool.pools[0].store_values:
            return
        dec_cols: List[int] = []
        dec_reqs: List[Any] = []
        with TraceAnnotation("loong.kv.write") as span:
            per_inst: Dict[int, Tuple[List[np.ndarray],
                                      List[np.ndarray]]] = {}
            for b, s in enumerate(segs):
                if s.decode:
                    if s.r.rid in emitted:  # quarantined rows stash no KV
                        dec_cols.append(int(col_of(starts[b])))
                        dec_reqs.append(s.r)
                    continue
                lo, hi = s.start, s.start + s.ln
                placed = work.batch.placement.get(s.r.rid, {})
                for inst, pos_list in placed.items():
                    if not pos_list or inst in eng.failed:
                        continue
                    p = np.asarray(pos_list, np.int64)
                    p = p[(p >= lo) & (p < hi)]
                    if not len(p):
                        continue
                    cols, slots = per_inst.setdefault(inst, ([], []))
                    cols.append(
                        np.asarray(col_of(starts[b] + (p - lo)), np.int64))
                    slots.append(eng.pool.pools[inst].slots_for(s.r.rid, p))
            n = self._write_through(per_inst, k_packed, v_packed)
            span.set_metadata(instances=len(per_inst), slots=n)
        if dec_cols:
            dc = jnp.asarray(np.asarray(dec_cols, np.int64))
            kd = np.asarray(jnp.take(k_packed, dc, axis=1), np.float32)
            vd = np.asarray(jnp.take(v_packed, dc, axis=1), np.float32)
            for j, r in enumerate(dec_reqs):
                eng._pending_kv[r.rid] = (kd[:, j : j + 1], vd[:, j : j + 1])


class MeshExecutor(LocalExecutor):
    """SPMD executor: DoP>1 packed ring prefill as a real shard_map program.

    Construction binds each engine instance ``i`` to data-mesh coordinate
    ``i`` of a ``("data", "model")`` mesh (`launch.mesh`): the instance's
    KV-pool device mirror is pinned to ``mesh.devices[i, 0]`` so both the
    ring pass's `fill_packed` write-through and the paged decode partials
    run on the device that owns the stripe.  A prefill batch over a subset
    of instances runs on the sub-mesh of exactly those devices (cached per
    instance tuple), so elastic DoP groups map to disjoint device groups of
    one physical mesh, like the paper's ESP groups on one GPU cluster.

    Decode is SPMD too (``spmd_decode=True``): the whole batched decode
    iteration compiles as ONE program in which every layer's multi-master
    LSE-merge is a shard_map collective over a 1-D "data" mesh of exactly
    the KV-holding instances' mirror devices.  The sharded paged operand is
    assembled ZERO-COPY from the per-rank pool mirrors
    (`KVPool.device_paged_kv` slices aliased together with
    `jax.make_array_from_single_device_arrays`), the query reaches the
    shards as a compiled replication instead of a per-shard `device_put`
    loop, and the merge is a `pmax`+`psum` on the weighted
    (o·exp(m-M), l·exp(m-M)) accumulator (`core.esp.paged_decode_spmd`) —
    no per-layer host sync points.  ``decode_overlap=False`` pins each
    merge collective behind an optimization barrier (the benchmark's
    sequential baseline, mirroring ``double_buffer=False`` for prefill).
    Groups that cannot get one distinct mirror device per KV-holding
    instance fall back to the per-shard loop.

    ``batch_shard=True`` (default) additionally BATCH-SHARDS the
    non-attention stack (LoongServe §4.2 multi-master): each rank embeds,
    runs FFN/norms, unembeds and greedy-samples only its B/n slice of the
    decode batch — per-rank decode FLOPs ~1/n instead of n-fold replicated
    — and the per-layer boundary becomes all_gather(q-slice) in /
    `psum_scatter` of the LSE-merged output back to batch shards
    (`core.esp.paged_decode_iteration_spmd`).  Sampled ids are exchanged
    in-program and each rank gathers the new KV rows of the requests it
    MASTERS (routing matrix from `DecodeBatch.masters`), so the routed
    per-master append rows land master-major — sharded onto the masters'
    own devices — instead of the host re-slicing a replicated tensor.
    Params stay replicated over the decode mesh: batch sharding is data
    parallelism, every rank runs the full layer stack on its slice, so no
    parameter axis is sharded over "data".  ``batch_shard=False`` keeps
    the PR 5 replicated-stack program (the benchmark's comparison arm).

    ``double_buffer=False`` degrades the ring to the sequential baseline
    (transfer strictly after compute) — the benchmark's comparison arm.
    """

    def __init__(self, engine, mesh=None, *, double_buffer: bool = True,
                 spmd_decode: bool = True, decode_overlap: bool = True,
                 batch_shard: bool = True):
        super().__init__(engine)
        if mesh is None:
            import jax

            from repro.launch.mesh import make_test_mesh

            n_dev = len(jax.devices())
            data = min(len(engine.pool.pools), n_dev)
            mesh = make_test_mesh(data=data, model=max(n_dev // data, 1))
        assert "data" in mesh.axis_names, mesh.axis_names
        self.mesh = mesh
        self.double_buffer = double_buffer
        self.spmd_decode = spmd_decode
        self.decode_overlap = decode_overlap
        self.batch_shard = batch_shard
        self._group_meshes: Dict[Tuple[int, ...], Any] = {}
        self._decode_meshes: Dict[Tuple[int, ...], Any] = {}
        self._params_rep: Dict[Any, Any] = {}
        self._bind_pool_devices()

    def _bind_pool_devices(self) -> None:
        """Pin instance i's KV mirror to data-shard device i (mod data)."""
        devs = self._data_devices()
        for i, pool in enumerate(self.eng.pool.pools):
            pool.bind_device(devs[i % len(devs)])

    def _data_devices(self):
        """One device per data coordinate (model coordinate 0)."""
        import numpy as np_

        devs = np_.asarray(self.mesh.devices)
        data_ax = list(self.mesh.axis_names).index("data")
        # move the data axis first, take coordinate 0 of every other axis
        devs = np_.moveaxis(devs, data_ax, 0)
        return [devs[i].flat[0] for i in range(devs.shape[0])]

    def on_instance_failed(self, inst: int) -> None:
        """Purge every cached sub-mesh containing the dead rank, plus the
        replicated params and compiled programs baked to those meshes.  A
        surviving group re-forms at DoP−1 through the normal `_group_mesh`
        / `_decode_mesh` path — the reduced-DoP program compiles (or LRU-
        hits) on first use, exactly like any other elastic resize."""
        dead = []
        for cache in (self._group_meshes, self._decode_meshes):
            for key in [k for k in cache if inst in k]:
                m = cache.pop(key)
                if m is not None:
                    dead.append(m)
        for m in dead:
            self._params_rep.pop(m, None)
        if dead:
            for key in [k for k in self._programs if any(m in key for m in dead)]:
                del self._programs[key]

    def _group_mesh(self, instances):
        """Sub-mesh ("data", "model") over exactly the group's devices.
        Returns None (-> in-process replay) when the group cannot get one
        distinct data-shard device per instance (more engine instances than
        data coordinates and the group aliases)."""
        import numpy as np_
        from jax.sharding import Mesh

        key = tuple(sorted(instances))
        if key in self._group_meshes:
            return self._group_meshes[key]
        devs = np_.asarray(self.mesh.devices)
        data_ax = list(self.mesh.axis_names).index("data")
        devs = np_.moveaxis(devs, data_ax, 0)
        n_data = devs.shape[0]
        coords = [i % n_data for i in key]
        if len(set(coords)) < len(coords):
            m = None  # aliased devices: no physical ring for this group
        else:
            rows = np_.stack(
                [devs[c].reshape(-1) for c in coords]
            )  # [dop, model*...]
            m = Mesh(rows, ("data", "model"))
        self._group_meshes[key] = m
        return m

    # prefill arming: the SAME packed step, ring under shard_map ----------
    def prefill_packed(self, batch) -> None:
        alive = tuple(
            i for i in batch.instances if i not in self.eng.failed
        )
        self._step_mesh = self._group_mesh(alive) if len(alive) > 1 else None
        try:
            return super().prefill_packed(batch)
        finally:
            self._step_mesh = None

    def _program_key(self, tb, bb, max_len_b, dop):
        # one compiled program per (bucket tuple, dop, mesh): the concrete
        # mesh (hashable) keys the cache because the shard_map bakes the
        # device group in — two DoP groups of the same shape on different
        # devices need separate programs
        return (tb, bb, max_len_b, dop, getattr(self, "_step_mesh", None))

    def _arm_packed_step(self, impl, offsets, max_len_b, dop):
        impl.begin_step(
            offsets, max_len_b, dop=dop,
            mesh=getattr(self, "_step_mesh", None),
            double_buffer=self.double_buffer,
        )

    # decode: the whole iteration as ONE SPMD program ---------------------
    def _decode_mesh(self, instances: Tuple[int, ...]):
        """1-D ("data",) mesh over exactly the KV-holding instances' mirror
        devices (cached per instance tuple).  Returns None (-> per-shard
        loop fallback) when the instances don't map to distinct devices."""
        if instances in self._decode_meshes:
            return self._decode_meshes[instances]
        import numpy as np_
        from jax.sharding import Mesh

        devs = [self.eng.pool.pools[i].device for i in instances]
        if None in devs or len(set(devs)) < len(devs):
            m = None
        else:
            m = Mesh(np_.asarray(devs), ("data",))
        self._decode_meshes[instances] = m
        return m

    def _replicated_params(self, mesh):
        """Engine params replicated over the decode mesh ONCE (committed),
        so steady-state decode iterations re-transfer nothing."""
        pr = self._params_rep.get(mesh)
        if pr is None:
            import jax
            from jax.sharding import NamedSharding, PartitionSpec as P

            pr = jax.device_put(
                self.eng.params, NamedSharding(mesh, P())
            )
            self._params_rep[mesh] = pr
        return pr

    def _decode_program(self, bb: int, mpb: int, mesh, rb=None):
        """Jitted whole-iteration decode program for one (batch bucket,
        page bucket, mesh[, route bucket]) tuple — O(log) compiled
        variants, like the prefill program cache.  ``rb=None`` compiles the
        replicated-stack program (every rank runs the full batch, per-layer
        pmax+psum merge); ``rb`` set compiles the batch-sharded iteration
        (`core.esp.paged_decode_iteration_spmd`) with R=rb routed KV-append
        rows per master."""
        key = ("decode", bb, mpb, mesh, self.decode_overlap, rb)
        fn = self._program_get(key)
        if fn is None:
            import jax

            from repro.core.paged_decode import SpmdPagedShards
            from repro.models.transformer import Cache

            model, impl = self.eng.model, self._paged_impl
            overlap = self.decode_overlap

            if rb is not None:
                from repro.core.esp import paged_decode_iteration_spmd

                def decode_routed_spmd_step(params, toks, n_cached, k_g, v_g,
                                            tbl_g, len_g, pos_g, route):
                    return paged_decode_iteration_spmd(
                        mesh, model, impl, params, toks, n_cached,
                        k_g, v_g, tbl_g, len_g, pos_g, route,
                        overlap=overlap,
                    )
                step = decode_routed_spmd_step
            else:
                def decode_spmd_step(params, toks, n_cached, k_g, v_g, tbl_g,
                                     len_g, pos_g):
                    shards = SpmdPagedShards(k_g, v_g, tbl_g, len_g, pos_g)
                    impl.begin_step(shards, mesh=mesh, overlap=overlap)
                    try:
                        logits, _, kvs = model.decode(
                            params, toks, Cache(length=n_cached)
                        )
                    finally:
                        impl.end_step()
                    return logits, kvs
                step = decode_spmd_step

            fn = self._program_put(key, jax.jit(step))
        return fn

    def _decode_spmd_setup(self, g):
        """Assemble the SPMD decode call for one DecodeBatch: returns
        (jitted program, concrete args, rowmap) or None when the group
        cannot run SPMD (single shard, unbound/aliased mirror devices).

        The paged operands are assembled from the per-rank mirrors IN
        PLACE: each pool's `device_paged_kv` view becomes data-rank i's
        slice of one mesh-sharded array — the executor ships per-request
        block-table rows (tiny) and ZERO KV bytes.

        ``rowmap`` is None for the replicated program; for the
        batch-sharded program it maps rid -> row of the master-major
        routed KV output (rank*rb + j, from the route matrix built out of
        `DecodeBatch.masters` — a master not holding KV in this group
        routes through rank 0)."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P

        eng = self.eng
        rids = [r.rid for r in g.requests]
        n_cached = np.array([r.seq_len - 1 for r in g.requests], np.int32)
        infos = []
        for pool in eng.pool.pools:
            if pool.instance_id in eng.failed:
                continue
            table, lengths = pool.block_table(rids)
            if lengths.any():
                infos.append((pool, table, lengths))
        if len(infos) < 2:
            return None
        mesh = self._decode_mesh(tuple(p.instance_id for p, _, _ in infos))
        if mesh is None:
            return None
        covered = np.sum([lg for _, _, lg in infos], axis=0)
        # cache holds tokens 0..seq_len-2; the processed token's KV is
        # produced by this step and appended at the master afterwards
        assert (covered == n_cached).all(), (covered, n_cached)
        n, b = len(infos), len(rids)
        bb = self._bucket(b, lo=1)
        if self.batch_shard:
            # each rank owns bb/n batch rows: round the bucket up to a
            # multiple of the rank count (padded rows hold zero KV
            # everywhere and their sampled tokens are discarded)
            bb = -(-bb // n) * n
        mpb = self._bucket(max(t.shape[1] for _, t, _ in infos), lo=1)
        sh = NamedSharding(mesh, P("data"))
        kds, vds, pds = [], [], []
        for pool, _, _ in infos:
            kd, vd, pd = pool.device_paged_kv()
            kds.append(kd[None])
            vds.append(vd[None])
            pds.append(pd[None])
        assemble = jax.make_array_from_single_device_arrays
        k_g = assemble((n,) + kds[0].shape[1:], sh, kds)
        v_g = assemble((n,) + vds[0].shape[1:], sh, vds)
        pos_g = (
            assemble((n,) + pds[0].shape[1:], sh, pds)
            if eng.cfg.sliding_window else None
        )
        tbl = np.zeros((n, bb, mpb), np.int32)
        lens = np.zeros((n, bb), np.int32)
        for i, (_, t, lg) in enumerate(infos):
            tbl[i, :b, : t.shape[1]] = t
            lens[i, :b] = lg
        toks = np.zeros(bb, np.int32)
        toks[:b] = [r.output_tokens[-1] for r in g.requests]
        ncb = np.zeros(bb, np.int32)
        ncb[:b] = n_cached
        rb = route = rowmap = None
        if self.batch_shard:
            # per-master KV-append routing: rank i gathers the new KV rows
            # of the requests instance infos[i] masters, so the routed
            # output lands master-major on the masters' own devices
            inst_rank = {
                p.instance_id: i for i, (p, _, _) in enumerate(infos)
            }
            per_rank: List[List[int]] = [[] for _ in range(n)]
            owner_of: List[Tuple[int, int]] = []
            for bi, r in enumerate(g.requests):
                rank = inst_rank.get(g.masters.get(r.rid), 0)
                owner_of.append((rank, len(per_rank[rank])))
                per_rank[rank].append(bi)
            rb = self._bucket(max(len(rows) for rows in per_rank), lo=1)
            route = np.zeros((n, rb), np.int32)  # padding rows read row 0
            for i, rows in enumerate(per_rank):
                route[i, : len(rows)] = rows
            rowmap = {
                r.rid: rank * rb + j
                for r, (rank, j) in zip(g.requests, owner_of)
            }
        fn = self._decode_program(bb, mpb, mesh, rb)
        args = [
            self._replicated_params(mesh), jnp.asarray(toks),
            jnp.asarray(ncb), k_g, v_g, jax.device_put(tbl, sh),
            jax.device_put(lens, sh), pos_g,
        ]
        if route is not None:
            args.append(jax.device_put(route, sh))
        return fn, tuple(args), rowmap

    def _decode_paged(self, g) -> None:
        """One shard_map decode iteration for the whole group: per layer,
        each rank's paged partial computes over the mirror it holds and the
        LSE-merge is a collective XLA can schedule against independent
        compute — zero per-shard Python-loop merges, zero per-layer
        `device_put` hops (see `core.esp.paged_decode_spmd`)."""
        with TraceAnnotation("loong.decode.pack"):
            setup = self._decode_spmd_setup(g) if self.spmd_decode else None
        if setup is None:
            return super()._decode_paged(g)
        fn, args, rowmap = setup
        eng = self.eng
        with TraceAnnotation("loong.decode.launch"):
            prev_impl = eng.model.attn_impl
            eng.model.attn_impl = self._paged_impl
            try:
                if rowmap is None:
                    logits, kvs = fn(*args)
                else:
                    toks_next, k_rt, v_rt = fn(*args)
            finally:
                eng.model.attn_impl = prev_impl
        if rowmap is None:
            self._emit_decoded(g, logits, kvs)
        else:
            self._emit_decoded_routed(g, toks_next, k_rt, v_rt, rowmap)

    def _emit_decoded_routed(self, g, toks_next, k_rt, v_rt, rowmap) -> None:
        """Batch-sharded epilogue: tokens were sampled IN-PROGRAM (each
        rank argmaxed its own logits slice, ids exchanged by all_gather) and
        the new per-layer KV arrives master-major pre-routed
        [L, n*rb, 1, KVH, D] — this just appends each request's id and
        stashes its routed KV rows for _on_decode_done to fill.

        NOTE: the NaN-logit value guard cannot apply here — logits never
        leave the program, only sampled ids do.  Chaos logit poisoning
        targets the host-sampling paths (`_emit_decoded`/serial/packed);
        `_logit_poison` entries are simply not consumed on this path."""
        eng = self.eng
        with TraceAnnotation("loong.decode.wait"):
            toks = np.asarray(toks_next)
            k_rt = np.asarray(k_rt, np.float32)
            v_rt = np.asarray(v_rt, np.float32)
        with TraceAnnotation("loong.decode.sample"):
            for b, r in enumerate(g.requests):
                r.output_tokens.append(int(toks[b]))
                row = rowmap[r.rid]
                eng._pending_kv[r.rid] = (k_rt[:, row], v_rt[:, row])

    # unified: the whole fused iteration as ONE shard_map program ---------
    def _unified_spmd_program(self, tb, bb, max_len_b, mesh):
        """Jitted SPMD unified program for one (bucket tuple, mesh) —
        cached in the same merged LRU iteration cache as the prefill and
        decode programs."""
        key = ("unified_spmd", tb, bb, max_len_b, mesh)
        fn = self._program_get(key)
        if fn is None:
            import jax

            from repro.core.esp import unified_iteration_spmd

            model, impl = self.eng.model, self._unified_impl
            dbuf = self.double_buffer

            def unified_spmd_step(params, toks, positions, offsets, last_idx,
                                  k_g, v_g, tbl_g, len_g, pos_g):
                return unified_iteration_spmd(
                    mesh, model, impl, params, toks, positions, offsets,
                    last_idx, k_g, v_g, tbl_g, len_g, pos_g,
                    max_seq_len=max_len_b, double_buffer=dbuf,
                )

            fn = self._program_put(key, jax.jit(unified_spmd_step))
        return fn

    def _unified_spmd_setup(self, work, segs):
        """Assemble the SPMD unified call: returns (fn, args, inv) or None
        when the iteration cannot run SPMD (fewer than two KV-holding
        instances with distinct mirror devices).  ``inv`` maps a packed
        column to its striped row on the program's token axis.

        Exactly `_decode_spmd_setup`'s zero-copy shape: each pool's
        `device_paged_kv` view becomes data-rank i's slice of one
        mesh-sharded array; the executor ships per-TOKEN prefix block-table
        rows (tiny, striped order) and ZERO KV bytes."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P

        from repro.core import striped

        eng = self.eng
        rids = [s.r.rid for s in segs]
        limits = np.array([s.limit for s in segs], np.int64)
        infos = []
        for pool in eng.pool.pools:
            if pool.instance_id in eng.failed:
                continue
            table, lengths = pool.prefix_block_table(rids, limits)
            if lengths.any():
                infos.append((pool, table, lengths))
        if len(infos) < 2:
            return None
        mesh = self._decode_mesh(tuple(p.instance_id for p, _, _ in infos))
        if mesh is None:
            return None
        covered = np.sum([lg for _, _, lg in infos], axis=0)
        assert (covered == limits).all(), (covered, limits)
        n = len(infos)
        total = sum(s.ln for s in segs)
        tb = self._token_bucket(-(-total // n)) * n
        tokens, positions, offsets, last_idx = self._unified_pack(segs, tb)
        bb = len(last_idx)
        max_len_b = self._bucket(max(s.ln for s in segs))
        # striped layout: packed col c lives at striped row inv[c] (rank
        # c % n); block-sharding a pre-striped array hands every rank
        # exactly its stripe
        perm = striped.stripe_indices(tb, n)
        inv = striped.unstripe_indices(tb, n)
        mpb = self._bucket(max(t.shape[1] for _, t, _ in infos), lo=1)
        sh = NamedSharding(mesh, P("data"))
        kds, vds, pds = [], [], []
        tbl = np.zeros((n, tb, mpb), np.int32)
        lens = np.zeros((n, tb), np.int32)
        for i, (pool, table, lengths) in enumerate(infos):
            kd, vd, pd = pool.device_paged_kv()
            kds.append(kd[None])
            vds.append(vd[None])
            pds.append(pd[None])
            len_t = np.zeros(tb, np.int32)
            tbl_t = np.zeros((tb, table.shape[1]), np.int32)
            c = 0
            for b, s in enumerate(segs):
                tbl_t[c : c + s.ln] = table[b]
                len_t[c : c + s.ln] = lengths[b]
                c += s.ln
            tbl[i, :, : table.shape[1]] = tbl_t[perm]
            lens[i] = len_t[perm]
        assemble = jax.make_array_from_single_device_arrays
        k_g = assemble((n,) + kds[0].shape[1:], sh, kds)
        v_g = assemble((n,) + vds[0].shape[1:], sh, vds)
        pos_g = (
            assemble((n,) + pds[0].shape[1:], sh, pds)
            if eng.cfg.sliding_window else None
        )
        fn = self._unified_spmd_program(tb, bb, max_len_b, mesh)
        args = (
            self._replicated_params(mesh),
            jax.device_put(tokens[perm], sh),
            jnp.asarray(positions[perm]),
            jnp.asarray(offsets),
            jnp.asarray(inv[last_idx].astype(np.int32)),
            k_g, v_g, jax.device_put(tbl, sh), jax.device_put(lens, sh),
            pos_g,
        )
        return fn, args, inv

    def unified(self, work) -> None:
        """The whole unified iteration as ONE shard_map program
        (`core.esp.unified_iteration_spmd`): per layer, the decode-style
        paged prefix merge and the prefill-style ppermute chunk ring run
        back to back on the striped token axis, and tokens are sampled
        in-program.  Falls back to the in-process fused loop when the group
        cannot run SPMD."""
        segs = self._unified_segments(work)
        with self._unified_span(work, segs):
            with TraceAnnotation("loong.unified.pack"):
                setup = (
                    self._unified_spmd_setup(work, segs)
                    if self.spmd_decode else None
                )
            if setup is None:
                return self._unified_local(work, segs)
            fn, args, inv = setup
            self._unified_count(segs)
            eng = self.eng
            with TraceAnnotation("loong.unified.launch"):
                prev_impl = eng.model.attn_impl
                eng.model.attn_impl = self._unified_impl
                try:
                    ids, k_packed, v_packed = fn(*args)
                finally:
                    eng.model.attn_impl = prev_impl
            with TraceAnnotation("loong.unified.wait"):
                ids = np.asarray(ids)
            self._unified_emit(work, segs, None, ids, k_packed, v_packed, inv)
