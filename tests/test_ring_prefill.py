"""Ring-fused packed prefill (DoP>1 ESP groups): kernel/chunk parity with
the dense oracle across DoP x {GQA, sliding window, softcap} in interpret and
XLA modes, striped shard-offset helpers, the lazy host copy for
`fill_packed` (device-only prefill critical path, on-demand sync), the
placement-aliveness requeue guard, and an e2e engine test asserting a DoP=2
packed prefill reproduces the serial-oracle token sequence with zero
per-request serial prefill calls and zero mirror re-uploads."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import REGISTRY, reduced
from repro.core import esp, striped
from repro.engine.request import Phase, Request
from repro.engine.server import LoongServeEngine
from repro.kernels import ops
from repro.kernels import ref as kref
from repro.manager.scheduler import PrefillBatch
from repro.models import build_model

CFG = reduced(REGISTRY["lwm-7b"])


def _packed_case(seed, lens, h, kvh, d, bucket):
    rng = np.random.default_rng(seed)
    total = sum(lens)
    assert total <= bucket
    off = np.full(len(lens) + 1, total, np.int32)
    off[0] = 0
    c = 0
    for i, n in enumerate(lens):
        c += n
        off[i + 1] = c
    q = rng.normal(size=(bucket, h, d)).astype(np.float32)
    k = rng.normal(size=(bucket, kvh, d)).astype(np.float32)
    v = rng.normal(size=(bucket, kvh, d)).astype(np.float32)
    return q, k, v, off


# ------------------------------------------------------- striped helpers


def test_shard_offsets_match_bruteforce():
    """shard_offsets[b] == number of shard-local tokens with global packed
    index < seq_offsets[b], for every shard and stride."""
    off = np.array([0, 5, 6, 23, 32, 44], np.int64)
    for n in (2, 3, 4):
        for r in range(n):
            got = np.asarray(striped.shard_offsets(off, n, r))
            want = [sum(1 for g in range(o) if g % n == r) for o in off]
            np.testing.assert_array_equal(got, want)
            # per-request runs are contiguous in the shard's local order
            assert (np.diff(got) >= 0).all()


def test_ring_chunk_schedule_covers_every_chunk_once():
    """Replaying the ring_pairs ppermute schedule hands every rank every
    chunk exactly once over the ring (incl. disjoint subgroups)."""
    for n, g in [(2, None), (4, None), (8, 4)]:
        sched = striped.ring_chunk_schedule(n, g)
        gg = g or n
        assert len(sched) == gg
        for r in range(n):
            seen = [sched[s][r] for s in range(gg)]
            base = (r // gg) * gg
            assert sorted(seen) == list(range(base, base + gg)), (n, g, r)
        assert sched[0] == list(range(n))  # step 0: own chunk


# ------------------------------------------------- kernel / chunk parity


@pytest.mark.parametrize("impl", ["xla", "interpret"])
@pytest.mark.parametrize("dop", [2, 4])
@pytest.mark.parametrize("window,softcap", [(None, None), (7, None), (None, 5.0)])
def test_ring_prefill_matches_dense_oracle(impl, dop, window, softcap):
    """The full fused ring (one chunk launch per instance per ring step,
    carried (acc, m, l) state) equals the single-launch dense packed oracle
    for mixed lengths (incl. length-1) under GQA, sliding window and logit
    softcap, at DoP 2 and 4; bucket padding never leaks into real rows."""
    lens = [5, 1, 17, 9, 12]
    h, kvh, d = 4, 2, 32
    q, k, v, off = _packed_case(0, lens, h, kvh, d, bucket=64)
    total = sum(lens)
    out = np.asarray(esp.ring_packed_prefill(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), jnp.asarray(off),
        dop, window=window, softcap=softcap, max_seq_len=32, impl=impl,
        block_q=8, block_k=8,
    ))
    dense = np.asarray(kref.packed_prefill_ref(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), jnp.asarray(off),
        window=window, softcap=softcap,
    ))
    np.testing.assert_allclose(out[:total], dense[:total], atol=2e-5)


@pytest.mark.parametrize("impl", ["xla", "interpret"])
def test_ring_chunk_step_matches_chunk_oracle(impl):
    """A single ring step (one chunk folded into a non-trivial carry) equals
    the dense per-chunk oracle — validates the carried-state contract, not
    just the fully-reduced ring."""
    lens = [3, 11, 8, 2]
    n = 2
    q, k, v, off = _packed_case(1, lens, 4, 2, 16, bucket=32)
    qs = jnp.asarray(q[1::n])  # shard 1 queries
    offs = [striped.shard_offsets(off, n, r) for r in range(n)]
    carry = None
    for step, c in enumerate([1, 0]):  # own chunk, then the rotated one
        kc, vc = jnp.asarray(k[c::n]), jnp.asarray(v[c::n])
        carry = ops.prefill_ring_chunk(
            qs, kc, vc, offs[1], offs[c], carry, q_shard=1, k_shard=c,
            n_shards=n, max_seq_len=16, impl=impl, block_q=8, block_k=8,
        )
        ref_carry = kref.packed_prefill_ring_chunk_ref(
            qs, kc, vc, jnp.asarray(off),
            (jnp.zeros_like(carry[0]), jnp.full_like(carry[1], -jnp.inf),
             jnp.zeros_like(carry[2])) if step == 0 else ref_carry,
            q_shard=1, k_shard=c, n_shards=n,
        )
        for got, want in zip(carry, ref_carry):
            got, want = np.asarray(got), np.asarray(want)
            fin = np.isfinite(want)
            np.testing.assert_allclose(got[fin], want[fin], atol=2e-5)
            np.testing.assert_array_equal(np.isfinite(got), fin)


@pytest.mark.parametrize("block_q,block_k", [(16, 32), (32, 16), (64, 64),
                                             (None, None)])
def test_ring_chunk_tiles_match_chunk_oracle(block_q, block_k):
    """Both ring steps of shard 1 of a DoP-2 group, at non-square tiles,
    tiles halved on a 3*2^j shard (64 on 96 -> 32) and the chosen tiles,
    with segment boundaries inside tiles, equal the dense chunk oracle and
    the banded XLA fallback."""
    lens = [5, 1, 17, 9, 12, 30, 40, 20]  # 134 tokens, bucket 192
    n = 2
    q, k, v, off = _packed_case(5, lens, 4, 2, 32, bucket=192)
    qs = jnp.asarray(q[1::n])  # shard 1 queries: 96 local slots
    offs = [striped.shard_offsets(off, n, r) for r in range(n)]
    carry = ref_carry = banded = None
    for c in (1, 0):  # own chunk, then the rotated one
        kc, vc = jnp.asarray(k[c::n]), jnp.asarray(v[c::n])
        kw = dict(q_shard=1, k_shard=c, n_shards=n)
        carry = ops.prefill_ring_chunk(
            qs, kc, vc, offs[1], offs[c], carry, impl="interpret",
            block_q=block_q, block_k=block_k, **kw)
        banded = ops.prefill_ring_chunk(
            qs, kc, vc, offs[1], offs[c], banded, impl="xla", block_q=8,
            max_seq_len=max(lens), **kw)
        ref_carry = kref.packed_prefill_ring_chunk_ref(
            qs, kc, vc, jnp.asarray(off),
            (jnp.zeros_like(carry[0]), jnp.full_like(carry[1], -jnp.inf),
             jnp.zeros_like(carry[2])) if ref_carry is None else ref_carry,
            **kw)
        for got, want, alt in zip(carry, ref_carry, banded):
            got, want, alt = (np.asarray(x) for x in (got, want, alt))
            fin = np.isfinite(want)
            np.testing.assert_allclose(got[fin], want[fin], atol=2e-5)
            np.testing.assert_allclose(got[fin], alt[fin], atol=2e-5)
            np.testing.assert_array_equal(np.isfinite(got), fin)


def test_ring_banded_fallback_band_widths():
    """The banded XLA chunk fallback equals the dense chunk oracle for every
    static reach bound, including bands narrower than the shard axis."""
    lens = [3, 11, 8, 2]
    n = 4
    q, k, v, off = _packed_case(2, lens, 4, 2, 16, bucket=32)
    offs = [striped.shard_offsets(off, n, r) for r in range(n)]
    empty = (
        jnp.zeros((32 // n, 4, 16), jnp.float32),
        jnp.full((32 // n, 4), -jnp.inf, jnp.float32),
        jnp.zeros((32 // n, 4), jnp.float32),
    )
    for r, c in [(0, 3), (2, 1), (3, 3)]:
        want = kref.packed_prefill_ring_chunk_ref(
            jnp.asarray(q[r::n]), jnp.asarray(k[c::n]), jnp.asarray(v[c::n]),
            jnp.asarray(off), empty, q_shard=r, k_shard=c, n_shards=n,
        )
        for max_len in (11, 16, 32, None):
            got = kref.packed_prefill_ring_chunk_banded(
                jnp.asarray(q[r::n]), jnp.asarray(k[c::n]),
                jnp.asarray(v[c::n]), offs[r], offs[c], empty,
                q_shard=r, k_shard=c, n_shards=n, block_q=4,
                max_seq_len=max_len,
            )
            for g, w in zip(got, want):
                g, w = np.asarray(g), np.asarray(w)
                fin = np.isfinite(w)
                np.testing.assert_allclose(g[fin], w[fin], atol=2e-5)


# --------------------------------------------------------- engine / e2e


def _prefill_batch(eng, rng, lengths, rid0=0, max_new=8):
    n_inst = len(eng.pool.pools)
    reqs, placement = [], {}
    for j, ln in enumerate(lengths):
        n = int(ln)
        r = Request(input_len=n, max_new_tokens=max_new,
                    prompt=rng.integers(0, eng.cfg.vocab_size, n).tolist())
        r.rid, r.phase = rid0 + j, Phase.PREFILL
        plan = eng.pool.plan_placement(r.rid, list(range(n)), range(n_inst))
        eng.pool.place(plan)
        placement[r.rid] = plan.assignment
        reqs.append(r)
    return PrefillBatch(reqs, list(range(n_inst)),
                        scale_down_to=list(range(n_inst)),
                        placement=placement)


def test_engine_dop2_prefill_serial_oracle_zero_reupload():
    """e2e: a DoP=2 packed prefill batch runs ZERO per-request serial
    model.prefill calls (dispatch counters), dispatches the ring-chunk
    kernel, reproduces the serial-oracle token sequence through decode, and
    uploads ZERO mirror slots for the prefill KV (write-through + lazy host
    copy: the critical path is device-only)."""
    model = build_model(CFG)
    params = model.init(jax.random.PRNGKey(0))
    eng = LoongServeEngine(CFG, 2, 4000, store_values=True, model=model,
                           params=params, page_size=16)
    rng = np.random.default_rng(23)
    # pre-create the mirrors so creation uploads don't mask the invariant
    for pool in eng.pool.pools:
        pool.device_kv()
        pool.mirror_uploaded_slots = 0
        pool.mirror_full_syncs = 0
    batch = _prefill_batch(eng, rng, [33, 17, 50, 8], max_new=4)
    reqs = list(batch.requests)
    ops.reset_dispatch_counts()
    eng._on_prefill_done(batch)  # runs the DoP=2 packed prefill + transitions
    assert ops.dispatch_counts.get("prefill_serial_model", 0) == 0
    assert ops.dispatch_counts["prefill_ring_chunk"] == 4  # dop^2 per step
    assert any(key[3] == 2 for key in eng._prefill_programs)  # a DoP=2 program
    for pool in eng.pool.pools:
        assert pool.mirror_uploaded_slots == 0  # prefill KV: zero re-upload
        assert pool.mirror_full_syncs == 0
        assert pool.dirty_slot_count() == 0
        assert pool.host_syncs == 0  # critical path stayed device-only
    # drive decode to completion (join event is a no-op that kicks the
    # scheduler's _try_schedule loop)
    eng._push(eng.clock, "join", 0)
    m = eng.run()
    assert len(m.finished) == len(reqs)
    assert ops.dispatch_counts.get("prefill_serial_model", 0) == 0
    # token parity: packed DoP=2 prefill + paged decode == serial oracle
    for r in reqs:
        toks = jnp.asarray(np.asarray(r.prompt)[None], jnp.int32)
        logits, cache = model.prefill(params, {"tokens": toks})
        nxt = int(np.argmax(np.asarray(logits[0, -1])))
        out = [nxt]
        S = r.input_len + 8
        k_pad = jnp.zeros((cache.k.shape[0], 1, S) + cache.k.shape[3:],
                          cache.k.dtype).at[:, :, : r.input_len].set(cache.k)
        v_pad = jnp.zeros_like(k_pad).at[:, :, : r.input_len].set(cache.v)
        cache = cache._replace(k=k_pad, v=v_pad)
        for _ in range(3):
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
        assert out == r.output_tokens, (r.rid, out, r.output_tokens)


def test_checkpoint_forces_lazy_host_sync():
    """state_dict snapshots the host copy, so checkpointing after a packed
    prefill must force the deferred device->host download first."""
    model = build_model(CFG)
    params = model.init(jax.random.PRNGKey(0))
    eng = LoongServeEngine(CFG, 2, 1024, store_values=True, model=model,
                           params=params, page_size=16)
    rng = np.random.default_rng(29)
    batch = _prefill_batch(eng, rng, [21, 42])
    eng._real_prefill(batch)
    pool = eng.pool.pools[0]
    assert pool.stale_host_slot_count() > 0 and pool.host_syncs == 0
    pool.state_dict()
    assert pool.stale_host_slot_count() == 0 and pool.host_syncs == 1
    kd, _, _ = pool.device_kv()
    np.testing.assert_allclose(np.asarray(kd), pool.k, atol=1e-6)


def test_full_mirror_resync_preserves_stale_fill_packed_kv():
    """A forced FULL mirror resync (host-write burst tripping the dirty
    tracker) must pull stale fill_packed slots down to the host first —
    otherwise the resync would overwrite the mirror's packed-prefill KV
    with never-synced host data and decode would attend over garbage."""
    model = build_model(CFG)
    params = model.init(jax.random.PRNGKey(0))
    eng = LoongServeEngine(CFG, 1, 512, store_values=True, model=model,
                           params=params, page_size=16)
    rng = np.random.default_rng(37)
    batch = _prefill_batch(eng, rng, [30, 45])
    eng._real_prefill(batch)
    pool = eng.pool.pools[0]
    assert pool.stale_host_slot_count() > 0
    kd_before, _, _ = pool.device_kv()
    ref = {r.rid: np.asarray(kd_before[:, pool.slots_of(r.rid)])
           for r in batch.requests}
    # host-write burst > capacity/4 on ANOTHER request -> _dirty_full
    n_burst = pool.capacity // 4 + 16
    kb = rng.normal(size=(pool.n_attn, n_burst, CFG.n_kv_heads,
                          CFG.head_dim)).astype(np.float32)
    pool.write(999, list(range(10_000, 10_000 + n_burst)), kb, kb)
    assert pool.dirty_slot_count() == pool.capacity  # full resync pending
    kd, _, _ = pool.device_kv()
    for r in batch.requests:  # packed KV survived the full resync
        np.testing.assert_allclose(
            np.asarray(kd[:, pool.slots_of(r.rid)]), ref[r.rid], atol=1e-6
        )


def test_prefill_done_requeues_requests_with_lost_placement():
    """A request whose reserved placement references a failed instance must
    be requeued for recompute instead of silently scattering a partial KV —
    the guard that backstops the epoch stamp when it is unavailable (e.g.
    after a checkpoint restore dropped the launch-time state)."""
    model = build_model(CFG)
    params = model.init(jax.random.PRNGKey(0))
    eng = LoongServeEngine(CFG, 3, 4000, store_values=True, model=model,
                           params=params, page_size=8)
    rng = np.random.default_rng(31)
    batch = _prefill_batch(eng, rng, [20, 30, 25])
    victim = next(
        i for i in range(3)
        if any(batch.placement[r.rid].get(i) for r in batch.requests)
    )
    lost = [r for r in batch.requests
            if batch.placement[r.rid].get(victim)]
    kept = [r for r in batch.requests if r not in lost]
    # simulate the post-restore scenario: the instance is failed but the
    # requeue bookkeeping (and the epoch stamp) was lost with the checkpoint
    eng.failed.add(victim)
    eng.busy_until[victim] = float("inf")
    eng._on_prefill_done(batch)
    for r in lost:
        assert r.phase is Phase.PENDING
        assert r in eng.pending
        assert eng.pool.request_tokens(r.rid) == 0  # reservation freed
    for r in kept:
        assert r.phase is Phase.DECODE
        assert len(r.output_tokens) == 1
