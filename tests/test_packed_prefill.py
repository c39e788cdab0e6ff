"""Packed ragged prefill: numerical parity with the per-request reference
(mixed lengths, GQA, sliding window, softcap), model-level packed vs serial
prefill equivalence, pool `fill_packed` write-through (zero mirror re-upload
before the first decode), bucketed compile counts, and the failure-path
satellites (graceful in-flight decode on instance failure, duplicate-free
KV placement order)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import REGISTRY, reduced
from repro.engine.request import Phase, Request
from repro.engine.server import LoongServeEngine
from repro.kernels import ops
from repro.manager.scheduler import DecodeBatch, PrefillBatch
from repro.models import attention as A
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


@pytest.mark.parametrize("impl", ["xla", "interpret"])
@pytest.mark.parametrize("window,softcap", [(None, None), (7, None), (None, 5.0)])
def test_packed_prefill_matches_per_request_reference(impl, window, softcap):
    """One packed ragged launch == per-request full_attention on every
    segment, for mixed lengths (incl. length-1) under GQA, sliding window
    and logit softcap; bucket padding rows never leak into real rows."""
    lens = [5, 1, 17, 9, 12]
    h, kvh, d = 4, 2, 32
    q, k, v, off = _packed_case(0, lens, h, kvh, d, bucket=64)
    kw = dict(block_q=16, block_k=16)
    if impl == "xla":
        kw["max_seq_len"] = 32  # force a banded (not full-reach) fallback
    out = np.asarray(ops.prefill_packed(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), jnp.asarray(off),
        window=window, softcap=softcap, impl=impl, **kw,
    ))
    c = 0
    for n in lens:
        ref = np.asarray(A.full_attention(
            jnp.asarray(q[None, c : c + n]), jnp.asarray(k[None, c : c + n]),
            jnp.asarray(v[None, c : c + n]), causal=True, window=window,
            softcap=softcap,
        ))[0]
        np.testing.assert_allclose(out[c : c + n], ref, atol=2e-5)
        c += n


def test_banded_fallback_matches_dense_oracle():
    """The production banded XLA fallback equals the O(T^2) dense oracle for
    every band width, including bands narrower than the packed axis."""
    from repro.kernels import ref as kref

    lens = [3, 11, 8, 2]
    q, k, v, off = _packed_case(1, lens, 4, 2, 16, bucket=32)
    dense = np.asarray(kref.packed_prefill_ref(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), jnp.asarray(off),
    ))
    for max_len in (11, 16, 32, None):
        banded = np.asarray(kref.packed_prefill_banded(
            jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), jnp.asarray(off),
            block_q=8, max_seq_len=max_len,
        ))
        np.testing.assert_allclose(banded, dense, atol=2e-5)


def _grid(fn, *args):
    """The grid of the one pallas_call in ``fn``'s jaxpr."""
    (eqn,) = [e for e in jax.make_jaxpr(fn)(*args).jaxpr.eqns
              if e.primitive.name == "pallas_call"]
    return eqn.params["grid_mapping"].grid


# (t, q_per_kv, d): glm4-9b's 16-head groups, lwm-7b/qwen1.5-4b's MHA, and
# the 3*2^j buckets whose tiles halve to a divisor
CHOOSER_CASES = [(t, g, 128) for t in (16, 96, 1536, 2048, 4096, 12288, 16384)
                 for g in (16, 1)] + [(16384, 8, 128), (3072, 4, 64)]


@pytest.mark.parametrize("t,q_per_kv,d", CHOOSER_CASES)
@pytest.mark.parametrize("carry", [False, True])
def test_choose_tiles_divide_and_fit(t, q_per_kv, d, carry):
    """The chosen tiles divide the token axis and their reckoned VMEM fits
    the budget; an MHA group (one head) gets a tile at least as large as
    the GQA group's at the same length."""
    from repro.kernels import paged_flash_prefill as pfp

    h = 32
    bq, bk = pfp.choose_tiles(t, q_per_kv=q_per_kv, d=d, h=h, carry=carry,
                              itemsize=2)
    assert t % bq == 0 and t % bk == 0, (bq, bk)
    assert pfp.tile_vmem_bytes(bq, bk, q_per_kv=q_per_kv, d=d, h=h,
                               carry=carry, itemsize=2) <= pfp.VMEM_BUDGET
    mq, mk = pfp.choose_tiles(t, q_per_kv=1, d=d, h=h, carry=carry,
                              itemsize=2)
    assert mq >= bq and mk >= bk, ((mq, mk), (bq, bk))


def test_explicit_tile_wins():
    """An explicit block_q/block_k sets the grid (halved to a divisor of
    the bucket); None takes the chooser's tiles."""
    from repro.kernels import paged_flash_prefill as pfp

    q, k, v, off = (jnp.asarray(x) for x in _packed_case(0, [40, 50], 4, 2,
                                                         32, bucket=96))
    grid = lambda **kw: _grid(  # noqa: E731
        lambda q, k, v, o: ops.prefill_packed(q, k, v, o, impl="interpret",
                                              **kw), q, k, v, off)
    assert grid(block_q=16, block_k=32) == (6, 2, 3)
    assert grid(block_q=64, block_k=64) == (3, 2, 3)  # 64 halves to 32
    bq, bk = pfp.choose_tiles(96, q_per_kv=2, d=32, h=4, carry=False,
                              itemsize=4)
    assert grid() == (96 // bq, 2, 96 // bk)
    assert grid(block_k=16) == (96 // bq, 2, 6)


@pytest.mark.parametrize("block_q,block_k", [(16, 32), (32, 16), (64, 64),
                                             (None, None)])
def test_packed_prefill_tiles_match_banded(block_q, block_k):
    """Non-square tiles, tiles halved on a 3*2^j bucket (64 on 96 -> 32)
    and the chosen tiles, with segment boundaries inside tiles, equal the
    banded XLA fallback and the dense oracle."""
    from repro.kernels import ref as kref

    lens = [5, 1, 17, 9, 12, 30]  # boundaries 5, 6, 23, 32, 44, 74
    q, k, v, off = (jnp.asarray(x) for x in _packed_case(4, lens, 4, 2, 32,
                                                         bucket=96))
    total = sum(lens)
    out = np.asarray(ops.prefill_packed(q, k, v, off, impl="interpret",
                                        block_q=block_q, block_k=block_k))
    banded = np.asarray(kref.packed_prefill_banded(
        q, k, v, off, block_q=8, max_seq_len=max(lens)))
    dense = np.asarray(kref.packed_prefill_ref(q, k, v, off))
    np.testing.assert_allclose(out[:total], banded[:total], atol=2e-5)
    np.testing.assert_allclose(out[:total], dense[:total], atol=2e-5)


def test_model_prefill_packed_matches_serial_prefill():
    """Model-level: one packed step reproduces per-request model.prefill —
    last-token logits AND the packed per-layer KV output."""
    from repro.core.paged_prefill import PackedPrefillAttnImpl

    model = build_model(CFG)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(3)
    lens = [19, 7, 33]
    prompts = [rng.integers(0, CFG.vocab_size, n).tolist() for n in lens]
    total = sum(lens)
    bucket = 64
    tokens = np.zeros(bucket, np.int32)
    positions = np.zeros(bucket, np.int32)
    off = np.full(len(lens) + 1, total, np.int32)
    off[0] = 0
    last = np.zeros(len(lens), np.int32)
    c = 0
    for i, p in enumerate(prompts):
        tokens[c : c + lens[i]] = p
        positions[c : c + lens[i]] = np.arange(lens[i])
        c += lens[i]
        off[i + 1] = c
        last[i] = c - 1
    impl = PackedPrefillAttnImpl()
    prev = model.attn_impl
    model.attn_impl = impl
    impl.begin_step(jnp.asarray(off), max_seq_len=64)
    try:
        logits, (kp, vp) = model.prefill_packed(
            params, {"tokens": jnp.asarray(tokens)[None]},
            jnp.asarray(positions), jnp.asarray(last),
        )
    finally:
        impl.end_step()
        model.attn_impl = prev
    logits = np.asarray(logits)
    kp, vp = np.asarray(kp), np.asarray(vp)
    c = 0
    for i, p in enumerate(prompts):
        ref_logits, cache = model.prefill(
            params, {"tokens": jnp.asarray(np.asarray(p, np.int32)[None])}
        )
        np.testing.assert_allclose(
            logits[i], np.asarray(ref_logits[0, -1]), atol=1e-4
        )
        np.testing.assert_allclose(
            kp[:, c : c + lens[i]], np.asarray(cache.k[:, 0]), atol=1e-4
        )
        np.testing.assert_allclose(
            vp[:, c : c + lens[i]], np.asarray(cache.v[:, 0]), atol=1e-4
        )
        c += lens[i]


def _prefill_batch(eng, rng, lengths, rid0=0):
    """Reserve striped placement + build a PrefillBatch, as the scheduler's
    proactive scale-down does before prefill executes."""
    n_inst = len(eng.pool.pools)
    reqs, placement = [], {}
    for j, ln in enumerate(lengths):
        n = int(ln)
        r = Request(input_len=n, max_new_tokens=8,
                    prompt=rng.integers(0, eng.cfg.vocab_size, n).tolist())
        r.rid, r.phase = rid0 + j, Phase.PREFILL
        plan = eng.pool.plan_placement(r.rid, list(range(n)), range(n_inst))
        eng.pool.place(plan)
        placement[r.rid] = plan.assignment
        reqs.append(r)
    return PrefillBatch(reqs, list(range(n_inst)),
                        scale_down_to=list(range(n_inst)),
                        placement=placement)


def test_fill_packed_write_through_zero_reupload():
    """After a packed prefill NO slot is dirty and the first decode-style
    mirror sync uploads ZERO slots — the write-through already updated the
    device mirror in place.  The host management copy is LAZY: the prefill
    critical path downloads nothing (slots stale, host_syncs == 0); the
    first management-plane read (gather) pulls them from the mirror once."""
    model = build_model(CFG)
    params = model.init(jax.random.PRNGKey(0))
    eng = LoongServeEngine(CFG, 2, 1024, store_values=True, model=model,
                           params=params, page_size=16)
    rng = np.random.default_rng(5)
    batch = _prefill_batch(eng, rng, [24, 61, 9, 40])
    eng._real_prefill(batch)
    for pool in eng.pool.pools:
        # dirty-tracking counters: nothing pending for the next sync
        assert pool.dirty_slot_count() == 0
        # lazy host copy: the critical path downloaded nothing
        assert pool.stale_host_slot_count() > 0
        assert pool.host_syncs == 0
        uploads_before = pool.mirror_uploaded_slots
        fulls_before = pool.mirror_full_syncs
        kd, vd, pd = pool.device_kv()  # first decode iteration's sync
        assert pool.mirror_uploaded_slots == uploads_before
        assert pool.mirror_full_syncs == fulls_before
        np.testing.assert_array_equal(np.asarray(pd), pool.slot_pos)
    # host copy materializes each request's prefill KV on demand (gather)
    for r in batch.requests:
        pos, k, _ = eng.pool.gather_request(r.rid)
        assert len(pos) == r.input_len
        assert float(np.abs(k).sum()) > 0.0
    for pool in eng.pool.pools:
        assert pool.host_syncs == 1  # one forced sync, then clean
        assert pool.stale_host_slot_count() == 0
        kd, vd, pd = pool.device_kv()
        # the mirror and the (now synced) host management copy agree
        np.testing.assert_allclose(np.asarray(kd), pool.k, atol=1e-6)
        np.testing.assert_allclose(np.asarray(vd), pool.v, atol=1e-6)


def test_engine_end_to_end_packed_prefill_matches_oracle():
    """Real-mode engine with simultaneous arrivals (a true multi-request
    packed batch): exactly one packed program compiles per bucket shape, the
    packed kernel is dispatched, and generated tokens match the per-request
    dense oracle."""
    model = build_model(CFG)
    params = model.init(jax.random.PRNGKey(0))
    eng = LoongServeEngine(CFG, 2, 4000, store_values=True, model=model,
                           params=params, page_size=16)
    rng = np.random.default_rng(7)
    reqs = []
    for _ in range(4):
        ln = int(rng.integers(16, 80))
        r = Request(input_len=ln, max_new_tokens=4, arrival=0.0,
                    prompt=rng.integers(0, CFG.vocab_size, ln).tolist())
        reqs.append(r)
        eng.submit(r)
    ops.reset_dispatch_counts()
    m = eng.run()
    assert len(m.finished) == len(reqs)
    assert m.scaling_migration_bytes == 0
    assert ops.dispatch_counts["prefill_packed"] > 0  # traced packed kernel
    assert len(eng._prefill_programs) >= 1
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


def test_inflight_instance_failure_graceful_real_decode():
    """A fail_instance landing between a decode launch and its decode_done
    must not trip the KV-coverage assert: affected requests are re-queued
    for recompute (emitted tokens folded into the prompt) and every request
    still finishes."""
    model = build_model(CFG)
    params = model.init(jax.random.PRNGKey(0))
    eng = LoongServeEngine(CFG, 3, 4000, store_values=True, model=model,
                           params=params, page_size=8)
    rng = np.random.default_rng(11)
    reqs = []
    for _ in range(5):
        ln = int(rng.integers(16, 64))
        r = Request(input_len=ln, max_new_tokens=5, arrival=0.0,
                    prompt=rng.integers(0, CFG.vocab_size, ln).tolist())
        reqs.append(r)
        eng.submit(r)
    # step events until a decode iteration is in flight, then fail one of
    # its instances NOW (clock < the pending decode_done's timestamp)
    guard = 0
    while not any(e[2] == "decode_done" for e in eng.events):
        assert eng.events and guard < 500, "no decode launched"
        eng.run(max_events=1)
        guard += 1
    g = next(e[3] for e in eng.events if e[2] == "decode_done")
    victim = next(
        i for i in g.instances
        if any(eng.pool.pools[i].tokens_of(r.rid) for r in g.requests)
    )
    eng.fail_instance(victim)
    m = eng.run()
    assert len(m.finished) == len(reqs)
    assert all(r.generated >= r.max_new_tokens for r in reqs)
    assert any(r.n_evictions > 0 for r in reqs)  # somebody was requeued


def test_inflight_instance_failure_graceful_real_prefill():
    """A fail_instance landing between a prefill launch and its prefill_done
    must not crash the packed KV scatter (the requeued requests' reserved
    slots are gone): stale requests are dropped from the batch and every
    request still finishes via recompute."""
    model = build_model(CFG)
    params = model.init(jax.random.PRNGKey(0))
    eng = LoongServeEngine(CFG, 3, 4000, store_values=True, model=model,
                           params=params, page_size=8)
    rng = np.random.default_rng(13)
    reqs = []
    for _ in range(4):
        ln = int(rng.integers(24, 64))
        r = Request(input_len=ln, max_new_tokens=3, arrival=0.0,
                    prompt=rng.integers(0, CFG.vocab_size, ln).tolist())
        reqs.append(r)
        eng.submit(r)
    guard = 0
    while not any(e[2] == "prefill_done" for e in eng.events):
        assert eng.events and guard < 500, "no prefill launched"
        eng.run(max_events=1)
        guard += 1
    b = next(e[3] for e in eng.events if e[2] == "prefill_done")
    victim = next(
        i for i in range(3)
        if any(eng.pool.pools[i].tokens_of(r.rid) for r in b.requests)
    )
    eng.fail_instance(victim)
    m = eng.run()
    assert len(m.finished) == len(reqs)
    assert any(r.n_evictions > 0 for r in reqs)


def test_stale_decode_done_after_recompute_is_skipped():
    """A decode_done whose request was requeued by a failure AND already
    recomputed into a fresh group (phase back to DECODE, seq_len moved past
    the launch-time stamp) must be ignored — processing it would emit a
    duplicate token and double-allocate the same KV position."""
    eng = LoongServeEngine(CFG, 2, 1000)
    r = Request(input_len=8, max_new_tokens=4)
    r.phase = Phase.DECODE
    r.generated = 1
    g = DecodeBatch([r], [0], {r.rid: 0})
    eng._decode_launch_seq[id(g)] = {r.rid: r.seq_len}  # as _execute_plan does
    # in-flight failure: requeue folds the emitted token into the prompt...
    eng._requeue_for_recompute(r)
    assert r.seq_len == 9 and r.generated == 0
    # ...and the recompute prefill completes before the stale decode_done
    r.phase = Phase.DECODE
    r.generated = 1  # prefill_done's first-token emission -> seq moved to 10
    eng._on_decode_done(g)
    assert r.generated == 1  # NOT bumped by the stale completion
    assert eng.pool.request_tokens(r.rid) == 0  # no KV allocated by it
    # control: a matching stamp processes normally
    g2 = DecodeBatch([r], [0], {r.rid: 0})
    eng._decode_launch_seq[id(g2)] = {r.rid: r.seq_len}
    eng._on_decode_done(g2)
    assert r.generated == 2
    assert eng.pool.request_tokens(r.rid) == 1


def test_placement_order_master_first_no_duplicates():
    """KV-append probe order: master first, then the group, then other live
    instances — each exactly once, even when the rid is missing from
    `g.masters` (regression: g.instances[0] used to appear twice) and with
    failed instances excluded."""
    eng = LoongServeEngine(CFG, 5, 1000)
    r = Request(input_len=4, max_new_tokens=2)
    g = DecodeBatch([r], instances=[2, 0, 3], masters={})  # rid missing
    order = eng._placement_order(r, g)
    assert order[0] == 2  # default master = g.instances[0]
    assert sorted(order) == [0, 1, 2, 3, 4]  # every instance exactly once
    assert order[:3] == [2, 0, 3]  # group preference preserved
    g2 = DecodeBatch([r], instances=[2, 0, 3], masters={r.rid: 3})
    order2 = eng._placement_order(r, g2)
    assert order2[0] == 3 and sorted(order2) == [0, 1, 2, 3, 4]
    eng.failed.add(0)
    assert 0 not in eng._placement_order(r, g2)
