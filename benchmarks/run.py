"""Benchmark harness: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows; the `derived` column carries
the figure's headline quantity (speedups, error percentages, overheads).

  PYTHONPATH=src python -m benchmarks.run [--quick] [--only NAME]

``--collate`` instead merges every committed ``BENCH_*.json`` artifact into
one ``BENCH_trajectory.json`` — per-path tok/s, per-iteration collective
bytes and speedup/ratio headlines, keyed by bench and git commit — so the
perf history over PRs reads from one file instead of scattered per-PR
artifacts (run by the CI smoke step)."""
from __future__ import annotations

import argparse
import sys
import time


def _row(name: str, us: float, derived: str) -> None:
    print(f"{name},{us:.1f},{derived}")
    sys.stdout.flush()


# ---------------------------------------------------------------- Fig. 2


def bench_scalability(quick: bool = False):
    """Fig. 2: prefill scales with DoP; decode scales sub-linearly."""
    from repro.configs import get_config
    from repro.manager.sib import SIB

    sib = SIB(get_config("lwm-7b"))
    t0 = time.perf_counter()
    rows = []
    for length in [1_000, 100_000]:
        t1 = sib.prefill_time(1, [length])
        t8 = sib.prefill_time(8, [length])
        rows.append(f"prefill{length//1000}k:{t1/t8:.2f}x@dop8")
    d1 = sib.decode_time(1, 32, 64_000)
    d8 = sib.decode_time(8, 32, 64_000)
    rows.append(f"decode:{d1/d8:.2f}x@dop8")
    ratio = sib.prefill_time(1, [100_000]) / sib.prefill_time(1, [1_000])
    rows.append(f"100k/1k:{ratio:.0f}x")
    us = (time.perf_counter() - t0) * 1e6
    _row("fig2_scalability", us, ";".join(rows))


# ---------------------------------------------------------------- Fig. 10


def bench_end_to_end(quick: bool = False):
    """Fig. 10: latency under load, 4 workloads × 4 systems (SIB clock)."""
    import copy

    from repro.configs import get_config
    from repro.data import poisson_workload
    from repro.launch.serve import build_engine

    cfg = get_config("lwm-7b")
    n = 40 if quick else 80
    for ds, rate in [("sharegpt", 4.0), ("leval", 0.5), ("lveval", 0.15),
                     ("mixed", 0.5)]:
        reqs = poisson_workload(ds, n, rate, seed=7)
        res = {}
        t0 = time.perf_counter()
        for name in ["loongserve", "vllm-tp", "chunked", "pd-disagg"]:
            eng = build_engine(name, cfg, 8, 250_000)
            for r in copy.deepcopy(reqs):
                eng.submit(r)
            res[name] = eng.run().summary().get("norm_e2e_mean", float("nan"))
        us = (time.perf_counter() - t0) * 1e6
        ls = res["loongserve"]
        derived = ";".join(
            f"vs_{k}:{v/ls:.2f}x" for k, v in res.items() if k != "loongserve"
        )
        _row(f"fig10_e2e_{ds}", us, derived)


# ---------------------------------------------------------------- Fig. 11


def bench_multinode(quick: bool = False):
    """Fig. 11: 16-instance (2-node) scaling on the Mixed workload."""
    import copy

    from repro.configs import get_config
    from repro.data import poisson_workload
    from repro.launch.serve import build_engine

    cfg = get_config("lwm-7b")
    n = 40 if quick else 80
    reqs = poisson_workload("mixed", n, 0.8, seed=17)
    t0 = time.perf_counter()
    res = {}
    for name in ["loongserve", "vllm-tp", "chunked"]:
        eng = build_engine(name, cfg, 16, 250_000)
        for r in copy.deepcopy(reqs):
            eng.submit(r)
        res[name] = eng.run().summary().get("norm_e2e_mean", float("nan"))
    us = (time.perf_counter() - t0) * 1e6
    ls = res["loongserve"]
    _row(
        "fig11_multinode", us,
        ";".join(f"vs_{k}:{v/ls:.2f}x" for k, v in res.items() if k != "loongserve"),
    )


# ---------------------------------------------------------------- Fig. 12


def bench_goodput_zipf(quick: bool = False):
    """Fig. 12: P90 goodput under Zipf length distributions, ESP vs
    static-SP vs replication ablations."""
    import copy

    from repro.baselines import FixedGroupsEngine, StaticTPEngine
    from repro.configs import get_config
    from repro.data import zipf_workload
    from repro.engine.server import LoongServeEngine

    cfg = get_config("lwm-7b")
    n = 40 if quick else 100
    for a in ([1.2] if quick else [0.9, 1.2, 1.5]):
        # load high enough that static strategies saturate (paper Fig. 12)
        reqs = zipf_workload(n, zipf_a=a, rate=2.0, seed=13)
        t0 = time.perf_counter()
        res = {}
        for name, ctor in [
            ("esp", lambda: LoongServeEngine(cfg, 8, 120_000)),
            ("static_sp", lambda: StaticTPEngine(cfg, 8, 120_000)),
            ("replicated", lambda: FixedGroupsEngine(
                cfg, 8, 120_000, groups=[[i] for i in range(8)])),
        ]:
            eng = ctor()
            for r in copy.deepcopy(reqs):
                eng.submit(r)
            m = eng.run()
            fin = [r for r in m.finished if r.finish_time is not None]
            lat = sorted(
                r.norm_e2e_latency() for r in fin if r.norm_e2e_latency()
            )
            if not lat:
                res[name] = 0.0
                continue
            slo = (lat[len(lat) // 2] or 1e-6) * 25  # paper: 25x light-load
            good = [r for r in fin if (r.norm_e2e_latency() or 9e9) <= slo]
            span = max(r.finish_time for r in fin) - min(r.arrival for r in fin)
            res[name] = sum(r.seq_len for r in good) / max(span, 1e-9)
        us = (time.perf_counter() - t0) * 1e6
        esp = res["esp"]
        _row(
            f"fig12_goodput_zipf{a}", us,
            ";".join(
                f"vs_{k}:{esp/max(v,1e-9):.2f}x" for k, v in res.items() if k != "esp"
            ),
        )


# ---------------------------------------------------------------- Fig. 13


def bench_scaling_overhead(quick: bool = False):
    """Fig. 13: overhead of scale-down (proactive) and scale-up
    (multi-master) measured on REAL CPU compute with a reduced model."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import REGISTRY, reduced
    from repro.models import attention as A
    from repro.models import build_model

    cfg = reduced(REGISTRY["lwm-7b"])
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    b, t = (1, 128) if quick else (2, 256)
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (b, t)), jnp.int32)

    # scale-DOWN: prefill with vs without proactive retention writes (the
    # retention reuses tensors the ring already produced — host pool writes)
    pre = jax.jit(lambda p, tk: model.prefill(p, {"tokens": tk}))
    pre(params, toks)[0].block_until_ready()
    # baseline: prefill + store full KV into ONE pool (every system stores KV)
    t0 = time.perf_counter()
    for _ in range(5):
        logits, cache = pre(params, toks)
        k = np.asarray(cache.k[:, 0])
        v = np.asarray(cache.v[:, 0])
    base = (time.perf_counter() - t0) / 5
    # proactive scale-down: same prefill, KV retained SPLIT across two target
    # pools per the placement plan (the ring already delivered every stripe)
    t0 = time.perf_counter()
    for _ in range(5):
        logits, cache = pre(params, toks)
        k = np.asarray(cache.k[:, 0])
        v = np.asarray(cache.v[:, 0])
        _ = (k[:, ::2], v[:, ::2], k[:, 1::2], v[:, 1::2])
    with_scale = (time.perf_counter() - t0) / 5
    down_ovh = (with_scale - base) / base * 100

    # scale-UP: decode partials across 1 -> 2 shards (multi-master combine)
    q = jnp.asarray(rng.normal(size=(b, 1, cfg.n_heads, cfg.head_dim)), jnp.float32)
    kc = jnp.asarray(rng.normal(size=(b, t, cfg.n_kv_heads, cfg.head_dim)), jnp.float32)
    vc = jnp.asarray(rng.normal(size=(b, t, cfg.n_kv_heads, cfg.head_dim)), jnp.float32)
    lens = jnp.full((b,), t, jnp.int32)
    one = jax.jit(
        lambda q, k, v: A.finalize_partial(A.partial_attention(q, k, v, None))
    )
    one(q, kc, vc).block_until_ready()
    t0 = time.perf_counter()
    for _ in range(10):
        one(q, kc, vc).block_until_ready()
    t_one = (time.perf_counter() - t0) / 10

    def two(q, k, v):  # same math split over 2 shards + LSE combine
        h = t // 2
        p1 = A.partial_attention(q, k[:, :h], v[:, :h], None)
        p2 = A.partial_attention(q, k[:, h:], v[:, h:], None)
        return A.finalize_partial(A.merge_partial(p1, p2))

    two_j = jax.jit(two)
    two_j(q, kc, vc).block_until_ready()
    t0 = time.perf_counter()
    for _ in range(10):
        two_j(q, kc, vc).block_until_ready()
    t_two = (time.perf_counter() - t0) / 10
    up_ovh = (t_two - t_one) / t_one * 100
    _row(
        "fig13_scaling_overhead", base * 1e6,
        f"scale_down_ovh:{down_ovh:.1f}%;scale_up_ovh:{up_ovh:.1f}%",
    )


# ---------------------------------------------------------------- Fig. 14


def bench_analytical_model(quick: bool = False):
    """Fig. 14: least-squares analytical model accuracy on REAL measured CPU
    prefill times of the reduced model."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import REGISTRY, reduced
    from repro.manager.sib import SIB
    from repro.models import build_model

    cfg = reduced(REGISTRY["lwm-7b"])
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    sib = SIB(cfg)
    rng = np.random.default_rng(0)
    fwd = jax.jit(lambda p, tk: model.forward(p, {"tokens": tk})[0])
    lengths = [32, 64, 96, 128] if quick else [32, 64, 96, 128, 160, 192]
    t0 = time.perf_counter()
    samples = []
    for ln in lengths:
        toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (1, ln)), jnp.int32)
        fwd(params, toks).block_until_ready()  # compile
        reps = 3
        t1 = time.perf_counter()
        for _ in range(reps):
            fwd(params, toks).block_until_ready()
        samples.append((ln, (time.perf_counter() - t1) / reps))
    for ln, dt in samples[:-1]:
        sib.record_prefill(1, [ln], dt)
    holdout = samples[-1]
    pred = sib.prefill_time(1, [holdout[0]])
    err = abs(pred - holdout[1]) / holdout[1] * 100
    us = (time.perf_counter() - t0) * 1e6
    _row("fig14_analytical_model", us, f"holdout_err:{err:.1f}%")


# ------------------------------------------------------------- kernels §6


def bench_kernels(quick: bool = False):
    """§6 kernels: interpret-mode correctness vs pure-jnp oracle."""
    import jax
    import jax.numpy as jnp

    from repro.core import striped as st
    from repro.kernels import ops

    b, s, h, kvh, d = 1, 256, 4, 2, 64
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (b, s, h, d))
    k = jax.random.normal(ks[1], (b, s, kvh, d))
    v = jax.random.normal(ks[2], (b, s, kvh, d))
    pos = st.striped_positions(s, 4)
    t0 = time.perf_counter()
    out_k = ops.attention(q, k, v, pos, pos, impl="interpret", block_q=64,
                          block_k=64)
    out_r = ops.attention(q, k, v, pos, pos, impl="xla")
    err = float(jnp.max(jnp.abs(out_k - out_r)))
    us = (time.perf_counter() - t0) * 1e6
    _row("kernel_striped_attention", us, f"allclose_err:{err:.1e}")

    lens = jnp.full((b,), s, jnp.int32)
    qd = jax.random.normal(ks[0], (b, 1, h, d))
    t0 = time.perf_counter()
    pk = ops.decode_partial(qd, k, v, lens, impl="interpret", block_k=64)
    pr = ops.decode_partial(qd, k, v, lens, impl="xla")
    err = float(jnp.max(jnp.abs(pk.o - pr.o)))
    us = (time.perf_counter() - t0) * 1e6
    _row("kernel_flash_decode", us, f"allclose_err:{err:.1e}")


# ------------------------------------------------------- paged decode step


def bench_decode_paged(quick: bool = False):
    """Decode-iteration benchmark on the REAL engine hot path: the legacy
    gather-dense dataflow (per-request host gather -> dense Cache -> one
    model.decode per request, i.e. O(batch) dispatches + O(tokens) host
    traffic per step) vs the batched paged path (block tables -> ONE batched
    model.decode with one paged launch per instance per layer).  Both arms
    run the same model, same pools, same DecodeBatch.  Writes
    BENCH_decode.json."""
    import json

    import jax
    import numpy as np

    from repro.configs import REGISTRY, reduced
    from repro.engine.request import Phase, Request
    from repro.engine.server import LoongServeEngine
    from repro.kernels import ops
    from repro.manager.scheduler import DecodeBatch
    from repro.models import build_model

    cfg = reduced(REGISTRY["lwm-7b"])
    page = 64
    b = 8 if quick else 16
    iters = 3 if quick else 10
    n_inst = 2
    rng = np.random.default_rng(0)
    lengths = np.sort(rng.integers(64, 1025, b))  # ragged cached KV

    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    capacity = (-(-int(lengths.sum()) // page) + 16) * page  # per instance
    eng = LoongServeEngine(cfg, n_inst, capacity, store_values=True,
                           model=model, params=params, page_size=page)
    # place ragged cached KV token-granularly across the instances and set up
    # one ready decode group, exactly as after prefill
    reqs = []
    for rid, ln in enumerate(lengths):
        n = int(ln)
        r = Request(input_len=n, max_new_tokens=64,
                    prompt=rng.integers(0, cfg.vocab_size, n).tolist())
        r.rid, r.generated, r.phase = rid, 1, Phase.DECODE
        r.output_tokens = [int(rng.integers(0, cfg.vocab_size))]
        plan = eng.pool.plan_placement(rid, list(range(n)), range(n_inst))
        k = rng.normal(size=(eng.pool.pools[0].n_attn, n, cfg.n_kv_heads,
                             cfg.head_dim))
        eng.pool.place(plan, k, k + 1)
        reqs.append(r)
    g = DecodeBatch(reqs, list(range(n_inst)),
                    {r.rid: r.rid % n_inst for r in reqs})
    impl = ops.get_default_impl()

    # steady state appends one token's KV per request per iteration; model it
    # in BOTH arms by re-filling each request's newest cached token so the
    # paged arm pays its incremental device-mirror sync and the dense arm its
    # re-gather (same host-side write cost on each side)
    fills = []
    for r in reqs:
        last = r.seq_len - 2
        inst = next(i for i in range(n_inst)
                    if last in eng.pool.pools[i].tokens_of(r.rid))
        kv1 = rng.normal(size=(eng.pool.pools[0].n_attn, 1, cfg.n_kv_heads,
                               cfg.head_dim))
        fills.append((eng.pool.pools[inst], r.rid, last, kv1))

    def run_arm(step):
        step(g)  # warmup / compile
        ops.reset_dispatch_counts()
        t0 = time.perf_counter()
        for _ in range(iters):
            for pool, rid, pos, kv1 in fills:
                pool.fill(rid, [pos], kv1, kv1)
            step(g)
        dt = (time.perf_counter() - t0) / iters
        return dt, {k: v // iters for k, v in ops.dispatch_counts.items()}

    t_dense, d_dense = run_arm(eng._real_decode_serial)
    t_paged, d_paged = run_arm(eng._real_decode_paged)
    results = {
        "gather_dense": {"s_per_decode_iter": t_dense, "dispatches": d_dense},
        "paged_batched": {"s_per_decode_iter": t_paged, "dispatches": d_paged},
    }
    speedup = t_dense / t_paged
    out = {
        "batch": b,
        "n_instances": n_inst,
        "page_size": page,
        "n_layers": int(eng.pool.pools[0].n_attn),
        "lengths": [int(x) for x in lengths],
        "kernel_impl": impl,
        # a decode iteration emits one token per request
        **{f"{k}_tok_s": float(b / v["s_per_decode_iter"])
           for k, v in results.items()},
        **{f"{k}_s_per_iter": v["s_per_decode_iter"]
           for k, v in results.items()},
        "dispatches_per_iter": {k: v["dispatches"] for k, v in results.items()},
        "speedup": speedup,
    }
    # quick mode gets its own artifact so it can't clobber the committed one
    path = "BENCH_decode_quick.json" if quick else "BENCH_decode.json"
    with open(path, "w") as f:
        json.dump(out, f, indent=2)
    _row(
        "decode_paged_vs_gather",
        t_paged * 1e6,
        f"speedup:{speedup:.2f}x;batch:{b};"
        f"paged_launches:{sum(d_paged.values())}",
    )


# ------------------------------------------------------ packed prefill step


def bench_prefill_packed(quick: bool = False):
    """Prefill benchmark on the REAL engine hot path: per-request serial
    prefill (one eager model.prefill per request — a fresh program per
    distinct prompt length, host-side pool.fill) vs packed ragged prefill
    (ONE jitted packed step per batch, segment-masked ragged attention,
    direct-to-pool paged KV write-through).  Same model, same pools, same
    PrefillBatch with reserved striped placement.  Writes
    BENCH_prefill.json."""
    import json

    import jax
    import numpy as np

    from repro.configs import REGISTRY, reduced
    from repro.engine.request import Phase, Request
    from repro.engine.server import LoongServeEngine
    from repro.kernels import ops
    from repro.manager.scheduler import PrefillBatch
    from repro.models import build_model

    cfg = reduced(REGISTRY["lwm-7b"])
    page = 64
    b = 8 if quick else 16
    iters = 2 if quick else 5
    n_inst = 2
    rng = np.random.default_rng(0)
    lo, hi = (32, 128) if quick else (64, 512)
    lengths = rng.integers(lo, hi + 1, b)
    lengths[0], lengths[-1] = lo, hi  # span >= 4x guaranteed

    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    capacity = (-(-int(lengths.sum()) // page) + 16) * page  # per instance
    eng = LoongServeEngine(cfg, n_inst, capacity, store_values=True,
                           model=model, params=params, page_size=page)
    # reserve striped token-granular placement across the instances, exactly
    # as the scheduler's proactive scale-down does before prefill executes
    reqs, placement = [], {}
    for rid, ln in enumerate(lengths):
        n = int(ln)
        r = Request(input_len=n, max_new_tokens=8,
                    prompt=rng.integers(0, cfg.vocab_size, n).tolist())
        r.rid, r.phase = rid, Phase.PREFILL
        plan = eng.pool.plan_placement(rid, list(range(n)), range(n_inst))
        eng.pool.place(plan)  # reserve slots; prefill fills the values
        placement[rid] = plan.assignment
        reqs.append(r)
    batch = PrefillBatch(reqs, list(range(n_inst)),
                         scale_down_to=list(range(n_inst)),
                         placement=placement)
    impl = ops.get_default_impl()

    def reset():
        for r in reqs:
            r.output_tokens = []

    def run_arm(step):
        reset()
        step(batch)  # warmup / compile
        t0 = time.perf_counter()
        for _ in range(iters):
            reset()
            step(batch)
        return (time.perf_counter() - t0) / iters

    t_serial = run_arm(eng._real_prefill_serial)
    t_packed = run_arm(eng._real_prefill_packed)

    # launch-count instrumentation: the jitted packed step fuses its
    # launches, so count the dataflow once in eager (disable_jit) mode —
    # exactly one prefill_packed dispatch per layer per batch
    ops.reset_dispatch_counts()
    with jax.disable_jit():
        reset()
        eng._real_prefill_packed(batch)
    packed_dispatches = dict(ops.dispatch_counts)

    # write-through invariant: after a packed prefill no slot is dirty, so
    # the first decode's mirror sync would upload zero prefill slots
    post_dirty = sum(p.dirty_slot_count() for p in eng.pool.pools)

    # bucketing: sweep random batch shapes up to max_tokens and count the
    # distinct compiled packed-prefill programs — O(log max_tokens), not one
    # per prompt length
    max_tokens = int(lengths.sum())
    n_sweep = 3 if quick else 12
    for s in range(n_sweep):
        ls = rng.integers(lo, hi + 1, int(rng.integers(2, b + 1)))
        sreqs = []
        for j, ln in enumerate(ls):
            r = Request(input_len=int(ln), max_new_tokens=8,
                        prompt=rng.integers(0, cfg.vocab_size, int(ln)).tolist())
            r.rid = 10_000 + s * 100 + j
            sreqs.append(r)
        # no placement -> the KV scatter is skipped; only the model step runs
        eng._real_prefill_packed(
            PrefillBatch(sreqs, list(range(n_inst)), scale_down_to=[])
        )
    n_programs = len(eng._prefill_programs)

    total = int(lengths.sum())
    speedup = t_serial / t_packed
    out = {
        "batch": b,
        "n_instances": n_inst,
        "page_size": page,
        "n_layers": int(eng.pool.pools[0].n_attn),
        "lengths": [int(x) for x in lengths],
        "total_prompt_tokens": total,
        "kernel_impl": impl,
        "serial_tok_s": float(total / t_serial),
        "packed_tok_s": float(total / t_packed),
        "serial_s_per_batch": t_serial,
        "packed_s_per_batch": t_packed,
        "speedup": speedup,
        # eager-instrumented dataflow: one prefill_packed launch per layer
        "packed_dispatches_per_batch": packed_dispatches,
        "prefill_packed_per_layer": (
            packed_dispatches.get("prefill_packed", 0)
            == int(eng.pool.pools[0].n_attn)
        ),
        "post_prefill_dirty_slots": int(post_dirty),
        "distinct_compiled_prefill_programs": n_programs,
        "sweep_batches": n_sweep + 1,
        "log2_max_tokens": int(np.ceil(np.log2(max(max_tokens, 2)))),
    }
    path = "BENCH_prefill_quick.json" if quick else "BENCH_prefill.json"
    with open(path, "w") as f:
        json.dump(out, f, indent=2)
    _row(
        "prefill_packed_vs_serial",
        t_packed * 1e6,
        f"speedup:{speedup:.2f}x;batch:{b};programs:{n_programs};"
        f"dirty_after:{post_dirty}",
    )


# ------------------------------------------------- ring-fused DoP>1 prefill


def bench_prefill_ring(quick: bool = False):
    """Ring-fused packed prefill for multi-instance (DoP>1) ESP groups on the
    REAL engine hot path: per-request serial prefill (the pre-fusion fallback
    for scaled-up groups — one eager model.prefill per request) vs the packed
    ring (ONE jitted packed step per batch; attention runs one packed ragged
    chunk launch per instance per ring step with carried flash state), at
    DoP in {1, 2, 4}.  Same model, same pools, same PrefillBatch with
    reserved striped placement.  Writes BENCH_prefill_ring.json."""
    import json

    import jax
    import numpy as np

    from repro.configs import REGISTRY, reduced
    from repro.engine.request import Phase, Request
    from repro.engine.server import LoongServeEngine
    from repro.kernels import ops
    from repro.manager.scheduler import PrefillBatch
    from repro.models import build_model

    cfg = reduced(REGISTRY["lwm-7b"])
    page = 64
    b = 4 if quick else 8
    iters = 2 if quick else 3
    lo, hi = (64, 256) if quick else (256, 1024)
    rng = np.random.default_rng(0)
    lengths = rng.integers(lo, hi + 1, b)
    lengths[0], lengths[-1] = lo, hi  # span guaranteed
    total = int(lengths.sum())

    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    impl = ops.get_default_impl()
    results = {}
    for dop in (1, 2, 4):
        capacity = (-(-total // page) + 16) * page  # per instance
        eng = LoongServeEngine(cfg, dop, capacity, store_values=True,
                               model=model, params=params, page_size=page)
        reqs, placement = [], {}
        for rid, ln in enumerate(lengths):
            n = int(ln)
            r = Request(input_len=n, max_new_tokens=8,
                        prompt=rng.integers(0, cfg.vocab_size, n).tolist())
            r.rid, r.phase = rid, Phase.PREFILL
            plan = eng.pool.plan_placement(rid, list(range(n)), range(dop))
            eng.pool.place(plan)  # reserve slots; the ring fills the values
            placement[rid] = plan.assignment
            reqs.append(r)
        batch = PrefillBatch(reqs, list(range(dop)),
                             scale_down_to=list(range(dop)),
                             placement=placement)

        def reset():
            for r in reqs:
                r.output_tokens = []

        def run_arm(step):
            reset()
            step(batch)  # warmup / compile
            best = float("inf")
            for _ in range(iters):
                reset()
                t0 = time.perf_counter()
                step(batch)
                best = min(best, time.perf_counter() - t0)
            return best  # min-of-iters: robust to background load spikes

        t_serial = run_arm(eng._real_prefill_serial)
        t_packed = run_arm(eng._real_prefill_packed)
        # eager-instrumented dataflow: zero per-request serial model.prefill
        # calls, dop^2 ring-chunk launches per layer (1 per instance per
        # ring step) — the jitted step fuses them, so count with disable_jit
        ops.reset_dispatch_counts()
        with jax.disable_jit():
            reset()
            eng._real_prefill_packed(batch)
        d = dict(ops.dispatch_counts)
        results[f"dop{dop}"] = {
            "serial_tok_s": float(total / t_serial),
            "packed_tok_s": float(total / t_packed),
            "serial_s_per_batch": t_serial,
            "packed_s_per_batch": t_packed,
            "speedup": t_serial / t_packed,
            "packed_dispatches_per_batch": d,
            "serial_model_prefill_calls": d.get("prefill_serial_model", 0),
            "post_prefill_dirty_slots": int(
                sum(p.dirty_slot_count() for p in eng.pool.pools)
            ),
            "host_syncs": int(sum(p.host_syncs for p in eng.pool.pools)),
        }
    out = {
        "batch": b,
        "page_size": page,
        "n_layers": int(cfg.n_attention_applications),
        "lengths": [int(x) for x in lengths],
        "total_prompt_tokens": total,
        "kernel_impl": impl,
        **results,
        "dop2_speedup": results["dop2"]["speedup"],
    }
    path = "BENCH_prefill_ring_quick.json" if quick else "BENCH_prefill_ring.json"
    with open(path, "w") as f:
        json.dump(out, f, indent=2)
    _row(
        "prefill_ring_vs_serial",
        results["dop2"]["packed_s_per_batch"] * 1e6,
        ";".join(
            f"{k}_speedup:{v['speedup']:.2f}x" for k, v in results.items()
        ) + f";batch:{b};serial_calls_in_packed:"
        f"{results['dop2']['serial_model_prefill_calls']}",
    )


# ------------------------------------------- unified mixed prefill+decode


def bench_mixed(quick: bool = False):
    """Mixed continuous-batching workload on the REAL engine: B=8 short
    requests are mid-decode when ONE long prompt arrives whose placement
    must span every instance.  Sequential baseline (``prefill_chunk_tokens``
    unset): the monolithic prefill annexes the decode instances and token
    emission stalls for the whole prompt.  Unified arm: the prefill runs as
    a chain of bounded chunks and the decode rows RIDE each fused iteration,
    so the worst-case time-between-tokens collapses from one-full-prefill to
    one-chunk.  Reports decode TBT p50/p99 (engine-clock emission
    timestamps), the p99 ratio, riding evidence from the fused-step token
    counters, and wall-clock tok/s.  Writes BENCH_mixed.json."""
    import copy
    import json

    import jax
    import numpy as np

    from repro.configs import REGISTRY, reduced
    from repro.engine.request import Request
    from repro.engine.server import LoongServeEngine
    from repro.kernels import ops
    from repro.kernels import ref as kref
    from repro.manager.scheduler import ManagerConfig
    from repro.models import build_model

    cfg = reduced(REGISTRY["lwm-7b"])
    n_inst = 2
    b = 8
    # short_new sized so the 8 stall-affected TBT samples (one per short,
    # the diff spanning the baseline's monolithic long prefill) sit fully
    # above the p99 index of the 8*(short_new-1) samples — p99 must measure
    # the stall, not interpolate across its boundary
    short_len, short_new = (16, 48) if quick else (32, 64)
    long_len, chunk = (1280, 64) if quick else (2048, 256)
    long_new = 4
    # capacity: sized so the long prompt IS admitted while the shorts are
    # still mid-decode (fleet-wide free >= its footprint + growth reserve)
    # but does NOT fit on one instance, so its placement (and the
    # baseline's monolithic prefill) spans both — stripping the shorts'
    # decode group — the contended scenario the unified step targets
    capacity = 912 if quick else 1600
    rng = np.random.default_rng(0)
    reqs = []
    for _ in range(b):
        reqs.append(Request(
            input_len=short_len, max_new_tokens=short_new, arrival=0.0,
            prompt=rng.integers(0, cfg.vocab_size, short_len).tolist(),
        ))
    long_req = Request(
        input_len=long_len, max_new_tokens=long_new, arrival=0.05,
        prompt=rng.integers(0, cfg.vocab_size, long_len).tolist(),
    )
    reqs.append(long_req)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    oracle = {
        i: kref.serial_decode_oracle(model, params, r.prompt,
                                     r.max_new_tokens - 1)
        for i, r in enumerate(reqs)
    }

    def seed_profile(sib):
        # serving-scale iteration-time profile (the paper's SQLite profile
        # store, condensed to a fitted plane): per-token prefill cost
        # dominates the launch overhead, so a monolithic long prefill
        # occupies its instances for time proportional to prompt length.
        # DoP=2 gets a mild efficiency edge so DP batching keeps the
        # same-instant burst in one spanning batch (one decode group).
        # Identical profile for both arms; decode keeps the napkin model.
        for dop in (1, 2):
            beta = 25e-6 / dop * (0.96 if dop == 2 else 1.0)
            for lens in ([64], [256], [1024], [2048], [512, 512]):
                s1 = sum(lens)
                s2 = sum(l * l for l in lens)
                sib.record_prefill(dop, lens, 0.003 + beta * s1 + 1e-11 * s2)
        # the memory-bound tipping point is profilable too (§5.1); the
        # napkin default reflects the reduced toy model, not this profile —
        # pin it so a burst of B shorts still forms one prefill batch
        sib.prefill_tipping_point = lambda dop: 0.012

    def run_arm(chunk_tokens):
        eng = LoongServeEngine(
            cfg, n_inst, capacity, store_values=True, model=model,
            params=params, page_size=16,
            mcfg=ManagerConfig(prefill_chunk_tokens=chunk_tokens),
        )
        seed_profile(eng.sib)
        rs = copy.deepcopy(reqs)
        shorts = rs[:b]
        # engine-clock emission timestamps of every short-request token
        emitted = {id(r): [0] * 0 for r in shorts}
        seen = {id(r): 0 for r in shorts}

        def watch(e, kind, payload):
            for r in shorts:
                if r.generated > seen[id(r)]:
                    emitted[id(r)].extend(
                        [e.clock] * (r.generated - seen[id(r)])
                    )
                    seen[id(r)] = r.generated

        ops.reset_dispatch_counts()
        for r in rs:
            eng.submit(r)
        eng.event_hooks.append(watch)
        t0 = time.perf_counter()
        m = eng.run()
        wall = time.perf_counter() - t0
        assert len(m.finished) == len(rs), (chunk_tokens, len(m.finished))
        for i, r in enumerate(rs):
            assert r.output_tokens == oracle[i], (chunk_tokens, i)
        tbt = np.concatenate([
            np.diff(np.asarray(ts)) for ts in emitted.values() if len(ts) > 1
        ])
        total_tok = sum(r.generated for r in rs)
        return {
            "decode_tbt_p50": float(np.percentile(tbt, 50)),
            "decode_tbt_p99": float(np.percentile(tbt, 99)),
            "decode_tbt_max": float(tbt.max()),
            "wall_tok_s": float(total_tok / wall),
            "unified_steps": int(ops.dispatch_counts["unified_step"]),
            "unified_decode_tokens": int(
                ops.dispatch_counts["unified_decode_tokens"]
            ),
        }

    seq = run_arm(None)
    uni = run_arm(chunk)
    ratio = seq["decode_tbt_p99"] / max(uni["decode_tbt_p99"], 1e-12)
    out = {
        "batch": b,
        "n_instances": n_inst,
        "short_len": short_len,
        "short_new_tokens": short_new,
        "long_len": long_len,
        "prefill_chunk_tokens": chunk,
        "kernel_impl": ops.get_default_impl(),
        "sequential": seq,
        "unified": uni,
        "tbt_p99_ratio": ratio,
    }
    path = "BENCH_mixed_quick.json" if quick else "BENCH_mixed.json"
    with open(path, "w") as f:
        json.dump(out, f, indent=2)
    _row(
        "mixed_unified_vs_sequential",
        uni["decode_tbt_p99"] * 1e6,
        f"tbt_p99_ratio:{ratio:.2f}x;"
        f"riders:{uni['unified_decode_tokens']};"
        f"steps:{uni['unified_steps']}",
    )


# ------------------------------------------------- SPMD mesh-executor ring


def _cpu_rehearsal(module: str, quick: bool) -> None:
    """Run one SPMD bench body in a child process on 8 CPU virtual devices.

    These benches are CPU virtual-device rehearsals: the child needs the
    host-device-count flag set before jax initializes, so it must be its own
    process, and a child must never need the chip a parent holds.  They
    therefore run only when this driver runs under ``JAX_PLATFORMS=cpu``
    (elsewhere they print a SKIP row); the child inherits that setting."""
    import os
    import pathlib
    import subprocess
    import sys

    name = module.rsplit(".", 1)[-1]
    if os.environ.get("JAX_PLATFORMS") != "cpu":
        _row(name, 0.0, "SKIP:CPU virtual-device rehearsal, needs "
             "JAX_PLATFORMS=cpu")
        return
    root = pathlib.Path(__file__).parent.parent
    # the child module self-appends the 8-device XLA flag before jax
    # initializes; only PYTHONPATH needs to be threaded through here
    env = dict(os.environ)
    env["PYTHONPATH"] = str(root / "src") + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else ""
    )
    cmd = [sys.executable, "-m", module]
    if quick:
        cmd.append("--quick")
    out = subprocess.run(cmd, env=env, cwd=root, capture_output=True,
                         text=True, timeout=3600)
    if out.returncode != 0:
        raise RuntimeError(out.stdout + "\n" + out.stderr)
    row = next(
        ln for ln in out.stdout.splitlines() if ln.startswith(name + ",")
    )
    _, us, derived = row.split(",", 2)
    _row(name, float(us), derived)


def bench_prefill_spmd(quick: bool = False):
    """Mesh-executor ring prefill on an 8-virtual-device host mesh: the
    DoP>1 packed prefill as ONE shard_map program with the KV stripes
    ppermuted between devices — double-buffered vs sequential ring vs the
    in-process LocalExecutor replay, plus exact per-ring-step ppermute
    bytes.  A CPU virtual-device rehearsal (`_cpu_rehearsal`): it runs in
    a child process, only under ``JAX_PLATFORMS=cpu``, so no parent that
    holds the chip ever spawns it.  Writes BENCH_prefill_spmd.json."""
    _cpu_rehearsal("benchmarks.prefill_spmd", quick)


# ------------------------------------------------ SPMD mesh-executor decode


def bench_decode_spmd(quick: bool = False):
    """Mesh-executor decode on an 8-virtual-device host mesh: the whole
    batched decode iteration as ONE shard_map program — the batch-sharded
    multi-master arm (stack on B/n rows per rank, all_gather/psum_scatter
    boundary, in-program sampling) vs the replicated overlapped/barriered
    programs vs the per-shard Python loop with explicit device hops — plus
    per-iteration collective payload bytes, structural StableHLO overlap
    evidence and the ~1/n dot-FLOP census ratio.  A CPU virtual-device
    rehearsal (`_cpu_rehearsal`): it runs in a child process, only under
    ``JAX_PLATFORMS=cpu``, so no parent that holds the chip ever spawns it.
    Writes BENCH_decode_spmd.json."""
    _cpu_rehearsal("benchmarks.decode_spmd", quick)


# -------------------------------------------------------------- roofline


def bench_roofline_summary(quick: bool = False):
    """Surfaces the dry-run roofline table if dryrun_singlepod.json exists."""
    import json
    import os

    path = "dryrun_singlepod.json"
    if not os.path.exists(path):
        _row("roofline_summary", 0.0, "run launch.dryrun --all first")
        return
    with open(path) as f:
        rows = json.load(f)
    t0 = time.perf_counter()
    ok = [r for r in rows if r.get("status") == "ok"]
    if not ok:
        _row("roofline_summary", 0.0, "no ok cells")
        return
    worst = min(
        ok,
        key=lambda r: r["roofline"]["compute_s"]
        / max(sum(r["roofline"][k] for k in ("compute_s", "memory_s", "collective_s")), 1e-12),
    )
    n_dom = {}
    for r in ok:
        dom = r["roofline"]["dominant"]
        n_dom[dom] = n_dom.get(dom, 0) + 1
    us = (time.perf_counter() - t0) * 1e6
    _row(
        "roofline_summary", us,
        f"cells:{len(ok)};dominants:{n_dom};worst:{worst['arch']}x{worst['shape']}",
    )


BENCHES = {
    "fig2": bench_scalability,
    "fig10": bench_end_to_end,
    "fig11": bench_multinode,
    "fig12": bench_goodput_zipf,
    "fig13": bench_scaling_overhead,
    "fig14": bench_analytical_model,
    "kernels": bench_kernels,
    "decode": bench_decode_paged,
    "prefill": bench_prefill_packed,
    "prefill_ring": bench_prefill_ring,
    "mixed": bench_mixed,
    "prefill_spmd": bench_prefill_spmd,
    "decode_spmd": bench_decode_spmd,
    "roofline": bench_roofline_summary,
}

# CI smoke: the engine hot paths (quick mode, *_quick.json artifacts);
# failures are fatal so the benchmark paths can't silently rot.
SMOKE = ("decode", "prefill", "prefill_ring", "mixed", "prefill_spmd",
         "decode_spmd")


def _bench_headline(data: dict) -> dict:
    """Extract one bench artifact's headline numbers: every ``*tok_s``
    leaf, every ``collective_bytes_per_iter`` table and every
    speedup/ratio leaf, each keyed by its dotted path in the artifact."""
    tok_s: dict = {}
    bytes_iter: dict = {}
    derived: dict = {}

    def walk(node, prefix):
        if not isinstance(node, dict):
            return
        for k, v in node.items():
            p = f"{prefix}.{k}" if prefix else k
            if k == "collective_bytes_per_iter" and isinstance(v, dict):
                for ck, cv in v.items():
                    bytes_iter[f"{p}.{ck}"] = cv
            elif isinstance(v, dict):
                walk(v, p)
            elif isinstance(v, (int, float)) and not isinstance(v, bool):
                if k.endswith("tok_s"):
                    tok_s[p] = v
                elif "speedup" in k or "ratio" in k:
                    derived[p] = v

    walk(data, "")
    out = {}
    if tok_s:
        out["tok_s"] = tok_s
    if bytes_iter:
        out["bytes_per_iter"] = bytes_iter
    if derived:
        out["derived"] = derived
    return out


def collate() -> None:
    """Merge the committed per-PR ``BENCH_*.json`` artifacts (the _quick CI
    variants excluded) into ``BENCH_trajectory.json``: a ``latest`` headline
    snapshot per bench plus an append-only per-commit ``history`` (one entry
    per commit, overwritten on re-run at the same commit)."""
    import glob
    import json
    import subprocess

    benches = {}
    for path in sorted(glob.glob("BENCH_*.json")):
        name = path[len("BENCH_"):-len(".json")]
        if name.endswith("_quick") or name == "trajectory":
            continue
        with open(path) as f:
            headline = _bench_headline(json.load(f))
        if headline:
            benches[name] = headline
    try:
        commit = subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"], capture_output=True,
            text=True, check=True,
        ).stdout.strip()
    except Exception:  # noqa: BLE001 — not a repo / no git: still collate
        commit = "unknown"
    out_path = "BENCH_trajectory.json"
    try:
        with open(out_path) as f:
            traj = json.load(f)
    except (FileNotFoundError, json.JSONDecodeError):
        traj = {"history": []}
    traj["latest"] = {"commit": commit, "benches": benches}
    history = [e for e in traj.get("history", []) if e.get("commit") != commit]
    history.append({"commit": commit, "benches": benches})
    traj["history"] = history
    with open(out_path, "w") as f:
        json.dump(traj, f, indent=2)
    _row("collate", 0.0,
         f"benches:{len(benches)};commits:{len(history)};out:{out_path}")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--only", default=None)
    ap.add_argument("--smoke", action="store_true",
                    help="CI: quick decode+prefill benches only; raise on error")
    ap.add_argument("--collate", action="store_true",
                    help="merge BENCH_*.json into BENCH_trajectory.json")
    args = ap.parse_args()
    if args.collate:
        print("name,us_per_call,derived")
        collate()
        return
    if args.smoke:
        args.quick = True
    print("name,us_per_call,derived")
    errors = []
    for name, fn in BENCHES.items():
        if args.smoke and name not in SMOKE:
            continue
        if args.only and args.only not in name:
            continue
        try:
            fn(quick=args.quick)
        except Exception as e:  # noqa: BLE001
            if args.smoke:
                raise
            _row(name, 0.0, f"ERROR:{type(e).__name__}:{e}")
            errors.append(name)
    if errors:
        print(f"benchmarks failed: {', '.join(errors)}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
