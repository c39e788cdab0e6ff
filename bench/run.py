#!/usr/bin/env python3
"""The benchmark: one cell of `BENCHMARK.json`, served open-loop on the chip.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up (timed as ``setup_s``, from the start of the process): weights made
on the device from the seed, the engine built through the program's normal
entry point (`build_engine("loongserve", ...)`: `LoongServeEngine` with the
default `ManagerConfig` and its `LocalExecutor`), every packed prefill
program the run's prompts can reach (each alone, and the batches the
scheduler can form from them) run once at each group size up to the
instance count, one request of each prompt length the run sends served
with those programs stood in by zeros (so the eager operations around them
meet every length), every instance's KV mirror uploaded, the eager decode
path run at each batch size the traffic can reach (none where every
request ends at its first token), and then the cell's own traffic served
for its warm-up time.  The persistent compilation cache lives in
``bench/.jax_cache``, so only a checkout's first run compiles.

The window: requests fall due on the cell's Poisson schedule and the
open-loop driver (`driver.py`) submits and stamps them for ``--seconds``
of host wall clock, then stops without draining.  With ``--trace 1`` the
profiler records the whole window and the per-layer metrics are read from
it; with ``--trace 0`` the end-to-end metrics are reported.

After the window: the peak device memory is read, the engine is freed, and
the plain reference decides ``correct`` on a sample of the served requests
and the logits rows they were sampled from (`check.py`).  The last line of standard output is one JSON object; the
lines before it and ``bench/.runs/`` hold everything else.  A run that
finds no TPU, or fewer chips than the cell asks for, exits with 2 and
prints no result.
"""
from __future__ import annotations

import argparse
import gc
import glob
import json
import os
import shutil
import sys
import time

START = time.perf_counter()
BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(ROOT, "src"))

import registry  # noqa: E402


class NoChip(RuntimeError):
    pass


def spec(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def metrics_for(bench: dict, cell: str, trace: bool) -> dict:
    """{metric name: unit} the cell reports in this kind of run."""
    entries = bench["per_layer"] if trace else bench["end_to_end"]
    return {e["name"]: e["unit"] for e in entries
            if cell in e.get("workloads", [cell])}


def err(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def use_compile_cache() -> str:
    """Persistent compilation cache at a fixed path inside the checkout,
    whatever JAX_COMPILATION_CACHE_DIR says, holding every program however
    fast it compiled, so that a run finds what earlier runs of the same
    checkout compiled and two checkouts share nothing."""
    import jax

    path = os.path.join(BENCH, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def chips(n: int):
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX found {devs[0].platform}")
    if len(devs) < n:
        raise NoChip(f"the cell asks for {n} chips, JAX found {len(devs)}")
    return devs


def prefill_batches(eng, lengths) -> list:
    """Every prompt batch the scheduler can form from ``lengths``: each
    prompt alone, and each larger set whose predicted time stays under the
    scheduler's tipping point at some group size (`GlobalManager._dispatch`
    stops filling a batch there).  Long prompts pass it alone."""
    sib, n = eng.sib, eng.n
    fits = lambda lens: any(  # noqa: E731
        sib.prefill_time(d, lens) <= sib.prefill_tipping_point(d)
        for d in range(1, n + 1))
    lengths, out = sorted(lengths), []
    most = eng.manager.mcfg.max_prefill_batch

    def grow(batch, start):
        for j in range(start, len(lengths)):
            if j > start and lengths[j] == lengths[j - 1]:
                continue  # the same set again
            nxt = batch + [lengths[j]]
            if batch and (len(nxt) > most or not fits(nxt)):
                return  # sorted: every later length is as long
            out.append(nxt)
            grow(nxt, j + 1)

    grow([], 0)
    return out


def prefill_shapes(eng, lengths) -> list:
    """(tokens, batch, max_len, dop) buckets of every prefill the run can
    send: each batch the scheduler can form from the run's prompt
    ``lengths``, at each group size up to the instances.  Every seed draws
    the same set of lengths, so every run of a cell meets the same
    shapes."""
    ex = eng.executor
    out = set()
    for lens in prefill_batches(eng, lengths):
        for dop in range(1, eng.n + 1):
            tb = ex._token_bucket(-(-sum(lens) // dop)) * dop
            out.add((tb, ex._bucket(len(lens), lo=1), ex._bucket(max(lens)),
                     dop))
    return sorted(out)


def warm_prefill(eng, shapes) -> None:
    """Run the executor's packed prefill program once for each bucket
    tuple, on one prompt of zeros, so that each is compiled (or loaded
    from the cache) and held by the process before the window opens."""
    import jax.numpy as jnp
    import numpy as np

    ex = eng.executor
    for tb, bb, max_len, dop in shapes:
        n = min(tb, max_len)
        positions = np.zeros(tb, np.int32)
        positions[:n] = np.arange(n)
        offsets = np.full(bb + 1, n, np.int32)
        offsets[0] = 0
        fn = ex._packed_prefill_step(tb, bb, max_len, dop)
        prev = eng.model.attn_impl
        eng.model.attn_impl = ex._packed_prefill_impl
        try:
            logits, _ = fn(eng.params, jnp.zeros(tb, jnp.int32),
                           jnp.asarray(positions), jnp.asarray(offsets),
                           jnp.full(bb, n - 1, jnp.int32))
            logits.block_until_ready()
        finally:
            eng.model.attn_impl = prev


def decode_reach(mix, *plans) -> int:
    """The largest decode batch the run's traffic can reach: none when
    every request ends at its first token, else every request it sends."""
    return 0 if mix.out_max <= 1 else sum(len(p) for p in plans)


def warm_decode(eng, batches: int, ctx: int = 16) -> None:
    """Upload every instance's KV mirror, then run the executor's decode
    iteration once at each batch size up to ``batches``, on throwaway
    requests whose ``ctx`` cached tokens are striped over all instances.
    The program's decode path runs eagerly, so every batch size it has not
    met compiles (or loads) each of its operations."""
    from repro.engine.request import Phase, Request
    from repro.manager.scheduler import DecodeBatch

    pools = eng.pool.pools
    for pool in pools:
        pool.device_kv()  # every instance's mirror, uploaded once
    insts = list(range(len(pools)))
    for b in range(1, batches + 1):
        reqs = []
        for _ in range(b):
            r = Request(input_len=ctx, max_new_tokens=2, prompt=[0] * ctx)
            r.generated, r.output_tokens, r.phase = 1, [0], Phase.DECODE
            # the cache striped over every instance, as the ring leaves it
            for i, pool in enumerate(pools):
                pool.alloc(r.rid, range(i, ctx, len(pools)))
            reqs.append(r)
        try:
            eng.executor.decode_paged(
                DecodeBatch(reqs, insts, {r.rid: 0 for r in reqs}))
        finally:
            for r in reqs:
                for pool in pools:
                    pool.free_request(r.rid)
                eng._pending_kv.pop(r.rid, None)


def tap_logits(eng) -> dict:
    """Keep, per request id, the logits row of every token the engine
    samples: the executor's value guard sees each row just before the
    sampler does.  Returns the dict it fills."""
    import numpy as np

    ex, rows = eng.executor, {}
    guard = ex._guard_logits

    def tapped(r, row):
        out = guard(r, row)
        if out is not None:
            rows.setdefault(r.rid, []).append(np.asarray(out, np.float32))
        return out

    ex._guard_logits = tapped
    return rows


def warm_lengths(eng, lengths) -> None:
    """Serve one request of each prompt length through the engine, one
    token each, with every packed prefill program replaced by zeros of
    its output shapes: the program's eager operations around the step
    (which compile per prompt length) meet every length the run sends,
    at the cost of its host work alone."""
    import jax
    import jax.numpy as jnp

    from repro.engine.request import Request

    ex = eng.executor
    real, outs = ex._packed_prefill_step, {}

    def zeros(*bucket):
        fn = real(*bucket)

        def step(*args):
            if bucket not in outs:
                outs[bucket] = jax.eval_shape(fn, *args)
            return jax.tree.map(lambda a: jnp.zeros(a.shape, a.dtype),
                                outs[bucket])
        return step

    ex._packed_prefill_step = zeros
    try:
        for n in sorted(set(lengths)):
            eng.submit(Request(input_len=n, max_new_tokens=1, prompt=[0] * n),
                       at=eng.clock)
        while eng.events:
            eng.run(max_events=1)
    finally:
        ex._packed_prefill_step = real


def build(cfg_file: dict, seed: int):
    """(model block, params, engine) for a configuration."""
    from repro.configs.base import ModelConfig
    from repro.launch.serve import build_engine
    from repro.models import build_model

    import weights

    m = cfg_file["model"]
    cfg = ModelConfig(**m)
    params = weights.make(m, seed)
    sv = cfg_file["serving"]
    eng = build_engine(
        "loongserve", cfg, sv["instances"], sv["slots"], model=build_model(cfg),
        params=params, store_values=True, page_size=sv["page_size"])
    return m, params, eng


def run_cell(name: str, seed: int, seconds: float, trace: bool, *,
             root: str = ROOT, bench_dir: str = BENCH, rate: float = None,
             require_chip: bool = True, fault=None,
             keep_trace: bool = False, control: bool = False) -> dict:
    """One run of cell ``name``; returns the result and the run's extra
    numbers.  ``fault(engine)`` breaks the served path (tests only);
    ``control`` also reads the fp8 control on the same sample
    (calibration only)."""
    import jax
    import numpy as np

    import check
    import generator
    import peaks
    from driver import CompileWatch, OpenLoop, RunRecord

    bench = spec(root)
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise ValueError(f"{name!r} is not a workload of BENCHMARK.json")
    cell = registry.cell(name, bench_dir)
    cfg_file = registry.config(cell["config"], bench_dir)
    mix = registry.traffic(cell["traffic"], bench_dir)
    rate = float(cell["rate"] if rate is None else rate)
    want = metrics_for(bench, name, trace)

    if require_chip:
        devs = chips(entry["chips"])
        cache = use_compile_cache()
    else:
        devs, cache = jax.devices(), None
    dev = devs[0]
    peak = peaks.peaks(dev.device_kind) if require_chip else peaks.PEAKS[
        "TPU v5 lite"]
    watch = CompileWatch()
    extra = {"compile_cache": cache}

    m, params, eng = build(cfg_file, seed)
    if fault is not None:
        fault(eng)
    vocab = m["vocab_size"]
    warm = generator.plan(mix, rate, cell["warmup_s"], seed, vocab, stream=0)
    planned = generator.plan(mix, rate, seconds, seed, vocab, stream=1)
    lengths = [len(p.prompt) for p in warm + planned]
    shapes = prefill_shapes(eng, lengths)
    warm_prefill(eng, shapes)
    warm_lengths(eng, lengths)
    reach = decode_reach(mix, warm, planned)
    warm_decode(eng, reach)
    extra["prefill_programs_warmed"] = len(shapes)
    extra["decode_batches_warmed"] = reach
    extra["setup_compiles_before_traffic"] = watch.compiles
    logits = tap_logits(eng)
    loop = OpenLoop(eng, annotate=trace, watch=watch)
    loop.run(warm, cell["warmup_s"])
    jax.effects_barrier()
    setup_s = time.perf_counter() - START
    extra["setup_compiles"] = watch.compiles
    extra["setup_compile_s"] = watch.compile_s
    extra["setup_cache_loads"] = watch.cache_hits

    from repro.kernels import ops

    ops.reset_dispatch_counts()
    carried = list(loop.live)
    watch.mark()
    tdir = os.path.join(bench_dir, ".traces", f"{name}.{seed}")
    if trace:
        shutil.rmtree(tdir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(tdir, profiler_options=opts)
        with jax.profiler.TraceAnnotation("bench.window"):
            served = loop.run(planned, seconds)
        jax.profiler.stop_trace()
    else:
        served = loop.run(planned, seconds)
    extra["window_compiles"] = watch.since_mark()
    extra["dispatch_counts"] = dict(sorted(ops.dispatch_counts.items()))
    extra["decode_batch_max"] = max(
        (len(e.decode) for e in loop.events), default=0)
    stats = [d.memory_stats() or {} for d in devs[: entry["chips"]]]
    mem_peak = max((s.get("peak_bytes_in_use", 0) for s in stats), default=0)

    lateness = [s.submitted - s.due for s in served if s.submitted is not None]
    extra["submit_late_s"] = {
        "p50": float(np.median(lateness)) if lateness else None,
        "max": max(lateness) if lateness else None}
    extra["requests"] = {
        "due": len(served),
        "refused": sum(s.refused for s in served),
        "finished": sum(s.req is not None and s.req.finish_time is not None
                        for s in served),
        "running_at_close": len(loop.live),
        "carried_in": len(carried),
    }
    rec = RunRecord(seconds, m, peak, served, carried, loop.events, setup_s)
    if trace:
        import xplane

        files = glob.glob(os.path.join(tdir, "**", "*.xplane.pb"),
                          recursive=True)
        rec.trace = xplane.read(files[0]) if files else None
        if files and keep_trace:
            extra["trace_names"] = xplane.names(files[0])
        else:
            shutil.rmtree(tdir, ignore_errors=True)

    # what the window served: every request with a served token, its
    # prompt as planned and every token it was served by the close
    done = [{"prompt": np.asarray(s.req.prompt[: s.n_prompt], np.int32),
             "out": list(s.req.output_tokens), "dop": s.dop or 0,
             "finished": s.req.finish_time is not None,
             "logits": logits.get(s.req.rid, [])}
            for s in carried + served
            if s.req is not None and s.req.output_tokens]
    del loop, eng, logits
    gc.collect()
    ref = check.reference(cfg_file["reference"], bench_dir)
    sample = check.choose(done, seed, cell["sample_tokens"],
                          cell["sample_requests"])
    t_check = time.perf_counter()
    with jax.default_matmul_precision("highest"):
        read = check.readings(params, m, ref, sample, control=control)
    extra["check_s"] = time.perf_counter() - t_check
    extra["check_requests"] = [
        [len(d["prompt"]), len(d["out"]), d["dop"], d["finished"]]
        for d in sample]
    limit = cell["limits"]["widest_logit_error"]
    correct = bool(sample) and read["widest_logit_error"] <= limit

    metrics = {}
    for mname, unit in want.items():
        v = registry.metric(mname, bench_dir)(rec)
        if v is not None:
            metrics[mname] = {"value": float(v), "unit": unit}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs), "memory_peak_bytes": int(mem_peak)}
    result = {"correct": correct, "attempted": len(served),
              "failed": extra["requests"]["refused"], "metrics": metrics,
              "device": device}
    if trace and rec.trace is not None:
        device["busy_s"] = rec.trace["busy_s"]
        device["window_s"] = rec.trace["window_s"]
        result["breakdown"] = {"device_ops": rec.trace["device_ops"],
                               "idle_gaps": rec.trace["idle_gaps"]}
        extra["trace"] = {k: rec.trace[k] for k in
                          ("spans", "kernel_names", "devices")}
    extra["check_served_tokens"] = read["served_tokens"]
    if control:
        # the control, put in the program's place, judged as a run is
        gap = read["control_widest_logit_error"]
        extra["control_widest_logit_error"] = gap
        extra["control_correct"] = bool(sample) and gap <= limit
    result["check"] = {
        "widest_logit_error": {"value": read["widest_logit_error"],
                               "limit": limit},
    }
    extra["record"] = rec
    return result, extra


def save(name: str, result: dict, extra: dict, rec) -> None:
    """The run's numbers, events and requests in ``bench/.runs/<name>.json``."""
    os.makedirs(os.path.join(BENCH, ".runs"), exist_ok=True)
    with open(os.path.join(BENCH, ".runs", f"{name}.json"), "w") as f:
        json.dump({"result": result, "extra": extra,
                   "events": [e.__dict__ for e in rec.events],
                   "requests": [{k: getattr(s, k) for k in
                                 ("due", "n_prompt", "max_new", "submitted",
                                  "refused", "stamps", "prefill_t0", "dop")}
                                for s in rec.requests]}, f)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rate", type=float, default=None,
                    help="override the cell's rate (for finding the knee)")
    ap.add_argument("--keep-trace", action="store_true",
                    help="keep the raw trace under bench/.traces and list "
                    "its op names, for reading it by hand")
    args = ap.parse_args(argv)
    try:
        result, extra = run_cell(args.workload, args.seed, args.seconds,
                                 bool(args.trace), rate=args.rate,
                                 keep_trace=args.keep_trace)
    except NoChip as e:
        err(f"bench: {e}")
        return 2
    rec = extra.pop("record")
    for k, v in extra.items():
        if k != "trace_names":
            print(f"bench: {k}: {json.dumps(v)}", flush=True)
    save(f"{args.workload}.{args.seed}.t{args.trace}", result, extra, rec)
    for k, v in result["check"].items():
        err(f"check: {k} {v['value']} limit {v['limit']}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
