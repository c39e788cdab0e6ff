#!/usr/bin/env python3
"""Chip smoke test: the LoongServe serving path on a TPU, end to end.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # four chips: the DoP-4 mesh path only

One chip.  `repro.launch.serve.build_engine("loongserve", ...)` — the
`LoongServeEngine` with the default `ManagerConfig` and its `LocalExecutor`
— serves six requests in real mode over 2 instances on the one chip:
prompts of 4096, 4096, 2048, 2048, 512 and 512 tokens, 16 new tokens each.
The scheduler prefills the 4096-token prompts as DoP-2 groups (the
in-process ESP ring, `ops.prefill_ring_chunk`) and the others at DoP 1
(`ops.prefill_packed`); every decode step runs `ops.paged_decode_partial`.
Before serving, each of those three Pallas kernels is checked against the
dense float32 oracle of `repro.kernels.ref` on a small ragged input at
lwm-7b's head widths.

Model: lwm-7b at its published widths (4096 hidden, 32 x 128 heads, 32 KV
heads, FFN 11008, vocab 32000) with bf16 weights drawn from ``--seed``.
Depth is cut to 4 of its 32 layers: ~2.15 GB of weights plus the float32 KV
mirror (128 KiB per token over 4 layers; 2 instances x 8192 slots is
~2.1 GB) leave most of a 16 GB chip to activations.  Pages hold 16 tokens,
so a decode grid step streams 16 tokens of every head.

Four chips (``--chips 4``).  `MeshExecutor` with 4 instances, one per chip,
serves three 4096-token prompts.  Each prefills as one DoP-4 group (the
shard_map ring across the chips).  With 3072 slots per instance no prompt's
KV fits one instance, so the scheduler keeps each on two and every decode
group spans at least two chips: decode runs as the SPMD iteration.  The
same requests then run through `LocalExecutor` on one chip, in the same
process, as the comparison.  Both runs use float32 weights and float32
("highest") matmuls, so they differ only in float32 summation order (the
collectives' reduction order against the in-process merge loop), orders of
magnitude below the logit margins argmax decides on: the emitted tokens
must match exactly.

The script exits non-zero, and prints no result line, when JAX finds no
TPU, when the kernel impl is not "pallas", when a check fails or when a
request is left unfinished.  On success its last line is one JSON object
naming the device.  Every time it prints is host wall-clock; the engine's
SIB clock is a cost model and no time is read from it.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

ARCH = "lwm-7b"
N_LAYERS = 4
PAGE_SIZE = 16
MAX_NEW = 16
ONE_CHIP = dict(instances=2, capacity=8192,
                lens=(4096, 4096, 2048, 2048, 512, 512))
FOUR_CHIPS = dict(instances=4, capacity=3072, lens=(4096,) * 3)
# normalized attention outputs are convex mixes of v rows (|v| < ~5): one
# bf16 rounding of the probabilities in p @ v moves them by < 5 * 2^-9;
# a wrong mask or a lost page moves them by O(0.1) or more
KERNEL_ATOL = 1e-2


class SmokeFailure(RuntimeError):
    pass


def log(msg: str) -> None:
    print(msg, flush=True)


def require(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


class CompileClock:
    """Sums the host wall-clock JAX spends in backend compiles."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_) -> None:
        if event == self.EVENT:
            self.seconds += duration
            self.count += 1


def make_requests(lens, vocab: int, seed: int):
    import numpy as np

    from repro.engine.request import Request

    rng = np.random.default_rng(seed)
    return [
        Request(input_len=n, max_new_tokens=MAX_NEW, arrival=0.0,
                prompt=rng.integers(0, vocab, n).tolist())
        for n in lens
    ]


def serve(cfg, model, params, setup, seed: int, **engine_kw):
    """Serve ``setup``'s requests to completion through the normal entry
    points; returns (engine, requests, host wall-clock seconds)."""
    from repro.launch.serve import build_engine

    eng = build_engine(
        "loongserve", cfg, setup["instances"], setup["capacity"],
        model=model, params=params, store_values=True, page_size=PAGE_SIZE,
        **engine_kw,
    )
    reqs = make_requests(setup["lens"], cfg.vocab_size, seed)
    for r in reqs:
        eng.submit(r)
    t0 = time.perf_counter()
    metrics = eng.run()
    secs = time.perf_counter() - t0
    require(metrics.rejected == 0, f"{metrics.rejected} requests rejected")
    require(metrics.nan_quarantined == 0,
            f"{metrics.nan_quarantined} requests had non-finite logits")
    done = {r.rid for r in metrics.finished}
    left = [r.rid for r in reqs if r.rid not in done]
    require(not left, f"requests left unfinished: {left}")
    for r in reqs:
        require(len(r.output_tokens) == MAX_NEW,
                f"request {r.rid}: {len(r.output_tokens)} tokens emitted")
        require(all(0 <= t < cfg.vocab_size for t in r.output_tokens),
                f"request {r.rid}: token id out of range")
    return eng, reqs, secs


def check_kernels(cfg, seed: int, impl: str) -> dict:
    """Each main-path kernel against its dense float32 oracle on a small
    ragged input at the model's head widths; returns the max abs errors."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import esp
    from repro.kernels import ops, ref
    from repro.models import attention as A

    h, kvh, d = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    rng = np.random.default_rng(seed)
    f32 = jnp.float32

    def draw(shape, dtype=jnp.bfloat16):
        # bf16-representable values, so the oracle sees the kernel's inputs
        return jnp.asarray(jnp.asarray(rng.normal(size=shape), jnp.bfloat16),
                           dtype)

    lens, t = [100, 1, 80, 50], 256
    off = np.full(len(lens) + 1, sum(lens), np.int32)
    off[0] = 0
    off[1:len(lens) + 1] = np.cumsum(lens)
    q, k, v = draw((t, h, d)), draw((t, kvh, d)), draw((t, kvh, d))
    with jax.default_matmul_precision("highest"):
        want = ref.packed_prefill_ref(q.astype(f32), k.astype(f32),
                                      v.astype(f32), off)
    real = slice(0, sum(lens))
    errs = {}
    got = ops.prefill_packed(q, k, v, off, impl=impl)
    errs["prefill_packed"] = float(jnp.max(jnp.abs(got[real] - want[real])))
    got = esp.ring_packed_prefill(q, k, v, off, 2, impl=impl)
    errs["prefill_ring_chunk"] = float(
        jnp.max(jnp.abs(got[real] - want[real])))

    # paged decode over a float32 pool (what the KV mirror holds), ragged
    # lengths incl. an empty request and a partial tail page
    n_pages, b = 128, 4
    lengths = np.array([0, 1, 200, 333], np.int32)
    kp, vp = draw((n_pages, PAGE_SIZE, kvh, d), f32), draw(
        (n_pages, PAGE_SIZE, kvh, d), f32)
    max_pages = -(-int(lengths.max()) // PAGE_SIZE)
    table = rng.permutation(n_pages)[: b * max_pages].reshape(b, max_pages)
    table = table.astype(np.int32)
    qd = draw((b, 1, h, d))
    got = A.finalize_partial(
        ops.paged_decode_partial(qd, kp, vp, table, lengths, impl=impl))
    with jax.default_matmul_precision("highest"):
        want = A.finalize_partial(ref.paged_flash_decode_partial_ref(
            qd.astype(f32), kp, vp, table, lengths))
    errs["paged_decode_partial"] = float(jnp.max(jnp.abs(got - want)))
    for name, err in errs.items():
        require(np.isfinite(err) and err <= KERNEL_ATOL,
                f"{name}: max abs error {err} > {KERNEL_ATOL} vs the oracle")
    return errs


def report(eng, reqs, secs: float) -> None:
    from repro.kernels import ops

    tokens = sum(len(r.output_tokens) for r in reqs)
    log(f"requests finished: {len(eng.metrics.finished)}/{len(reqs)}, "
        f"tokens emitted: {tokens}")
    log(f"serve host wall-clock seconds: {secs:.3f}")
    log(f"dispatch_counts: {json.dumps(dict(sorted(ops.dispatch_counts.items())))}")


def one_chip(cfg, model, params, seed: int) -> None:
    from repro.kernels import ops

    errs = check_kernels(cfg, seed, ops.get_default_impl())
    log(f"kernel max abs error vs oracle (atol {KERNEL_ATOL}): "
        f"{json.dumps(errs)}")
    ops.reset_dispatch_counts()
    eng, reqs, secs = serve(cfg, model, params, ONE_CHIP, seed)
    report(eng, reqs, secs)
    for key in ("paged_decode_partial", "prefill_packed",
                "prefill_ring_chunk"):
        require(ops.dispatch_counts[key] > 0, f"{key} never dispatched")


def four_chips(cfg, model, params, seed: int) -> None:
    import jax

    from repro.kernels import ops

    require(len(jax.devices()) >= 4,
            f"--chips 4 needs 4 devices, found {len(jax.devices())}")
    ops.reset_dispatch_counts()
    eng, reqs, secs = serve(cfg, model, params, FOUR_CHIPS, seed)
    log("comparison: LocalExecutor on one chip")
    report(eng, reqs, secs)
    want = [list(r.output_tokens) for r in reqs]
    del eng, reqs
    gc.collect()

    ops.reset_dispatch_counts()
    eng, reqs, secs = serve(cfg, model, params, FOUR_CHIPS, seed,
                            executor="mesh")
    log("MeshExecutor, one instance per chip")
    report(eng, reqs, secs)
    mirror_devs = {
        str(d) for p in eng.pool.pools for d in p.device_kv()[0].devices()
    }
    log(f"pool mirror devices: {sorted(mirror_devs)}")
    require(len(mirror_devs) == 4, "pool mirrors are not on 4 devices")
    c = ops.dispatch_counts
    for key in ("prefill_ring_spmd", "decode_iteration_spmd"):
        require(c[key] > 0, f"{key} never dispatched")
    for key in ("prefill_ring_replay", "decode_merge_loop",
                "prefill_serial_model"):
        require(c[key] == 0, f"{key} dispatched {c[key]} times")
    got = [list(r.output_tokens) for r in reqs]
    same = sum(a == b for x, y in zip(got, want) for a, b in zip(x, y))
    log(f"tokens equal to the one-chip LocalExecutor run: "
        f"{same}/{sum(map(len, want))}")
    require(got == want, "MeshExecutor tokens differ from LocalExecutor")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=[1, 4])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    import jax

    from repro.kernels import ops
    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.serve import real_model

    cache_dir = enable_compile_cache()

    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {dev.platform})",
              file=sys.stderr)
        return 1
    log(f"device_kind: {dev.device_kind}, platform: {dev.platform}, "
        f"count: {len(devs)}")
    log(f"compile cache: {cache_dir}")
    impl = ops.get_default_impl()
    log(f"kernel impl: {impl}")
    if impl != "pallas":
        print(f"chip_smoke: kernel impl is {impl!r}, not 'pallas'",
              file=sys.stderr)
        return 1
    clock = CompileClock()
    if args.chips == 4:
        # float32 weights and matmuls: the two executors then differ only in
        # float32 summation order (see the module docstring)
        jax.config.update("jax_default_matmul_precision", "highest")
    dtype = "float32" if args.chips == 4 else None
    cfg, model, params = real_model(ARCH, widths="published",
                                    n_layers=N_LAYERS, dtype=dtype,
                                    seed=args.seed)
    n_params = sum(x.size for x in jax.tree_util.tree_leaves(params))
    log(f"model: {ARCH} published widths (d_model {cfg.d_model}, "
        f"{cfg.n_heads}x{cfg.head_dim} heads, {cfg.n_kv_heads} kv heads, "
        f"ffn {cfg.d_ff}, vocab {cfg.vocab_size}), depth cut to "
        f"{cfg.n_layers} layers, {cfg.dtype} params: {n_params} "
        f"parameters, page size {PAGE_SIZE}")
    try:
        if args.chips == 4:
            four_chips(cfg, model, params, args.seed)
        else:
            one_chip(cfg, model, params, args.seed)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    log(f"compile host wall-clock seconds: {clock.seconds:.3f} "
        f"({clock.count} backend compiles)")
    stats = [d.memory_stats() or {} for d in devs[: args.chips]]
    log("peak_bytes_in_use: "
        + json.dumps([s.get("peak_bytes_in_use") for s in stats]))
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
