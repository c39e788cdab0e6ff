"""Time the prefill ring-chunk kernel at every candidate tile, on a TPU.

    python benchmarks/prefill_tile_sweep.py [--lengths 1536,4096,...]
        [--segments 1,4,8] [--reps 10] [--out prefill_tile_sweep.json]

One row per (widths, shard length, segment count, tile): the time of
shard 0's two launches of a DoP-2 ring step pair (its own KV chunk, then
the other shard's) with the float32 carry, on the host clock around a
blocking call, the median over ``--reps`` runs after a warm-up, and the
useful (causal, within-segment) attention FLOP/s it reaches at bf16 as a
share of the chip's peak.  A side that does not divide the length runs
halved (``used``).  The packed token axis is
``2 * length`` tokens cut into equal segments; the offsets always hold 8
segments (trailing ones empty), so one compiled kernel serves every segment
count.  Widths: glm4-9b's GQA groups (32 query heads, 2 KV heads, d 128)
and qwen1.5-4b's MHA (20 heads of 128, at the first two lengths and one
segment).  The operands are drawn once per (widths, length); every program
is lowered, then compiled in parallel threads, before the first is timed,
and a tile that fails to compile is reported as a row with its error.
Needs a TPU: anywhere else it exits with 2.
"""
from __future__ import annotations

import argparse
import concurrent.futures as cf
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "src"))

PEAK_BF16 = 197e12  # TPU v5e, Google Cloud "TPU v5e" documentation
WIDTHS = {"glm4-9b": (32, 2, 128), "qwen1.5-4b": (20, 20, 128)}
N_SEQS = 8


def _offsets(total: int, segments: int):
    import numpy as np

    off = np.full(N_SEQS + 1, total, np.int32)
    off[: segments + 1] = np.linspace(0, total, segments + 1).astype(np.int32)
    return off


def _useful_flops(h: int, d: int, off) -> float:
    """QK^T and PV over the causal pairs of every segment, over both
    shards' four launches, divided by two (shard 0's share)."""
    lens = [int(b - a) for a, b in zip(off[:-1], off[1:])]
    return 4 * h * d * sum(n * (n + 1) // 2 for n in lens) / 2


def _operands(model: str, length: int):
    """Shard 0's q, both shards' k/v and an empty float32 carry, drawn once
    per (widths, length) and shared by every tile."""
    import jax
    import jax.numpy as jnp

    h, kvh, d = WIDTHS[model]
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(length), 3)
    q = jax.random.normal(kq, (length, h, d), jnp.bfloat16)
    ks = jax.random.normal(kk, (2, length, kvh, d), jnp.bfloat16)
    vs = jax.random.normal(kv, (2, length, kvh, d), jnp.bfloat16)
    carry = (jnp.zeros((length, h, d), jnp.float32),
             jnp.full((length, h), -jnp.inf, jnp.float32),
             jnp.zeros((length, h), jnp.float32))
    return q, ks[0], vs[0], ks[1], vs[1], carry


def _args(operands, length: int, segments: int):
    """The launch pair's arguments at ``segments`` equal segments, and the
    packed offsets they come from."""
    import jax.numpy as jnp

    from repro.core import striped

    q, k0, v0, k1, v1, carry = operands
    off = _offsets(2 * length, segments)
    offs = [jnp.asarray(striped.shard_offsets(off, 2, r), jnp.int32)
            for r in range(2)]
    return (q, k0, v0, k1, v1, offs[0], offs[1], carry), off


def _pair(tile):
    """Jitted shard 0 of a DoP-2 ring step pair at ``tile``."""
    import jax

    from repro.kernels import ops

    bq, bk = tile

    def pair(q, k0, v0, k1, v1, off0, off1, carry):
        for k, v, ko, ks in ((k0, v0, off0, 0), (k1, v1, off1, 1)):
            carry = ops.prefill_ring_chunk(
                q, k, v, off0, ko, carry, q_shard=0, k_shard=ks, n_shards=2,
                impl="pallas", block_q=bq, block_k=bk)
        return carry

    return jax.jit(pair)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--lengths", default="1536,4096,8192,12288,16384")
    ap.add_argument("--segments", default="1,4,8")
    ap.add_argument("--reps", type=int, default=10)
    ap.add_argument("--threads", type=int, default=8)
    ap.add_argument("--out", default="prefill_tile_sweep.json")
    a = ap.parse_args(argv)

    import jax

    from repro.kernels.paged_flash_prefill import TILE_ORDER, _block

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"no TPU: JAX found {dev.platform}", file=sys.stderr)
        return 2
    lengths = [int(x) for x in a.lengths.split(",")]
    segments = [int(x) for x in a.segments.split(",")]
    cases = [("glm4-9b", n, segments) for n in lengths]
    cases += [("qwen1.5-4b", n, segments[:1]) for n in lengths[:2]]
    tiles = sorted(TILE_ORDER)

    operands = {(m, n): _operands(m, n) for m, n, _ in cases}
    t0 = time.perf_counter()
    lowered = {(m, n, t): _pair(t).lower(*_args(operands[m, n], n, 1)[0])
               for m, n, _ in cases for t in tiles}

    def compile_one(key):
        try:
            return key, lowered[key].compile()
        except Exception as e:  # a tile Mosaic refuses is a row, not a stop
            return key, f"{type(e).__name__}: {str(e).splitlines()[0]}"

    with cf.ThreadPoolExecutor(a.threads) as pool:
        compiled = dict(pool.map(compile_one, lowered))
    compile_s = time.perf_counter() - t0
    print(f"compiled {len(compiled)} programs in {compile_s:.1f} s",
          flush=True)

    rows = []
    for model, n, segs in cases:
        h, _, d = WIDTHS[model]
        for s in segs:
            args, off = _args(operands[model, n], n, s)
            for tile in tiles:
                row = {"model": model, "length": n, "segments": s,
                       "block_q": tile[0], "block_k": tile[1],
                       "used": [_block(n, tile[0]), _block(n, tile[1])]}
                fn = compiled[(model, n, tile)]
                if isinstance(fn, str):
                    rows.append({**row, "error": fn})
                    print(f"{model},{n},{s},{tile[0]}x{tile[1]},{fn}",
                          flush=True)
                    continue
                jax.block_until_ready(fn(*args))
                times = []
                for _ in range(a.reps):
                    t1 = time.perf_counter()
                    jax.block_until_ready(fn(*args))
                    times.append(time.perf_counter() - t1)
                ms = 1e3 * statistics.median(times)
                share = 100 * _useful_flops(h, d, off) / (ms / 1e3) / PEAK_BF16
                rows.append({**row, "ms": ms, "peak_share": share})
                print(f"{model},{n},{s},{tile[0]}x{tile[1]},{ms:.3f},"
                      f"{share:.2f}", flush=True)
    os.makedirs(os.path.dirname(a.out) or ".", exist_ok=True)
    with open(a.out, "w") as f:
        json.dump({"device": dev.device_kind, "compile_s": compile_s,
                   "rows": rows}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
