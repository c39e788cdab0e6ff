"""End-to-end serving driver (the paper's kind of system => serving driver).

Runs the LoongServe engine over a synthetic workload, in `sim` mode (SIB
clock; paper-scale) or `real` mode (a model with random weights actually
generating tokens through the distributed pools).  Real mode runs the toy
float32 preset (`configs.reduced`, what the CPU tests use) unless
``--widths published`` keeps the architecture's published widths and dtype,
optionally cut to ``--layers`` whole layers.

  PYTHONPATH=src python -m repro.launch.serve --arch lwm-7b --dataset mixed \
      --rate 0.5 --n 64 --system loongserve
  PYTHONPATH=src python -m repro.launch.serve --real --n 8 --dataset sharegpt
  PYTHONPATH=src python -m repro.launch.serve --real --widths published \
      --layers 4 --instances 2 --capacity 8192 --page-size 16 --max-len 4096
"""
from __future__ import annotations

import argparse
import json
import sys
from typing import Optional


def build_engine(system: str, cfg, n_instances: int, capacity: int, **kw):
    from repro.baselines import (
        ChunkedPrefillEngine,
        FixedGroupsEngine,
        PDDisaggEngine,
        StaticTPEngine,
    )
    from repro.engine.server import LoongServeEngine

    if system == "loongserve":
        return LoongServeEngine(cfg, n_instances, capacity, **kw)
    if system == "vllm-tp":
        return StaticTPEngine(cfg, n_instances, capacity, **kw)
    if system == "chunked":
        return ChunkedPrefillEngine(cfg, n_instances, capacity, **kw)
    if system == "pd-disagg":
        return PDDisaggEngine(cfg, n_instances, capacity, **kw)
    if system == "replicated":
        groups = [[i] for i in range(n_instances)]
        return FixedGroupsEngine(cfg, n_instances, capacity, groups=groups, **kw)
    raise ValueError(system)


def real_model(arch: str, *, widths: str = "reduced",
               n_layers: Optional[int] = None, dtype: Optional[str] = None,
               seed: int = 0):
    """(cfg, model, params) for real mode: random weights from ``seed``.
    ``widths="reduced"`` is the toy float32 preset; ``"published"`` keeps
    the config's widths and dtype.  ``n_layers`` cuts depth to that many
    whole layers; ``dtype`` overrides the parameter dtype."""
    import dataclasses

    import jax

    from repro.configs import get_config, reduced
    from repro.models import build_model

    cfg = get_config(arch)
    if widths == "reduced":
        cfg = reduced(cfg)
    elif widths != "published":
        raise ValueError(f"widths={widths!r}: expected reduced or published")
    if n_layers is not None:
        cfg = dataclasses.replace(cfg, n_layers=n_layers)
    if dtype is not None:
        cfg = dataclasses.replace(cfg, dtype=dtype)
    model = build_model(cfg)
    params = jax.jit(model.init)(jax.random.PRNGKey(seed))
    return cfg, model, params


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="lwm-7b")
    ap.add_argument("--system", default="loongserve",
                    choices=["loongserve", "vllm-tp", "chunked", "pd-disagg",
                             "replicated"])
    ap.add_argument("--dataset", default="mixed",
                    choices=["sharegpt", "leval", "lveval", "mixed"])
    ap.add_argument("--rate", type=float, default=0.5)
    ap.add_argument("--n", type=int, default=64)
    ap.add_argument("--instances", type=int, default=8)
    ap.add_argument("--capacity", type=int, default=None,
                    help="KV slots per instance (sim: 250000, real: 4096)")
    ap.add_argument("--real", action="store_true",
                    help="real token generation with random weights")
    ap.add_argument("--widths", default="reduced",
                    choices=["reduced", "published"],
                    help="real mode: toy float32 preset or published widths")
    ap.add_argument("--layers", type=int, default=None,
                    help="real mode: keep this many whole layers")
    ap.add_argument("--page-size", type=int, default=1)
    ap.add_argument("--max-len", type=int, default=256,
                    help="real mode: prompt length cap")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--json", action="store_true")
    args = ap.parse_args(argv)

    from repro.configs import get_config
    from repro.data import poisson_workload, with_prompts

    kw = {"page_size": args.page_size}
    if args.real:
        from repro.launch.compile_cache import enable_compile_cache

        enable_compile_cache()
        cfg, model, params = real_model(
            args.arch, widths=args.widths, n_layers=args.layers,
            seed=args.seed,
        )
        kw.update(store_values=True, model=model, params=params)
        capacity = args.capacity or 4096
        reqs = poisson_workload(args.dataset, args.n, args.rate,
                                seed=args.seed, max_len=args.max_len)
        for r in reqs:
            r.max_new_tokens = min(r.max_new_tokens, 16)
        with_prompts(reqs, cfg.vocab_size, args.seed)
    else:
        cfg = get_config(args.arch)
        capacity = args.capacity or 250_000
        reqs = poisson_workload(args.dataset, args.n, args.rate, seed=args.seed)

    eng = build_engine(args.system, cfg, args.instances, capacity, **kw)
    for r in reqs:
        eng.submit(r)
    metrics = eng.run()
    summary = metrics.summary()
    if args.json:
        print(json.dumps(summary, indent=1))
    else:
        print(f"=== {args.system} on {args.dataset} (rate {args.rate}) ===")
        for k, v in summary.items():
            print(f"  {k:28s} {v}")
        if args.real and metrics.finished:
            r0 = metrics.finished[0]
            print(f"  sample output tokens: {r0.output_tokens[:8]}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
