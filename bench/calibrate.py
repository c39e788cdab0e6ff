#!/usr/bin/env python3
"""Readings that set a cell's rate and its correctness limit, many runs in
one process (the compiled programs stay warm between them).

    python3 bench/calibrate.py --workload <cell> --seeds 11,12,13 \\
        --seconds 20 [--control 11,12,13] [--rates 0.3,0.5,0.7]

For each seed (and each rate, when ``--rates`` is given) it runs the cell
as `run.py` does and prints one JSON line: the widest logit error of the
served tokens, the fp8 control's on the same sample for the seeds in
``--control`` and whether the harness's comparison passes it (it must
not), the end-to-end metrics, and how far the queue got behind
(requests due in the window that no prefill had started by its close).
Its set-up times are not `setup_s`: only a fresh process measures that.
"""
from __future__ import annotations

import argparse
import json
import sys

import run


def ints(s: str):
    return [int(x) for x in s.split(",") if x]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=ints, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control", type=ints, default=[])
    ap.add_argument("--rates", default="")
    args = ap.parse_args(argv)
    rates = [float(x) for x in args.rates.split(",") if x] or [None]
    try:
        for rate in rates:
            for seed in args.seeds:
                result, extra = run.run_cell(
                    args.workload, seed, args.seconds, False, rate=rate,
                    control=seed in args.control)
                rec = extra.pop("record")
                run.save(f"calibrate.{args.workload}.{seed}.{rate}", result,
                         extra, rec)
                line = {
                    "seed": seed, "rate": rate,
                    "widest_logit_error":
                        result["check"]["widest_logit_error"]["value"],
                    "control_widest_logit_error":
                        extra.get("control_widest_logit_error"),
                    "control_correct": extra.get("control_correct"),
                    "served_tokens": extra["check_served_tokens"],
                    "check_requests": extra["check_requests"],
                    "check_s": extra["check_s"],
                    "not_prefilled_at_close": sum(
                        s.prefill_t0 is None for s in rec.requests),
                    "requests": extra["requests"],
                    "window_compiles": extra["window_compiles"],
                    "metrics": {k: v["value"]
                                for k, v in result["metrics"].items()},
                    "memory_peak_bytes":
                        result["device"]["memory_peak_bytes"],
                }
                print("calibrate: " + json.dumps(line), flush=True)
    except run.NoChip as e:
        print(f"calibrate: {e}", file=sys.stderr)
        return 2
    return 0


if __name__ == "__main__":
    sys.exit(main())
