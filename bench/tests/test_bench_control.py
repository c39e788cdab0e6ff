"""The control that `correct` must fail (the reference computed in fp8),
at a toy size on the CPU, and the runs the harness refuses."""
import os
import shutil
import subprocess
import sys

import numpy as np

import check
import weights
from benchtools import BENCH, TINY_MODEL

ROOT = os.path.dirname(BENCH)
LIMIT = 0.01  # the tiny cell's limit (see test_bench_run.py)


def test_fp8_control_reads_above_the_limit_and_the_program_below():
    """On the same positions, the served path's bf16 rounding stays under
    the limit and the reference computed in fp8 goes over it."""
    import jax
    import jax.numpy as jnp

    from repro.configs.base import ModelConfig
    from repro.models import build_model

    m = dict(TINY_MODEL)
    ref = check.reference("dense", BENCH)
    prog_gap, ctl_gap = [], []
    for seed in range(3):
        params = weights.make(m, seed)
        toks = np.random.default_rng(seed).integers(0, m["vocab_size"], 120)
        rows = np.arange(10, 120)
        with jax.default_matmul_precision("highest"):
            want = ref.logits(params, m, toks, rows)
            low = ref.logits(params, m, toks, rows, mode="fp8")
        served = np.asarray(build_model(ModelConfig(**m)).forward(
            params, {"tokens": jnp.asarray(toks)[None]})[0][0])[rows]
        prog_gap.append(check.widest(want, served, served.argmax(axis=1)))
        ctl_gap.append(check.widest(want, low, low.argmax(axis=1)))
    assert max(prog_gap) < LIMIT < min(ctl_gap)


def _bench_cmd(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "glm4-9b.leval32k.choice",
         "--seed", "3", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_no_tpu_is_refused_without_a_result():
    out = _bench_cmd(ROOT)
    assert out.returncode != 0
    assert "no TPU" in out.stderr
    assert not [l for l in out.stdout.splitlines() if l.startswith("{")]


def test_benchmark_alone_without_the_program_is_refused(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".*", "__pycache__"))
    out = _bench_cmd(tmp_path)
    assert out.returncode != 0
    assert not [l for l in out.stdout.splitlines() if l.startswith("{")]
