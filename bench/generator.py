"""Open-loop traffic: one general generator for every mix under `traffic/`.

A mix is a JSON file of parameters.  Arrivals are a Poisson process at the
cell's rate; prompt lengths follow a clipped lognormal and output lengths a
uniform range, the shapes of the paper's datasets (ShareGPT, L-Eval,
LV-Eval; LoongServe §7.1).  The length arithmetic is a copy of the
program's `data/workload.py` sampler, kept here so that the yardstick does
not move with the program.

One seed gives one list of requests (`plan`).  Seeds may exceed 32 bits;
numpy's generator takes any non-negative int.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from statistics import NormalDist
from typing import List

import numpy as np


@dataclass(frozen=True)
class Mix:
    """Parameters of one traffic mix (a `traffic/<name>.json` file)."""

    name: str
    prompt_median: float
    prompt_sigma: float
    prompt_min: int
    prompt_max: int
    out_min: int
    out_max: int

    @classmethod
    def from_dict(cls, name: str, d: dict) -> "Mix":
        keys = ("prompt_median", "prompt_sigma", "prompt_min", "prompt_max",
                "out_min", "out_max")
        missing = [k for k in keys if k not in d]
        if missing:
            raise ValueError(f"traffic {name}: missing {missing}")
        mix = cls(name, float(d["prompt_median"]), float(d["prompt_sigma"]),
                  int(d["prompt_min"]), int(d["prompt_max"]),
                  int(d["out_min"]), int(d["out_max"]))
        if not (1 <= mix.prompt_min <= mix.prompt_max
                and 1 <= mix.out_min <= mix.out_max):
            raise ValueError(f"traffic {name}: empty length range")
        return mix


@dataclass(frozen=True)
class Planned:
    """One request as the generator plans it; `due_s` is relative to the
    start of its phase (warm-up or window)."""

    due_s: float
    prompt: np.ndarray  # int32 token ids
    max_new: int


def sample_lengths(mix: Mix, u_prompt: float, u_out: float):
    """(prompt length, output length) at quantiles ``u_prompt``/``u_out`` of
    the program's `LengthDist`: a lognormal prompt clipped to its range and
    a uniform output length, the same arithmetic as `LengthDist.sample`
    with the draws replaced by quantiles."""
    z = NormalDist().inv_cdf(u_prompt)
    ln = int(np.clip(math.exp(math.log(mix.prompt_median)
                              + mix.prompt_sigma * z),
                     mix.prompt_min, mix.prompt_max))
    span = mix.out_max - mix.out_min + 1
    out = mix.out_min + min(int(u_out * span), span - 1)
    return ln, out


def plan(mix: Mix, rate: float, seconds: float, seed: int, vocab: int,
         stream: int) -> List[Planned]:
    """``floor(rate * seconds)`` requests due in ``[0, seconds)``.

    Every seed gets the same set of requests in its own order: the prompt
    and output lengths are the stratified quantiles ``(i + 1/2) / n`` of
    the mix's distributions, the gaps between arrivals the same quantiles
    of the exponential at ``rate`` (a Poisson process's gaps, unscaled),
    and the seed draws the order of each list and the token ids.  So runs
    of one cell on different seeds do the same amount of work, and what
    the order changes (which prompt queues behind which) is in their
    spread.  The gaps sum to less than ``seconds`` for any ``n >= 1``.
    ``stream`` separates the warm-up traffic (0) from the window's (1)."""
    if rate <= 0 or seconds <= 0:
        raise ValueError(f"rate and seconds must be positive: {rate}, {seconds}")
    n = max(int(rate * seconds), 1)
    rng = np.random.default_rng([int(seed), int(stream)])
    u = (np.arange(n) + 0.5) / n
    due = np.cumsum(rng.permutation(-np.log1p(-u) / rate))
    u_prompt, u_out = rng.permutation(u), rng.permutation(u)
    out: List[Planned] = []
    for i in range(n):
        ln, new = sample_lengths(mix, float(u_prompt[i]), float(u_out[i]))
        prompt = rng.integers(0, vocab, ln, dtype=np.int32)
        out.append(Planned(float(due[i]), prompt, new))
    return out
