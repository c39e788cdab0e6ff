"""The comparison that decides `correct` for a served model.

Once the window has closed, a sample of the requests that the window served
is drawn from the seed: the one with the longest prompt, the longest of
those prefilled at each other group size (so the DoP-2 ring and the DoP-1
packed prefill are both in it whenever the window ran both), then finished
requests at random and then unfinished ones, until the sample holds
``tokens`` served tokens or ``most`` requests (the reference's time over
long prompts bounds it).  An unfinished request contributes every token it
was served by the close.

The harness keeps the logits row from which the program sampled each
served token (`run.tap_logits`).  The plain reference runs once over each
prompt followed by its served tokens, and at every served position reads
two gaps: the widest distance between the program's logit and the
reference's over the whole vocabulary, and the gap by which the served
token's reference logit lies below the reference's best.  The number
compared, ``widest_logit_error``, is the larger of the two over the
sample.  The first is the program's rounding and every fault of the model
step; the second catches a token altered after the logits (greedy decoding
serves the argmax, so for a sound run it is at most twice the first).

The control puts the reference in the program's place, computed in fp8
(`references/<name>.py`, ``mode="fp8"``: every linear layer's inputs
rounded to float8 e4m3, the step below the configurations' bf16): at the
same positions its logits and the token they put first are read the same
way.
"""
from __future__ import annotations

import importlib.util
import os
from typing import Dict, List, Optional

import numpy as np


def reference(name: str, root: str):
    """The reference module ``references/<name>.py`` under ``root``."""
    path = os.path.join(root, "references", f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"bench_ref_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def choose(done: List[dict], seed: int, tokens: int,
           most: int) -> List[dict]:
    """Sample of at most ``most`` served requests (dicts with ``prompt``,
    ``out``, ``dop`` and ``finished``)."""
    if not done:
        return []
    rng = np.random.default_rng([int(seed), 2])
    order = sorted(range(len(done)), key=lambda i: -len(done[i]["prompt"]))
    pick = [order[0]]
    for dop in sorted({d["dop"] for d in done}):
        if not any(done[i]["dop"] == dop for i in pick):
            pick.append(next(i for i in order if done[i]["dop"] == dop))
    rest = [int(i) for i in rng.permutation(len(done)) if i not in pick]
    rest.sort(key=lambda i: not done[i]["finished"])  # stable: finished first
    while rest and sum(len(done[i]["out"]) for i in pick) < tokens:
        pick.append(rest.pop(0))
    return [done[i] for i in pick[:most]]


def widest(want: np.ndarray, got: np.ndarray, served) -> float:
    """Larger of the widest |logit - reference| over every row and column
    and the widest gap of a served token below the reference's best."""
    err = float(np.abs(np.asarray(got, np.float32) - want).max())
    at = want[np.arange(len(want)), np.asarray(served)]
    return max(err, float((want.max(axis=1) - at).max()))


def readings(params, m: dict, ref, sample: List[dict],
             control: bool = False) -> Dict[str, float]:
    """``widest_logit_error`` of the served tokens and their logits (and
    of the fp8 control's, when ``control``) over the sample."""
    worst = 0.0
    worst_ctl: Optional[float] = None
    served = 0
    for d in sample:
        prompt, out = np.asarray(d["prompt"], np.int32), list(d["out"])
        seq = np.concatenate([prompt, np.asarray(out[:-1], np.int32)])
        rows = np.arange(len(prompt) - 1, len(seq))
        want = ref.logits(params, m, seq, rows)
        worst = max(worst, widest(want, d["logits"], out))
        served += len(out)
        if control:
            low = ref.logits(params, m, seq, rows, mode="fp8")
            g = widest(want, low, low.argmax(axis=1))
            worst_ctl = g if worst_ctl is None else max(worst_ctl, g)
    out = {"widest_logit_error": worst, "served_tokens": served}
    if worst_ctl is not None:
        out["control_widest_logit_error"] = worst_ctl
    return out
