#!/usr/bin/env python3
"""The serving program's own host spans, read from a profiler trace beside
what `xplane` reads.

`xplane` names every idle gap and every kernel by the driver's
``engine.<kind>`` span.  Inside those spans the program opens its own,
all named ``loong.*``: ``loong.schedule`` (the scheduler's pass),
``loong.prefill``, ``loong.decode`` and ``loong.unified`` with their
``.pack``, ``.launch``, ``.wait`` and ``.sample`` phases (the executor's
host work), and ``loong.kv.write``, ``loong.kv.upload`` and
``loong.kv.host_sync`` (the KV pool).  They sit on the host plane, on the
clock of the device planes.  `reduce` returns `xplane.reduce`'s summary
with its keys as they are, plus:

* ``loong_idle_s``: device-idle time in the window inside a ``loong.*``
  span;
* ``idle_by_span``: every second of device-idle time in the window, by
  ``engine.<kind>>loong.<innermost span>`` (the innermost ``loong.*``
  span open then; ``engine.<kind>`` alone where none is, ``driver.wait``
  outside the driver's spans);
* ``idle_gaps_named``: the longest idle gaps under those labels, each
  named at its midpoint as `xplane` names them;
* ``device_ops_named``: the ops that took most device time, a Pallas op
  under the name its ``pallas_call`` gave it (``kernel_metadata``);
* ``kv_write_s``: per ``loong.kv.write`` span, from its start to the end
  of the last device op that started inside it (or to the span's own end,
  if later), summed;
* ``sched_s``: the summed time of the ``loong.schedule`` spans;
* ``prefilled``: requests of the ``loong.prefill`` spans that ended in
  the window (their ``n_req``);
* ``loong_spans``: the number of ``loong.*`` spans in the window.

    python3 bench/spans.py <trace.xplane.pb or a directory holding one>

prints that summary as JSON.
"""
from __future__ import annotations

import bisect
import glob
import json
import os
import re
import sys
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import xplane  # noqa: E402

PREFIX = "loong."
# the custom call's frontend attribute, as the compiled HLO text and the
# trace's op names print it: kernel_metadata={"kernel":"<name>"}, with the
# quotes escaped in some printings
KERNEL_RE = re.compile(
    r'kernel_metadata="?\{[^}]*?\\?"kernel\\?"\s*:\s*\\?"([\w.-]+)')


def kernel_name(op: str) -> Optional[str]:
    """The name a Pallas op's ``pallas_call(metadata={"kernel": ...})``
    gave it, or None."""
    m = KERNEL_RE.search(op)
    return m.group(1) if m else None


def label(op: str, module: str = "") -> str:
    """`xplane.short`, with a named Pallas op called by its kernel name."""
    name = kernel_name(op) if xplane.is_kernel(op) else None
    if name is None:
        return xplane.short(op, module)
    mod = module.split("(", 1)[0]
    return f"{mod}:{name}[pallas]" if mod else f"{name}[pallas]"


def events(profile):
    """`xplane._events`' (host, device) and the ``loong.*`` spans as
    [(name, start, end, args)], times in ns."""
    host, dev = xplane._events(profile)
    loong = []
    for plane in profile.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(PREFIX):
                    s = int(ev.start_ns)
                    loong.append((ev.name, s, s + int(ev.duration_ns),
                                  dict(ev.stats)))
    return host, loong, dev


def innermost(spans) -> List[Tuple[int, int, str]]:
    """Disjoint (start, end, name) pieces, each named by the innermost of
    the nested ``spans`` [(name, start, end, ...)] open over it."""
    out: List[Tuple[int, int, str]] = []
    stack: List[Tuple[int, str]] = []  # (end, name), innermost last
    t = None

    def close_to(limit):
        nonlocal t
        while stack and stack[-1][0] <= limit:
            end, name = stack.pop()
            if end > t:
                out.append((t, end, name))
                t = end

    for name, s, e, *_ in sorted(spans, key=lambda x: (x[1], -x[2])):
        close_to(s)
        if stack and s > t:
            out.append((t, s, stack[-1][1]))
        t = s if t is None else max(t, s)
        # a span that outlives its parent (another thread) is cut to it
        stack.append((min(e, stack[-1][0]) if stack else e, name))
    if stack:
        close_to(stack[0][0])
    return out


def _at(pieces: List[Tuple[int, int, str]], starts: List[int],
        t: float) -> Optional[str]:
    i = bisect.bisect_right(starts, t) - 1
    return pieces[i][2] if i >= 0 and pieces[i][1] > t else None


def reduce(host: list, loong: list, dev: Dict[str, list]) -> Optional[dict]:
    """`xplane.reduce(host, dev)` with the keys of this module's docstring
    added, or None where `xplane` reads nothing."""
    out = xplane.reduce(host, dev)
    if out is None:
        return None
    w0, w1 = next((s, e) for n, s, e, _ in host if n == xplane.WINDOW)
    drv = sorted((s, e, n) for n, s, e, _ in host
                 if n.startswith(xplane.SPAN_PREFIX) and s >= w0 and e <= w1)
    inside = [sp for sp in loong if sp[1] < w1 and sp[2] > w0]
    pieces = innermost(inside)
    p_starts = [p[0] for p in pieces]
    d_starts = [s for s, _, _ in drv]
    ops = sorted((max(s, w0), min(e, w1), n, rest[0] if rest else "")
                 for v in dev.values() for n, s, e, *rest in v
                 if e > w0 and s < w1)
    busy = xplane.merged([(s, e) for s, e, _, _ in ops])
    b_starts = [s for s, _ in busy]

    def busy_at(t: float) -> bool:
        i = bisect.bisect_right(b_starts, t) - 1
        return i >= 0 and busy[i][1] > t

    def name_at(t: float) -> str:
        i = bisect.bisect_right(d_starts, t) - 1
        eng = drv[i][2] if i >= 0 and drv[i][1] >= t else "driver.wait"
        span = _at(pieces, p_starts, t)
        return f"{eng}>{span}" if span else eng

    # idle time by label, exact: cut the window at every boundary
    cuts = {w0, w1}
    for s, e in busy:
        cuts.update((s, e))
    for s, e, _ in drv:
        cuts.update((s, e))
    for s, e, _ in pieces:
        cuts.update((s, e))
    cuts = sorted(t for t in cuts if w0 <= t <= w1)
    idle: Dict[str, int] = defaultdict(int)
    for a, b in zip(cuts, cuts[1:]):
        mid = (a + b) / 2
        if b > a and not busy_at(mid):
            idle[name_at(mid)] += b - a
    gaps = []
    prev = w0
    for s, e in busy + [(w1, w1)]:
        if s > prev:
            gaps.append((name_at((s + prev) // 2), (s - prev) / 1e9))
        prev = max(prev, e)
    gaps.sort(key=lambda x: -x[1])
    op_time: Dict[str, int] = defaultdict(int)
    for s, e, n, mod in ops:
        op_time[label(n, mod)] += e - s

    o_starts = [s for s, _, _, _ in ops]
    kv_write = sched = prefilled = 0
    for name, s, e, args in inside:
        if name == "loong.kv.write":
            lo = bisect.bisect_left(o_starts, s)
            hi = bisect.bisect_left(o_starts, e)
            end = max([e] + [ops[j][1] for j in range(lo, hi)])
            kv_write += end - s
        elif name == "loong.schedule":
            sched += min(e, w1) - max(s, w0)
        elif name == "loong.prefill" and e <= w1:
            prefilled += int(args.get("n_req", 0))
    out.update({
        "loong_idle_s": sum(t for k, t in idle.items() if ">" in k) / 1e9,
        "idle_by_span": {k: t / 1e9 for k, t in
                         sorted(idle.items(), key=lambda x: -x[1])},
        "idle_gaps_named": [[n, t] for n, t in gaps[:10]],
        "device_ops_named": [[n, t / 1e9] for n, t in sorted(
            op_time.items(), key=lambda x: -x[1])[:10]],
        "kv_write_s": kv_write / 1e9,
        "sched_s": sched / 1e9,
        "prefilled": prefilled,
        "loong_spans": len(inside),
    })
    return out


def read(path: str) -> Optional[dict]:
    """`reduce` over one ``.xplane.pb`` file."""
    from jax.profiler import ProfileData

    with open(path, "rb") as f:
        profile = ProfileData.from_serialized_xspace(f.read())
    return reduce(*events(profile))


def main(argv=None) -> int:
    args = sys.argv[1:] if argv is None else argv
    if len(args) != 1:
        print(__doc__, file=sys.stderr)
        return 2
    path = args[0]
    if os.path.isdir(path):
        files = glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                          recursive=True)
        if not files:
            print(f"no .xplane.pb under {path}", file=sys.stderr)
            return 1
        path = files[0]
    out = read(path)
    if out is not None:
        out["kernel_s"] = {str(k): v for k, v in out["kernel_s"].items()}
    print(json.dumps(out))
    return 0 if out is not None else 1


if __name__ == "__main__":
    sys.exit(main())
