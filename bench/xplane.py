"""Reduction of one profiler trace to the numbers the metrics read.

The traced run wraps its whole measured window in a host annotation
``bench.window`` and every engine event it handles in ``engine.<kind>``,
carrying the event's index in the run record as the annotation's argument
``i``.  From the
device planes (``/device:TPU:<n>``, line ``XLA Ops``) this module takes:

* busy time: the union of the intervals in which an operation ran, inside
  the window, averaged over the devices that ran anything;
* kernel time per engine event: the summed device time of the Pallas
  kernels (`is_kernel`) that started inside that event's host span;
* the breakdown: the operations that took most device time, and the
  longest idle gaps, each named by the host span open at its midpoint.

It reads an ``.xplane.pb`` with `jax.profiler.ProfileData` and nothing of
the program.
"""
from __future__ import annotations

import bisect
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

WINDOW = "bench.window"
SPAN_PREFIX = "engine."


def is_kernel(name: str) -> bool:
    """A Pallas (Mosaic) kernel's device op.  Neither of the program's
    `pallas_call`s is named, so both reach the trace as an op whose HLO text
    calls the custom-call target ``tpu_custom_call`` (``%closed_call.N`` in
    the jitted prefill step, ``%tpu_custom_call.N`` in eager decode); the
    host span they started in tells prefill from decode."""
    return 'custom_call_target="tpu_custom_call"' in name


def short(op: str, module: str = "") -> str:
    """A readable name for one device op: its enclosing program, without
    the fingerprint, and the HLO instruction name (the op's HLO text runs to
    hundreds of characters)."""
    name = op.split(" ", 1)[0]
    if is_kernel(op):
        name += "[pallas]"
    mod = module.split("(", 1)[0]
    return f"{mod}:{name}" if mod else name


def union_length(intervals: List[Tuple[int, int]]) -> int:
    """Total length covered by half-open intervals (any order)."""
    total, end = 0, None
    for s, e in sorted(intervals):
        if end is None or s > end:
            total += e - s
            end = e
        elif e > end:
            total += e - end
            end = e
    return total


def merged(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[List[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _events(profile):
    """([(annotation, start, end, index)], {device: [(op, start, end,
    program)]}), times in ns."""
    host: List[Tuple[str, int, int, Optional[int]]] = []
    dev: Dict[str, list] = defaultdict(list)
    for plane in profile.planes:
        if plane.name.startswith("/device:"):
            lines = {line.name: line for line in plane.lines}
            if "XLA Ops" not in lines:
                continue
            mods = sorted((int(ev.start_ns), int(ev.start_ns + ev.duration_ns),
                           ev.name) for ev in
                          (lines["XLA Modules"].events
                           if "XLA Modules" in lines else []))
            mstart = [m[0] for m in mods]
            for ev in lines["XLA Ops"].events:
                s = int(ev.start_ns)
                i = bisect.bisect_right(mstart, s) - 1
                mod = mods[i][2] if i >= 0 and mods[i][1] >= s else ""
                dev[plane.name].append(
                    (ev.name, s, s + int(ev.duration_ns), mod))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == WINDOW or ev.name.startswith(SPAN_PREFIX):
                        s = int(ev.start_ns)
                        idx = dict(ev.stats).get("i")
                        host.append((ev.name, s, s + int(ev.duration_ns),
                                     None if idx is None else int(idx)))
    return host, dev


def reduce(host: list, dev: Dict[str, list]) -> Optional[dict]:
    """The trace summary, or None when the trace holds no window or no
    device operation inside it."""
    windows = [(s, e) for n, s, e, _ in host if n == WINDOW]
    if not windows:
        return None
    w0, w1 = windows[0]
    spans = sorted((s, e, n, i) for n, s, e, i in host
                   if n.startswith(SPAN_PREFIX) and s >= w0 and e <= w1)
    starts = [s for s, _, _, _ in spans]

    def span_at(t: int) -> Optional[int]:
        i = bisect.bisect_right(starts, t) - 1
        return i if i >= 0 and spans[i][1] >= t else None

    busy_per_dev = []
    op_time: Dict[str, int] = defaultdict(int)
    kernel_ns: Dict[int, int] = defaultdict(int)
    kernel_names: Dict[str, int] = defaultdict(int)
    unassigned = 0
    all_busy: List[Tuple[int, int]] = []
    for ops in dev.values():
        inside = [(max(s, w0), min(e, w1), n, mod) for n, s, e, *rest in ops
                  for mod in [rest[0] if rest else ""]
                  if e > w0 and s < w1]
        if not inside:
            continue
        iv = [(s, e) for s, e, _, _ in inside]
        busy_per_dev.append(union_length(iv))
        all_busy += iv
        for s, e, n, mod in inside:
            op_time[short(n, mod)] += e - s
            if is_kernel(n):
                i = span_at(s)
                if i is not None and spans[i][3] is not None:
                    kernel_ns[spans[i][3]] += e - s
                    kernel_names[short(n, mod)] += 1
                else:
                    unassigned += 1
    if not busy_per_dev:
        return None
    gaps = []
    prev = w0
    for s, e in merged(all_busy) + [(w1, w1)]:
        if s > prev:
            i = span_at((s + prev) // 2)
            label = spans[i][2] if i is not None else "driver.wait"
            gaps.append((label, (s - prev) / 1e9))
        prev = max(prev, e)
    gaps.sort(key=lambda x: -x[1])
    top_ops = sorted(op_time.items(), key=lambda x: -x[1])[:10]
    return {
        "window_s": (w1 - w0) / 1e9,
        "busy_s": sum(busy_per_dev) / len(busy_per_dev) / 1e9,
        "devices": len(busy_per_dev),
        "kernel_s": {i: ns / 1e9 for i, ns in kernel_ns.items()},
        "kernel_names": dict(kernel_names),
        "kernels_outside_spans": unassigned,
        "spans": len(spans),
        "device_ops": [[n, t / 1e9] for n, t in top_ops],
        "idle_gaps": [[n, t] for n, t in gaps[:10]],
    }


def read(path: str) -> Optional[dict]:
    """`reduce` over one ``.xplane.pb`` file."""
    from jax.profiler import ProfileData

    with open(path, "rb") as f:
        profile = ProfileData.from_serialized_xspace(f.read())
    return reduce(*_events(profile))


def names(path: str, limit: int = 60) -> dict:
    """Op and line names with counts, and the stats of the first event of
    each name, for looking at a trace by hand."""
    from jax.profiler import ProfileData

    with open(path, "rb") as f:
        profile = ProfileData.from_serialized_xspace(f.read())
    out: Dict[str, dict] = {}
    for plane in profile.planes:
        for line in plane.lines:
            cnt: Dict[str, int] = defaultdict(int)
            first: Dict[str, list] = {}
            for ev in line.events:
                cnt[ev.name] += 1
                if ev.name not in first:
                    try:
                        first[ev.name] = [[str(k), str(v)[:200]]
                                          for k, v in list(ev.stats)[:12]]
                    except Exception as e:  # the stats API is not stable
                        first[ev.name] = [["error", repr(e)]]
            top = sorted(cnt.items(), key=lambda x: -x[1])[:limit]
            out[f"{plane.name} | {line.name}"] = {
                n: {"count": c, "stats": first[n]} for n, c in top}
    return out
