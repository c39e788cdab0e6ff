"""The open-loop driver and the run record the metrics read.

The engine is a discrete-event loop on a modelled clock.  In real mode it
runs each step's device work synchronously inside the handler of the
step's completion event and reads the logits to the host, so the host wall
clock around one handled event is that step's real time.  The driver pumps
the engine one event at a time (`run(max_events=1)`), submits each request
once its wall-clock due time has passed (``submit(req, at=engine.clock)``,
between events: a request that comes due during a long step waits, and the
wait counts), and stamps every emitted token with the host wall clock
after the handler that produced it returns.  All times in a record are
seconds from the opening of the window.
"""
from __future__ import annotations

import time
from contextlib import nullcontext
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class CompileWatch:
    """Counts backend compiles (and their seconds) and persistent-cache
    loads, in total and since `mark()`."""

    def __init__(self):
        import jax

        self.compiles = 0
        self.compile_s = 0.0
        self.cache_hits = 0
        self._mark = (0, 0.0, 0)
        jax.monitoring.register_event_duration_secs_listener(self._on_dur)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_dur(self, event: str, duration: float, **_) -> None:
        if event == COMPILE_EVENT:
            self.compiles += 1
            self.compile_s += duration

    def _on_event(self, event: str, **_) -> None:
        if event == CACHE_HIT_EVENT:
            self.cache_hits += 1

    def mark(self) -> None:
        self._mark = (self.compiles, self.compile_s, self.cache_hits)

    def since_mark(self) -> Dict[str, float]:
        c, s, h = self._mark
        return {"compiles": self.compiles - c,
                "compile_s": self.compile_s - s,
                "cache_loads": self.cache_hits - h}


@dataclass
class Served:
    """One request of a phase: what was planned and what the host saw."""

    due: float
    n_prompt: int
    max_new: int
    req: Any = None  # the engine's Request, once submitted
    submitted: Optional[float] = None
    refused: bool = False
    stamps: List[float] = field(default_factory=list)
    prefill_t0: Optional[float] = None  # start of the event that prefilled it
    dop: Optional[int] = None  # instances of its prefill group


@dataclass
class Event:
    """One handled engine event with the work it did."""

    kind: str
    t0: float
    t1: float
    prefill: List[Tuple[int, int]] = field(default_factory=list)  # (ctx, new)
    decode: List[int] = field(default_factory=list)  # ctx of each decode row
    sampled: int = 0  # tokens emitted
    uploads: int = 0  # pool mirror slots uploaded during the event
    compiles: int = 0  # backend compiles requested during the event
    cache_loads: int = 0  # of which the persistent cache served


@dataclass
class RunRecord:
    """What the metrics read: the window's requests and events, plus the
    token stamps of requests submitted during warm-up that ran on into
    the window."""

    seconds: float
    model: Dict[str, Any]
    peak: Dict[str, Any]
    requests: List[Served]  # due in the window
    carried: List[Served]  # submitted in warm-up, still running at open
    events: List[Event]
    setup_s: float = 0.0
    trace: Optional[dict] = None

    def ttfts(self) -> List[float]:
        """Due time to first token for every request due in the window; a
        request with no first token by the close (refused ones included)
        counts the wait it has had so far."""
        out = []
        for s in self.requests:
            first = s.stamps[0] if s.stamps else None
            if first is None or first > self.seconds:
                out.append(self.seconds - s.due)
            else:
                out.append(first - s.due)
        return out

    def gaps(self) -> List[float]:
        """Every gap between successive tokens of one request that ends
        inside the window, pooled across requests."""
        out = []
        for s in self.requests + self.carried:
            st = s.stamps
            out += [b - a for a, b in zip(st, st[1:]) if 0.0 <= b <= self.seconds]
        return out

    def tokens_in_window(self) -> int:
        return sum(1 for s in self.requests + self.carried
                   for t in s.stamps if 0.0 <= t <= self.seconds)

    def in_window(self) -> List[Event]:
        return [e for e in self.events if e.t1 <= self.seconds]


def quantile(values: List[float], q: float) -> Optional[float]:
    """Linear-interpolated quantile (numpy's default), None when empty."""
    if not values:
        return None
    return float(np.quantile(np.asarray(values, np.float64), q))


class OpenLoop:
    """Drives one engine through phases of planned requests."""

    def __init__(self, engine, annotate: bool = False,
                 watch: Optional[CompileWatch] = None):
        from repro.engine.request import Request

        self.eng = engine
        self.watch = watch
        self._Request = Request
        self.annotate = annotate
        self.live: List[Served] = []  # submitted, not yet finished
        self.by_req: Dict[int, Served] = {}  # id(Request) -> its record
        self.events: List[Event] = []
        self.t_open = 0.0

    # --------------------------------------------------------------- phase
    def run(self, planned, seconds: float) -> List[Served]:
        """Serve ``planned`` (due times relative to now) for ``seconds`` of
        host wall clock; returns the phase's requests.  Stops at the close
        without draining: the event in hand finishes, nothing after it."""
        clock = time.perf_counter
        opened = clock()
        # requests carried over from the previous phase keep their stamps,
        # moved onto this phase's clock
        shift = self.t_open - opened
        for s in self.live:
            s.due += shift
            s.stamps = [t + shift for t in s.stamps]
        self.t_open = opened
        self.events = []
        served = [Served(p.due_s, len(p.prompt), p.max_new) for p in planned]
        end = self.t_open + seconds
        i = 0
        while True:
            now = clock()
            if now >= end:
                break
            while i < len(planned) and self.t_open + planned[i].due_s <= now:
                self._submit(served[i], planned[i])
                i += 1
            if self.eng.events:
                self._step()
            else:
                nxt = self.t_open + planned[i].due_s if i < len(planned) else end
                time.sleep(max(0.0, min(nxt, end) - clock()))
        return served

    def rel(self, t: float) -> float:
        return t - self.t_open

    def _submit(self, s: Served, p) -> None:
        eng = self.eng
        req = self._Request(input_len=len(p.prompt), max_new_tokens=p.max_new,
                            prompt=p.prompt.tolist())
        refused = eng.metrics.rejected
        eng.submit(req, at=eng.clock)
        s.req = req
        s.submitted = self.rel(time.perf_counter())
        if eng.metrics.rejected > refused:
            s.refused = True
            return
        self.live.append(s)
        self.by_req[id(req)] = s

    # ---------------------------------------------------------------- step
    def _work(self, kind: str, payload) -> Tuple[list, list, int]:
        """(prefill rows, decode rows, group size) the event is about to
        run, from its payload."""
        from repro.engine.request import Phase

        pre, dec, dop = [], [], 0
        if kind == "prefill_done":
            pre = [(r, 0, r.input_len) for r in payload.requests
                   if r.phase is Phase.PREFILL]
            dop = len([i for i in payload.instances if i not in self.eng.failed])
        elif kind == "decode_done":
            dec = [(r, r.seq_len - 1) for r in payload.requests
                   if r.phase is Phase.DECODE]
        elif kind == "unified_done":
            rows = {r.rid: r for r in payload.batch.requests}
            pre = [(rows[rid], start, ln)
                   for rid, (start, ln) in payload.chunks.items() if rid in rows]
            dec = [(r, r.seq_len - 1) for g in payload.groups
                   for r in g.requests if r.phase is Phase.DECODE]
            dop = len(payload.alive_instances(self.eng.failed))
        return pre, dec, dop

    def _step(self) -> None:
        eng = self.eng
        _, _, kind, payload = eng.events[0]
        pre, dec, dop = self._work(kind, payload)
        before = {id(s.req): len(s.req.output_tokens) for s in self.live}
        up0 = sum(p.mirror_uploaded_slots for p in eng.pool.pools)
        idx = len(self.events)
        if self.annotate:
            import jax

            span = jax.profiler.TraceAnnotation(f"engine.{kind}", i=idx)
        else:
            span = nullcontext()
        c0 = (self.watch.compiles, self.watch.cache_hits) if self.watch else (0, 0)
        with span:
            t0 = time.perf_counter()
            eng.run(max_events=1)
            t1 = time.perf_counter()
        t0, t1 = self.rel(t0), self.rel(t1)
        grew = set()
        still = []
        for s in self.live:
            n = len(s.req.output_tokens) - before[id(s.req)]
            if n > 0:
                grew.add(id(s.req))
                s.stamps += [t1] * n
            if s.req.finish_time is None:
                still.append(s)
        self.live = still
        ev = Event(kind, t0, t1)
        if self.watch:
            ev.compiles = self.watch.compiles - c0[0]
            ev.cache_loads = self.watch.cache_hits - c0[1]
        ev.uploads = sum(p.mirror_uploaded_slots for p in eng.pool.pools) - up0
        ev.sampled = len(grew)
        ev.decode = [ctx for r, ctx in dec if id(r) in grew]
        for r, ctx, new in pre:
            # a unified chunk that does not end its prompt samples nothing
            if kind == "unified_done" or id(r) in grew:
                ev.prefill.append((ctx, new))
            s = self.by_req.get(id(r))
            if s is not None and s.prefill_t0 is None and id(r) in grew:
                s.prefill_t0, s.dop = t0, dop
        self.events.append(ev)
