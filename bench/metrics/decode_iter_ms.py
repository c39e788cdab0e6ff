"""Host wall time of the decode iterations in the window, over their
number."""


def value(rec):
    evs = [e for e in rec.in_window() if e.decode and not e.prefill]
    if not evs:
        return None
    return sum(e.t1 - e.t0 for e in evs) / len(evs) * 1e3
