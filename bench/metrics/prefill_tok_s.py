"""Prompt tokens prefilled in the window, over the host wall time of the
engine events that prefilled them."""


def value(rec):
    evs = [e for e in rec.in_window() if e.prefill]
    wall = sum(e.t1 - e.t0 for e in evs)
    toks = sum(new for e in evs for _, new in e.prefill)
    return toks / wall if wall > 0 else None
