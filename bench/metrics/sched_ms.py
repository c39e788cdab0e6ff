"""Summed time of the scheduler's ``loong.schedule`` spans in the traced
window (`spans.reduce`'s ``sched_s``), over the requests prefilled there,
in ms."""


def value(rec):
    t = rec.trace or {}
    if "sched_s" not in t or not t.get("prefilled"):
        return None
    return t["sched_s"] / t["prefilled"] * 1e3
