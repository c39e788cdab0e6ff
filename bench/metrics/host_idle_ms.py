"""Device-idle time in the traced window that falls inside one of the
program's ``loong.*`` host spans (`spans.reduce`'s ``loong_idle_s``), over
the requests its ``loong.prefill`` spans prefilled there, in ms: the host
work of the scheduler, executor and KV pool that the device waits on."""


def value(rec):
    t = rec.trace or {}
    if "loong_idle_s" not in t or not t.get("prefilled"):
        return None
    return t["loong_idle_s"] / t["prefilled"] * 1e3
