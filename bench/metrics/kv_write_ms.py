"""Time of the KV write-through in the traced window, over the requests
prefilled there, in ms: per ``loong.kv.write`` span, from its start to the
end of the last device op that started inside it (`spans.reduce`'s
``kv_write_s``)."""


def value(rec):
    t = rec.trace or {}
    if "kv_write_s" not in t or not t.get("prefilled"):
        return None
    return t["kv_write_s"] / t["prefilled"] * 1e3
