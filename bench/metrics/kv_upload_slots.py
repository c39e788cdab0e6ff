"""KV pool slots uploaded host-to-device (the pools' `mirror_uploaded_slots`
counter, summed over instances) in the window, per decode iteration: the
host round trip of the decode append."""


def value(rec):
    evs = rec.in_window()
    iters = sum(1 for e in evs if e.decode)
    if not iters:
        return None
    return sum(e.uploads for e in evs) / iters
