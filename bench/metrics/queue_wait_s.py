"""Mean, over requests due in the window, of the time from the due time to
the start of the engine event that prefilled them (censored at the close
for requests not yet prefilled)."""


def value(rec):
    waits = []
    for s in rec.requests:
        t = s.prefill_t0
        if t is None or t > rec.seconds:
            t = rec.seconds
        waits.append(t - s.due)
    return sum(waits) / len(waits) if waits else None
