"""Least time of the paged decode kernel over its device time in the trace,
in percent.  The least time is counted per traced decode iteration from
each request's cached length at bf16 (`work.decode_attn`); the device time
is the summed duration of the Pallas kernel ops that started inside that
iteration's host span."""
import work


def value(rec):
    if rec.trace is None:
        return None
    least = dev = 0.0
    for i, e in enumerate(rec.events):
        t = rec.trace["kernel_s"].get(i)
        if not t or not e.decode or e.prefill:
            continue
        fb = [work.decode_attn(rec.model, c) for c in e.decode]
        least += work.least_time(sum(f for f, _ in fb), sum(b for _, b in fb),
                                 rec.peak)
        dev += t
    return 100.0 * least / dev if dev > 0 else None
