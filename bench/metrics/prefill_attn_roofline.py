"""Least time of the prefill attention kernels (packed and ring-chunk) over
their device time in the trace, in percent.  The least time is counted
per traced prefill step from its requests' shapes at bf16
(`work.prefill_attn`); the device time is the summed duration of the
Pallas kernel ops that started inside that step's host span."""
import work


def value(rec):
    if rec.trace is None:
        return None
    least = dev = 0.0
    for i, e in enumerate(rec.events):
        t = rec.trace["kernel_s"].get(i)
        if not t or not e.prefill or e.decode:
            continue
        fb = [work.prefill_attn(rec.model, c, n) for c, n in e.prefill]
        least += work.least_time(sum(f for f, _ in fb), sum(b for _, b in fb),
                                 rec.peak)
        dev += t
    return 100.0 * least / dev if dev > 0 else None
