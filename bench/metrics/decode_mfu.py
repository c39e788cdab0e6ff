"""Model FLOPs of the decode iterations in the window over their host wall
time times the chip's bf16 peak, in percent (`work.step_flops`, one new
token per request)."""
import work


def value(rec):
    evs = [e for e in rec.in_window() if e.decode and not e.prefill]
    wall = sum(e.t1 - e.t0 for e in evs)
    if wall <= 0:
        return None
    flops = sum(work.step_flops(rec.model, [(c, 1) for c in e.decode],
                                len(e.decode)) for e in evs)
    return 100.0 * flops / (wall * rec.peak["bf16_flops"])
