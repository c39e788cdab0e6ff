"""Model FLOPs of the prefill steps in the window over their host wall time
times the chip's bf16 peak, in percent.  FLOPs (`work.step_flops`): two per
matrix weight per token, causal attention, and the unembedding of the one
sampled row per request."""
import work


def value(rec):
    evs = [e for e in rec.in_window() if e.prefill and not e.decode]
    wall = sum(e.t1 - e.t0 for e in evs)
    if wall <= 0:
        return None
    flops = sum(work.step_flops(rec.model, e.prefill, e.sampled) for e in evs)
    return 100.0 * flops / (wall * rec.peak["bf16_flops"])
