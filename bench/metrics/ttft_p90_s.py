"""90th percentile of time to first token over every request due in the
window (p90: the long-prompt cell has only some tens of requests)."""
from driver import quantile


def value(rec):
    return quantile(rec.ttfts(), 0.9)
