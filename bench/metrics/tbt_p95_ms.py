"""95th percentile of the gaps between successive tokens of one request,
over every gap that ends in the window, pooled across requests."""
from driver import quantile


def value(rec):
    q = quantile(rec.gaps(), 0.95)
    return None if q is None else q * 1e3
