"""Median time to first token over every request due in the window."""
from driver import quantile


def value(rec):
    return quantile(rec.ttfts(), 0.5)
