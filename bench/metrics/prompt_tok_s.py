"""Prompt tokens of every request answered inside the window (its first
token stamped by the close), over the window: the long-document
throughput users pay for when every answer is one token."""


def value(rec):
    n = sum(s.n_prompt for s in rec.requests + rec.carried
            if s.stamps and 0.0 <= s.stamps[0] <= rec.seconds)
    return n / rec.seconds
