"""Mean requests per decode iteration in the window, from the decode
groups the engine handed to its executor."""


def value(rec):
    sizes = [len(e.decode) for e in rec.in_window() if e.decode]
    return sum(sizes) / len(sizes) if sizes else None
