"""Output tokens emitted in the window, over the window."""


def value(rec):
    return rec.tokens_in_window() / rec.seconds
