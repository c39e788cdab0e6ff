"""Seconds from the start of the process to the opening of the window:
imports, weights, engine, compiles or cache loads, and warm-up traffic."""


def value(rec):
    return rec.setup_s
