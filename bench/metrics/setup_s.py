"""Seconds from the start of the process to the start of the window:
plan, weights, warm-up and the first admissions (host clock)."""


def read(ctx):
    return ctx.setup_s
