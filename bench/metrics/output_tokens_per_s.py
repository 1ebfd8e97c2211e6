"""Tokens emitted inside the window (first tokens of prefills and decode
tokens), per second of window (host clock)."""


def read(ctx):
    return ctx.win.tokens / ctx.win.seconds
