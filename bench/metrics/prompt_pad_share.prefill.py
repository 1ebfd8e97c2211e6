"""Padded prompt positions that hold no request token, over all prefilled
positions, for the prefills admitted inside the window, in % (prompt
lengths and ``ServeScheduler.prompt_bucket``)."""


def read(ctx):
    lens = [len(ctx.requests[rid].prompt) for rid in ctx.win.prefilled()]
    if not lens:
        return None
    padded = sum(ctx.prompt_bucket(p) for p in lens)
    return 100.0 * (padded - sum(lens)) / padded
