"""Host time the device waits for after each sync, as % of the window:
for each sync event in the window, the end of the first ``serve.admit`` or
``serve.decode`` span after it, less the end of its ``serve.sync`` span
(``ServeStats.spans``, bench/scope_reduce.py).  Over the whole window,
not only the traced part.  None where the program records no spans."""

from bench import scope_reduce


def read(ctx):
    spans = getattr(ctx.stats, "spans", None)
    if not spans:
        return None
    return scope_reduce.host_stall_share(spans, ctx.stats.events, ctx.win.t0, ctx.win.t1)
