"""Live slots over slot capacity, over the decode steps of the window, in
% (``ServeStats.active_per_step``)."""


def read(ctx):
    s0, s1 = ctx.win.steps
    live = ctx.stats.active_per_step[s0:s1]
    if not live:
        return None
    return 100.0 * sum(live) / (ctx.capacity * len(live))
