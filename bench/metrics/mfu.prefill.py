"""The least time the chip could take for the work the model requires in
the window (bench/work.py: every prefill and decode step of the window,
each at its compute or memory roofline), over the window's wall time, in %
of the chip's peak."""


def read(ctx):
    if ctx.peaks is None:
        return None
    return 100.0 * ctx.required_roofline_s() / ctx.win.seconds
