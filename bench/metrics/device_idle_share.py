"""Share of the traced window in which no operation ran on the device,
in percent: one minus the union of the ``XLA Ops`` intervals over the
window from the first traced sweep's start to the last one's end."""


def read(ctx):
    if ctx.trace is None or ctx.window is None or not ctx.trace.ops:
        return None
    from bench import trace
    busy = trace.busy([(s, e) for _, s, e in ctx.trace.ops], ctx.window)
    return 100.0 * (1.0 - busy / (ctx.window[1] - ctx.window[0]))
