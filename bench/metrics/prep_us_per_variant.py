"""Host preparation per variant: ``_prep`` (topology, placement,
schedules, random streams, encoding) plus the rest of
``run_scenarios`` outside the runner and ``_wrap`` (grouping and
``np.stack``), in microseconds of host time per variant."""


def read(ctx):
    s = ctx.spans.seconds
    if not ctx.variants or not {"sweep_call", "prep", "runner", "wrap"} <= set(s):
        return None
    stack = s["sweep_call"] - s["prep"] - s["runner"] - s["wrap"]
    return 1e6 * (s["prep"] + stack) / ctx.variants
