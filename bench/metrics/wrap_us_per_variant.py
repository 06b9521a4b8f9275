"""Result assembly per variant: ``_wrap`` (float series, link bytes,
``Result`` objects), in microseconds of host time per variant."""


def read(ctx):
    s = ctx.spans.seconds
    if not ctx.variants or "wrap" not in s:
        return None
    return 1e6 * s["wrap"] / ctx.variants
