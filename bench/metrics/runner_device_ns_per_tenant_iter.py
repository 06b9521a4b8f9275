"""Device time of the compiled runner programs (the scan over
iterations, vmapped over variants; both ring lengths) per simulated
tenant-iteration, in nanoseconds: the summed device durations of the
``XLA Modules`` events of the programs the runner calls ran."""


def read(ctx):
    names = {"jit_" + n for n in ctx.spans.runner_names if n}
    if ctx.trace is None or not names or not ctx.tenant_iters:
        return None
    total = sum(e - s for n, s, e in ctx.trace.modules
                if n.split("(")[0] in names)
    return 1e9 * total / ctx.tenant_iters if total > 0 else None
