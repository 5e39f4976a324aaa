"""Valid rows over bucket rows, in %, summed over the window's
dispatches (the benchmark's record of each ``run`` of the stage fns)."""


def read(ctx):
    log = ctx.out.get("dispatch_log")
    if not log:
        return None
    return 100.0 * sum(n for _s, n, _b, _l in log) / sum(b for _s, _n, b, _l in log)
