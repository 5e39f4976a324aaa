"""Share of the traced window in which no op ran on the device, in %:
1 minus the union of device op intervals over the window."""


def read(ctx):
    w = ctx.summary["window_s"]
    return 100.0 * (1.0 - ctx.summary["busy_s"] / w) if w > 0 else None
