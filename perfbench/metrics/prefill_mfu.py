"""Model FLOPs of the stages the window executed, over the traced window
and the chip's bf16 peak, in %.  Each dispatch counts its valid rows only,
at their length bucket (``bench.flops.stage_flops``)."""

from bench import flops


def read(ctx):
    log = ctx.out.get("dispatch_log")
    if not log:
        return None
    work = sum(flops.stage_flops(ctx.m, s, n, L) for s, n, _b, L in log)
    return 100.0 * work / (ctx.summary["window_s"] * ctx.peak["flops_bf16"])
