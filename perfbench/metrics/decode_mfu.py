"""Model FLOPs of the tokens decoded, over the traced window and the
chip's bf16 peak, in %.  Each token step counts one whole-depth pass for
every row at its context length (``bench.flops.decode_token_flops``); the
shallower depths that each step recomputes do not count."""

from bench import flops


def read(ctx):
    n = ctx.out.get("n_tokens")
    if not n:
        return None
    rows = ctx.out["tokens"].shape[0]
    work = sum(flops.decode_token_flops(ctx.m, rows, k + 1) for k in range(n))
    return 100.0 * work / (ctx.summary["window_s"] * ctx.peak["flops_bf16"])
