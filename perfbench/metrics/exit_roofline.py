"""The fused exit kernel's share of its roofline, in %.

Bound: for each dispatch, the larger of the exit's operations over the
bf16 peak and its bytes (the vocabulary projection once, the rows) over
HBM bandwidth (``bench.flops.exit_bound_s``), for the valid rows.  Time:
the device time of the exit's ops in the traced window, that is the
kernel's custom call (output ``f32[rows,4]``) and the copy that pads the
vocabulary projection to a whole number of vocabulary blocks; that copy
is time the exit takes, but no needed work.
"""
import re

from bench import flops

_KERNEL = re.compile(r"^%\S+ = f32\[\d+,4\]\{[^}]*\} custom-call\(")
_PAD = re.compile(r"^%\S+ = \w+\[(\d+),(\d+)\]\{[^}]*\} pad\(")
BLOCK_V = 512


def is_exit_op(name, d, v):
    if _KERNEL.match(name):
        return True
    p = _PAD.match(name)
    vp = -(-v // BLOCK_V) * BLOCK_V
    return bool(p) and vp != v and (int(p.group(1)), int(p.group(2))) == (d, vp)


def read(ctx):
    log = ctx.out.get("dispatch_log")
    if not log:
        return None
    d, v = ctx.m["hidden_size"], ctx.m["vocab_size"]
    t = sum(s for n, s in ctx.summary["op_seconds"].items()
            if is_exit_op(n, d, v))
    if t <= 0:
        return None
    bound = sum(flops.exit_bound_s(ctx.m, n, ctx.peak) for _s, n, _b, _l in log)
    return 100.0 * bound / t
