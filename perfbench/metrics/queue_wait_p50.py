"""Median queue wait of the window's answered requests, in ms: arrival to
the first stage's dispatch, as the program's tracer records it
(``queue_wait`` in each per-request row).  Layer: the service and runtime
core's admission and dispatch queue."""


def read(ctx):
    waits = [r["queue_wait"] for r in ctx.e2e["served"] if "queue_wait" in r]
    if not waits:
        return None
    return 1e3 * float(ctx.np.median(waits))
