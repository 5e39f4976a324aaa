"""Scheduler milliseconds per dispatch: the benchmark's own timing of the
policy's ``on_arrival``, ``on_stage_done``, ``next_task`` and
``batch_rank`` calls over the window, over the dispatches made."""


def read(ctx):
    n = ctx.out.get("n_dispatches")
    s = ctx.out.get("sched_s")
    if not n or s is None:
        return None
    return 1e3 * s / n
