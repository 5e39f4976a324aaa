"""Host milliseconds per dispatch in the device executor: the executor's
own count of seconds spent staging, launching and committing
(``device_time_stats()["host_time"]``) over the dispatches made."""


def read(ctx):
    n = ctx.out.get("n_dispatches")
    h = ctx.out.get("host_time")
    if not n or h is None:
        return None
    return 1e3 * h / n
