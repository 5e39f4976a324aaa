"""Device-idle milliseconds per served token whose innermost program span
is ``repro.executor.readback``: the host reading a window's results back
while the device waits (``bench.spans``)."""

from bench import spans


def read(ctx):
    return spans.idle_ms_per_token(ctx, "readback")
