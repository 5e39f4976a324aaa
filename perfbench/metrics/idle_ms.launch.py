"""Device-idle milliseconds per served token whose innermost program span
is ``repro.executor.launch``: the host enqueueing device work while the
device waits (``bench.spans``)."""

from bench import spans


def read(ctx):
    return spans.idle_ms_per_token(ctx, "launch")
