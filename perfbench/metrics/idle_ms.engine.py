"""Device-idle milliseconds per served token whose innermost program span
is the serving loop's own: ``repro.engine.*``, ``repro.scheduler`` or
``repro.source.*`` (``bench.spans``)."""

from bench import spans


def read(ctx):
    return spans.idle_ms_per_token(ctx, "engine")
