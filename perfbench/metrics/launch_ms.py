"""Mean duration of a ``repro.executor.launch`` span in the window, in ms:
the host's busy time per enqueue, whether or not the device was idle
(``bench.spans``)."""

from bench import spans


def read(ctx):
    r = spans.read(ctx)
    if r is None or not r["launch_s"]:
        return None
    return 1e3 * sum(r["launch_s"]) / len(r["launch_s"])
