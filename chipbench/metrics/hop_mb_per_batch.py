"""MB carried across every cut per micro-batch over the window, from the
program's per-tensor hop counter (``HopObservations.tensor_bytes``)."""


def read(ctx):
    return ctx.get("hop_mb_per_batch")
