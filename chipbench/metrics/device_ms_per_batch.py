"""Device busy time (the union of its operations) in the traced window,
per micro-batch completed in that window."""


def read(ctx):
    tr, n = ctx.get("trace"), ctx.get("traced_batches", 0)
    if tr is None or not n or not tr.busy_ns:
        return None
    return tr.busy_ns[tr.devices[0]] / 1e6 / n
