"""Useful rows over padded rows, over every micro-batch of the window,
in percent (``QoSRecord.occupancy`` is one batch's share)."""


def read(ctx):
    qos = ctx.get("qos")
    if not qos:
        return None
    per_batch = {r.seq: r.occupancy for r in qos}
    return 100.0 * sum(per_batch.values()) / len(per_batch)
