"""Median time a request queued at the gateway before its micro-batch
was submitted (``QoSRecord.queue_s``), over every request of the window."""
import numpy as np


def read(ctx):
    qos = ctx.get("qos")
    if not qos:
        return None
    return float(np.median([r.queue_s for r in qos])) * 1e3
