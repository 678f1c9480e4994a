"""Median time from a micro-batch's submit to the session to its arrival
(``QoSRecord.service_s``): the session, the stages and the hops."""
import numpy as np


def read(ctx):
    qos = ctx.get("qos")
    if not qos:
        return None
    return float(np.median([r.service_s for r in qos])) * 1e3
