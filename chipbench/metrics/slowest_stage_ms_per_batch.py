"""The slowest stage's device time per micro-batch completed in the
traced window: the trace's op time grouped by stage program
(``jit_worker<i>_blocks_<lo>_<hi>``), the largest group."""
import re

STAGE = re.compile(r"^jit_worker(\d+)_blocks_")


def read(ctx):
    tr, n = ctx.get("trace"), ctx.get("traced_batches", 0)
    if tr is None or not n:
        return None
    by_stage: dict[str, float] = {}
    for label, ns in tr.op_ns.items():
        m = STAGE.match(label)
        if m:
            by_stage[m.group(1)] = by_stage.get(m.group(1), 0.0) + ns
    if not by_stage:
        return None
    return max(by_stage.values()) / 1e6 / n
