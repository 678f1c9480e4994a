"""The stage programs' share of the chip's bf16 peak while the device
was busy: useful rows completed in the traced window times the
benchmark's own FLOPs per image, over busy time times the peak, in
percent.  Padded rows are not useful work."""


def read(ctx):
    tr, rows = ctx.get("trace"), ctx.get("traced_rows", 0)
    if tr is None or not rows or not tr.busy_ns:
        return None
    busy_s = tr.busy_ns[tr.devices[0]] / 1e9
    if busy_s <= 0:
        return None
    return 100.0 * rows * ctx["flops_per_item"] / (
        busy_s * ctx["peaks"]["bf16_flops_per_s"])
