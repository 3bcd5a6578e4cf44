"""Slot manager: share of the batch's slots that decoded, over the decode
steps of the window (``ServeStats.slot_steps / (batch * decode_steps)``,
counted by the program)."""


def read(ctx):
    c = ctx["counters"]
    if not c["decode_steps"]:
        return None
    return 100.0 * c["slot_steps"] / (ctx["batch"] * c["decode_steps"])
