"""Slot manager: share of the window's prompt chunks that rode a launch
fused with decode-side work (``ServeStats.fused_prefill_chunks /
prefill_chunks``, counted by the program)."""


def read(ctx):
    c = ctx["counters"]
    if not c["prefill_chunks"]:
        return None
    return 100.0 * c["fused_prefill_chunks"] / c["prefill_chunks"]
