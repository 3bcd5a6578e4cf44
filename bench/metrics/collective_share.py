"""Collectives: share of the traced steps' device time on chip 0 spent in
operations that exchange data between chips (all-reduce, all-gather,
reduce-scatter, collective-permute, and their -start/-done halves)."""


def read(ctx):
    total = sum(s["device_s"] for s in ctx["steps"])
    coll = sum(s["collective_s"] for s in ctx["steps"])
    if total <= 0 or coll <= 0:
        return None
    return 100.0 * coll / total
