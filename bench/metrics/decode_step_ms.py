"""Jitted step: median device time (ms) of the traced steps that carry no
prompt chunk."""
import statistics


def read(ctx):
    t = [s["device_s"] for s in ctx["steps"] if s["n"] == 0]
    return 1e3 * statistics.median(t) if t else None
