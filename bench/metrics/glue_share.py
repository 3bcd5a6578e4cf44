"""Kernels and glue: share of the traced steps' device time spent outside
the Pallas launches (the XLA operations around them)."""


def read(ctx):
    total = sum(s["device_s"] for s in ctx["steps"])
    if total <= 0:
        return None
    pallas = sum(s["pallas_s"] for s in ctx["steps"])
    return 100.0 * (total - pallas) / total
