"""Device, over a mesh: model FLOPs of the traced steps over their device
time on chip 0 times the chips' bf16 peak."""


def read(ctx):
    total = sum(s["device_s"] for s in ctx["steps"])
    if total <= 0:
        return None
    flops = sum(s["flops"] for s in ctx["steps"])
    return 100.0 * flops / (total * ctx["chips"]
                            * ctx["peaks"]["bf16_flops_per_s"])
