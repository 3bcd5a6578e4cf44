"""Kernels and glue, over a mesh: the least time ``chips`` chips could take
for the traced steps' whole-model work, max(FLOPs / (n * peak), bytes /
(n * HBM bandwidth)) per step, over the steps' device time on chip 0.
Work that every chip repeats (a replicated head) counts once, so the share
is a lower bound on how near the chips come to their roofline."""


def read(ctx):
    total = sum(s["device_s"] for s in ctx["steps"])
    if total <= 0:
        return None
    pk, n = ctx["peaks"], ctx["chips"]
    least = sum(max(s["flops"] / (n * pk["bf16_flops_per_s"]),
                    s["bytes"] / (n * pk["hbm_bytes_per_s"]))
                for s in ctx["steps"])
    return 100.0 * least / total
