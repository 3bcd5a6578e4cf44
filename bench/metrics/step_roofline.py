"""Kernels and glue: the least time the chip could take for the traced
steps' work, max(FLOPs / peak, bytes / HBM bandwidth) per step, over their
device time.  The work is what the model needs (``bench.model.step_work``),
so moving work between kernels and glue cannot raise it."""


def read(ctx):
    total = sum(s["device_s"] for s in ctx["steps"])
    if total <= 0:
        return None
    pk = ctx["peaks"]
    least = sum(max(s["flops"] / pk["bf16_flops_per_s"],
                    s["bytes"] / pk["hbm_bytes_per_s"])
                for s in ctx["steps"])
    return 100.0 * least / total
