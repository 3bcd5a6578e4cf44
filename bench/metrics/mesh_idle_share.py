"""Device: mean over the cell's chips of the share of the traced window in
which no operation ran on that chip."""


def read(ctx):
    busy = ctx["chip_busy_s"]
    if ctx["window_s"] <= 0 or not busy or min(busy) <= 0:
        return None
    return 100.0 * (1.0 - sum(busy) / (len(busy) * ctx["window_s"]))
