"""Share of the traced training window in which no operation ran on the
chip, as a mean over the cell's chips: 1 - busy union / window."""


def read(ctx):
    red = ctx.get("trace")
    if ctx["driver"] != "train" or red is None:
        return None
    return 100.0 * (1.0 - red.mean_busy_s / red.window_s)
