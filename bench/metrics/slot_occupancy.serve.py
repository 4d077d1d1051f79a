"""Mean share of the scheduler's slots that held a request in each decode
step, over all decode steps of the window's serve calls
(``ServingStats.occupancy`` weighted by ``decode_steps``)."""


def read(ctx):
    if ctx["driver"] != "serve" or not ctx.get("decode_steps"):
        return None
    return 100.0 * ctx["occupancy_steps"] / ctx["decode_steps"]
