"""Flash attention's share of its roofline in training: the least time the
chip could take for the work the algorithm requires (bench/flops
.flash_train_work: forward 2 and backward 4 products over each document's
causal pairs at the unpadded head dim, and Q K V O dO dQ dK dV lse moved
once) over the device time of every flash kernel in the trace (forward,
its recompute under remat, delta, dQ and dK/dV).  Where the trace covers
only the first part of the window (``trace_seconds``), the work counted is
the window's times the traced share of its length: steps are alike, and a
step cut at the trace's end is off by at most its own flash time.

The kernels are the custom calls the jitted ``flash_attention`` wrapper
lowers to: their HLO op names start with ``flash_attention``
(``flash_attention.54``; kernels named ``flash_attention_<part>`` match too)."""

import sys

KERNELS = r"^flash_attention"


def read(ctx):
    red = ctx.get("trace")
    if ctx["driver"] != "train" or red is None:
        return None
    seconds = red.kernel_seconds(KERNELS) / red.devices
    if seconds <= 0:
        return None
    from bench.flops import roofline_seconds
    # a trace cut short of the window holds that share of its steps' work
    traced = min(1.0, red.window_s / ctx["window_s"])
    least, bound = roofline_seconds(
        traced * ctx["flash_ops"] / ctx["chips"],
        traced * ctx["flash_bytes"] / ctx["chips"], ctx["peak"])
    print(f"flash_attention_roofline: bound by {bound}; kernels "
          f"{seconds!r} s, roofline {least!r} s", file=sys.stderr)
    return 100.0 * least / seconds
