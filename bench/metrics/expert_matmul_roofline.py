"""The held experts' grouped matmul's share of its roofline in training:
the least time the chip could take for the work the algorithm requires
over the device time of every ``expert_gmm*`` kernel in the trace
(``expert_gmm`` forward, ``expert_gmm_dx`` and ``expert_gmm_dw`` backward;
remat's recompute of the forward included in the time, not in the work).

The work, from the configuration and the window's tokens: the held
experts expect ``tokens · top_k · held / router`` (token, slot) pairs a
layer; each runs the three SwiGLU products (gate, up, down) once forward
and twice backward (input and weight gradients), 2 operations a
multiply-add; each of those nine products moves its two operands and its
result once in bf16 (the pairs' rows and the held experts' weights).
Where the trace covers only the first part of the window, the work counted
is the window's times the traced share of its length, as
``flash_attention_roofline`` counts it."""

import sys

KERNELS = r"^expert_gmm"
BF16_BYTES = 2


def read(ctx):
    red = ctx.get("trace")
    if ctx["driver"] != "train" or red is None:
        return None
    seconds = red.kernel_seconds(KERNELS) / red.devices
    if seconds <= 0:
        return None
    cfg = ctx["cfg"]
    d, ff, held = cfg.d_model, cfg.moe_d_ff, cfg.n_experts
    layers = cfg.n_layers - cfg.first_k_dense
    slots = ctx["tokens"] * cfg.top_k * held / (cfg.router_experts or held)
    ops = layers * 9 * 2 * slots * d * ff
    nbytes = layers * 9 * BF16_BYTES * (slots * d + slots * ff + held * d * ff)
    from bench.flops import roofline_seconds
    traced = min(1.0, red.window_s / ctx["window_s"])
    least, bound = roofline_seconds(traced * ops / ctx["chips"],
                                    traced * nbytes / ctx["chips"], ctx["peak"])
    print(f"expert_matmul_roofline: bound by {bound}; kernels {seconds!r} s, "
          f"roofline {least!r} s", file=sys.stderr)
    return 100.0 * least / seconds
