"""The whole training step's share of the chips' bf16 peak: the model
operations of the window's steps (bench/flops.train_flops: 6 per matmul
weight per token, every layer and the LM head, plus causal attention per
document; no recomputation) over window x chips x peak."""


def read(ctx):
    if ctx["driver"] != "train":
        return None
    peak = ctx["peak"]["bf16_flops_per_s"]
    return 100.0 * ctx["model_flops"] / (ctx["window_s"] * ctx["chips"] * peak)
