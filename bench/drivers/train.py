"""Training cells: ``TrainSession.run`` on a packed-document stream.

Set-up builds one session, puts the seed's weights in its state, and drives
it through its first three steps with ``run`` on the feed the window uses;
those steps are what the reference checks.  A plan over the cell's chips
(``world`` equal to the cell's ``chips``) runs on the recipe mesh of those
chips, the state and the feed placed by the session's shardings; a plan of
one chip runs on the first device with no mesh.  Two more steps time one step,
and the window is the whole steps that fit in ``--seconds`` at that time,
run by one more ``run`` call that ends in ``block_until_ready``.  No
checkpoint directory is given, so nothing is saved, and ``log_every`` keeps
its default.
"""

from __future__ import annotations

import math
import time

import jax
import numpy as np

from bench import correct, flops, harness, spec, traffic, weights

CHECK_STEPS = 3
CALIBRATE_STEPS = 2


def _session_class():
    from repro.session import TrainSession

    class FeedSession(TrainSession):
        """A ``TrainSession`` whose data is the benchmark's feed: a stream
        of prepared device batches, in order, whatever step the loop asks
        for."""

        def __init__(self, *a, feed, **kw):
            super().__init__(*a, **kw)
            self.feed = feed
            self.served = 0

        def batches(self, step):
            b = self.feed[self.served % len(self.feed)]
            self.served += 1
            return b

    return FeedSession


def _run(sess, steps: int) -> dict:
    """One ``TrainSession.run`` call on the same session.  ``run`` refuses a
    second call because it restarts the data schedule at step 0; this feed
    is a stream that ignores the step index, so the guard is reset."""
    sess._next_step = 0
    return sess.run(steps, log=lambda s: None)


def stacked_layers(cfg) -> int:
    """Layers in the ``blocks/`` stack: a model whose first layers are
    unstacked (``pre_blocks``) stacks fewer than ``n_layers``."""
    from repro.models.transformer import layer_plan
    return layer_plan(cfg)[1]


def counts(job):
    """``train_flops`` and ``flash_train_work``: the configuration's
    reference module's own where it defines them (a block the dense count
    does not describe), else ``bench/flops``'s."""
    ref = spec.reference(job.conf)
    return (getattr(ref, "train_flops", flops.train_flops),
            getattr(ref, "flash_train_work", flops.flash_train_work))


def layer_norms(tree, n_layers: int, scale: float = 1.0) -> dict:
    """Per-layer norms of a program tree, layers in canonical order."""
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    norm = jax.jit(lambda x: jax.numpy.sqrt(jax.numpy.sum(
        jax.numpy.square(x.astype(jax.numpy.float32) * scale),
        axis=tuple(range(1, x.ndim)))))
    out = {}
    for path, x in flat:
        name = weights.path_name(path)
        shape = weights.canonical_shape(name, x.shape, n_layers)
        if name.startswith("blocks/"):
            out[name] = np.asarray(norm(x.reshape(shape)))
        else:
            out[name] = np.asarray(norm(x.reshape((1,) + tuple(shape))))
    return out


def _change_norms(params, seed, n_layers, shardings):
    """Per-layer norms of the change from the seed's weights, leaf by leaf
    so that no whole difference is ever held."""
    abstract = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), params)
    p0 = weights.make(abstract, seed, n_layers, np.float32, shardings)
    sub = jax.jit(jax.numpy.subtract)
    out = {}
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(params)[0],
                            jax.tree_util.tree_leaves(p0)):
        out.update(layer_norms({weights.path_name(path): sub(a, b)}, n_layers))
    harness.free(p0)
    return out


def host_batches(job, n: int) -> list:
    """The first ``n`` batches of the cell's feed, on the host."""
    return [traffic.train_batch(job.mix, job.conf["train"]["batch"],
                                job.model_cfg.vocab_size, job.seed, i)
            for i in range(n)]


def setup(job):
    """The session with the seed's weights, driven through the checked
    steps.  → (session, host batches, the program's readings)."""
    from repro.core import stepfn
    from repro.optim.adamw import AdamWConfig

    conf, mix, seed = job.conf, job.mix, job.seed
    cfg, plan = job.model_cfg, job.plan
    train = conf["train"]
    opt = train["optimizer"]
    n_feed = mix["distinct_batches"]
    chips = job.cell["chips"]
    if plan.world != chips:
        raise ValueError(f"the plan spans {plan.world} chip(s), the cell "
                         f"{chips}")
    tcfg = stepfn.TrainConfig(
        peak_lr=opt["peak_lr"], warmup=opt["warmup"],
        total_steps=opt["total_steps"],
        adam=AdamWConfig(b1=opt["b1"], b2=opt["b2"], eps=opt["eps"],
                         weight_decay=opt["weight_decay"],
                         grad_clip=opt["grad_clip"]))
    if opt["warmup"] <= CHECK_STEPS:
        raise ValueError("the reference follows the warm-up phase only")

    host = host_batches(job, n_feed)
    mesh = None
    if chips > 1:
        from repro.launch.mesh import make_plan_mesh
        mesh = make_plan_mesh(plan, job.devices[:chips])
        feed = [jax.device_put(b, stepfn.batch_shardings(b, mesh))
                for b in host]
    else:
        feed = [jax.device_put(b, job.devices[0]) for b in host]
    sess = _session_class()(cfg, plan=plan, train_cfg=tcfg, mesh=mesh,
                            feed=feed)
    n_layers = stacked_layers(cfg)
    params = sess.state["params"]
    shardings = jax.tree_util.tree_map(lambda x: x.sharding, params)
    abstract = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), params)
    harness.free(params)
    sess.state["params"] = weights.make(abstract, seed, n_layers,
                                        np.float32, shardings)

    # --- the checked steps, through the window's call and feed ----------
    out = _run(sess, 1)
    losses = [h["loss"] for h in out["history"]]
    grad = layer_norms(sess.state["opt"]["m"], n_layers,
                       1.0 / (1.0 - opt["b1"]))
    out = _run(sess, CHECK_STEPS - 1)
    losses += [h["loss"] for h in out["history"]]
    delta = _change_norms(sess.state["params"], seed, n_layers, shardings)
    return sess, host, {"loss": losses, "grad": grad, "delta": delta}


def reference(job, host, precision="f32") -> dict:
    return spec.reference(job.conf).train_readings(
        job.conf["model"], job.conf["train"]["optimizer"], host[:CHECK_STEPS],
        job.seed, precision, eps=job.conf["norm_eps"],
        devices=job.devices[:job.cell["chips"]])


def run(job) -> dict:
    cfg, mix = job.model_cfg, job.mix
    batch = job.conf["train"]["batch"]
    sess, host, program = setup(job)

    # --- one step's time, then the window --------------------------------
    t0 = time.perf_counter()
    _run(sess, CALIBRATE_STEPS)
    jax.block_until_ready(sess.state)
    step_s = (time.perf_counter() - t0) / CALIBRATE_STEPS
    steps = max(2, math.ceil(job.seconds / step_s))
    job.setup_done()

    with job.window() as win:
        out = _run(sess, steps)
        jax.block_until_ready(sess.state)
    window_s = win.seconds
    skipped = int(out["skipped_steps"])
    peak = harness.peak_bytes(job.devices[:job.cell["chips"]])
    harness.free(sess.state)
    sess = None

    numbers = correct.train_numbers(program, reference(job, host))

    tokens = steps * batch * mix["seq_len"]
    pairs = flops.causal_pairs(flops.segment_lengths(host[0]["segment_ids"]))
    train_flops, flash_train_work = counts(job)
    f_ops, f_bytes = flash_train_work(cfg, batch, mix["seq_len"], pairs)
    return {
        "numbers": numbers,
        "attempted": steps, "failed": skipped,
        "e2e": {"train_tokens_per_s": tokens / window_s},
        "memory_peak_bytes": peak,
        "ctx": {"driver": "train", "window_s": window_s, "steps": steps,
                "tokens": tokens, "chips": job.cell["chips"],
                "model_flops": steps * train_flops(
                    cfg, batch * mix["seq_len"], pairs),
                "flash_ops": steps * f_ops, "flash_bytes": steps * f_bytes},
    }
