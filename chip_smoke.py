#!/usr/bin/env python3
"""Drive the training and serving paths once on a TPU and check the results.

    python chip_smoke.py                # one chip: train phase, serve phase
    python chip_smoke.py --four-chips   # one 4-chip host: pp=2 x tp=2 vs 1 chip

Both phases use granite_3_2b at its published widths with random weights
made from a fixed seed, through the entry points a user calls
(``TrainSession.from_recipe(...).run``, ``InferenceSession.from_recipe(...)
.serve``).

* train: the depth (and batch) is the largest candidate whose compiled step
  fits the chip by ``compile().memory_analysis()``; 10 steps of packed
  synthetic documents at seq 2048 (so the segment-id flash kernels run), one
  checkpoint after step 5 and a resume from it in a fresh session.  Fails
  unless the compiled step holds the Pallas kernel, the first step's loss
  and grad-norm match the XLA attention path, every loss is finite and the
  loss falls, no step was skipped and no checkpoint write failed.
* serve: all 40 layers in bf16 answer 8 requests through the paged KV pool
  with the Pallas paged-decode kernel on the decode path (page size 128).
  Fails unless every request completes and the decode kernels agree with
  the reference at the served shapes.
* --four-chips: the same cut model as ``ParallelismConfig(pp=2, tp=2,
  gas=4)`` on the four local chips, global batch 8 x 2048, against the same
  batch, seed and gas on one chip.  Fails unless the first step's loss and
  grad-norm agree and chip 0 holds at most 1.5x the others' mean peak.

Lines before the last are observations, not metrics.  The last line of
stdout is ``{"ok": true, "device": {"platform", "kind", "count"}}``; without
a TPU the script exits non-zero and prints no such line.  It runs in this
one process (a chip belongs to one process) and keeps its compile cache in
``JAX_COMPILATION_CACHE_DIR`` or ``<checkout>/.jax_cache``.
"""

from __future__ import annotations

import argparse
import collections
import dataclasses
import gc
import json
import math
import shutil
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

ARCH = "granite_3_2b"
SEED = 0
SEQ = 2048
STEPS, CKPT_AT = 10, 5
LR = 1e-3
MEM_FRACTION = 0.9        # of the device's bytes_limit a compiled program may plan
RTOL = 2e-2               # loss / grad-norm agreement (bf16 compute: eps 2**-8)
DECODE_ATOL = 3e-2        # decode kernel vs reference, bf16 outputs of O(1)
TRAIN_CANDIDATES = ((8, 4), (8, 2), (6, 2), (4, 2))  # (layers, batch), first fit wins
FOUR_CHIP_LAYERS = (8, 6, 4)
FOUR_CHIP_BATCH = 8
N_REQUESTS, PAGE_SIZE, N_SLOTS = 8, 128, 4


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


class Failed(Exception):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise Failed(what)
    log(f"PASS {what}")


def close(a: float, b: float, rtol: float) -> bool:
    return math.isfinite(a) and math.isfinite(b) and abs(a - b) <= rtol * abs(b)


# ---------------------------------------------------------------------------
# shared pieces
# ---------------------------------------------------------------------------

def full():
    """granite_3_2b as published."""
    from repro.configs import get_config
    return get_config(ARCH)


def cut(layers: int):
    """granite_3_2b at its published widths, depth cut to ``layers``."""
    return dataclasses.replace(full(), n_layers=layers)


def train_cfg():
    from repro.core import stepfn
    return stepfn.TrainConfig(peak_lr=LR, warmup=2, total_steps=STEPS)


def data_cfg(batch: int):
    from repro.data import DataConfig
    return DataConfig(seq_len=SEQ, global_batch=batch, pack_documents=True,
                      seed=SEED)


def batch_specs(batch: int):
    import jax
    import jax.numpy as jnp
    return {k: jax.ShapeDtypeStruct((batch, SEQ), dt) for k, dt in
            (("tokens", jnp.int32), ("labels", jnp.int32),
             ("segment_ids", jnp.int32), ("loss_mask", jnp.float32))}


def planned_bytes(compiled) -> int:
    """Device bytes one compiled program plans: its buffers, donated state
    counted once."""
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes)


def step_footprint(cfg, plan, batch: int, mesh=None) -> int:
    """Per-device bytes of the compiled train step, from the session's
    abstract lowering (no state is made)."""
    from repro.session import TrainSession
    sess = TrainSession(cfg, plan=plan, train_cfg=train_cfg(), mesh=mesh,
                        abstract=True)
    return planned_bytes(sess.lower(batch_specs(batch)).compile())


def reference_footprint(cfg, plan, batch: int) -> int:
    """Bytes of the XLA-attention reference gradient over the params alone
    (it runs before the session's optimizer state exists)."""
    import jax
    from repro.models import api as model_api
    from repro.runtime import flags
    params = jax.eval_shape(lambda k: model_api.init_params(cfg, k),
                            jax.random.PRNGKey(SEED))
    with flags.flag_ctx(flash_attention=False):
        return planned_bytes(loss_and_grad_norm(cfg, plan).lower(
            params, batch_specs(batch)).compile())


def loss_and_grad_norm(cfg, plan):
    """The train step's step-0 loss and grad-norm (gas 1), as its own jit."""
    import jax
    from repro.models import api as model_api
    from repro.optim import adamw

    def fn(params, batch):
        def loss(p):
            return model_api.loss_fn(cfg, p, batch,
                                     remat_policy=plan.remat_policy)
        (l, _), grads = jax.value_and_grad(loss, has_aux=True)(params)
        return l, adamw.global_norm(grads)
    return jax.jit(fn)


def checksum(params):
    """Exact fingerprint of fp32 params: per-leaf wrapping sums of the bits."""
    import jax
    import jax.numpy as jnp
    return [int(jnp.sum(jax.lax.bitcast_convert_type(x, jnp.uint32),
                        dtype=jnp.uint32))
            for x in jax.tree_util.tree_leaves(params)]


def reference_step0(cfg, plan, batch: int):
    """Step-0 loss and grad-norm on the XLA attention path, over the params
    and batch the session is about to make (same init, seed and pipeline).
    Returns (loss, grad_norm, params checksum, step-0 tokens)."""
    import jax
    from repro.data import make_dataset
    from repro.data.pipeline import add_modality_inputs
    from repro.models import api as model_api
    from repro.runtime import flags
    params = jax.jit(lambda k: model_api.init_params(cfg, k))(
        jax.random.PRNGKey(SEED))
    dc = data_cfg(batch)
    b0 = add_modality_inputs(make_dataset(dc, cfg).batch(0), cfg, 0, dc.seed)
    with flags.flag_ctx(flash_attention=False):
        loss, gn = loss_and_grad_norm(cfg, plan)(params, b0)
    return float(loss), float(gn), checksum(params), b0["tokens"]


class StepClock:
    """Tracker that stamps the host clock at every logged step; the loop
    logs after the metrics reach the host, so gaps are step times."""

    def __init__(self):
        self.stamps = []

    def log_metrics(self, step, metrics):
        self.stamps.append(time.perf_counter())

    def log_event(self, step, kind, payload):
        pass

    def finish(self):
        pass

    def step_seconds(self):
        return [b - a for a, b in zip(self.stamps, self.stamps[1:])]


def peak_bytes(dev) -> int:
    return int((dev.memory_stats() or {}).get("peak_bytes_in_use", 0))


def live_bytes() -> int:
    import jax
    return sum(x.nbytes for x in jax.live_arrays())


def release(out) -> None:
    """Drop the final train state a ``run`` output holds, so the next
    session on the same chips has their memory."""
    out.pop("state")


def median(xs):
    xs = sorted(xs)
    return xs[len(xs) // 2] if xs else float("nan")


def first_step(out):
    h = out["history"][0]
    return float(h["loss"]), float(h["grad_norm"])


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def train_phase(dev, budget: int) -> None:
    import jax
    import numpy as np
    from repro.core.recipe import ParallelismConfig
    from repro.session import TrainSession

    plan = ParallelismConfig()
    chosen = None
    for layers, batch in TRAIN_CANDIDATES:
        t0 = time.perf_counter()
        step = step_footprint(cut(layers), plan, batch)
        ref_need = reference_footprint(cut(layers), plan, batch)
        log(f"sizing layers={layers} batch={batch}x{SEQ}: train step plans "
            f"{step / 1e9:.2f} GB, XLA reference {ref_need / 1e9:.2f} GB, "
            f"of {budget / 1e9:.2f} GB ({time.perf_counter() - t0:.1f}s to "
            f"compile)")
        if max(step, ref_need) <= budget:
            chosen = (layers, batch)
            break
    check(chosen is not None, "a candidate depth fits the chip")
    layers, batch = chosen
    cfg = cut(layers)
    log(f"train: {ARCH} d_model={cfg.d_model} heads={cfg.n_heads}/"
        f"{cfg.n_kv_heads} head_dim={cfg.hd} d_ff={cfg.d_ff} "
        f"vocab={cfg.vocab_size}, layers={layers} (of 40), batch={batch}x{SEQ}")

    t0 = time.perf_counter()
    ref_loss, ref_gn, ref_sum, ref_tokens = reference_step0(cfg, plan, batch)
    gc.collect()
    log(f"XLA-attention reference step 0: loss={ref_loss:.6f} "
        f"grad_norm={ref_gn:.6f} ({time.perf_counter() - t0:.1f}s)")

    ckpt = ROOT / "ckpt_chip_smoke"
    shutil.rmtree(ckpt, ignore_errors=True)

    def session():
        return TrainSession.from_recipe(cfg, train_cfg=train_cfg(),
                                        data_cfg=data_cfg(batch), seed=SEED)

    t0 = time.perf_counter()
    sess = session()
    batch0 = sess.batches(0)
    jax.block_until_ready(sess.state)
    log(f"train state made in {time.perf_counter() - t0:.1f}s "
        f"({sess.n_params / 1e6:.1f}M params)")
    check(checksum(sess.state["params"]) == ref_sum
          and np.array_equal(np.asarray(batch0["tokens"]), ref_tokens),
          "the reference saw the session's step-0 params and batch")

    t0 = time.perf_counter()
    compiled = sess.train_step.lower(sess.state, batch0).compile()
    log(f"train step compiled in {time.perf_counter() - t0:.1f}s, plans "
        f"{planned_bytes(compiled) / 1e9:.2f} GB")
    check("tpu_custom_call" in compiled.as_text(),
          "compiled train step contains the Pallas flash kernel")
    del compiled

    clock_a = StepClock()
    t0 = time.perf_counter()
    out_a = sess.run(CKPT_AT, ckpt_dir=ckpt, ckpt_every=CKPT_AT, log_every=1,
                     tracker=clock_a, log=lambda s: None)
    log(f"steps 0-{CKPT_AT - 1} + checkpoint in "
        f"{time.perf_counter() - t0:.1f}s")
    loss0, gn0 = first_step(out_a)
    log(f"flash step 0: loss={loss0:.6f} grad_norm={gn0:.6f}; rtol={RTOL}")
    check(close(loss0, ref_loss, RTOL) and close(gn0, ref_gn, RTOL),
          f"flash and XLA attention agree on step 0 (rtol {RTOL})")
    release(out_a)
    sess = None
    gc.collect()
    log(f"live device bytes before the resume: {live_bytes() / 1e9:.2f} GB")

    clock_b = StepClock()
    t0 = time.perf_counter()
    sess = session()
    out_b = sess.run(STEPS, ckpt_dir=ckpt, ckpt_every=STEPS + 1, log_every=1,
                     tracker=clock_b, log=lambda s: None)
    log(f"resumed session ran steps {CKPT_AT}-{STEPS - 1} in "
        f"{time.perf_counter() - t0:.1f}s (session made, state restored)")
    check(out_b["resumed_from"] == CKPT_AT,
          f"fresh session resumed from the step-{CKPT_AT} checkpoint")

    hist = out_a["history"] + out_b["history"]
    losses = [float(h["loss"]) for h in hist]
    log("losses " + " ".join(f"{x:.4f}" for x in losses))
    check([h["step"] for h in hist] == list(range(STEPS)),
          f"{STEPS} steps logged across save and resume")
    check(all(math.isfinite(x) for x in losses), "every loss is finite")
    check(sum(losses[-3:]) / 3 < losses[0], "the loss falls")
    skipped = out_a["skipped_steps"] + out_b["skipped_steps"]
    check(skipped == 0, "skipped_steps == 0")
    failed = [e for e in out_a["events"] + out_b["events"]
              if e.kind == "ckpt_write_failed"]
    check(not failed, "no ckpt_write_failed events")
    steps_s = clock_a.step_seconds()[1:] + clock_b.step_seconds()[1:]
    log(f"step seconds (host clock, after the first of each session): "
        f"median {median(steps_s):.4f} over {len(steps_s)}")
    log(f"train peak_bytes_in_use {peak_bytes(dev) / 1e9:.2f} GB")
    release(out_b)
    sess = None
    gc.collect()
    shutil.rmtree(ckpt, ignore_errors=True)


def serve_phase(dev) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels import ops, ref
    from repro.session import InferenceSession

    rng = np.random.RandomState(SEED)
    t0 = time.perf_counter()
    inf = InferenceSession.from_recipe(full(), seed=SEED)
    jax.block_until_ready(inf.params)
    cfg = inf.cfg
    log(f"serve: {ARCH} all {cfg.n_layers} layers {cfg.dtype} made in "
        f"{time.perf_counter() - t0:.1f}s")
    plens = [int(x) for x in rng.randint(64, 700, N_REQUESTS)]
    gens = [int(x) for x in rng.randint(8, 40, N_REQUESTS)]
    prompts = [rng.randint(1, cfg.vocab_size, p).astype(np.int32)
               for p in plens]
    t0 = time.perf_counter()
    outs, stats = inf.serve(prompts, gens, n_slots=N_SLOTS, paged=True,
                            page_size=PAGE_SIZE)
    log(f"served {stats.requests} requests ({sum(gens)} tokens) in "
        f"{time.perf_counter() - t0:.1f}s incl. compiles; pool "
        f"{stats.pool_pages} pages of {stats.page_size}; {stats}")
    check(stats.requests == N_REQUESTS and len(outs) == N_REQUESTS
          and all(len(o) == p + g for o, p, g in zip(outs, plens, gens))
          and all(np.all((o >= 0) & (o < cfg.vocab_size)) for o in outs),
          f"all {N_REQUESTS} requests complete with in-vocabulary tokens")
    log(f"serve peak_bytes_in_use {peak_bytes(dev) / 1e9:.2f} GB")

    # the decode kernels against the reference at the served shapes
    n_max = -(-max(p + g for p, g in zip(plens, gens)) // PAGE_SIZE)
    n_pages = stats.pool_pages + 1
    Hq, Hkv, D = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    key = jax.random.PRNGKey(SEED)
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (N_SLOTS, 1, Hq, D), jnp.bfloat16)
    kp = jax.random.normal(kk, (n_pages, PAGE_SIZE, Hkv, D), jnp.bfloat16)
    vp = jax.random.normal(kv, (n_pages, PAGE_SIZE, Hkv, D), jnp.bfloat16)
    ts = rng.randint(0, n_max * PAGE_SIZE, N_SLOTS).astype(np.int32)
    pt = np.full((N_SLOTS, n_max), -1, np.int32)
    for b, t in enumerate(ts):
        used = t // PAGE_SIZE + 1
        pt[b, :used] = rng.choice(np.arange(1, n_pages), used, replace=False)
    pt, ts = jnp.asarray(pt), jnp.asarray(ts)
    got = ops.paged_decode_attention(q, kp[None], vp[None], pt, ts=ts,
                                     layer=jnp.int32(0))
    want = ref.paged_decode_attention_reference(q, kp, vp, pt, ts=ts)
    err = float(jnp.max(jnp.abs(got.astype(jnp.float32)
                                - want.astype(jnp.float32))))
    log(f"paged decode kernel vs reference: max |err| {err:.3e} "
        f"(slots {N_SLOTS}, pool {n_pages}x{PAGE_SIZE}, table {n_max})")
    check(err <= DECODE_ATOL, f"paged decode kernel agrees (atol {DECODE_ATOL})")
    # the same pages laid out contiguously, through the contiguous kernel
    S = n_max * PAGE_SIZE
    kc = kp[jnp.maximum(pt, 0)].reshape(N_SLOTS, S, Hkv, D)
    vc = vp[jnp.maximum(pt, 0)].reshape(N_SLOTS, S, Hkv, D)
    kpos = jnp.where(jnp.repeat(pt >= 0, PAGE_SIZE, axis=1),
                     jnp.arange(S, dtype=jnp.int32)[None], -1)
    for b in range(N_SLOTS):
        got = ops.decode_attention(q[b:b + 1], kc[b:b + 1], vc[b:b + 1],
                                   kpos[b:b + 1], t=ts[b])
        want = ref.decode_attention_reference(q[b:b + 1], kc[b:b + 1],
                                              vc[b:b + 1], kpos[b:b + 1],
                                              t=ts[b])
        err = max(err, float(jnp.max(jnp.abs(got.astype(jnp.float32)
                                             - want.astype(jnp.float32)))))
    log(f"contiguous decode kernel vs reference: max |err| {err:.3e}")
    check(err <= DECODE_ATOL,
          f"contiguous decode kernel agrees (atol {DECODE_ATOL})")


def four_chip_phase(devs, budget: int) -> None:
    import numpy as np
    from repro.core.recipe import ParallelismConfig
    from repro.launch.mesh import make_plan_mesh
    from repro.session import TrainSession

    check(len(devs) == 4, f"four chips present (found {len(devs)})")
    plan4 = ParallelismConfig(pp=2, tp=2, gas=4)
    plan1 = ParallelismConfig(gas=4)
    mesh = make_plan_mesh(plan4)
    B = FOUR_CHIP_BATCH
    layers = None
    for cand in FOUR_CHIP_LAYERS:
        t0 = time.perf_counter()
        need4 = step_footprint(cut(cand), plan4, B, mesh)
        need1 = step_footprint(cut(cand), plan1, B)
        log(f"sizing layers={cand} batch={B}x{SEQ}: pp2xtp2 step plans "
            f"{need4 / 1e9:.2f} GB/chip, one-chip gas=4 step "
            f"{need1 / 1e9:.2f} GB, of {budget / 1e9:.2f} GB "
            f"({time.perf_counter() - t0:.1f}s to compile)")
        if max(need4, need1) <= budget:
            layers = cand
            break
    check(layers is not None, "a candidate depth fits both layouts")
    cfg = cut(layers)

    t0 = time.perf_counter()
    sess = TrainSession.from_recipe(cfg, plan=plan4, mesh=mesh,
                                    train_cfg=train_cfg(), data_cfg=data_cfg(B),
                                    seed=SEED)
    compiled = sess.train_step.lower(sess.state, sess.batches(0)).compile()
    log(f"pp2xtp2 session made and step compiled in "
        f"{time.perf_counter() - t0:.1f}s")
    check("tpu_custom_call" in compiled.as_text(),
          "sharded train step contains the Pallas flash kernel")
    del compiled
    clock = StepClock()
    out4 = sess.run(3, log_every=1, tracker=clock, log=lambda s: None)
    peaks = [peak_bytes(d) for d in devs]
    log("pp2xtp2 peak_bytes_in_use per chip: "
        + " ".join(f"{p / 1e9:.2f}GB" for p in peaks))
    log(f"pp2xtp2 step seconds (host clock): "
        + " ".join(f"{s:.4f}" for s in clock.step_seconds()))
    check(out4["skipped_steps"] == 0 and all(
        math.isfinite(float(h["loss"])) for h in out4["history"]),
          "sharded steps finite, none skipped")
    check(peaks[0] <= 1.5 * float(np.mean(peaks[1:])),
          "chip 0 peak <= 1.5x the other chips' mean")
    loss4, gn4 = first_step(out4)
    release(out4)
    sess = None
    gc.collect()
    log(f"live device bytes before the one-chip run: {live_bytes() / 1e9:.2f} GB")

    sess = TrainSession.from_recipe(cfg, plan=plan1, train_cfg=train_cfg(),
                                    data_cfg=data_cfg(B), seed=SEED)
    out1 = sess.run(1, log_every=1, log=lambda s: None)
    loss1, gn1 = first_step(out1)
    log(f"step 0 pp2xtp2: loss={loss4:.6f} grad_norm={gn4:.6f}; one chip: "
        f"loss={loss1:.6f} grad_norm={gn1:.6f}; rtol={RTOL}")
    check(close(loss4, loss1, RTOL) and close(gn4, gn1, RTOL),
          f"pp2xtp2 and one chip agree on step 0 (rtol {RTOL})")


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the pp=2 x tp=2 phase on four local chips")
    args = ap.parse_args(argv)

    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: JAX finds no TPU (platform {devs[0].platform}); "
              f"nothing was run", file=sys.stderr)
        return 2

    from repro.runtime.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    cache_events = collections.Counter()
    jax.monitoring.register_event_listener(
        lambda event, **kw: cache_events.update([event])
        if event.startswith("/jax/compilation_cache/") else None)

    dev = devs[0]
    limit = int((dev.memory_stats() or {}).get("bytes_limit", 16 * 2 ** 30))
    budget = int(MEM_FRACTION * limit)
    log(f"device {dev.platform} {dev.device_kind} x{len(devs)}; "
        f"bytes_limit {limit / 1e9:.2f} GB; compile cache {cache_dir}")
    t0 = time.perf_counter()
    try:
        if args.four_chips:
            four_chip_phase(devs, budget)
        else:
            train_phase(dev, budget)
            serve_phase(dev)
    except Failed as e:
        log(f"FAIL {e}")
        return 1
    log(f"compile cache: hits {cache_events['/jax/compilation_cache/cache_hits']}"
        f", misses {cache_events['/jax/compilation_cache/cache_misses']}")
    log(f"all phases passed in {time.perf_counter() - t0:.1f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
