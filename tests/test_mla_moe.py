"""DeepSeek-V2-Lite's block on the CPU at a tiny size, on seeded random
weights: latent attention (MLA, YaRN) and the expert layer that holds a
share of the experts, against the plain reference
``bench/reference/mla_moe.py``.

- the program's logits, and its loss and gradients through the train
  step the benchmark drives (``TrainSession``), equal the reference's;
- the shares of a layer's experts, the shared experts counted once, add up
  to the uncut layer (what EP's exchange would sum);
- no (token, slot) pair is dropped, however the router loads one expert;
- the whole program config and the benchmark's cut count their
  parameters exactly (``n_params``).
"""

import copy
import dataclasses
import math
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import correct, harness, spec  # noqa: E402
from bench.reference import mla_moe as ref  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.models import moe  # noqa: E402
from repro.models.registry import family_of  # noqa: E402

CELL = "deepseek_v2_lite_ep8.train.pack8k"
TINY = dict(n_layers=3, d_model=64, n_heads=4, n_kv_heads=4, d_ff=128,
            vocab_size=256, n_experts=4, router_experts=16, top_k=6,
            moe_d_ff=32, kv_lora_rank=32, qk_nope_head_dim=16,
            qk_rope_head_dim=8, v_head_dim=8, rope_original_max=64,
            dtype="float32")


def _job(seed=2 ** 33 + 5):
    """The cell's own configuration, driver and reference at tiny widths
    (the cell's YaRN, routing and balance settings kept), in float32."""
    from bench.run import Job
    bench = spec.benchmark()
    cell = spec.cell(CELL, bench)
    conf = copy.deepcopy(spec.config_file(cell["config"], bench))
    conf["model"].update(TINY)
    mix = copy.deepcopy(spec.traffic(cell["traffic"]))
    mix.update(seq_len=128, doc_mean=32, distinct_batches=3)
    return Job(bench, CELL, seed, 1, False, jax.devices(),
               harness.CompileCounter(), conf=conf, mix=mix)


def _weights(job):
    from bench import weights
    return ref.nested(weights.make_canonical(
        ref.param_shapes(job.conf["model"]), job.seed, jnp.float32))


def _program_params(w):
    """The reference's tree in the program's layout (a list of leading
    dense blocks)."""
    p = dict(w)
    pre = p.pop("pre_blocks")
    p["pre_blocks"] = [pre[str(i)] for i in range(len(pre))]
    return p


def test_logits_match_reference():
    job = _job()
    cfg = job.model_cfg
    from bench.drivers import train
    b = train.host_batches(job, 1)[0]
    w = _weights(job)
    logits = family_of(cfg).forward(cfg, _program_params(w),
                                    {k: jnp.asarray(v) for k, v in b.items()})
    with jax.default_matmul_precision("highest"):
        for r in range(b["tokens"].shape[0]):
            want, _ = ref.forward(job.conf["model"], w,
                                  jnp.asarray(b["tokens"][r]),
                                  jnp.asarray(b["segment_ids"][r]))
            np.testing.assert_allclose(np.asarray(logits[r]),
                                       np.asarray(want), atol=2e-5, rtol=1e-4)


def test_train_step_matches_reference():
    """Loss, first clipped gradient and 3 steps' change through the
    benchmark's train driver (``TrainSession.run``) against the reference,
    by the cell's own comparison: in float32 they agree to round-off."""
    from bench.drivers import train
    job = _job()
    sess, host, program = train.setup(job)
    harness.free(sess.state)
    nums = correct.train_numbers(program, train.reference(job, host))
    assert nums["loss_gap"][0] < 1e-5, nums
    assert nums["grad_gap"][0] < 1e-4, nums
    assert nums["delta_gap"][0] < 1e-4, nums


def _layer(n_router, held, seed=0, T=48, d=32, ff=16, k=3):
    """A tiny expert layer's config, its full weights (all ``n_router``
    experts) and its input."""
    cfg = dataclasses.replace(
        get_config("deepseek_v2_lite").reduced(), d_model=d, moe_d_ff=ff,
        n_experts=held, router_experts=n_router, top_k=k, dtype="float32")
    ks = jax.random.split(jax.random.PRNGKey(seed), 8)
    p = {"router": jax.random.normal(ks[0], (d, n_router)) / math.sqrt(d),
         "w_gate": jax.random.normal(ks[1], (n_router, d, ff)) / math.sqrt(d),
         "w_up": jax.random.normal(ks[2], (n_router, d, ff)) / math.sqrt(d),
         "w_out": jax.random.normal(ks[3], (n_router, ff, d)) / math.sqrt(ff),
         "shared": {"w_gate": jax.random.normal(ks[4], (d, ff)) / math.sqrt(d),
                    "w_up": jax.random.normal(ks[5], (d, ff)) / math.sqrt(d),
                    "w_out": jax.random.normal(ks[6], (ff, d)) / math.sqrt(ff)}}
    x = jax.random.normal(ks[7], (2, T // 2, d))
    return cfg, p, x


def _share(p, first, held):
    """The weights one chip of EP holds: experts ``first..first+held-1``
    as its ids 0..held-1, the router's columns turned to match."""
    out = dict(p, router=jnp.roll(p["router"], -first, axis=1))
    for n in ("w_gate", "w_up", "w_out"):
        out[n] = p[n][first:first + held]
    return out


def test_expert_shares_sum_to_the_whole_layer():
    """8 shares of 2 experts of a 16-expert layer: the held parts, plus the
    shared experts once, are the uncut reference's layer; each share reads
    the whole router's balance loss."""
    E, held, shares = 16, 2, 8
    cfg, p, x = _layer(E, held)
    total, auxes = 0.0, []
    for j in range(shares):
        out, stats = moe.moe_apply(cfg, _share(p, j * held, held), x)
        total = total + out
        auxes.append(float(stats["aux"]))
    shared = jax.vmap(lambda xr: ref._swiglu(ref._ops("f32"), p["shared"], xr))(x)
    total = total - (shares - 1) * shared
    m = {"n_experts": E, "top_k": cfg.top_k, "norm_topk_prob": False}
    with jax.default_matmul_precision("highest"):
        for r in range(x.shape[0]):
            want, bal = ref._experts(m, ref._ops("f32"), p, x[r])
            np.testing.assert_allclose(np.asarray(total[r]), np.asarray(want),
                                       atol=2e-5, rtol=1e-4)
    np.testing.assert_allclose(auxes, auxes[0], rtol=1e-6)


def test_no_pair_dropped_when_one_expert_takes_every_token():
    """A router that sends every token to expert 0 first: all T pairs of
    slot 0 land on one held expert (past any capacity a GShard layer would
    give it), and the layer still equals the reference's routed sum."""
    E, held = 8, 4
    cfg, p, x = _layer(E, held, seed=1)
    x = jnp.abs(x) + 0.5
    p = _share(dict(p, router=p["router"].at[:, 0].set(3.0)), 0, held)
    out, stats = moe.moe_apply(cfg, p, x)
    _, _, gate_idx = moe._route(cfg, p, x.reshape(-1, x.shape[-1]))
    T = x.shape[0] * x.shape[1]
    assert bool(jnp.all(gate_idx[:, 0] == 0))
    assert float(stats["moe_held_tokens"]) == float(jnp.sum(gate_idx < held))
    mean = float(stats["moe_held_tokens"]) / held
    assert float(stats["moe_max_load"]) == pytest.approx(T / mean)
    m = {"n_experts": held, "top_k": cfg.top_k, "norm_topk_prob": False}
    with jax.default_matmul_precision("highest"):
        for r in range(x.shape[0]):
            want, _ = ref._experts(m, ref._ops("f32"), p, x[r])
            np.testing.assert_allclose(np.asarray(out[r]), np.asarray(want),
                                       atol=2e-5, rtol=1e-4)


def _cut():
    bench = spec.benchmark()
    return spec.model_config(spec.config_file("deepseek_v2_lite_ep8", bench))


@pytest.mark.parametrize("which", ["whole", "ep8_cut"])
def test_n_params_counts_the_initialized_tree(which):
    cfg = get_config("deepseek_v2_lite") if which == "whole" else _cut()
    tree = jax.eval_shape(lambda k: family_of(cfg).init_params(cfg, k),
                          jax.random.PRNGKey(0))
    assert sum(math.prod(x.shape) for x in jax.tree_util.tree_leaves(tree)) \
        == cfg.n_params()
    if which == "ep8_cut":
        assert cfg.n_params() == 635_466_752


def test_yarn_softmax_scale_and_frequencies():
    """192^-0.5 · m² with m = 0.1 · 0.707 · ln 40 + 1; the fastest rotary
    dims keep their frequency, the slowest are divided by the factor."""
    from repro.models import attention, layers
    cfg = get_config("deepseek_v2_lite")
    m = 0.1 * 0.707 * math.log(40) + 1
    assert attention.mla_softmax_scale(cfg) == pytest.approx(192 ** -0.5 * m * m)
    f = np.asarray(layers.yarn_freqs(64, 1e4, 40.0, 4096, 32.0, 1.0))
    base = np.asarray(layers.rope_freqs(64, 1e4))
    assert f[0] == pytest.approx(base[0]) and f[-1] == pytest.approx(base[-1] / 40)
    want, _, _ = ref._yarn({"qk_rope_head_dim": 64, "qk_nope_head_dim": 128,
                            "rope_scaling_factor": 40.0,
                            "rope_original_max": 4096})
    np.testing.assert_allclose(f, np.asarray(want), rtol=1e-6)


def test_mla_refuses_decode():
    from repro.models import attention
    cfg = get_config("deepseek_v2_lite").reduced()
    with pytest.raises(NotImplementedError):
        attention.attention_decode(cfg, {}, jnp.zeros((1, 1, cfg.d_model)),
                                   jnp.int32(0), {})
