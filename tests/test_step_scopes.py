"""The training step's phases carry names: the model loss runs under the
``forward`` scope, so its ops lower as ``jvp(forward)`` and the backward's,
remat's recompute included, as ``transpose(jvp(forward))``; everything
after the gradients (norm, anomaly gate, AdamW, skip select) runs under
``optimizer``.  Device traces carry these name stacks for every op, and the
train loop and the serving scheduler write host spans beside them."""

import re
from collections import Counter
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.core import stepfn
from repro.core.recipe import ParallelismConfig


def test_train_step_phases_by_name_stack():
    cfg = get_config("granite_3_2b").reduced()
    tc = stepfn.TrainConfig(peak_lr=1e-3, warmup=4, total_steps=8)
    plan = ParallelismConfig(remat_policy="full")
    state = jax.eval_shape(
        lambda: stepfn.init_state(cfg, plan, jax.random.PRNGKey(0), tc))
    tok = jax.ShapeDtypeStruct((2, 32), jnp.int32)
    hlo = jax.jit(stepfn.make_train_step(cfg, plan, tc)).lower(
        state, {"tokens": tok, "labels": tok}).compile().as_text()
    names = re.findall(r'op_name="(jit\(train_step\)/[^"]*)"', hlo)
    for phase in ("jvp(forward)", "transpose(jvp(forward))", "optimizer"):
        assert any(n.startswith(f"jit(train_step)/{phase}/") for n in names)
    # remat's recompute of the forward sits under the backward's stack
    assert any("transpose(jvp(forward))" in n and "rematted_computation" in n
               for n in names)
    # AdamW's moment updates are the optimizer's
    assert any(n.endswith("optimizer/sqrt") for n in names)


def _host_span_names(log_dir) -> Counter:
    from jax.profiler import ProfileData
    (path,) = Path(log_dir).rglob("*.xplane.pb")
    pd = ProfileData.from_file(str(path))
    return Counter(ev.name for pl in pd.planes if pl.name == "/host:CPU"
                   for ln in pl.lines for ev in ln.events)


def test_loops_write_spans_into_the_profile(tmp_path):
    """The train loop's step span and its children, and the scheduler's
    spans, land in a profiler trace (on any backend)."""
    from repro.session import InferenceSession, TrainSession
    cfg = get_config("granite_3_2b").reduced()
    tok = np.random.RandomState(0).randint(0, cfg.vocab_size, (2, 33))
    batch = {"tokens": jnp.asarray(tok[:, :-1]),
             "labels": jnp.asarray(tok[:, 1:])}
    sess = TrainSession(cfg, train_cfg=stepfn.TrainConfig(
        peak_lr=1e-3, warmup=4, total_steps=8))
    sess.batches = lambda step: batch
    inf = InferenceSession.from_recipe("granite_3_2b", reduced=True, seed=0)
    prompts = [tok[0, :6].astype(np.int32), tok[1, :5].astype(np.int32)]
    with jax.profiler.trace(str(tmp_path / "trace")):
        sess.run(3, log_every=2, log=lambda s: None,
                 ckpt_dir=str(tmp_path / "ckpt"), ckpt_every=2)
        for paged in (False, True):
            inf.serve(prompts, [3, 2], n_slots=1, paged=paged)
    names = _host_span_names(tmp_path / "trace")
    for span, n in [("train", 3), ("train.batch", 3), ("train.dispatch", 3),
                    ("train.observe", 3), ("train.log", 2)]:
        assert names[span] == n, span
    # the save after step 1 and the wait for its background writer at the
    # end of the run
    assert names["train.ckpt"] == 2
    # two requests through one slot, each path: two admissions, and
    # 2 + 1 decode steps (the first token comes from the prefill)
    assert names["serve.admit"] == 4
    for span in ("serve.decode", "serve.read", "serve.retire"):
        assert names[span] == 6, span
