"""The reduction of a trace by the program's names (``bench/named.py``) and
the numbers ``bench/breakdown.py`` prints: interval arithmetic and
readers by hand, and two recorded TPU traces (``data/v5e_flash.xplane.pb``,
no program names; ``data/v5e_train_toy.xplane.pb``, three steps of a toy
train session, ``tests/record_train_trace.py``).

This is the file under ``bench/`` that tier 1 collects, so it also holds
the trace reduction's collective arithmetic (``trace.collective_times``)
by hand, and the train driver's layer count for a model whose first layer
is not stacked."""

from pathlib import Path

import pytest

from bench import named, trace

DATA = Path(__file__).parent / "data"


@pytest.mark.parametrize("stack,want", [
    ("jit(train_step)/jvp(forward)/while/body/closed_call/dot_general",
     "forward"),
    ("jit(train_step)/transpose(jvp(forward))/while/body/closed_call/"
     "checkpoint/rematted_computation/jit(flash_attention)/"
     "flash_attention_fwd/pallas_call", "backward"),
    ("jit(train_step)/optimizer/jit(_where)/select_n", "optimizer"),
    ("jit(train_step)/is_finite", "other"),
    ("jit(paged_serve_step)/dot_general", "other"),
])
def test_phase_by_name_stack(stack, want):
    assert named.phase(stack) == want


def test_kernel_name():
    assert named.kernel_name("flash_attention_dkv.12") == "flash_attention_dkv"
    assert named.kernel_name("paged_decode_attention") == \
        "paged_decode_attention"


def test_interval_arithmetic_by_hand():
    assert named.merge([(5, 7), (0, 2), (1, 3)]) == [(0, 3), (5, 7)]
    assert named.overlap([(0, 4), (6, 10)], [(3, 7), (9, 12)]) == 1 + 1 + 1
    assert named._holes([(2, 3), (2.5, 5), (7, 8)], 0, 10) == \
        [(0, 2), (5, 7), (8, 10)]


def _named(**kw):
    base = dict(window_s=1.0, devices=1, busy_s=0.9, phase_s={},
                kernel_s={}, spans={}, idle=[])
    return named.Named(**dict(base, **kw))


def test_idle_by_span_by_hand():
    nm = _named(spans={"train": [(0, 10), (10, 20)],
                       "train.dispatch": [(1, 2), (10, 12)],
                       "train.observe": [(2, 6), (12, 16)]},
                idle=[(3, 5), (8, 11), (15, 17)])
    assert nm.idle_s("train.observe") == pytest.approx(3e-9)
    assert nm.idle_s("train.dispatch", "train.observe") == \
        pytest.approx(4e-9)
    by = nm.idle_by_span()
    assert by["train"] == pytest.approx(7e-9)
    assert by["(none)"] == pytest.approx(0.0)
    assert nm.longest_idle(2) == [("train", 3e-9), ("train.observe", 2e-9)]
    assert nm.count("train.dispatch") == 2


def test_flash_trace_without_program_names():
    """The recorded flash call: the same window and busy time as
    ``trace.reduce`` reads, one kernel, no phase and no program span."""
    path = DATA / "v5e_flash.xplane.pb"
    nm, red = named.reduce(path), trace.reduce(path)
    assert nm.window_s == red.window_s
    assert nm.busy_s == pytest.approx(red.busy_s[0])
    assert nm.kernel_s == {"other/flash_attention":
                           pytest.approx((17868 + 17871) * 1e-9)}
    assert set(nm.phase_s) == {"other"} and nm.spans == {}
    assert nm.longest_idle(1) == [("(none)", pytest.approx(805835e-9))]


def test_train_summary_by_hand():
    nm = _named(busy_s=0.5, phase_s={"forward": 0.1, "backward": 0.3,
                                     "optimizer": 0.05, "other": 0.05},
                kernel_s={"backward/flash_attention_fwd": 0.02},
                spans={"train": [(0, 5e8), (5e8, 1e9)],
                       "train.dispatch": [(0, 1e6), (5e8, 5.01e8)],
                       "train.observe": [(1e6, 3e8), (5.01e8, 8e8)]},
                idle=[(2e8, 2.1e8), (6e8, 6.3e8)])
    out = named.summary(nm, {"driver": "train", "steps": 2}, [])
    assert out["step_forward_ms"] == pytest.approx(50.0)
    assert out["step_backward_ms"] == pytest.approx(150.0)
    assert out["step_optimizer_ms"] == pytest.approx(25.0)
    assert out["step_other_ms"] == pytest.approx(25.0)
    assert out["busy_ms_per_step"] == pytest.approx(250.0)
    assert out["host_idle_ms.train"] == pytest.approx(20.0)
    assert out["span_ms"]["train.observe"] == pytest.approx(299.0)
    assert out["kernel_ms_per_step"] == {
        "backward/flash_attention_fwd": pytest.approx(10.0)}


def test_serve_summary_by_hand():
    from repro.session.scheduler import ServingStats
    a = ServingStats(admit_s=0.3, step_times=[1.0, 1.02, 1.05],
                     first_token_times=[0.99], token_steps=[(0, 3)])
    b = ServingStats(admit_s=0.1, step_times=[2.0, 2.03],
                     first_token_times=[1.95], token_steps=[(0, 2)])
    nm = _named(spans={"serve.decode": [(0, 1e6), (3e6, 4e6)],
                       "serve.read": [(1e6, 2e6), (4e6, 5e6)]},
                idle=[(1.5e6, 3.5e6)])
    out = named.summary(nm, {"driver": "serve", "window_s": 10.0},
                            [a, b])
    # gaps 10, 20, 30 ms (a) and 50, 30 ms (b)
    assert out["inter_token_ms.serve"] == pytest.approx(30.0)
    assert out["max_token_gap_ms"] == pytest.approx(50.0)
    assert out["prefill_share.serve"] == pytest.approx(4.0)
    assert out["host_idle_ms.serve"] == pytest.approx(0.5)    # 1 ms, 2 steps


def test_recorded_train_trace():
    """Three toy train steps on one chip.  Kernel times, span counts and
    idle inside spans summed by hand from the file's 1,410 ``XLA Ops``
    events and its host events; the forward kernel appears twice, once
    under the backward's stack (the remat recompute)."""
    path = DATA / "v5e_train_toy.xplane.pb"
    nm, red = named.reduce(path), trace.reduce(path)
    assert nm.window_s == pytest.approx(51482936e-9)
    assert nm.busy_s == pytest.approx(10816277e-9) == red.busy_s[0]
    want = {"forward/flash_attention_fwd": 2585024,
            "backward/flash_attention_fwd": 2443943,
            "backward/flash_attention_delta": 42416,
            "backward/flash_attention_dq": 2181966,
            "backward/flash_attention_dkv": 2459243}
    for kernel, ns in want.items():
        assert nm.kernel_s[kernel] == pytest.approx(ns * 1e-9), kernel
    # the phases hold every op, each op's own time once
    assert set(nm.phase_s) == {"forward", "backward", "optimizer", "other"}
    assert sum(nm.phase_s.values()) == pytest.approx(nm.busy_s)
    assert nm.phase_s["backward"] > nm.phase_s["forward"] > \
        nm.phase_s["optimizer"] > 0
    assert {k: nm.count(k) for k in nm.spans} == {
        "train": 3, "train.batch": 3, "train.dispatch": 3,
        "train.observe": 3, "train.log": 2}
    assert nm.idle_s("train.observe") == pytest.approx(6679802e-9)
    assert nm.idle_s("train.log") == pytest.approx(10014424e-9)
    assert nm.idle_s("train.dispatch") == pytest.approx(119135e-9)
    out = named.summary(nm, {"driver": "train", "steps": 3}, [])
    assert out["host_idle_ms.train"] == pytest.approx(16815512e-6 / 3)
    assert out["step_forward_ms"] + out["step_backward_ms"] + \
        out["step_optimizer_ms"] + out["step_other_ms"] == \
        pytest.approx(out["busy_ms_per_step"])


@pytest.mark.parametrize("events,want", [
    # a collective wholly under a fusion, and one beside it
    ([(0, 10, "fusion.1"), (2, 6, "all-reduce.3")], (4, 0)),
    # half covered: the uncovered half is exposed
    ([(0, 4, "fusion.1"), (2, 6, "collective-permute-done.2")], (4, 2)),
    # a loop encloses its body: its span is no work of its own
    ([(0, 20, "while.4"), (1, 5, "fusion.2"), (5, 9, "all-gather-start"),
      (9, 12, "all-gather-done")], (7, 7)),
    ([(0, 3, "fusion.1"), (3, 5, "copy.2")], (0, 0)),
])
def test_collective_times_by_hand(events, want):
    assert trace.collective_times(events) == want


def test_collective_names():
    for name in ("all-reduce.12", "all-reduce-start.3", "all-reduce-done",
                 "collective-permute-start.1", "reduce-scatter.2",
                 "all-to-all.7", "all-gather-done.5"):
        assert trace.COLLECTIVE.match(name), name
    for name in ("fusion.12", "all-reduce-scatter-fusion", "copy.4",
                 "flash_attention_fwd.2"):
        assert not trace.COLLECTIVE.match(name), name


def test_collective_exposed_share_is_the_mean_over_chips():
    from bench import spec
    red = trace.Reduced(window_s=10.0, devices=2, busy_s=[9.0, 9.0],
                        op_self_s={}, idle_gaps=[], collective_s=[3.0, 1.0],
                        collective_exposed_s=[2.0, 0.0])
    read = spec.metric_reader("collective_exposed_share")
    assert read({"driver": "train", "trace": red}) == pytest.approx(10.0)
    none = trace.Reduced(10.0, 1, [9.0], {}, [], [0.0], [0.0])
    assert read({"driver": "train", "trace": none}) is None


def test_flash_roofline_of_a_trace_cut_short():
    """A trace of the window's first half holds half its work: half the
    kernel time reads the same share as the whole window's."""
    from bench import peaks, spec
    read = spec.metric_reader("flash_attention_roofline")
    ctx = {"driver": "train", "window_s": 30.0, "chips": 4,
           "flash_ops": 4 * 197e12, "flash_bytes": 0.0,
           "peak": peaks.peak("TPU v5 lite")}

    def red(window_s, kernel_s):
        return trace.Reduced(window_s, 4, [window_s] * 4,
                             {"flash_attention_fwd.1": 4 * kernel_s}, [],
                             [0.0] * 4, [0.0] * 4)
    whole = read(dict(ctx, trace=red(30.0001, 20.0)))
    assert whole == pytest.approx(5.0)
    assert read(dict(ctx, trace=red(15.0, 10.0))) == pytest.approx(whole)


def test_recorded_traces_hold_no_collective():
    for f in ("v5e_flash.xplane.pb", "v5e_train_toy.xplane.pb"):
        red = trace.reduce(DATA / f)
        assert red.collective_s == [0.0] == red.collective_exposed_s


def test_dense_prefix_tree_goes_through_weights_and_norms():
    """DeepSeekMoE's first layer is dense and unstacked (``pre_blocks``):
    its ``blocks/`` stack holds ``n_layers - 1`` layers, which the driver
    hands to ``weights.make`` and ``layer_norms``."""
    import jax
    import numpy as np
    from bench import weights
    from bench.drivers import train
    from repro.configs import get_config
    from repro.models import api as model_api
    cfg = get_config("deepseek_moe_16b").reduced()
    assert cfg.first_k_dense == 1
    n = train.stacked_layers(cfg)
    assert n == cfg.n_layers - 1
    abstract = jax.eval_shape(lambda: model_api.init_params(
        cfg, jax.random.PRNGKey(0)))
    params = weights.make(abstract, 5, n, np.float32)
    norms = train.layer_norms(params, n)
    assert any(k.startswith("pre_blocks/") for k in norms)
    assert all(v.shape == (n,) for k, v in norms.items()
               if k.startswith("blocks/"))
    with pytest.raises(ValueError):
        weights.canonical_shape("blocks/attn/wq",
                                abstract["blocks"]["attn"]["wq"].shape,
                                cfg.n_layers)


def test_granite_tiny_readings_unchanged():
    """The one-chip train cell's tiny job (``tiny.py``): the program's and
    the reference's readings as the driver gave them before it counted
    stacked layers and could spread the reference over chips."""
    from bench import traffic
    from bench.drivers import train
    from bench.tests import tiny
    job = tiny.job(tiny.TRAIN)
    sess, host, prog = train.setup(job)
    ref = train.reference(job, host[:train.CHECK_STEPS])
    assert prog["loss"] == pytest.approx(
        [6.33021354675293, 6.351413249969482, 6.325653553009033], rel=1e-5)
    assert ref["loss"] == pytest.approx(
        [6.330146603467988, 6.351176440231199, 6.325819806354802], rel=1e-6)
    assert list(prog["grad"]["blocks/mlp/w_out"]) == pytest.approx(
        [0.2054683417081833, 0.11930213868618011], rel=1e-4)
    assert list(ref["grad"]["blocks/mlp/w_out"]) == pytest.approx(
        [0.20545929670333862, 0.1192716434597969], rel=1e-5)
    assert list(prog["delta"]["embed"]) == pytest.approx(
        [0.002748674713075161], rel=1e-4)
    assert list(ref["delta"]["embed"]) == pytest.approx(
        [0.0027480819262564182], rel=1e-5)
    assert traffic.train_batch(job.mix, 2, job.model_cfg.vocab_size,
                               job.seed, 0)["tokens"].shape == (2, 128)
