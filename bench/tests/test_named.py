"""The reduction of a trace by the program's names (``bench/named.py``) and
the numbers ``bench/breakdown.py`` prints: interval arithmetic and
readers by hand, and two recorded TPU traces (``data/v5e_flash.xplane.pb``,
no program names; ``data/v5e_train_toy.xplane.pb``, three steps of a toy
train session, ``tests/record_train_trace.py``)."""

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
