"""The trace reduction: interval arithmetic by hand, and one recorded TPU
trace (``data/v5e_flash.xplane.pb``: a flash-attention call and a matmul
under ``bench_window`` on one TPU v5 lite)."""

from pathlib import Path

import pytest

from bench import trace

DATA = Path(__file__).parent / "data"


def test_op_name():
    assert trace.op_name("%flash_attention.54 = (f32[2]) custom-call(x)") \
        == "flash_attention.54"


def test_union_by_hand():
    assert trace.union_length([(0, 4), (2, 6), (8, 9)]) == 7
    assert trace.union_length([(0, 10), (2, 3)]) == 10


def test_self_times_exclude_enclosed_ops():
    ev = [(0, 10, "while"), (1, 3, "a"), (4, 8, "b"), (12, 13, "a")]
    st = trace.self_times(ev)
    assert st == {"while": 4, "a": 3, "b": 4}


def test_idle_gaps_named_by_host_span():
    gaps = trace._idle_gaps([(2, 3), (7, 8)], 0, 10,
                            [(0, 10, "outer"), (4, 6, "inner")], n=2)
    assert gaps == [["inner", 4e-9], ["outer", 2e-9]]
    # an op inside another's span opens no gap
    gaps = trace._idle_gaps([(0, 5), (1, 2), (6, 10)], 0, 10, [], n=3)
    assert gaps == [["no host span", 1e-9]]


def test_recorded_trace():
    """Numbers summed by hand from the file's 20 ``XLA Ops`` events (none
    overlap) and its ``bench_window`` span."""
    red = trace.reduce(DATA / "v5e_flash.xplane.pb")
    assert red.devices == 1
    assert red.window_s == pytest.approx(3083520e-9)
    assert red.busy_s[0] == pytest.approx(70469e-9)
    assert red.kernel_seconds(r"^flash_attention(\.\d+)?$") == \
        pytest.approx((17868 + 17871) * 1e-9)
    # copy.1 ends at 39828296 ns, the next op starts at 40634131 ns
    assert red.idle_gaps[0][1] == pytest.approx(805835e-9)
