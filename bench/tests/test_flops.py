"""Operation and byte counts against hand-worked numbers."""

import dataclasses

import numpy as np
import pytest

from bench import flops, peaks


@dataclasses.dataclass
class Cfg:
    n_layers: int = 1
    d_model: int = 8
    n_heads: int = 2
    n_kv_heads: int = 1
    d_ff: int = 16
    vocab_size: int = 10
    hd: int = 4
    gated_mlp: bool = True


def test_matmul_params_by_hand():
    # q,k,v: 8 x (8 + 4 + 4); o: 8 x 8; gated MLP: 3 x 8 x 16; head: 10 x 8
    assert flops.layer_matmul_params(Cfg()) == 128 + 64 + 384
    assert flops.matmul_params(Cfg()) == 576 + 80


def test_packed_batch_counts_by_hand():
    seg = np.array([[0, 0, 0, 1, 1]])
    assert flops.segment_lengths(seg) == [3, 2]
    pairs = flops.causal_pairs(flops.segment_lengths(seg))
    assert pairs == 6 + 3                       # 3*4/2 + 2*3/2
    # forward attention: 2 products x 2 ops x 9 pairs x head_dim 4 x 2 heads
    assert flops.attention_fwd_flops(Cfg(), pairs) == 288
    # 6 per weight per token, attention three times its forward, no remat
    assert flops.train_flops(Cfg(), 5, pairs) == 6 * 656 * 5 + 3 * 288


def test_flash_work_unpadded_and_without_recompute():
    ops, nbytes = flops.flash_train_work(Cfg(), batch=1, seq=5, pairs=9)
    assert ops == 6 * 2 * 9 * 4 * 2            # six products, head_dim 4 not 64
    q_like = 1 * 5 * 2 * 4 * 2                 # Q O dO dQ in bf16
    kv_like = 1 * 5 * 1 * 4 * 2                # K V dK dV in bf16
    assert nbytes == 4 * q_like + 4 * kv_like + 1 * 2 * 5 * 4


def test_roofline_names_its_bound():
    v5e = peaks.peak("TPU v5 lite")
    t, bound = flops.roofline_seconds(197e12, 1.0, v5e)
    assert bound == "flops" and t == pytest.approx(1.0)
    t, bound = flops.roofline_seconds(1.0, 819e9, v5e)
    assert bound == "bytes" and t == pytest.approx(1.0)


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError):
        peaks.peak("TPU v4")
