"""Differentiable flash attention: the custom_vjp's fused Pallas backward
kernels (delta preprocess, dQ sweep, dK/dV sweep) must match reference-
attention autodiff across causal / sliding-window / GQA / odd-head-dim
cases, and the backward HLO must never materialize the (B, H, S, S) score
matrix (the residuals are (q, k, v, O, lse) only)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention

KEY = jax.random.PRNGKey(0)


def _qkv(B, S, Hq, Hkv, D, dtype=jnp.float32):
    q = jax.random.normal(KEY, (B, S, Hq, D), dtype)
    k = jax.random.normal(jax.random.fold_in(KEY, 1), (B, S, Hkv, D), dtype)
    v = jax.random.normal(jax.random.fold_in(KEY, 2), (B, S, Hkv, D), dtype)
    return q, k, v


def _grads(fn, q, k, v, cot):
    return jax.grad(lambda q, k, v: (fn(q, k, v).astype(jnp.float32) * cot).sum(),
                    argnums=(0, 1, 2))(q, k, v)


@pytest.mark.parametrize("causal,window,Hq,Hkv,D", [
    (True, None, 4, 4, 64),     # plain causal MHA
    (True, 64, 8, 2, 64),       # sliding window + GQA
    (True, 32, 4, 2, 96),       # window + GQA + padded head dim
    (False, None, 4, 1, 64),    # bidirectional MQA
    (True, None, 4, 4, 120),    # odd head dim (pad to 128 inside the kernel)
    (True, None, 32, 1, 64),    # MQA: the fold cap splits the group of 32
    (False, None, 2, 2, 64),    # g = 1: no fold, dead-tile clamp only
    (True, 48, 4, 2, 64),       # sliding window + g = 2 folded
])
def test_flash_vjp_matches_reference_autodiff(causal, window, Hq, Hkv, D):
    B, S = 2, 128
    q, k, v = _qkv(B, S, Hq, Hkv, D)
    cot = jax.random.normal(jax.random.fold_in(KEY, 3), (B, S, Hq, D))

    def fl(q, k, v):
        return flash_attention(q, k, v, causal=causal, window=window,
                               bq=64, bk=64, interpret=True)

    def rf(q, k, v):
        return ref.mha_reference(q, k, v, causal=causal, window=window)

    np.testing.assert_allclose(np.asarray(fl(q, k, v)), np.asarray(rf(q, k, v)),
                               atol=2e-5, rtol=2e-5)
    for g_fl, g_rf, name in zip(_grads(fl, q, k, v, cot),
                                _grads(rf, q, k, v, cot), "qkv"):
        np.testing.assert_allclose(np.asarray(g_fl), np.asarray(g_rf),
                                   atol=5e-4, rtol=5e-4,
                                   err_msg=f"d{name} mismatch")


def _segments(B, S, lens):
    assert sum(lens) == S
    seg = np.concatenate([np.full(l, i, np.int32) for i, l in enumerate(lens)])
    return jnp.asarray(np.broadcast_to(seg, (B, S)).copy())


_SEG_CASES = [
    (True, None, 4, 4, 64),     # packed causal MHA
    (True, 32, 8, 2, 64),       # packed + sliding window + GQA
    (False, None, 4, 2, 96),    # packed bidirectional + padded head dim
    (True, None, 8, 2, 64),     # packed causal, g = 4 in one fold
]


@pytest.mark.parametrize("causal,window,Hq,Hkv,D,S,lens", [
    pytest.param(*c, 128, (40, 50, 38), id="-".join(map(str, c)))
    for c in _SEG_CASES
] + [
    # documents on block edges: whole tile rows and columns are dead, so the
    # index maps clamp in fwd, dQ and dK/dV, before and after the live range
    pytest.param(True, None, 8, 2, 64, 256, (64, 64, 128), id="dead-rows"),
])
def test_flash_vjp_segment_ids_match_reference_autodiff(causal, window, Hq,
                                                        Hkv, D, S, lens):
    """Segment-aware kernels (block-skip + in-tile mask, fwd AND the three
    bwd sweeps) against reference autodiff with the same equality mask."""
    B = 2
    q, k, v = _qkv(B, S, Hq, Hkv, D)
    seg = _segments(B, S, lens)
    cot = jax.random.normal(jax.random.fold_in(KEY, 3), (B, S, Hq, D))

    def fl(q, k, v):
        return flash_attention(q, k, v, segment_ids=seg, causal=causal,
                               window=window, bq=64, bk=64, interpret=True)

    def rf(q, k, v):
        return ref.mha_reference(q, k, v, causal=causal, window=window,
                                 segment_ids=seg)

    np.testing.assert_allclose(np.asarray(fl(q, k, v)), np.asarray(rf(q, k, v)),
                               atol=2e-5, rtol=2e-5)
    for g_fl, g_rf, name in zip(_grads(fl, q, k, v, cot),
                                _grads(rf, q, k, v, cot), "qkv"):
        np.testing.assert_allclose(np.asarray(g_fl), np.asarray(g_rf),
                                   atol=5e-4, rtol=5e-4,
                                   err_msg=f"d{name} mismatch")


@pytest.mark.parametrize("causal,Hq,Hkv,D,Dv,scale,lens", [
    (True, 4, 4, 192, 128, 192 ** -0.5 * 1.2608 ** 2, None),  # MLA's dims
    (True, 4, 4, 192, 128, 0.11, (40, 50, 38)),    # packed, MLA's dims
    (True, 4, 2, 96, 64, None, None),              # GQA, Dv < D, default scale
    (False, 2, 2, 64, 128, 0.2, None),             # bidirectional, Dv > D
])
def test_flash_vjp_split_head_dims_and_scale(causal, Hq, Hkv, D, Dv, scale,
                                             lens):
    """V's head dim apart from Q/K's and a given softmax scale, forward and
    VJP of all four kernels, against reference autodiff."""
    B, S = 2, 128
    q, k, _ = _qkv(B, S, Hq, Hkv, D)
    v = jax.random.normal(jax.random.fold_in(KEY, 2), (B, S, Hkv, Dv))
    seg = None if lens is None else _segments(B, S, lens)
    cot = jax.random.normal(jax.random.fold_in(KEY, 3), (B, S, Hq, Dv))

    def fl(q, k, v):
        return flash_attention(q, k, v, segment_ids=seg, causal=causal,
                               bq=64, bk=64, scale=scale, interpret=True)

    def rf(q, k, v):
        return ref.mha_reference(q, k, v, causal=causal, segment_ids=seg,
                                 scale=scale)

    np.testing.assert_allclose(np.asarray(fl(q, k, v)), np.asarray(rf(q, k, v)),
                               atol=2e-5, rtol=2e-5)
    for g_fl, g_rf, name in zip(_grads(fl, q, k, v, cot),
                                _grads(rf, q, k, v, cot), "qkv"):
        np.testing.assert_allclose(np.asarray(g_fl), np.asarray(g_rf),
                                   atol=5e-4, rtol=5e-4,
                                   err_msg=f"d{name} mismatch")


def test_flash_vjp_segment_ids_bf16():
    B, S, Hq, Hkv, D = 1, 128, 4, 2, 64
    q, k, v = _qkv(B, S, Hq, Hkv, D, jnp.bfloat16)
    seg = _segments(B, S, (64, 64))
    cot = jax.random.normal(jax.random.fold_in(KEY, 3), (B, S, Hq, D))

    def fl(q, k, v):
        return flash_attention(q, k, v, segment_ids=seg, causal=True,
                               bq=64, bk=64, interpret=True)

    def rf(q, k, v):
        return ref.mha_reference(q, k, v, causal=True, segment_ids=seg)

    for g_fl, g_rf in zip(_grads(fl, q, k, v, cot), _grads(rf, q, k, v, cot)):
        np.testing.assert_allclose(np.asarray(g_fl, np.float32),
                                   np.asarray(g_rf, np.float32),
                                   atol=5e-2, rtol=5e-2)


def test_sdpa_segment_flash_training_path_matches_reference():
    """Model-level dispatch with a packed batch: grads through sdpa with the
    kernel forced on equal the einsum path's grads."""
    from repro.models.attention import sdpa
    from repro.runtime import flags
    q, k, v = _qkv(2, 128, 4, 2, 64)
    seg = _segments(2, 128, (30, 98))
    cot = jax.random.normal(jax.random.fold_in(KEY, 3), q.shape)

    def loss(q, k, v):
        return (sdpa(q, k, v, None, causal=True, segment_ids=seg)
                .astype(jnp.float32) * cot).sum()

    base = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    with flags.flag_ctx(flash_attention=True):
        fast = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    for g_b, g_f in zip(base, fast):
        np.testing.assert_allclose(np.asarray(g_b), np.asarray(g_f),
                                   atol=5e-4, rtol=5e-4)


def test_flash_vjp_bf16_tolerance():
    B, S, Hq, Hkv, D = 1, 128, 4, 2, 64
    q, k, v = _qkv(B, S, Hq, Hkv, D, jnp.bfloat16)
    cot = jax.random.normal(jax.random.fold_in(KEY, 3), (B, S, Hq, D))

    def fl(q, k, v):
        return flash_attention(q, k, v, causal=True, bq=64, bk=64, interpret=True)

    def rf(q, k, v):
        return ref.mha_reference(q, k, v, causal=True)

    for g_fl, g_rf in zip(_grads(fl, q, k, v, cot), _grads(rf, q, k, v, cot)):
        np.testing.assert_allclose(np.asarray(g_fl, np.float32),
                                   np.asarray(g_rf, np.float32),
                                   atol=5e-2, rtol=5e-2)


def test_backward_hlo_has_no_quadratic_intermediate():
    """The whole point of the fused backward: no (B, H, S, S) tensor —
    only (bq, bk) tiles — anywhere in the compiled gradient HLO."""
    B, S, H, D = 1, 256, 2, 64
    q, k, v = _qkv(B, S, H, H, D)

    def loss(q, k, v):
        return flash_attention(q, k, v, causal=True, bq=64, bk=64,
                               interpret=True).sum()

    hlo = (jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
           .lower(q, k, v).compile().as_text())
    assert f"{S},{S}" not in hlo, "backward materialized the S×S score matrix"


def test_sdpa_flash_training_path_matches_reference():
    """Model-level dispatch: grads through sdpa with the flash flag forced on
    equal the reference path's grads — training can take the tiled path."""
    from repro.models.attention import sdpa
    from repro.runtime import flags
    q, k, v = _qkv(2, 128, 4, 2, 64)
    cot = jax.random.normal(jax.random.fold_in(KEY, 3), q.shape)

    def loss(q, k, v):
        return (sdpa(q, k, v, None, causal=True, window=None)
                .astype(jnp.float32) * cot).sum()

    base = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    with flags.flag_ctx(flash_attention=True):
        fast = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    for g_b, g_f in zip(base, fast):
        np.testing.assert_allclose(np.asarray(g_b), np.asarray(g_f),
                                   atol=5e-4, rtol=5e-4)


def test_block_size_override_threads_through_ops():
    """The ParallelismConfig → flags → kernels.ops autotuning hook: an
    override that doesn't divide S must disable the flash path (clean
    fallback), one that does must change nothing numerically."""
    from repro.kernels import ops
    from repro.runtime import flags
    q, k, v = _qkv(1, 128, 2, 2, 64)
    with flags.flag_ctx(flash_block_q=96, flash_block_k=96):
        assert not ops.flash_supported(q, k, causal=True, window=None)
    with flags.flag_ctx(flash_block_q=32, flash_block_k=64,
                        flash_attention=True):
        assert ops.flash_supported(q, k, causal=True, window=None)
        out = ops.flash_attention(q, k, v, causal=True)
    want = ref.mha_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("S,Hq,Hkv,packed", [
    (2048, 32, 8, True),        # the packed training cell: GQA g = 4
    (2048, 32, 8, False),
    (4096, 32, 32, False),      # MHA: no fold
    (1024, 64, 1, True),        # MQA: the fold cap splits the group
    (640, 8, 2, True),          # only 128 divides
    (32, 4, 2, False),          # shorter than a block: the block is S
])
def test_flash_block_table_fits_the_shape(S, Hq, Hkv, packed):
    """ops._flash_blocks: tiles that divide S, a fold that divides the group
    and keeps gf·bq within the cap, gf = 1 for MHA."""
    from repro.kernels import ops
    from repro.kernels.flash_attention import MAX_FOLD_ROWS
    g = Hq // Hkv
    bq, bk, gf = ops._flash_blocks(S, S, g=g, packed=packed)
    assert S % bq == 0 and S % bk == 0
    assert g % gf == 0 and gf * bq <= max(MAX_FOLD_ROWS, bq)
    if g == 1:
        assert gf == 1
    if g * bq > MAX_FOLD_ROWS:
        assert gf < g
    q, k, _ = _qkv(1, S, Hq, Hkv, 64)
    seg = jnp.zeros((1, S), jnp.int32) if packed else None
    assert ops.flash_supported(q, k, causal=True, segment_ids=seg)


def test_flash_block_table_falls_back_when_nothing_divides():
    from repro.kernels import ops
    q, k, _ = _qkv(1, 96, 4, 2, 64)
    assert ops._flash_blocks(96, 96, g=2, packed=True)[:2] == (64, 64)
    assert not ops.flash_supported(q, k, causal=True,
                                   segment_ids=jnp.zeros((1, 96), jnp.int32))
    assert not ops.flash_supported(q, k, causal=True)


def test_tile_table_clamps_dead_tiles_and_mirrors_cost_model():
    """The kernels' tile table: documents on block edges give each q block a
    live K range strictly inside the row (the clamp has work to do), and the
    live count equals cost_model.flash_block_skip_fraction's mirror."""
    from repro.core.cost_model import flash_block_skip_fraction
    from repro.kernels import flash_attention as fa
    S, bq, bk = 256, 64, 64
    seg = _segments(2, S, (64, 64, 128))
    lo, hi, *bounds = fa._tile_table(seg, 2, S // bq, S // bk, bq=bq, bk=bk,
                                     causal=True, window=None, sweep_axis=2)
    assert lo[:4].tolist() == [0, 1, 2, 2] and hi[:4].tolist() == [0, 1, 2, 3]
    lo, hi, *_ = fa._tile_table(seg, 2, S // bq, S // bk, bq=bq, bk=bk,
                                causal=True, window=None, sweep_axis=1)
    assert lo[:4].tolist() == [0, 1, 2, 3] and hi[:4].tolist() == [0, 1, 3, 3]
    rng = np.random.default_rng(0)
    for causal, window in ((True, None), (True, 100), (False, None)):
        ids = np.sort(rng.integers(0, 6, (3, S)), axis=1).astype(np.int32)
        bounds = fa._seg_bounds(jnp.asarray(ids), bq, bk)
        live = fa._live_tiles(bounds, 3, S // bq, S // bk, bq=bq, bk=bk,
                              causal=causal, window=window)
        skip = flash_block_skip_fraction(ids, bq=bq, bk=bk, causal=causal,
                                         window=window)
        assert float(1 - live.mean()) == pytest.approx(skip)
