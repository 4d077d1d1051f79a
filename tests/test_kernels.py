"""Pallas kernel validation: shape/dtype sweeps against the pure-jnp oracles
(interpret=True executes the kernel body on CPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.decode_attention import decode_attention
from repro.kernels.flash_attention import flash_attention
from repro.kernels.rmsnorm import rmsnorm

KEY = jax.random.PRNGKey(0)


def _qkv(B, Sq, Sk, Hq, Hkv, D, dtype):
    q = jax.random.normal(KEY, (B, Sq, Hq, D), dtype)
    k = jax.random.normal(jax.random.fold_in(KEY, 1), (B, Sk, Hkv, D), dtype)
    v = jax.random.normal(jax.random.fold_in(KEY, 2), (B, Sk, Hkv, D), dtype)
    return q, k, v


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5), (jnp.bfloat16, 2e-2)])
@pytest.mark.parametrize("Hq,Hkv", [(4, 4), (8, 2), (4, 1)])
@pytest.mark.parametrize("causal,window", [(True, None), (True, 64), (False, None)])
def test_flash_attention_sweep(dtype, tol, Hq, Hkv, causal, window):
    B, S, D = 2, 128, 64
    q, k, v = _qkv(B, S, S, Hq, Hkv, D, dtype)
    out = flash_attention(q, k, v, causal=causal, window=window,
                          bq=64, bk=64, interpret=True)
    want = ref.mha_reference(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), atol=tol, rtol=tol)


@pytest.mark.parametrize("D", [64, 128])
@pytest.mark.parametrize("blocks", [(128, 128), (64, 128), (128, 64)])
def test_flash_attention_block_shapes(D, blocks):
    bq, bk = blocks
    B, S = 1, 256
    q, k, v = _qkv(B, S, S, 4, 2, D, jnp.float32)
    out = flash_attention(q, k, v, causal=True, bq=bq, bk=bk, interpret=True)
    want = ref.mha_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5, rtol=2e-5)


def test_flash_attention_window_larger_than_seq():
    q, k, v = _qkv(1, 128, 128, 2, 2, 64, jnp.float32)
    out = flash_attention(q, k, v, causal=True, window=4096, bq=64, bk=64, interpret=True)
    want = ref.mha_reference(q, k, v, causal=True, window=None)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5), (jnp.bfloat16, 2e-2)])
@pytest.mark.parametrize("fill", [0, 300, 1023])
@pytest.mark.parametrize("window", [None, 128])
def test_decode_attention_sweep(dtype, tol, fill, window):
    """Ring-buffer states: empty-ish, partially filled, full."""
    B, S, Hq, Hkv, D = 2, 1024, 4, 2, 64
    q, k, v = _qkv(B, 1, S, Hq, Hkv, D, dtype)
    kpos = jnp.where(jnp.arange(S)[None] <= fill, jnp.arange(S)[None], -1)
    kpos = jnp.broadcast_to(kpos.astype(jnp.int32), (B, S))
    t = jnp.int32(fill)
    out = decode_attention(q, k, v, kpos, t=t, window=window, bk=128, interpret=True)
    want = ref.decode_attention_reference(q, k, v, kpos, t=t, window=window)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), atol=tol, rtol=tol)


def test_decode_attention_wrapped_ring():
    """Positions written mod buffer size (true ring wraparound)."""
    B, S, H, D = 1, 256, 2, 64
    q, k, v = _qkv(B, 1, S, H, H, D, jnp.float32)
    t = jnp.int32(900)  # buffer wrapped several times; slots hold 645..900
    slots = jnp.arange(S)
    kpos = ((900 - slots) % S * 0 + (900 // S * S + slots))
    kpos = jnp.where(kpos > 900, kpos - S, kpos).astype(jnp.int32)[None]
    out = decode_attention(q, k, v, kpos, t=t, window=128, bk=64, interpret=True)
    want = ref.decode_attention_reference(q, k, v, kpos, t=t, window=128)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("shape", [(8, 128), (3, 7, 256), (64, 512)])
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-5), (jnp.bfloat16, 2e-2)])
def test_rmsnorm_sweep(shape, dtype, tol):
    x = jax.random.normal(KEY, shape, dtype)
    scale = jax.random.normal(jax.random.fold_in(KEY, 7), (shape[-1],), jnp.float32)
    out = rmsnorm(x, scale, interpret=True)
    want = ref.rmsnorm_reference(x, scale)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), atol=tol, rtol=tol)


def test_model_attention_matches_kernel_path():
    """The model's sdpa (flag-dispatched) equals the kernel output."""
    from repro.models.attention import sdpa
    from repro.runtime import flags
    q, k, v = _qkv(2, 128, 128, 4, 2, 64, jnp.float32)
    base = sdpa(q, k, v, None, causal=True, window=None)
    with flags.flag_ctx(flash_attention=True):
        fast = sdpa(q, k, v, None, causal=True, window=None)
    np.testing.assert_allclose(np.asarray(base), np.asarray(fast), atol=2e-5, rtol=2e-5)
