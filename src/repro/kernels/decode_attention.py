"""Flash-decode for TPU (Pallas): single-token attention against a (possibly
ring-buffer) KV cache, the hot kernel of the ``decode_32k`` / ``long_500k``
serving shapes.

The query position ``t`` arrives via scalar prefetch (SMEM) — the TPU
idiom for runtime scalars that steer masking.  The K sweep is the innermost
grid dimension with f32 accumulators in VMEM scratch (same online-softmax
structure as the training kernel, degenerate q-block of 1).

One grid step handles every query head of a batch row against one K/V block:
the block is (bk, Hkv, D), whose two minor dims are the array's own (the TPU
tiling rule), so each K/V byte is DMA'd once, and the ``g = Hq / Hkv`` query
heads of a KV head form one (g, D) × (D, bk) matmul.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _attend_block(q_ref, k_ref, v_ref, valid, acc_ref, m_ref, l_ref, *,
                  scale: float):
    """Online-softmax update of every KV head's (g, D) accumulators with one
    K/V block.  q_ref block (1, Hkv, g, D); k_ref/v_ref block (1, bk, Hkv, D);
    ``valid`` (1, bk) marks the keys this query may see.  Shared by the
    contiguous and paged kernels, so equal blocks give bit-identical output."""
    for h in range(k_ref.shape[2]):
        q = q_ref[0, h].astype(jnp.float32) * scale          # (g, D)
        k = k_ref[0, :, h, :].astype(jnp.float32)            # (bk, D)
        v = v_ref[0, :, h, :].astype(jnp.float32)
        s = jnp.where(valid, q @ k.T, NEG_INF)               # (g, bk)
        m_prev = m_ref[h]                                    # (g, 1)
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_cur)
        # zero masked entries explicitly: exp(-inf − -inf) = 1 otherwise
        p = jnp.exp(s - m_cur) * valid
        l_ref[h] = l_ref[h] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[h] = acc_ref[h] * alpha + p @ v
        m_ref[h] = m_cur


def _init(acc_ref, m_ref, l_ref):
    acc_ref[...] = jnp.zeros_like(acc_ref)
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)


def _finalize(o_ref, acc_ref, l_ref):
    o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def _decode_kernel(t_ref, q_ref, k_ref, v_ref, kpos_ref, o_ref,
                   acc_ref, m_ref, l_ref, *, n_kv_blocks: int,
                   window: Optional[int], scale: float):
    ik = pl.program_id(1)
    pl.when(ik == 0)(lambda: _init(acc_ref, m_ref, l_ref))
    t = t_ref[0]
    kpos = kpos_ref[0, 0]                                    # (1, bk)
    valid = (kpos >= 0) & (kpos <= t)
    if window is not None:
        valid &= kpos > t - window
    _attend_block(q_ref, k_ref, v_ref, valid, acc_ref, m_ref, l_ref,
                  scale=scale)
    pl.when(ik == n_kv_blocks - 1)(lambda: _finalize(o_ref, acc_ref, l_ref))


def _paged_decode_kernel(pt_ref, ts_ref, q_ref, k_ref, v_ref, o_ref,
                         acc_ref, m_ref, l_ref, *, ps: int, n_blocks: int,
                         window: Optional[int], scale: float):
    b = pl.program_id(0)
    ik = pl.program_id(1)
    pl.when(ik == 0)(lambda: _init(acc_ref, m_ref, l_ref))
    t = ts_ref[b]
    page = pt_ref[b * n_blocks + ik]
    # token j of logical page ik sits at absolute position ik*ps + j; an
    # unmapped page (-1, DMA'd from the trash page) is masked out entirely
    kpos = jax.lax.broadcasted_iota(jnp.int32, (1, ps), 1) + ik * ps
    valid = (page >= 0) & (kpos <= t)
    if window is not None:
        valid &= kpos > t - window
    _attend_block(q_ref, k_ref, v_ref, valid, acc_ref, m_ref, l_ref,
                  scale=scale)
    pl.when(ik == n_blocks - 1)(lambda: _finalize(o_ref, acc_ref, l_ref))


def _scratch(Hkv: int, g: int, D: int):
    return [pltpu.VMEM((Hkv, g, D), jnp.float32),   # acc
            pltpu.VMEM((Hkv, g, 1), jnp.float32),   # running max m
            pltpu.VMEM((Hkv, g, 1), jnp.float32)]   # running sum l


_COMPILER_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "arbitrary"))


@functools.partial(jax.jit, static_argnames=("window", "interpret"))
def paged_decode_attention(q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
                           page_table: jax.Array, *, ts: jax.Array,
                           window: Optional[int] = None,
                           interpret: bool = False) -> jax.Array:
    """Decode attention gathering K/V through a page table.

    q: (B, 1, Hq, D); k_pool/v_pool: (n_pages, page_size, Hkv, D);
    page_table: (B, n_max) physical page per logical page (-1 = unmapped);
    ts: (B,) per-request query positions → (B, 1, Hq, D).

    The page table arrives via scalar prefetch and steers the K/V BlockSpec
    index maps directly: block (b, ik) DMAs physical page
    ``page_table[b, ik]`` (clamped to the trash page 0 when unmapped — those
    scores are masked).  The K sweep runs in LOGICAL page order with the same
    online-softmax accumulation as ``decode_attention``, so with
    ``bk == page_size`` the two are bit-identical on equivalent caches."""
    B, _, Hq, D = q.shape
    ps, Hkv = k_pool.shape[1], k_pool.shape[2]
    n_max = page_table.shape[1]
    g = Hq // Hkv
    pt_flat = page_table.astype(jnp.int32).reshape(-1)
    ts_arr = jnp.asarray(ts, jnp.int32).reshape(B)

    kernel = functools.partial(_paged_decode_kernel, ps=ps, n_blocks=n_max,
                               window=window, scale=D ** -0.5)

    def kv_map(b, ik, pt, ts):
        return (jnp.maximum(pt[b * n_max + ik], 0), 0, 0, 0)

    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, n_max),
            in_specs=[
                pl.BlockSpec((1, Hkv, g, D), lambda b, ik, pt, ts: (b, 0, 0, 0)),
                pl.BlockSpec((1, ps, Hkv, D), kv_map),
                pl.BlockSpec((1, ps, Hkv, D), kv_map),
            ],
            out_specs=pl.BlockSpec((1, Hkv, g, D),
                                   lambda b, ik, pt, ts: (b, 0, 0, 0)),
            scratch_shapes=_scratch(Hkv, g, D),
        ),
        out_shape=jax.ShapeDtypeStruct((B, Hkv, g, D), q.dtype),
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
    )(pt_flat, ts_arr, q.reshape(B, Hkv, g, D), k_pool, v_pool)
    return out.reshape(B, 1, Hq, D)


@functools.partial(jax.jit, static_argnames=("window", "bk", "interpret"))
def decode_attention(q: jax.Array, k: jax.Array, v: jax.Array, kpos: jax.Array,
                     *, t: jax.Array, window: Optional[int] = None,
                     bk: int = 512, interpret: bool = False) -> jax.Array:
    """q: (B, 1, Hq, D); k/v: (B, S, Hkv, D); kpos: (B, S) absolute positions
    (-1 empty); t: scalar query position → (B, 1, Hq, D)."""
    B, _, Hq, D = q.shape
    S, Hkv = k.shape[1], k.shape[2]
    g = Hq // Hkv
    bk = min(bk, S)
    assert S % bk == 0, (S, bk)
    nk = S // bk
    t_arr = jnp.asarray(t, jnp.int32).reshape(1)

    kernel = functools.partial(_decode_kernel, n_kv_blocks=nk,
                               window=window, scale=D ** -0.5)

    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, nk),
            in_specs=[
                pl.BlockSpec((1, Hkv, g, D), lambda b, ik, t: (b, 0, 0, 0)),
                pl.BlockSpec((1, bk, Hkv, D), lambda b, ik, t: (b, ik, 0, 0)),
                pl.BlockSpec((1, bk, Hkv, D), lambda b, ik, t: (b, ik, 0, 0)),
                # positions as (1, bk) rows: a tileable block for any bk
                pl.BlockSpec((1, 1, 1, bk), lambda b, ik, t: (b, ik, 0, 0)),
            ],
            out_specs=pl.BlockSpec((1, Hkv, g, D), lambda b, ik, t: (b, 0, 0, 0)),
            scratch_shapes=_scratch(Hkv, g, D),
        ),
        out_shape=jax.ShapeDtypeStruct((B, Hkv, g, D), q.dtype),
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
    )(t_arr, q.reshape(B, Hkv, g, D), k, v, kpos.reshape(B, nk, 1, bk))
    return out.reshape(B, 1, Hq, D)
