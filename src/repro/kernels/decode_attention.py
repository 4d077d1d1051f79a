"""Flash-decode for TPU (Pallas): single-token attention against a (possibly
ring-buffer) KV cache, the hot kernel of the ``decode_32k`` / ``long_500k``
serving shapes.

The query position ``t`` arrives via scalar prefetch (SMEM) — the TPU
idiom for runtime scalars that steer masking.  The K sweep is the innermost
grid dimension with f32 accumulators in VMEM scratch (same online-softmax
structure as the training kernel, degenerate q-block of 1).

One grid step handles every query head of a batch row against one K/V block:
the block is (bk, Hkv, D), or a page's (Hkv, D, page_size) where the paged
pool keeps positions minor (``keys_minor``); either way its two minor dims
are the array's own (the TPU tiling rule), so each K/V byte is DMA'd once,
and the ``g = Hq / Hkv`` query heads of a KV head form one (g, D) × (D, bk)
matmul.
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
                  scale: float, keys_minor: bool = False):
    """Online-softmax update of every KV head's (g, D) accumulators with one
    K/V block.  q_ref block (1, Hkv, g, D); k_ref/v_ref block (1, bk, Hkv, D),
    or (1, Hkv, D, bk) with ``keys_minor``; ``valid`` (1, bk) marks the keys
    this query may see.  Shared by the contiguous and paged kernels, so
    equal blocks give bit-identical output."""
    for h in range(q_ref.shape[1]):
        q = q_ref[0, h].astype(jnp.float32) * scale          # (g, D)
        if keys_minor:
            kt = k_ref[0, h].astype(jnp.float32)             # (D, bk)
            vt = v_ref[0, h].astype(jnp.float32)
            s = q @ kt
        else:
            k = k_ref[0, :, h, :].astype(jnp.float32)        # (bk, D)
            v = v_ref[0, :, h, :].astype(jnp.float32)
            s = q @ k.T
        s = jnp.where(valid, s, NEG_INF)                     # (g, bk)
        m_prev = m_ref[h]                                    # (g, 1)
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_cur)
        # zero masked entries explicitly: exp(-inf − -inf) = 1 otherwise
        p = jnp.exp(s - m_cur) * valid
        l_ref[h] = l_ref[h] * alpha + jnp.sum(p, axis=1, keepdims=True)
        if keys_minor:
            pv = jax.lax.dot_general(p, vt, (((1,), (1,)), ((), ())))
        else:
            pv = p @ v
        acc_ref[h] = acc_ref[h] * alpha + pv
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


def _paged_decode_kernel(pt_ref, ts_ref, layer_ref, q_ref, k_ref, v_ref,
                         o_ref, acc_ref, m_ref, l_ref, *, ps: int,
                         n_blocks: int, window: Optional[int], scale: float,
                         keys_minor: bool):
    b = pl.program_id(0)
    ik = pl.program_id(1)
    pl.when(ik == 0)(lambda: _init(acc_ref, m_ref, l_ref))
    t = ts_ref[b]
    page = pt_ref[b * n_blocks + ik]

    # pages past the slot's last live page were not fetched (the index map
    # repeats that page's block) and hold no key this query may see
    @pl.when(ik * ps <= t)
    def _():
        # token j of logical page ik sits at absolute position ik*ps + j; an
        # unmapped page (-1, DMA'd from the trash page) is masked out
        kpos = jax.lax.broadcasted_iota(jnp.int32, (1, ps), 1) + ik * ps
        valid = (page >= 0) & (kpos <= t)
        if window is not None:
            valid &= kpos > t - window
        _attend_block(q_ref, k_ref, v_ref, valid, acc_ref, m_ref, l_ref,
                      scale=scale, keys_minor=keys_minor)

    pl.when(ik == n_blocks - 1)(lambda: _finalize(o_ref, acc_ref, l_ref))


def _scratch(Hkv: int, g: int, D: int):
    return [pltpu.VMEM((Hkv, g, D), jnp.float32),   # acc
            pltpu.VMEM((Hkv, g, 1), jnp.float32),   # running max m
            pltpu.VMEM((Hkv, g, 1), jnp.float32)]   # running sum l


_COMPILER_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "arbitrary"))


def keys_minor(head_dim: int) -> bool:
    """Whether a TPU keeps a (..., page_size, Hkv, head_dim) pool with the
    page's positions as its minor (lane) axis.  It does when the head dim
    does not fill a lane tile (and the page size does): the array is then
    laid out as (..., Hkv, head_dim, page_size), which is what the kernel
    must read to read it in place."""
    return head_dim % 128 != 0


@functools.partial(jax.jit, static_argnames=("window", "interpret"))
def paged_decode_attention(q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
                           page_table: jax.Array, *, ts: jax.Array,
                           layer: jax.Array, window: Optional[int] = None,
                           interpret: bool = False) -> jax.Array:
    """Decode attention of one layer, read in place from the stacked pool.

    q: (B, 1, Hq, D); k_pool/v_pool: (L, n_pages, page_size, Hkv, D), every
    layer's pages; page_table: (B, n_max) physical page per logical page
    (-1 = unmapped); ts: (B,) per-request query positions; layer: the
    layer to read → (B, 1, Hq, D).

    The page table, positions and layer arrive via scalar prefetch and steer
    the K/V BlockSpec index maps directly: block (b, ik) DMAs block
    ``(layer, page_table[b, ik])`` of the pool (the trash page 0 when
    unmapped — those scores are masked), so no layer's pool is sliced or
    gathered in HBM.  ``ik`` is clamped to the slot's last live page
    ``ts[b] // page_size``: past it the block index repeats, Pallas issues
    no DMA, and the kernel skips the compute.  The pool is read in the
    orientation the TPU stores it in (``keys_minor``): the transpose to
    (L, n_pages, Hkv, D, page_size) is then a relabelling, not a copy.  The
    K sweep runs in LOGICAL page order with the same online-softmax
    accumulation as ``decode_attention``, so with ``bk == page_size`` the
    two are bit-identical on equivalent caches."""
    B, _, Hq, D = q.shape
    ps, Hkv = k_pool.shape[2], k_pool.shape[3]
    n_max = page_table.shape[1]
    g = Hq // Hkv
    pt_flat = page_table.astype(jnp.int32).reshape(-1)
    ts_arr = jnp.asarray(ts, jnp.int32).reshape(B)
    layer_arr = jnp.asarray(layer, jnp.int32).reshape(1)
    minor = keys_minor(D)
    if minor:
        k_pool = k_pool.transpose(0, 1, 3, 4, 2)
        v_pool = v_pool.transpose(0, 1, 3, 4, 2)
    kv_block = (None, 1) + k_pool.shape[2:]

    kernel = functools.partial(_paged_decode_kernel, ps=ps, n_blocks=n_max,
                               window=window, scale=D ** -0.5,
                               keys_minor=minor)

    def kv_map(b, ik, pt, ts, layer):
        live = jnp.minimum(ik, ts[b] // ps)
        return (layer[0], jnp.maximum(pt[b * n_max + live], 0), 0, 0, 0)

    def q_map(b, ik, pt, ts, layer):
        return (b, 0, 0, 0)

    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(B, n_max),
            in_specs=[
                pl.BlockSpec((1, Hkv, g, D), q_map),
                pl.BlockSpec(kv_block, kv_map),
                pl.BlockSpec(kv_block, kv_map),
            ],
            out_specs=pl.BlockSpec((1, Hkv, g, D), q_map),
            scratch_shapes=_scratch(Hkv, g, D),
        ),
        out_shape=jax.ShapeDtypeStruct((B, Hkv, g, D), q.dtype),
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
        name="paged_decode_attention",
    )(pt_flat, ts_arr, layer_arr, q.reshape(B, Hkv, g, D), k_pool, v_pool)
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
        name="decode_attention",
    )(t_arr, q.reshape(B, Hkv, g, D), k, v, kpos.reshape(B, nk, 1, bk))
    return out.reshape(B, 1, Hq, D)
