"""Flash attention for TPU (Pallas): online-softmax tiling with explicit
BlockSpec VMEM residency; causal and sliding-window block skipping; GQA via
the K/V index map (no materialized head repeat).

TPU adaptation (DESIGN.md §2): the GPU flash kernel tunes for SRAM/warps; here
the block shape is chosen for VMEM (≤ ~2 MB working set/step) and the MXU —
q/k blocks are multiples of 128 in the sequence dims, the head dim is padded
to a lane multiple so D = 64/96/120/128 all work.  Grid order (B, Hq, nQ, nK)
with the K dimension innermost and "arbitrary" semantics so the f32
accumulators live in VMEM scratch across the K sweep.

Differentiable: :func:`flash_attention` is a ``jax.custom_vjp``.  The forward
kernel also emits the online-softmax statistics ``lse = m + log(l)`` per row,
and the backward pass is three fused Pallas kernels that *recompute* the score
tiles instead of saving them (residuals are ``(q, k, v, O, lse)`` — never the
(B, H, S, S) matrix):

  * ``_delta_kernel``   — preprocess ``delta = rowsum(dO ⊙ O)``;
  * ``_dq_kernel``      — dQ, sweeping K blocks innermost (dQ tile stays in
    VMEM scratch across the sweep);
  * ``_dkv_kernel``     — dK/dV, sweeping Q blocks innermost; GQA heads write
    per-query-head tiles that are group-summed outside the kernel (O(S·D),
    not O(S²)).

All three reuse the forward's causal / sliding-window block skipping, so the
backward does the same ~halved causal work as the forward.

Segment-aware (packed sequences): all four kernels accept optional per-token
``segment_ids`` (B, S) int32.  Attention is allowed only where
``seg[q] == seg[k]`` (composed with causal / window), which is the mask packed
training and batched mixed-length serving prefills share with the reference /
chunked fallbacks.  (q-block, k-block) tiles whose segment-id ranges cannot
intersect are skipped at the block level, reusing the same ``pl.when`` skip
machinery as the causal/window masks — a row packed with n equal documents
does ~1/n of the causal work.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1e30


def _block_relevant(q_start, k_start, *, bq: int, bk: int, causal: bool,
                    window: Optional[int], qseg=None, kseg=None):
    """True iff any (q, k) pair in the (bq, bk) tile survives the mask —
    entirely masked-out tiles do no work (fwd AND bwd block skipping).

    ``qseg``/``kseg`` are the tile's (bq, 1)/(1, bk) segment ids: when the
    id ranges cannot intersect, no ``seg[q] == seg[k]`` pair exists — a
    conservative interval test that is exact for the monotone ids the packer
    emits and safe (never skips live work) for any other layout."""
    relevant = True
    if causal:
        relevant = jnp.logical_and(relevant, k_start <= q_start + bq - 1)
    if window is not None:
        relevant = jnp.logical_and(relevant, k_start + bk - 1 > q_start - window)
    if qseg is not None:
        relevant = jnp.logical_and(relevant, jnp.max(qseg) >= jnp.min(kseg))
        relevant = jnp.logical_and(relevant, jnp.max(kseg) >= jnp.min(qseg))
    return relevant


def _tile_mask(q_start, k_start, *, bq: int, bk: int, causal: bool,
               window: Optional[int], qseg=None, kseg=None):
    qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = jnp.ones((bq, bk), bool)
    if causal:
        mask &= kpos <= qpos
    if window is not None:
        mask &= kpos > qpos - window
    if qseg is not None:
        mask &= qseg == kseg                                 # (bq,1)==(1,bk)
    return mask


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, *rest, bq: int, bk: int,
                n_kv_blocks: int, causal: bool, window: Optional[int],
                scale: float, has_seg: bool):
    if has_seg:
        qs_ref, ks_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref = rest
        qseg, kseg = qs_ref[0], ks_ref[0, 0]                 # (bq,1), (1,bk)
    else:
        o_ref, lse_ref, acc_ref, m_ref, l_ref = rest
        qseg = kseg = None
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q_start = iq * bq
    k_start = ik * bk

    @pl.when(_block_relevant(q_start, k_start, bq=bq, bk=bk, causal=causal,
                             window=window, qseg=qseg, kseg=kseg))
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * scale          # (bq, D)
        k = k_ref[0, 0].astype(jnp.float32)                  # (bk, D)
        v = v_ref[0, 0].astype(jnp.float32)
        s = q @ k.T                                          # (bq, bk)
        mask = _tile_mask(q_start, k_start, bq=bq, bk=bk, causal=causal,
                          window=window, qseg=qseg, kseg=kseg)
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[...]                                  # (bq, 1)
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_cur)
        # zero masked entries explicitly: exp(-inf − -inf) = 1 otherwise
        p = jnp.exp(s - m_cur) * mask
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + p @ v
        m_ref[...] = m_cur

    @pl.when(ik == n_kv_blocks - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)
        lse_ref[0, 0] = m_ref[...] + jnp.log(l)


def _pad_head_dim(x: jax.Array) -> jax.Array:
    """Pad the trailing head dim up to a TPU lane multiple (64 below 64,
    otherwise the next multiple of 128): D = 64/96/120/128 all tile."""
    D = x.shape[-1]
    Dp = 64 if D <= 64 else -(-D // 128) * 128
    if Dp == D:
        return x
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, Dp - D)])


def _seg_operands(segment_ids, bq: int, bk: int):
    """Segment ids laid out so every block's two minor dims are TPU-tileable:
    the q side as a (B, S, 1) column (block (1, bq, 1) → a (bq, 1) tile) and
    the k side as (B, S/bk, 1, bk) rows (block (1, 1, 1, bk) → a (1, bk)
    tile), so the in-tile mask is one broadcast compare with no relayout."""
    B, S = segment_ids.shape
    return segment_ids[:, :, None], segment_ids.reshape(B, S // bk, 1, bk)


def _forward(q, k, v, segment_ids, causal, window, bq, bk, interpret):
    """Shared fwd implementation → (out (B,Sq,Hq,D), lse (B,Hq,Sq,1) f32)."""
    B, Sq, Hq, D = q.shape
    Sk, Hkv = k.shape[1], k.shape[2]
    g = Hq // Hkv
    bq = min(bq, Sq)
    bk = min(bk, Sk)
    assert Sq % bq == 0 and Sk % bk == 0, (Sq, bq, Sk, bk)
    if segment_ids is not None:
        assert segment_ids.shape == (B, Sq) and Sq == Sk, \
            (segment_ids.shape, q.shape, k.shape)
    nq, nk = Sq // bq, Sk // bk
    # head-major layout so a block is (1, 1, seq_block, D); zero-padded head
    # dim is score/output-neutral (padded q·k columns contribute 0)
    qt = _pad_head_dim(q.transpose(0, 2, 1, 3))          # (B, Hq, Sq, Dp)
    kt = _pad_head_dim(k.transpose(0, 2, 1, 3))          # (B, Hkv, Sk, Dp)
    vt = _pad_head_dim(v.transpose(0, 2, 1, 3))
    Dp = qt.shape[-1]
    has_seg = segment_ids is not None

    kernel = functools.partial(
        _fwd_kernel, bq=bq, bk=bk, n_kv_blocks=nk, causal=causal,
        window=window, scale=D ** -0.5, has_seg=has_seg)

    in_specs = [
        pl.BlockSpec((1, 1, bq, Dp), lambda b, h, iq, ik: (b, h, iq, 0)),
        pl.BlockSpec((1, 1, bk, Dp), lambda b, h, iq, ik: (b, h // g, ik, 0)),
        pl.BlockSpec((1, 1, bk, Dp), lambda b, h, iq, ik: (b, h // g, ik, 0)),
    ]
    inputs = [qt, kt, vt]
    if has_seg:
        in_specs += [
            pl.BlockSpec((1, bq, 1), lambda b, h, iq, ik: (b, iq, 0)),
            pl.BlockSpec((1, 1, 1, bk), lambda b, h, iq, ik: (b, ik, 0, 0)),
        ]
        inputs += list(_seg_operands(segment_ids, bq, bk))

    out, lse = pl.pallas_call(
        kernel,
        grid=(B, Hq, nq, nk),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, 1, bq, Dp), lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, bq, 1), lambda b, h, iq, ik: (b, h, iq, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, Hq, Sq, Dp), q.dtype),
            jax.ShapeDtypeStruct((B, Hq, Sq, 1), jnp.float32),
        ],
        scratch_shapes=_scratch(bq, Dp),
        compiler_params=_compiler_params(),
        interpret=interpret,
    )(*inputs)
    return out[..., :D].transpose(0, 2, 1, 3), lse


# ---------------------------------------------------------------------------
# backward kernels
# ---------------------------------------------------------------------------

def _delta_kernel(o_ref, do_ref, delta_ref):
    """Preprocess: delta = rowsum(dO ⊙ O) — the softmax-normalization term
    shared by the dQ and dK sweeps."""
    delta_ref[0, 0] = jnp.sum(
        o_ref[0, 0].astype(jnp.float32) * do_ref[0, 0].astype(jnp.float32),
        axis=1, keepdims=True)


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *rest,
               bq: int, bk: int, n_kv_blocks: int, causal: bool,
               window: Optional[int], scale: float, has_seg: bool):
    if has_seg:
        qs_ref, ks_ref, dq_ref, acc_ref = rest
        qseg, kseg = qs_ref[0], ks_ref[0, 0]                 # (bq,1), (1,bk)
    else:
        dq_ref, acc_ref = rest
        qseg = kseg = None
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start = iq * bq
    k_start = ik * bk

    @pl.when(_block_relevant(q_start, k_start, bq=bq, bk=bk, causal=causal,
                             window=window, qseg=qseg, kseg=kseg))
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)                  # (bq, D)
        k = k_ref[0, 0].astype(jnp.float32)                  # (bk, D)
        v = v_ref[0, 0].astype(jnp.float32)
        do = do_ref[0, 0].astype(jnp.float32)
        mask = _tile_mask(q_start, k_start, bq=bq, bk=bk, causal=causal,
                          window=window, qseg=qseg, kseg=kseg)
        s = jnp.where(mask, (q @ k.T) * scale, NEG_INF)
        p = jnp.exp(s - lse_ref[0, 0]) * mask               # recomputed probs
        dp = do @ v.T                                        # (bq, bk)
        ds = p * (dp - delta_ref[0, 0])
        acc_ref[...] += (ds @ k) * scale

    @pl.when(ik == n_kv_blocks - 1)
    def _finalize():
        dq_ref[0, 0] = acc_ref[...].astype(dq_ref.dtype)


def _dkv_kernel(k_ref, v_ref, q_ref, do_ref, lse_ref, delta_ref, *rest,
                bq: int, bk: int, n_q_blocks: int, causal: bool,
                window: Optional[int], scale: float, has_seg: bool):
    if has_seg:
        ks_ref, qs_ref, dk_ref, dv_ref, dk_acc, dv_acc = rest
        qseg, kseg = qs_ref[0], ks_ref[0, 0]                 # (bq,1), (1,bk)
    else:
        dk_ref, dv_ref, dk_acc, dv_acc = rest
        qseg = kseg = None
    ikb = pl.program_id(2)
    iqb = pl.program_id(3)

    @pl.when(iqb == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    q_start = iqb * bq
    k_start = ikb * bk

    @pl.when(_block_relevant(q_start, k_start, bq=bq, bk=bk, causal=causal,
                             window=window, qseg=qseg, kseg=kseg))
    def _compute():
        k = k_ref[0, 0].astype(jnp.float32)                  # (bk, D)
        v = v_ref[0, 0].astype(jnp.float32)
        q = q_ref[0, 0].astype(jnp.float32)                  # (bq, D)
        do = do_ref[0, 0].astype(jnp.float32)
        mask = _tile_mask(q_start, k_start, bq=bq, bk=bk, causal=causal,
                          window=window, qseg=qseg, kseg=kseg)
        s = jnp.where(mask, (q @ k.T) * scale, NEG_INF)
        p = jnp.exp(s - lse_ref[0, 0]) * mask               # (bq, bk)
        dp = do @ v.T
        ds = p * (dp - delta_ref[0, 0])
        dv_acc[...] += p.T @ do
        dk_acc[...] += (ds.T @ q) * scale

    @pl.when(iqb == n_q_blocks - 1)
    def _finalize():
        dk_ref[0, 0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[...].astype(dv_ref.dtype)


def _backward(q, k, v, segment_ids, o, lse, do, causal, window, bq, bk,
              interpret):
    B, Sq, Hq, D = q.shape
    Sk, Hkv = k.shape[1], k.shape[2]
    g = Hq // Hkv
    bq = min(bq, Sq)
    bk = min(bk, Sk)
    nq, nk = Sq // bq, Sk // bk
    scale = D ** -0.5
    has_seg = segment_ids is not None

    qt = _pad_head_dim(q.transpose(0, 2, 1, 3))          # (B, Hq, Sq, Dp)
    kt = _pad_head_dim(k.transpose(0, 2, 1, 3))          # (B, Hkv, Sk, Dp)
    vt = _pad_head_dim(v.transpose(0, 2, 1, 3))
    ot = _pad_head_dim(o.transpose(0, 2, 1, 3))
    dot = _pad_head_dim(do.transpose(0, 2, 1, 3))
    Dp = qt.shape[-1]

    delta = pl.pallas_call(
        _delta_kernel,
        grid=(B, Hq, nq),
        in_specs=[
            pl.BlockSpec((1, 1, bq, Dp), lambda b, h, iq: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, bq, Dp), lambda b, h, iq: (b, h, iq, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, 1), lambda b, h, iq: (b, h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hq, Sq, 1), jnp.float32),
        compiler_params=_compiler_params(("parallel",) * 3),
        interpret=interpret,
    )(ot, dot)

    from jax.experimental.pallas import tpu as pltpu

    dq_in_specs = [
        pl.BlockSpec((1, 1, bq, Dp), lambda b, h, iq, ik: (b, h, iq, 0)),
        pl.BlockSpec((1, 1, bk, Dp), lambda b, h, iq, ik: (b, h // g, ik, 0)),
        pl.BlockSpec((1, 1, bk, Dp), lambda b, h, iq, ik: (b, h // g, ik, 0)),
        pl.BlockSpec((1, 1, bq, Dp), lambda b, h, iq, ik: (b, h, iq, 0)),
        pl.BlockSpec((1, 1, bq, 1), lambda b, h, iq, ik: (b, h, iq, 0)),
        pl.BlockSpec((1, 1, bq, 1), lambda b, h, iq, ik: (b, h, iq, 0)),
    ]
    dq_inputs = [qt, kt, vt, dot, lse, delta]
    if has_seg:
        qseg, kseg = _seg_operands(segment_ids, bq, bk)
        dq_in_specs += [
            pl.BlockSpec((1, bq, 1), lambda b, h, iq, ik: (b, iq, 0)),
            pl.BlockSpec((1, 1, 1, bk), lambda b, h, iq, ik: (b, ik, 0, 0)),
        ]
        dq_inputs += [qseg, kseg]

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, bq=bq, bk=bk, n_kv_blocks=nk,
                          causal=causal, window=window, scale=scale,
                          has_seg=has_seg),
        grid=(B, Hq, nq, nk),
        in_specs=dq_in_specs,
        out_specs=pl.BlockSpec((1, 1, bq, Dp), lambda b, h, iq, ik: (b, h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hq, Sq, Dp), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bq, Dp), jnp.float32)],
        compiler_params=_compiler_params(),
        interpret=interpret,
    )(*dq_inputs)

    # dK/dV: per *query* head tiles (the K/V index maps mirror the forward's
    # GQA mapping); the g-way group sum happens outside — O(S·D) extra, no S².
    dkv_in_specs = [
        pl.BlockSpec((1, 1, bk, Dp), lambda b, h, ik, iq: (b, h // g, ik, 0)),
        pl.BlockSpec((1, 1, bk, Dp), lambda b, h, ik, iq: (b, h // g, ik, 0)),
        pl.BlockSpec((1, 1, bq, Dp), lambda b, h, ik, iq: (b, h, iq, 0)),
        pl.BlockSpec((1, 1, bq, Dp), lambda b, h, ik, iq: (b, h, iq, 0)),
        pl.BlockSpec((1, 1, bq, 1), lambda b, h, ik, iq: (b, h, iq, 0)),
        pl.BlockSpec((1, 1, bq, 1), lambda b, h, ik, iq: (b, h, iq, 0)),
    ]
    dkv_inputs = [kt, vt, qt, dot, lse, delta]
    if has_seg:
        dkv_in_specs += [
            pl.BlockSpec((1, 1, 1, bk), lambda b, h, ik, iq: (b, ik, 0, 0)),
            pl.BlockSpec((1, bq, 1), lambda b, h, ik, iq: (b, iq, 0)),
        ]
        dkv_inputs += [kseg, qseg]

    dkh, dvh = pl.pallas_call(
        functools.partial(_dkv_kernel, bq=bq, bk=bk, n_q_blocks=nq,
                          causal=causal, window=window, scale=scale,
                          has_seg=has_seg),
        grid=(B, Hq, nk, nq),
        in_specs=dkv_in_specs,
        out_specs=[
            pl.BlockSpec((1, 1, bk, Dp), lambda b, h, ik, iq: (b, h, ik, 0)),
            pl.BlockSpec((1, 1, bk, Dp), lambda b, h, ik, iq: (b, h, ik, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, Hq, Sk, Dp), jnp.float32),
            jax.ShapeDtypeStruct((B, Hq, Sk, Dp), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((bk, Dp), jnp.float32),
                        pltpu.VMEM((bk, Dp), jnp.float32)],
        compiler_params=_compiler_params(),
        interpret=interpret,
    )(*dkv_inputs)

    if g > 1:
        dkh = dkh.reshape(B, Hkv, g, Sk, Dp).sum(axis=2)
        dvh = dvh.reshape(B, Hkv, g, Sk, Dp).sum(axis=2)
    dq = dq[..., :D].transpose(0, 2, 1, 3).astype(q.dtype)
    dk = dkh[..., :D].transpose(0, 2, 1, 3).astype(k.dtype)
    dv = dvh[..., :D].transpose(0, 2, 1, 3).astype(v.dtype)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# custom_vjp plumbing + public entry point
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def _flash(q, k, v, segment_ids, causal, window, bq, bk, interpret):
    out, _ = _forward(q, k, v, segment_ids, causal, window, bq, bk, interpret)
    return out


def _flash_fwd(q, k, v, segment_ids, causal, window, bq, bk, interpret):
    out, lse = _forward(q, k, v, segment_ids, causal, window, bq, bk, interpret)
    # residuals are O(B·S·(3D + 1)) — the S×S score matrix is never saved
    return out, (q, k, v, segment_ids, out, lse)


def _flash_bwd(causal, window, bq, bk, interpret, res, do):
    q, k, v, segment_ids, out, lse = res
    dq, dk, dv = _backward(q, k, v, segment_ids, out, lse, do, causal, window,
                           bq, bk, interpret)
    return dq, dk, dv, None          # segment ids carry no tangent


_flash.defvjp(_flash_fwd, _flash_bwd)


@functools.partial(jax.jit, static_argnames=("causal", "window", "bq", "bk", "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    segment_ids: Optional[jax.Array] = None,
                    causal: bool = True, window: Optional[int] = None,
                    bq: int = 128, bk: int = 128,
                    interpret: bool = False) -> jax.Array:
    """q: (B, Sq, Hq, D); k/v: (B, Sk, Hkv, D) → (B, Sq, Hq, D).

    Differentiable: gradients run through the fused Pallas backward kernels
    (recompute-style — no (B, H, S, S) intermediate), so training can route
    through the tiled path, not just inference.

    ``segment_ids`` (B, S) int32 restricts attention to
    ``seg[q] == seg[k]`` — packed-sequence training and mixed-length batched
    prefills (serving uses id ``-1`` on padded positions).  Requires aligned
    self-attention (Sq == Sk); the fwd AND bwd kernels skip (q-block,
    k-block) tiles whose id ranges cannot intersect.
    """
    if segment_ids is not None:
        segment_ids = segment_ids.astype(jnp.int32)
    return _flash(q, k, v, segment_ids, causal, window, bq, bk, interpret)


def _scratch(bq: int, D: int):
    from jax.experimental.pallas import tpu as pltpu
    return [
        pltpu.VMEM((bq, D), jnp.float32),   # acc
        pltpu.VMEM((bq, 1), jnp.float32),   # running max m
        pltpu.VMEM((bq, 1), jnp.float32),   # running sum l
    ]


def _compiler_params(dimension_semantics=("parallel", "parallel", "parallel",
                                          "arbitrary")):
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.CompilerParams(dimension_semantics=dimension_semantics)
