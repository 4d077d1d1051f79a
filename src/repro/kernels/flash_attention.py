"""Flash attention for TPU (Pallas): online-softmax tiling with explicit
BlockSpec VMEM residency; causal, sliding-window and segment block skipping;
GQA by folding a KV head's query heads into one tile (no materialized head
repeat).

TPU adaptation (DESIGN.md §2): the GPU flash kernel tunes for SRAM/warps; here
the block shape is chosen for VMEM and the MXU — q/k blocks are multiples of
128 in the sequence dims, the head dim is padded to a lane multiple so
D = 64/96/120/128 all work.  V's head dim may differ from Q/K's (latent
attention: 192 and 128), each padded to its own lane multiple, and the
softmax scale is an argument (default ``D ** -0.5``).  Grid order (B, Hq/gf, nQ, nK) with the K
dimension innermost and "arbitrary" semantics so the f32 accumulators live in
VMEM scratch across the K sweep.

A grid step costs a fixed overhead whether or not its tile does any work, so
the grid is cut two ways:

  * **GQA fold.**  One step takes the ``gf`` query heads that share a K/V
    head as one ``(gf·bq, D)`` tile (``gf`` divides the group size
    ``g = Hq/Hkv``; see :func:`group_fold`), so each K/V block is fetched
    once per fold, not once per query head.  The row-wise state (``m``,
    ``l``, ``lse``, ``delta``) is laid out per row of the folded tile, and
    the masks see the tile as ``(gf, bq, bk)``: every head of the fold has
    the same query positions and segment ids.
  * **Dead tiles fetch nothing.**  For every (row, block) of the outer axis
    the first and last live block of the swept axis arrive by scalar
    prefetch, and the index maps clamp the swept block index into that
    range: past it the pipeline sees the block it already holds and issues
    no DMA.  The ``pl.when`` tile test (:func:`_block_relevant`) stays the
    guard, reading the tile's segment-id bounds from the same prefetch, so
    the result never depends on the clamp.

Differentiable: :func:`flash_attention` is a ``jax.custom_vjp``.  The forward
kernel also emits the online-softmax statistics ``lse = m + log(l)`` per row,
and the backward pass is three fused Pallas kernels that *recompute* the score
tiles instead of saving them (residuals are ``(q, k, v, O, lse)`` — never the
(B, H, S, S) matrix):

  * ``_delta_kernel``   — preprocess ``delta = rowsum(dO ⊙ O)``;
  * ``_dq_kernel``      — dQ, sweeping K blocks innermost (dQ tile stays in
    VMEM scratch across the sweep);
  * ``_dkv_kernel``     — dK/dV per KV head, sweeping the group's folds and
    then Q blocks innermost; the group sum happens in the VMEM accumulators.

All three reuse the forward's block skipping, so the backward does the same
~halved causal work as the forward.

Segment-aware (packed sequences): all four kernels accept optional per-token
``segment_ids`` (B, S) int32.  Attention is allowed only where
``seg[q] == seg[k]`` (composed with causal / window), which is the mask packed
training and batched mixed-length serving prefills share with the reference /
chunked fallbacks.  (q-block, k-block) tiles whose segment-id ranges cannot
intersect are skipped at the block level, with the same ``pl.when`` and
clamp as the causal/window masks — a row packed with n equal documents does
~1/n of the causal work.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
# rows of a folded tile (gf·bq): bounds the f32 scores tile and scratch
MAX_FOLD_ROWS = 1024


def group_fold(g: int, bq: int) -> int:
    """Query heads per grid step: the largest divisor of the GQA group size
    ``g`` whose folded tile (``gf·bq`` rows) stays within MAX_FOLD_ROWS —
    1 for MHA, and a split group for MQA."""
    return max((d for d in range(1, g + 1)
                if g % d == 0 and d * bq <= MAX_FOLD_ROWS), default=1)


def _block_relevant(q_start, k_start, *, bq: int, bk: int, causal: bool,
                    window: Optional[int], seg=None):
    """True iff any (q, k) pair in the (bq, bk) tile survives the mask —
    entirely masked-out tiles do no work (fwd AND bwd block skipping).

    ``seg`` is the tile's ``(qmin, qmax, kmin, kmax)`` segment ids: when the
    id ranges cannot intersect, no ``seg[q] == seg[k]`` pair exists — a
    conservative interval test that is exact for the monotone ids the packer
    emits and safe (never skips live work) for any other layout.  Scalars in
    the kernels, broadcast arrays for the host-side tile table."""
    relevant = True
    if causal:
        relevant = jnp.logical_and(relevant, k_start <= q_start + bq - 1)
    if window is not None:
        relevant = jnp.logical_and(relevant, k_start + bk - 1 > q_start - window)
    if seg is not None:
        qmin, qmax, kmin, kmax = seg
        relevant = jnp.logical_and(relevant, qmax >= kmin)
        relevant = jnp.logical_and(relevant, kmax >= qmin)
    return relevant


def _tile_mask(q_start, k_start, *, bq: int, bk: int, causal: bool,
               window: Optional[int], qseg=None, kseg=None):
    """(bq, bk) mask of one head's tile; it broadcasts over a fold's heads."""
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
# the tile table: what the kernels skip, and what their index maps clamp to
# ---------------------------------------------------------------------------

def _seg_bounds(segment_ids, bq: int, bk: int):
    """Per-block segment-id bounds, each flattened (B·n,) int32:
    (qmin, qmax) over q blocks, (kmin, kmax) over k blocks."""
    B, S = segment_ids.shape
    qs = segment_ids.reshape(B, S // bq, bq)
    ks = segment_ids.reshape(B, S // bk, bk)
    return tuple(x.reshape(-1) for x in
                 (qs.min(-1), qs.max(-1), ks.min(-1), ks.max(-1)))


def _live_tiles(bounds, B: int, nq: int, nk: int, *, bq: int, bk: int,
                causal: bool, window: Optional[int]):
    """(B, nq, nk) bool: :func:`_block_relevant` for every tile."""
    iq = jnp.arange(nq)[None, :, None]
    ik = jnp.arange(nk)[None, None, :]
    seg = None
    if bounds is not None:
        qmin, qmax, kmin, kmax = (x.reshape(B, -1) for x in bounds)
        seg = (qmin[:, :, None], qmax[:, :, None],
               kmin[:, None, :], kmax[:, None, :])
    live = _block_relevant(iq * bq, ik * bk, bq=bq, bk=bk, causal=causal,
                           window=window, seg=seg)
    return jnp.broadcast_to(live, (B, nq, nk))


def _sweep_range(live, axis: int):
    """First and last live block along the swept ``axis`` for every (row,
    outer block), flattened (B·n,) int32; (0, 0) where nothing is live."""
    n = live.shape[axis]
    any_live = live.any(axis)
    lo = jnp.argmax(live, axis)
    hi = n - 1 - jnp.argmax(jnp.flip(live, axis), axis)
    return tuple(jnp.where(any_live, x, 0).astype(jnp.int32).reshape(-1)
                 for x in (lo, hi))


def _clamp(i, lo_ref, hi_ref, r):
    """Swept block index ``i`` held inside row ``r``'s live range: a dead
    step maps to a block the pipeline already holds, so it fetches nothing."""
    return jnp.minimum(jnp.maximum(i, lo_ref[r]), hi_ref[r])


def _tile_live(bounds, b, iq, ik, nq: int, nk: int, **mask):
    """The kernels' guard: :func:`_block_relevant` for tile (iq, ik) of row
    ``b``, with the segment-id bounds read from the scalar prefetch."""
    seg = None
    if bounds:
        qmin, qmax, kmin, kmax = bounds
        seg = (qmin[b * nq + iq], qmax[b * nq + iq],
               kmin[b * nk + ik], kmax[b * nk + ik])
    return _block_relevant(iq * mask["bq"], ik * mask["bk"], seg=seg, **mask)


def _split_prefetch(refs, has_seg: bool):
    """(segment-id bounds, the other refs): the scalar-prefetch refs lead,
    the clamp range first, which only the index maps read."""
    n = 6 if has_seg else 2
    return refs[2:n], refs[n:]


def _folded(x, gf: int, bq: int):
    """(gf·bq, n) rows of a folded tile → (gf, bq, n), and back."""
    if x.ndim == 2:
        return x.reshape(gf, bq, x.shape[-1])
    return x.reshape(gf * bq, x.shape[-1])


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _fwd_kernel(*refs, bq: int, bk: int, gf: int, nq: int, nk: int,
                causal: bool, window: Optional[int], scale: float,
                has_seg: bool):
    bounds, refs = _split_prefetch(refs, has_seg)
    if has_seg:
        q_ref, k_ref, v_ref, qs_ref, ks_ref, o_ref, lse_ref, acc_ref, m_ref, \
            l_ref = refs
    else:
        q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref = refs
    b = pl.program_id(0)
    iq = pl.program_id(2)
    ik = pl.program_id(3)
    mask_kw = dict(bq=bq, bk=bk, causal=causal, window=window)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(_tile_live(bounds, b, iq, ik, nq, nk, **mask_kw))
    def _compute():
        q = _folded(q_ref[0].astype(jnp.float32), gf, bq) * scale  # (R, D)
        k = k_ref[0, 0].astype(jnp.float32)                  # (bk, D)
        v = v_ref[0, 0].astype(jnp.float32)
        s = _folded(q @ k.T, gf, bq)                         # (gf, bq, bk)
        mask = _tile_mask(iq * bq, ik * bk, **mask_kw,
                          qseg=qs_ref[0] if has_seg else None,
                          kseg=ks_ref[0, 0] if has_seg else None)
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[...]                                  # (gf, bq, 1)
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=2, keepdims=True))
        alpha = jnp.exp(m_prev - m_cur)
        # zero masked entries explicitly: exp(-inf − -inf) = 1 otherwise
        p = jnp.exp(s - m_cur) * mask
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=2, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + _folded(
            _folded(p, gf, bq) @ v, gf, bq)
        m_ref[...] = m_cur

    @pl.when(ik == nk - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)
        lse_ref[0] = m_ref[...] + jnp.log(l)


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


def _geometry(q, k, segment_ids, bq: int, bk: int, gf: Optional[int]):
    """Static tiling of one call → (B, Hkv, g, gf, bq, bk, nq, nk)."""
    B, Sq, Hq, _ = q.shape
    Sk, Hkv = k.shape[1], k.shape[2]
    g = Hq // Hkv
    bq = min(bq, Sq)
    bk = min(bk, Sk)
    gf = gf or group_fold(g, bq)
    assert Sq % bq == 0 and Sk % bk == 0, (Sq, bq, Sk, bk)
    assert g % gf == 0, (g, gf)
    if segment_ids is not None:
        assert segment_ids.shape == (B, Sq) and Sq == Sk, \
            (segment_ids.shape, q.shape, k.shape)
    return B, Hkv, g, gf, bq, bk, Sq // bq, Sk // bk


def _tile_table(segment_ids, B: int, nq: int, nk: int, *, bq: int, bk: int,
                causal: bool, window: Optional[int], sweep_axis: int):
    """Scalar-prefetch operands: the clamp range of the swept axis, then
    (packed rows only) the segment-id bounds the guard reads."""
    bounds = None if segment_ids is None else _seg_bounds(segment_ids, bq, bk)
    live = _live_tiles(bounds, B, nq, nk, bq=bq, bk=bk, causal=causal,
                       window=window)
    return _sweep_range(live, sweep_axis) + (bounds or ())


def _forward(q, k, v, segment_ids, causal, window, bq, bk, gf, scale,
             interpret):
    """Shared fwd implementation → (out (B,Sq,Hq,Dv), lse (B,Hq,Sq,1) f32)."""
    B, Sq, Hq, D = q.shape
    Dv = v.shape[-1]
    B, Hkv, g, gf, bq, bk, nq, nk = _geometry(q, k, segment_ids, bq, bk, gf)
    ng = g // gf
    # head-major layout so a block is (1, gf, seq_block, D); zero-padded head
    # dim is score/output-neutral (padded q·k columns contribute 0)
    qt = _pad_head_dim(q.transpose(0, 2, 1, 3))          # (B, Hq, Sq, Dp)
    kt = _pad_head_dim(k.transpose(0, 2, 1, 3))          # (B, Hkv, Sk, Dp)
    vt = _pad_head_dim(v.transpose(0, 2, 1, 3))
    Dp, Dvp = qt.shape[-1], vt.shape[-1]
    has_seg = segment_ids is not None
    table = _tile_table(segment_ids, B, nq, nk, bq=bq, bk=bk, causal=causal,
                        window=window, sweep_axis=2)

    kernel = functools.partial(
        _fwd_kernel, bq=bq, bk=bk, gf=gf, nq=nq, nk=nk, causal=causal,
        window=window, scale=scale, has_seg=has_seg)

    def q_map(b, h, iq, ik, *t):
        return (b, h, iq, 0)

    def kv_map(b, h, iq, ik, *t):
        return (b, h // ng, _clamp(ik, t[0], t[1], b * nq + iq), 0)

    in_specs = [
        pl.BlockSpec((1, gf, bq, Dp), q_map),
        pl.BlockSpec((1, 1, bk, Dp), kv_map),
        pl.BlockSpec((1, 1, bk, Dvp), kv_map),
    ]
    inputs = [qt, kt, vt]
    if has_seg:
        in_specs += [
            pl.BlockSpec((1, bq, 1), lambda b, h, iq, ik, *t: (b, iq, 0)),
            pl.BlockSpec((1, 1, 1, bk), lambda b, h, iq, ik, *t:
                         (b, _clamp(ik, t[0], t[1], b * nq + iq), 0, 0)),
        ]
        inputs += list(_seg_operands(segment_ids, bq, bk))

    out, lse = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(table),
            grid=(B, Hkv * ng, nq, nk),
            in_specs=in_specs,
            out_specs=[
                pl.BlockSpec((1, gf, bq, Dvp), q_map),
                pl.BlockSpec((1, gf, bq, 1), q_map),
            ],
            scratch_shapes=_scratch(gf, bq, Dvp),
        ),
        out_shape=[
            jax.ShapeDtypeStruct((B, Hq, Sq, Dvp), q.dtype),
            jax.ShapeDtypeStruct((B, Hq, Sq, 1), jnp.float32),
        ],
        compiler_params=_compiler_params(),
        interpret=interpret,
        name="flash_attention_fwd",
    )(*table, *inputs)
    return out[..., :Dv].transpose(0, 2, 1, 3), lse


# ---------------------------------------------------------------------------
# backward kernels
# ---------------------------------------------------------------------------

def _delta_kernel(o_ref, do_ref, delta_ref):
    """Preprocess: delta = rowsum(dO ⊙ O) — the softmax-normalization term
    shared by the dQ and dK sweeps."""
    delta_ref[0] = jnp.sum(
        o_ref[0].astype(jnp.float32) * do_ref[0].astype(jnp.float32),
        axis=2, keepdims=True)


def _probs_and_ds(q, k, v, do, lse, delta, mask, *, gf: int, bq: int,
                  scale: float):
    """Recomputed probabilities and dS of a folded tile, both (R, bk)."""
    s = jnp.where(mask, _folded(q @ k.T, gf, bq) * scale, NEG_INF)
    p = jnp.exp(s - lse) * mask                          # (gf, bq, bk)
    dp = _folded(do @ v.T, gf, bq)
    ds = p * (dp - delta)
    return _folded(p, gf, bq), _folded(ds, gf, bq)


def _dq_kernel(*refs, bq: int, bk: int, gf: int, nq: int, nk: int,
               causal: bool, window: Optional[int], scale: float,
               has_seg: bool):
    bounds, refs = _split_prefetch(refs, has_seg)
    if has_seg:
        q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, qs_ref, ks_ref, \
            dq_ref, acc_ref = refs
    else:
        q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, acc_ref = refs
    b = pl.program_id(0)
    iq = pl.program_id(2)
    ik = pl.program_id(3)
    mask_kw = dict(bq=bq, bk=bk, causal=causal, window=window)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(_tile_live(bounds, b, iq, ik, nq, nk, **mask_kw))
    def _compute():
        q = _folded(q_ref[0].astype(jnp.float32), gf, bq)    # (R, D)
        do = _folded(do_ref[0].astype(jnp.float32), gf, bq)
        k = k_ref[0, 0].astype(jnp.float32)                  # (bk, D)
        v = v_ref[0, 0].astype(jnp.float32)
        mask = _tile_mask(iq * bq, ik * bk, **mask_kw,
                          qseg=qs_ref[0] if has_seg else None,
                          kseg=ks_ref[0, 0] if has_seg else None)
        _, ds = _probs_and_ds(q, k, v, do, lse_ref[0], delta_ref[0], mask,
                              gf=gf, bq=bq, scale=scale)
        acc_ref[...] += _folded((ds @ k) * scale, gf, bq)

    @pl.when(ik == nk - 1)
    def _finalize():
        dq_ref[0] = acc_ref[...].astype(dq_ref.dtype)


def _dkv_kernel(*refs, bq: int, bk: int, gf: int, ng: int, nq: int, nk: int,
                causal: bool, window: Optional[int], scale: float,
                has_seg: bool):
    bounds, refs = _split_prefetch(refs, has_seg)
    if has_seg:
        k_ref, v_ref, q_ref, do_ref, lse_ref, delta_ref, ks_ref, qs_ref, \
            dk_ref, dv_ref, dk_acc, dv_acc = refs
    else:
        k_ref, v_ref, q_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref, \
            dk_acc, dv_acc = refs
    b = pl.program_id(0)
    ikb = pl.program_id(2)
    ig = pl.program_id(3)
    iqb = pl.program_id(4)
    mask_kw = dict(bq=bq, bk=bk, causal=causal, window=window)

    @pl.when(jnp.logical_and(ig == 0, iqb == 0))
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    @pl.when(_tile_live(bounds, b, iqb, ikb, nq, nk, **mask_kw))
    def _compute():
        k = k_ref[0, 0].astype(jnp.float32)                  # (bk, D)
        v = v_ref[0, 0].astype(jnp.float32)
        q = _folded(q_ref[0].astype(jnp.float32), gf, bq)    # (R, D)
        do = _folded(do_ref[0].astype(jnp.float32), gf, bq)
        mask = _tile_mask(iqb * bq, ikb * bk, **mask_kw,
                          qseg=qs_ref[0] if has_seg else None,
                          kseg=ks_ref[0, 0] if has_seg else None)
        p, ds = _probs_and_ds(q, k, v, do, lse_ref[0], delta_ref[0], mask,
                              gf=gf, bq=bq, scale=scale)
        dv_acc[...] += p.T @ do                              # sums the fold
        dk_acc[...] += (ds.T @ q) * scale

    @pl.when(jnp.logical_and(ig == ng - 1, iqb == nq - 1))
    def _finalize():
        dk_ref[0, 0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[...].astype(dv_ref.dtype)


def _backward(q, k, v, segment_ids, o, lse, do, causal, window, bq, bk, gf,
              scale, interpret):
    B, Sq, Hq, D = q.shape
    Sk, Dv = k.shape[1], v.shape[-1]
    B, Hkv, g, gf, bq, bk, nq, nk = _geometry(q, k, segment_ids, bq, bk, gf)
    ng = g // gf
    has_seg = segment_ids is not None

    qt = _pad_head_dim(q.transpose(0, 2, 1, 3))          # (B, Hq, Sq, Dp)
    kt = _pad_head_dim(k.transpose(0, 2, 1, 3))          # (B, Hkv, Sk, Dp)
    vt = _pad_head_dim(v.transpose(0, 2, 1, 3))
    ot = _pad_head_dim(o.transpose(0, 2, 1, 3))
    dot = _pad_head_dim(do.transpose(0, 2, 1, 3))
    Dp, Dvp = qt.shape[-1], vt.shape[-1]
    mask_kw = dict(bq=bq, bk=bk, causal=causal, window=window)

    def row_map(b, h, iq):
        return (b, h, iq, 0)

    delta = pl.pallas_call(
        _delta_kernel,
        grid=(B, Hkv * ng, nq),
        in_specs=[
            pl.BlockSpec((1, gf, bq, Dvp), row_map),
            pl.BlockSpec((1, gf, bq, Dvp), row_map),
        ],
        out_specs=pl.BlockSpec((1, gf, bq, 1), row_map),
        out_shape=jax.ShapeDtypeStruct((B, Hq, Sq, 1), jnp.float32),
        compiler_params=_compiler_params(("parallel",) * 3),
        interpret=interpret,
        name="flash_attention_delta",
    )(ot, dot)

    if has_seg:
        qseg, kseg = _seg_operands(segment_ids, bq, bk)

    # dQ: grid (B, Hkv·ng, nq, nk), K innermost, clamped to the row's range
    table = _tile_table(segment_ids, B, nq, nk, sweep_axis=2, **mask_kw)

    def q_map(b, h, iq, ik, *t):
        return (b, h, iq, 0)

    def kv_map(b, h, iq, ik, *t):
        return (b, h // ng, _clamp(ik, t[0], t[1], b * nq + iq), 0)

    dq_in_specs = [
        pl.BlockSpec((1, gf, bq, Dp), q_map),
        pl.BlockSpec((1, 1, bk, Dp), kv_map),
        pl.BlockSpec((1, 1, bk, Dvp), kv_map),
        pl.BlockSpec((1, gf, bq, Dvp), q_map),
        pl.BlockSpec((1, gf, bq, 1), q_map),
        pl.BlockSpec((1, gf, bq, 1), q_map),
    ]
    dq_inputs = [qt, kt, vt, dot, lse, delta]
    if has_seg:
        dq_in_specs += [
            pl.BlockSpec((1, bq, 1), lambda b, h, iq, ik, *t: (b, iq, 0)),
            pl.BlockSpec((1, 1, 1, bk), lambda b, h, iq, ik, *t:
                         (b, _clamp(ik, t[0], t[1], b * nq + iq), 0, 0)),
        ]
        dq_inputs += [qseg, kseg]

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, gf=gf, nq=nq, nk=nk, scale=scale,
                          has_seg=has_seg, **mask_kw),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(table),
            grid=(B, Hkv * ng, nq, nk),
            in_specs=dq_in_specs,
            out_specs=pl.BlockSpec((1, gf, bq, Dp), q_map),
            scratch_shapes=[pltpu.VMEM((gf, bq, Dp), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((B, Hq, Sq, Dp), q.dtype),
        compiler_params=_compiler_params(),
        interpret=interpret,
        name="flash_attention_dq",
    )(*table, *dq_inputs)

    # dK/dV: grid (B, Hkv, nk, ng, nq) — one KV head's whole query group is
    # swept inside the step's accumulators, Q blocks innermost and clamped
    table = _tile_table(segment_ids, B, nq, nk, sweep_axis=1, **mask_kw)

    def k_map(b, h, ik, ig, iq, *t):
        return (b, h, ik, 0)

    def qrow_map(b, h, ik, ig, iq, *t):
        return (b, h * ng + ig, _clamp(iq, t[0], t[1], b * nk + ik), 0)

    dkv_in_specs = [
        pl.BlockSpec((1, 1, bk, Dp), k_map),
        pl.BlockSpec((1, 1, bk, Dvp), k_map),
        pl.BlockSpec((1, gf, bq, Dp), qrow_map),
        pl.BlockSpec((1, gf, bq, Dvp), qrow_map),
        pl.BlockSpec((1, gf, bq, 1), qrow_map),
        pl.BlockSpec((1, gf, bq, 1), qrow_map),
    ]
    dkv_inputs = [kt, vt, qt, dot, lse, delta]
    if has_seg:
        dkv_in_specs += [
            pl.BlockSpec((1, 1, 1, bk), lambda b, h, ik, ig, iq, *t:
                         (b, ik, 0, 0)),
            pl.BlockSpec((1, bq, 1), lambda b, h, ik, ig, iq, *t:
                         (b, _clamp(iq, t[0], t[1], b * nk + ik), 0)),
        ]
        dkv_inputs += [kseg, qseg]

    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, gf=gf, ng=ng, nq=nq, nk=nk,
                          scale=scale, has_seg=has_seg, **mask_kw),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(table),
            grid=(B, Hkv, nk, ng, nq),
            in_specs=dkv_in_specs,
            out_specs=[pl.BlockSpec((1, 1, bk, Dp), k_map),
                       pl.BlockSpec((1, 1, bk, Dvp), k_map)],
            scratch_shapes=[pltpu.VMEM((bk, Dp), jnp.float32),
                            pltpu.VMEM((bk, Dvp), jnp.float32)],
        ),
        out_shape=[jax.ShapeDtypeStruct((B, Hkv, Sk, Dp), k.dtype),
                   jax.ShapeDtypeStruct((B, Hkv, Sk, Dvp), v.dtype)],
        compiler_params=_compiler_params(
            ("parallel", "parallel", "parallel", "arbitrary", "arbitrary")),
        interpret=interpret,
        name="flash_attention_dkv",
    )(*table, *dkv_inputs)

    def unpad(x, n):
        return x[..., :n].transpose(0, 2, 1, 3)

    return unpad(dq, D), unpad(dk, D), unpad(dv, Dv)


# ---------------------------------------------------------------------------
# custom_vjp plumbing + public entry point
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9, 10))
def _flash(q, k, v, segment_ids, causal, window, bq, bk, gf, scale,
           interpret):
    out, _ = _forward(q, k, v, segment_ids, causal, window, bq, bk, gf,
                      scale, interpret)
    return out


def _flash_fwd(q, k, v, segment_ids, causal, window, bq, bk, gf, scale,
               interpret):
    out, lse = _forward(q, k, v, segment_ids, causal, window, bq, bk, gf,
                        scale, interpret)
    # residuals are O(B·S·(3D + 1)) — the S×S score matrix is never saved
    return out, (q, k, v, segment_ids, out, lse)


def _flash_bwd(causal, window, bq, bk, gf, scale, interpret, res, do):
    q, k, v, segment_ids, out, lse = res
    dq, dk, dv = _backward(q, k, v, segment_ids, out, lse, do, causal, window,
                           bq, bk, gf, scale, interpret)
    return dq, dk, dv, None          # segment ids carry no tangent


_flash.defvjp(_flash_fwd, _flash_bwd)


@functools.partial(jax.jit, static_argnames=("causal", "window", "bq", "bk",
                                             "gf", "scale", "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    segment_ids: Optional[jax.Array] = None,
                    causal: bool = True, window: Optional[int] = None,
                    bq: int = 128, bk: int = 128, gf: Optional[int] = None,
                    scale: Optional[float] = None,
                    interpret: bool = False) -> jax.Array:
    """q: (B, Sq, Hq, D); k: (B, Sk, Hkv, D); v: (B, Sk, Hkv, Dv)
    → (B, Sq, Hq, Dv).

    The softmax scale is ``scale`` (default ``D ** -0.5``); V's head dim
    ``Dv`` may differ from Q/K's (latent attention: 192 and 128), and each
    is padded to its own lane multiple.

    Differentiable: gradients run through the fused Pallas backward kernels
    (recompute-style — no (B, H, S, S) intermediate), so training can route
    through the tiled path, not just inference.

    ``gf`` query heads of one KV group share a grid step (it divides
    ``Hq // Hkv``); ``None`` takes :func:`group_fold`'s choice.

    ``segment_ids`` (B, S) int32 restricts attention to
    ``seg[q] == seg[k]`` — packed-sequence training and mixed-length batched
    prefills (serving uses id ``-1`` on padded positions).  Requires aligned
    self-attention (Sq == Sk); the fwd AND bwd kernels skip (q-block,
    k-block) tiles whose id ranges cannot intersect.
    """
    if segment_ids is not None:
        segment_ids = segment_ids.astype(jnp.int32)
    if scale is None:
        scale = q.shape[-1] ** -0.5
    return _flash(q, k, v, segment_ids, causal, window, bq, bk, gf, scale,
                  interpret)


def _scratch(gf: int, bq: int, D: int):
    return [
        pltpu.VMEM((gf, bq, D), jnp.float32),   # acc
        pltpu.VMEM((gf, bq, 1), jnp.float32),   # running max m
        pltpu.VMEM((gf, bq, 1), jnp.float32),   # running sum l
    ]


def _compiler_params(dimension_semantics=("parallel", "parallel", "parallel",
                                          "arbitrary")):
    return pltpu.CompilerParams(dimension_semantics=dimension_semantics)
