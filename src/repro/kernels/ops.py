"""Jit'd dispatch wrappers: model code calls these; they pick the Pallas
kernel (compiled on TPU, interpreted on CPU) and fall back to the jnp oracle.
"""

from __future__ import annotations

from typing import Optional

import jax
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core import sharding as shd
from repro.kernels import ref
from repro.runtime import flags


def _interpret() -> bool:
    """Pallas interpret mode is decided by the backend alone: the CPU runs
    the kernels through the interpreter (validation), a TPU compiles them."""
    return jax.default_backend() == "cpu"


# Flash tiles by shape class, measured on a TPU v5e at the packed training
# cell's shapes (PERF.md §5): a step folds gf·bq = MAX_FOLD_ROWS query rows,
# and sweeps K blocks of bk by whether segment ids are present.
_FLASH_BK = {True: 512, False: 1024}
# f32 (gf·bq, bk) tile elements at a padded head dim up to 128; a wider head
# gets proportionally fewer (the compiler's VMEM limit)
_FLASH_TILE_ELEMS = 1 << 20


def _fit(want: int, S: int) -> int:
    """The largest of ``want``, ``want/2``, ... 64 that divides ``S`` (``S``
    itself when shorter); 64 when none does, which flash_supported refuses."""
    b = want
    while b > 64 and S % b:
        b //= 2
    return min(b, S)


def _flash_blocks(Sq: int, Sk: int, *, g: int = 1, D: int = 64,
                  Dv: Optional[int] = None, packed: bool = False):
    """(bq, bk, gf) for the flash kernels from the call's shape: the fold
    takes the largest divisor of the GQA group ``g = Hq/Hkv`` that leaves
    bq ≥ 128, bq fills the fold's MAX_FOLD_ROWS, and bk comes from the
    table, held to the VMEM budget of the wider of the Q/K head dim ``D``
    and the V head dim ``Dv`` (default ``D``); each cut to what divides the
    sequence.  A ParallelismConfig / flags override of bq or bk
    (autotuning hook) wins over the table, and the fold follows its bq."""
    from repro.kernels.flash_attention import MAX_FOLD_ROWS, group_fold
    obq, obk = flags.flash_block_sizes()
    if obq:
        bq = min(obq, Sq)
        gf = group_fold(g, bq)
    else:
        gf = group_fold(g, 128)
        rows = 1 << (MAX_FOLD_ROWS // gf).bit_length() - 1     # power of 2
        bq = _fit(rows, Sq)
    if obk:
        bk = min(obk, Sk)
    else:
        Dp = max(128, -(-max(D, Dv or D) // 128) * 128)
        budget = _FLASH_TILE_ELEMS * 128 // Dp // (gf * bq)
        bk = _fit(min(_FLASH_BK[packed], max(budget, 128)), Sk)
    return bq, bk, gf


def flash_supported(q, k, *, causal: bool = True,
                    window: Optional[int] = None,
                    segment_ids=None, v=None) -> bool:
    """True iff the tiled flash path covers these shapes — callers fall back
    to the reference/chunked paths otherwise (never a silent wrong answer).

    Conditions: seq lens divide the (possibly overridden) block sizes, and
    position-dependent masks (causal / sliding window / packed
    ``segment_ids``) only apply to aligned self-attention (Sq == Sk).  The
    head dim is unconstrained — the kernels pad it to a lane multiple
    internally.  Packed batches (``segment_ids`` present) take the tiled
    path too: the kernels fold the segment mask into the online softmax and
    skip dead (q-block, k-block) tiles.
    """
    Sq, Sk = q.shape[1], k.shape[1]
    if not isinstance(window, (int, type(None))):
        return False        # traced per-layer window (Hymba) → reference path
    if (causal or window is not None or segment_ids is not None) and Sq != Sk:
        return False
    bq, bk, _ = _flash_blocks_for(q, k, v, segment_ids)
    return Sq % bq == 0 and Sk % bk == 0


def _flash_blocks_for(q, k, v, segment_ids):
    """``_flash_blocks`` for a call's operands (``v`` None: V's head dim is
    Q/K's)."""
    return _flash_blocks(q.shape[1], k.shape[1], g=q.shape[2] // k.shape[2],
                         D=q.shape[3], Dv=None if v is None else v.shape[3],
                         packed=segment_ids is not None)


def _per_shard(fn, q, k, v, segment_ids):
    """Run ``fn(q, k, v, segment_ids)`` once per shard of the active mesh.

    XLA cannot partition a Pallas kernel, so on a multi-device mesh the call
    goes through ``shard_map``: rows over the data axes, heads over tp (when
    both the query and the KV heads divide), sequence whole.  A vmap with an
    ``spmd_axis_name`` (the pipeline's stage axis) adds its own mesh axis."""
    rules = shd.active_rules()
    if rules is None or rules.mesh.size == 1:
        return fn(q, k, v, segment_ids)
    mesh = rules.mesh

    def ways(ax):
        axes = ax if isinstance(ax, tuple) else (ax,)
        return int(np.prod([mesh.shape[a] for a in axes if a is not None]))

    rows = rules.resolve(("batch",))[0]
    if rows is not None and q.shape[0] % ways(rows):
        rows = None
    heads = rules.resolve(("tp",))[0]
    if heads is not None and (q.shape[2] % ways(heads)
                              or k.shape[2] % ways(heads)):
        heads = None
    qkv = P(rows, None, heads, None)
    seg = P(rows, None)
    if segment_ids is None:
        return jax.shard_map(lambda q, k, v: fn(q, k, v, None), mesh=mesh,
                             in_specs=(qkv,) * 3, out_specs=qkv,
                             check_vma=False)(q, k, v)
    return jax.shard_map(fn, mesh=mesh, in_specs=(qkv,) * 3 + (seg,),
                         out_specs=qkv, check_vma=False)(q, k, v, segment_ids)


def flash_attention(q, k, v, *, causal: bool = True,
                    window: Optional[int] = None,
                    segment_ids=None, scale: Optional[float] = None) -> jax.Array:
    """Differentiable flash attention (fused fwd+bwd Pallas kernels), with a
    clean fallback to the jnp oracle for shapes the tiling can't cover.
    ``scale`` is the softmax scale (default ``D ** -0.5``); V's head dim may
    differ from Q/K's."""
    from repro.kernels import flash_attention as fa
    if not flash_supported(q, k, causal=causal, window=window,
                           segment_ids=segment_ids, v=v):
        return ref.mha_reference(q, k, v, causal=causal, window=window,
                                 segment_ids=segment_ids, scale=scale)
    bq, bk, gf = _flash_blocks_for(q, k, v, segment_ids)

    def kernel(q, k, v, segment_ids):
        return fa.flash_attention(q, k, v, segment_ids=segment_ids,
                                  causal=causal, window=window, bq=bq, bk=bk,
                                  gf=gf, scale=scale, interpret=_interpret())

    return _per_shard(kernel, q, k, v, segment_ids)


def decode_attention(q, k, v, kpos, *, t, window: Optional[int] = None) -> jax.Array:
    from repro.kernels import decode_attention as da
    S = k.shape[1]
    if S % 512 and S % 128:
        return ref.decode_attention_reference(q, k, v, kpos, t=t, window=window)
    bk = 512 if S % 512 == 0 else 128
    return da.decode_attention(q, k, v, kpos, t=t, window=window, bk=bk,
                               interpret=_interpret())


def paged_decode_attention(q, k_pool, v_pool, page_table, *, ts,
                           window: Optional[int] = None) -> jax.Array:
    """Decode attention through a block-paged KV pool (per-request page
    tables, see ``repro.session.kvpool``).  The Pallas kernel steers its K/V
    DMAs straight off the scalar-prefetched page table; pools whose page size
    doesn't fill a TPU lane tile fall back to the gather-einsum oracle."""
    from repro.kernels import decode_attention as da
    ps = k_pool.shape[1]
    if ps % 128 and not _interpret():
        return ref.paged_decode_attention_reference(q, k_pool, v_pool,
                                                    page_table, ts=ts,
                                                    window=window)
    return da.paged_decode_attention(q, k_pool, v_pool, page_table, ts=ts,
                                     window=window,
                                     interpret=_interpret())


def rmsnorm(x, scale, *, eps: float = 1e-6) -> jax.Array:
    if not flags.use_fused_rmsnorm():
        return ref.rmsnorm_reference(x, scale, eps=eps)
    from repro.kernels import rmsnorm as rn
    return rn.rmsnorm(x, scale, eps=eps, interpret=_interpret())
