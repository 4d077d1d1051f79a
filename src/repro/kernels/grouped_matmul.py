"""Grouped matrix multiply over the experts a layer holds (Pallas, TPU).

The rows of ``x`` come sorted by expert, and each expert's rows are padded
to a whole number of row tiles (``TILE_M``): row tile ``i`` belongs to
expert ``tile_expert[i]``, and only the first ``n_tiles`` row tiles hold
rows.  The grid runs over those tiles alone (its row extent is the traced
``n_tiles``), so a layout sized for the worst case costs no work for the
tiles it leaves empty.  Rows of the tiles past ``n_tiles`` are left
unwritten: callers read only the rows they placed.

Three kernels, each named in the trace:

  * ``expert_gmm``     — ``out[rows of e] = x[rows of e] @ w[e]``;
  * ``expert_gmm_dx``  — the same with ``w[e]`` transposed (the input
    gradient ``dY @ w[e]^T``);
  * ``expert_gmm_dw``  — ``dw[e] = x[rows of e]^T @ dY[rows of e]``, one
    expert's tiles summed in a VMEM accumulator (an expert with no rows
    gets zeros).

:func:`expert_matmul` is the differentiable entry point (``jax.custom_vjp``
over the three).  Products run on the MXU in the operands' dtype with f32
accumulation.  On the CPU the kernels run in Pallas interpret mode.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

TILE_M = 256


def _tile(dim: int, want: int) -> int:
    """``want`` where it divides ``dim``, else the whole dim (a block may
    always span a full dimension)."""
    return want if dim % want == 0 else dim


def tile_layout(sizes: jax.Array, rows: int):
    """Row-tile table of experts with ``sizes`` (E,) rows each, every group
    padded to whole tiles: (first padded row of each expert (E,),
    ``tile_expert`` (rows/TILE_M,), ``n_tiles``).  Tiles past ``n_tiles``
    name the last expert and are never visited."""
    tiles = (sizes + TILE_M - 1) // TILE_M
    end = jnp.cumsum(tiles)
    idx = jnp.arange(rows // TILE_M, dtype=jnp.int32)
    tile_expert = jnp.minimum(jnp.searchsorted(end, idx, side="right"),
                              sizes.shape[0] - 1).astype(jnp.int32)
    return ((end - tiles) * TILE_M).astype(jnp.int32), tile_expert, \
        end[-1].astype(jnp.int32).reshape(1)


def _mm_kernel(te_ref, n_ref, x_ref, w_ref, o_ref, acc_ref, *, nk: int,
               transpose_w: bool):
    kk = pl.program_id(2)

    @pl.when(kk == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    contract = ((1,), (1,)) if transpose_w else ((1,), (0,))
    acc_ref[...] += jax.lax.dot_general(
        x_ref[...], w_ref[0], (contract, ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(kk == nk - 1)
    def _store():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


# jitted so that each kernel keeps its own name inside a differentiated
# step (under a transform's name stack it would read ``jvp_expert_gmm_``)
@functools.partial(jax.jit, static_argnames=("transpose_w", "interpret",
                                             "name"))
def _gmm(x, w, tile_expert, n_tiles, *, transpose_w: bool, interpret: bool,
         name: str):
    """(M, K) rows times their expert's (K, N) matrix (``w`` (E, K, N), or
    (E, N, K) with ``transpose_w``) → (M, N) in ``x``'s dtype."""
    M, K = x.shape
    N = w.shape[1] if transpose_w else w.shape[2]
    tm, tk, tn = TILE_M, _tile(K, 1024), _tile(N, 1024)
    nk = K // tk
    if transpose_w:
        w_spec = pl.BlockSpec((1, tn, tk), lambda j, i, kk, te, n:
                              (te[i], j, kk))
    else:
        w_spec = pl.BlockSpec((1, tk, tn), lambda j, i, kk, te, n:
                              (te[i], kk, j))
    return pl.pallas_call(
        functools.partial(_mm_kernel, nk=nk, transpose_w=transpose_w),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(N // tn, n_tiles[0], nk),
            in_specs=[pl.BlockSpec((tm, tk), lambda j, i, kk, te, n: (i, kk)),
                      w_spec],
            out_specs=pl.BlockSpec((tm, tn), lambda j, i, kk, te, n: (i, j)),
            scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((M, N), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=interpret,
        name=name,
    )(tile_expert, n_tiles, x, w)


def _dw_kernel(te_ref, n_ref, x_ref, dy_ref, o_ref, acc_ref):
    i = pl.program_id(2)
    e = te_ref[i]
    last_tile = te_ref.shape[0] - 1
    first = jnp.logical_or(i == 0, te_ref[jnp.maximum(i - 1, 0)] != e)
    last = jnp.logical_or(i == n_ref[0] - 1,
                          te_ref[jnp.minimum(i + 1, last_tile)] != e)

    @pl.when(first)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        x_ref[...], dy_ref[...], (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(last)
    def _store():
        o_ref[0] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("dtype", "interpret"))
def _gmm_dw(x, dy, tile_expert, n_tiles, sizes, dtype, *, interpret: bool):
    """(E, K, N): each expert's rows of ``x`` (M, K) against the same rows
    of ``dy`` (M, N)."""
    M, K = x.shape
    N = dy.shape[1]
    E = sizes.shape[0]
    tm, tk, tn = TILE_M, _tile(K, 512), _tile(N, 512)
    dw = pl.pallas_call(
        _dw_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(K // tk, N // tn, n_tiles[0]),
            in_specs=[pl.BlockSpec((tm, tk), lambda a, b, i, te, n: (i, a)),
                      pl.BlockSpec((tm, tn), lambda a, b, i, te, n: (i, b))],
            out_specs=pl.BlockSpec((1, tk, tn), lambda a, b, i, te, n:
                                   (te[i], a, b)),
            scratch_shapes=[pltpu.VMEM((tk, tn), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((E, K, N), dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="expert_gmm_dw",
    )(tile_expert, n_tiles, x, dy)
    # an expert with no rows has no tile: its block was never written
    return jnp.where((sizes > 0)[:, None, None], dw, jnp.zeros((), dtype))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def expert_matmul(x, w, tile_expert, n_tiles, sizes, interpret=False):
    """x (M, K) sorted and tile-padded by expert (see :func:`tile_layout`),
    w (E, K, N) → (M, N); rows of tiles past ``n_tiles`` are unwritten.
    ``sizes`` (E,) are the experts' row counts."""
    return _gmm(x, w.astype(x.dtype), tile_expert, n_tiles, transpose_w=False,
                interpret=interpret, name="expert_gmm")


def _fwd(x, w, tile_expert, n_tiles, sizes, interpret):
    return (expert_matmul(x, w, tile_expert, n_tiles, sizes, interpret),
            (x, w, tile_expert, n_tiles, sizes))


def _bwd(interpret, res, dy):
    x, w, tile_expert, n_tiles, sizes = res
    dx = _gmm(dy, w.astype(dy.dtype), tile_expert, n_tiles, transpose_w=True,
              interpret=interpret, name="expert_gmm_dx")
    dw = _gmm_dw(x, dy, tile_expert, n_tiles, sizes, dtype=w.dtype,
                 interpret=interpret)
    return dx, dw, None, None, None


expert_matmul.defvjp(_fwd, _bwd)
