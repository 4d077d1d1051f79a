"""Fused RMSNorm (Pallas): one pass over rows in VMEM blocks — saves the
separate mean-square reduction + rescale round-trips through HBM that the
unfused XLA lowering costs when the fusion heuristic splits them."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _rmsnorm_kernel(x_ref, s_ref, o_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    o_ref[...] = (x * jax.lax.rsqrt(var + eps) *
                  s_ref[...].astype(jnp.float32)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("eps", "block_rows", "interpret"))
def rmsnorm(x: jax.Array, scale: jax.Array, *, eps: float = 1e-6,
            block_rows: int = 256, interpret: bool = False) -> jax.Array:
    """x: (..., d); scale: (d,)."""
    import math
    orig_shape = x.shape
    d = x.shape[-1]
    rows = math.prod(x.shape[:-1]) if x.ndim > 1 else 1
    x2 = x.reshape(rows, d)
    br = min(block_rows, rows)
    while rows % br:
        br -= 1
    out = pl.pallas_call(
        functools.partial(_rmsnorm_kernel, eps=eps),
        grid=(rows // br,),
        in_specs=[
            pl.BlockSpec((br, d), lambda i: (i, 0)),
            pl.BlockSpec((d,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((br, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, d), x.dtype),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel",)),
        interpret=interpret,
    )(x2, scale)
    return out.reshape(orig_shape)
