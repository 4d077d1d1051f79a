"""Pure-jnp oracles for every Pallas kernel in this package.

These are the ground truth the kernel tests ``assert_allclose`` against, and
the paper-faithful "out-of-the-box XLA" path used when kernels are disabled.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp


def mha_reference(q: jax.Array, k: jax.Array, v: jax.Array, *,
                  causal: bool = True, window: Optional[int] = None,
                  segment_ids: Optional[jax.Array] = None,
                  scale: Optional[float] = None) -> jax.Array:
    """q: (B, Sq, Hq, D); k: (B, Sk, Hkv, D); v: (B, Sk, Hkv, Dv); GQA by
    head grouping; softmax scale ``scale`` (default ``D ** -0.5``).
    Assumes q positions are aligned with k positions (self-attention).
    ``segment_ids`` (B, S) int32 restricts attention to equal ids — the
    packed-sequence mask the flash kernel shares (Sq must equal Sk)."""
    B, Sq, Hq, D = q.shape
    Sk, Hkv = k.shape[1], k.shape[2]
    g = Hq // Hkv
    qf = q.astype(jnp.float32) * (D ** -0.5 if scale is None else scale)
    qf = qf.reshape(B, Sq, Hkv, g, D)
    scores = jnp.einsum("bqhgd,bkhd->bhgqk", qf, k.astype(jnp.float32))
    qpos = jnp.arange(Sq)[:, None]
    kpos = jnp.arange(Sk)[None, :]
    ok = jnp.ones((Sq, Sk), bool)
    if causal:
        ok &= kpos <= qpos
    if window is not None:
        ok &= kpos > qpos - window
    if segment_ids is not None:
        okb = ok[None] & (segment_ids[:, :, None] == segment_ids[:, None, :])
        scores = jnp.where(okb[:, None, None], scores, -1e30)
    else:
        scores = jnp.where(ok[None, None, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", probs, v.astype(jnp.float32))
    return out.reshape(B, Sq, Hq, v.shape[-1]).astype(q.dtype)


def decode_attention_reference(q: jax.Array, k: jax.Array, v: jax.Array,
                               kpos: jax.Array, *, t: jax.Array,
                               window: Optional[int] = None) -> jax.Array:
    """Single-token attention over a ring-buffer KV cache.

    q: (B, 1, Hq, D); k/v: (B, S, Hkv, D); kpos: (B, S) absolute positions
    (-1 = empty slot); t: the query's absolute position."""
    B, _, Hq, D = q.shape
    S, Hkv = k.shape[1], k.shape[2]
    g = Hq // Hkv
    qf = q.astype(jnp.float32).reshape(B, Hkv, g, D) * (D ** -0.5)
    scores = jnp.einsum("bhgd,bkhd->bhgk", qf, k.astype(jnp.float32))
    valid = (kpos >= 0) & (kpos <= t)
    if window is not None:
        valid &= kpos > t - window
    scores = jnp.where(valid[:, None, None, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhgk,bkhd->bhgd", probs, v.astype(jnp.float32))
    return out.reshape(B, 1, Hq, D).astype(q.dtype)


def paged_decode_attention_reference(q: jax.Array, k_pool: jax.Array,
                                     v_pool: jax.Array, page_table: jax.Array,
                                     *, ts: jax.Array,
                                     window: Optional[int] = None) -> jax.Array:
    """Single-token attention against a block-paged KV pool.

    q: (B, 1, Hq, D); k_pool/v_pool: (n_pages, page_size, Hkv, D);
    page_table: (B, n_max) physical page of each logical page, -1 = unmapped
    (page 0 is the pool's reserved trash page — gathering it is safe because
    unmapped logical positions are masked out); ts: (B,) per-request query
    positions.  Token k of logical page i sits at absolute position
    i*page_size + k — there is no ``kpos`` array; validity is derived from
    the table.  Gathering pages into logical order and reusing the decode
    einsum keeps this token-identical to ``decode_attention_reference`` over
    the equivalent contiguous cache."""
    B, _, Hq, D = q.shape
    ps, Hkv = k_pool.shape[1], k_pool.shape[2]
    n_max = page_table.shape[1]
    g = Hq // Hkv
    pages = jnp.maximum(page_table, 0)
    k = k_pool[pages].reshape(B, n_max * ps, Hkv, D)
    v = v_pool[pages].reshape(B, n_max * ps, Hkv, D)
    logical = jnp.arange(n_max * ps, dtype=jnp.int32)[None]
    mapped = jnp.repeat(page_table >= 0, ps, axis=1)
    kpos = jnp.where(mapped, logical, -1)
    qf = q.astype(jnp.float32).reshape(B, Hkv, g, D) * (D ** -0.5)
    scores = jnp.einsum("bhgd,bkhd->bhgk", qf, k.astype(jnp.float32))
    t = ts[:, None]
    valid = (kpos >= 0) & (kpos <= t)
    if window is not None:
        valid &= kpos > t - window
    scores = jnp.where(valid[:, None, None, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhgk,bkhd->bhgd", probs, v.astype(jnp.float32))
    return out.reshape(B, 1, Hq, D).astype(q.dtype)


def rmsnorm_reference(x: jax.Array, scale: jax.Array, *, eps: float = 1e-6) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)).astype(x.dtype)
