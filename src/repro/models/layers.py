"""Primitive layers shared by every architecture in the zoo.

Everything is a plain function over explicit parameter pytrees; no framework
state.  Initializers return fp32; the forward pass casts to ``cfg.dtype``.
"""

from __future__ import annotations

import math
from typing import Any, Dict

import jax
import jax.numpy as jnp

Params = Dict[str, Any]


# ---------------------------------------------------------------------------
# initializers
# ---------------------------------------------------------------------------

def dense_init(key, d_in: int, d_out: int, *, scale: float | None = None):
    """Truncated-normal fan-in init (matches Megatron's init recipe)."""
    std = scale if scale is not None else 1.0 / math.sqrt(d_in)
    return jax.random.truncated_normal(key, -3.0, 3.0, (d_in, d_out), jnp.float32) * std


def embed_init(key, vocab: int, d: int):
    return jax.random.normal(key, (vocab, d), jnp.float32) * 0.02


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------

def rmsnorm_init(d: int) -> Params:
    return {"scale": jnp.ones((d,), jnp.float32)}


def rmsnorm(p: Params, x: jax.Array, *, eps: float = 1e-6) -> jax.Array:
    dt = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + eps) * p["scale"].astype(jnp.float32)
    return out.astype(dt)


def layernorm_init(d: int) -> Params:
    return {"scale": jnp.ones((d,), jnp.float32), "bias": jnp.zeros((d,), jnp.float32)}


def layernorm(p: Params, x: jax.Array, *, eps: float = 1e-5) -> jax.Array:
    dt = x.dtype
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
    out = (xf - mu) * jax.lax.rsqrt(var + eps)
    out = out * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)
    return out.astype(dt)


def norm_init(kind: str, d: int) -> Params:
    return rmsnorm_init(d) if kind == "rmsnorm" else layernorm_init(d)


def norm_apply(kind: str, p: Params, x: jax.Array) -> jax.Array:
    return rmsnorm(p, x) if kind == "rmsnorm" else layernorm(p, x)


# ---------------------------------------------------------------------------
# rotary position embeddings
# ---------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float = 10000.0) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention-temperature factor ``0.1·mscale·ln(factor) + 1``."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_freqs(head_dim: int, theta: float, factor: float, original_max: int,
               beta_fast: float, beta_slow: float) -> jax.Array:
    """YaRN (Peng et al. 2023) rotary frequencies, as DeepSeek-V2 computes
    them: dims rotating faster than ``beta_fast`` turns over the original
    context keep their frequency, those slower than ``beta_slow`` turns are
    divided by ``factor``, and a linear ramp blends the dims between."""
    def turns_dim(turns):
        return (head_dim * math.log(original_max / (turns * 2 * math.pi))
                / (2 * math.log(theta)))
    lo = max(math.floor(turns_dim(beta_fast)), 0)
    hi = min(math.ceil(turns_dim(beta_slow)), head_dim - 1)
    hi = hi + 0.001 if lo == hi else hi
    extra = rope_freqs(head_dim, theta)
    ramp = jnp.clip((jnp.arange(head_dim // 2, dtype=jnp.float32) - lo)
                    / (hi - lo), 0.0, 1.0)
    return extra / factor * ramp + extra * (1.0 - ramp)


def apply_rope(x: jax.Array, positions: jax.Array, theta: float = 10000.0,
               freqs: jax.Array | None = None) -> jax.Array:
    """x: (..., S, H, D); positions: broadcastable to (..., S).  ``freqs``
    (D/2,) replaces the plain ``theta`` frequencies (YaRN)."""
    d = x.shape[-1]
    if freqs is None:
        freqs = rope_freqs(d, theta)                   # (D/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., S, D/2)
    sin = jnp.sin(angles)[..., None, :]                # (..., S, 1, D/2)
    cos = jnp.cos(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# activations / MLP
# ---------------------------------------------------------------------------

def swiglu(gate: jax.Array, up: jax.Array) -> jax.Array:
    return jax.nn.silu(gate) * up


def mlp_init(key, d_model: int, d_ff: int, *, gated: bool = True, bias: bool = False) -> Params:
    k1, k2, k3 = jax.random.split(key, 3)
    p: Params = {"w_out": dense_init(k2, d_ff, d_model)}
    if gated:
        p["w_gate"] = dense_init(k1, d_model, d_ff)
        p["w_up"] = dense_init(k3, d_model, d_ff)
    else:
        p["w_in"] = dense_init(k1, d_model, d_ff)
    if bias:
        p["b_in"] = jnp.zeros((d_ff,), jnp.float32)
        p["b_out"] = jnp.zeros((d_model,), jnp.float32)
    return p


def mlp_apply(p: Params, x: jax.Array, *, gated: bool = True, act: str = "silu") -> jax.Array:
    dt = x.dtype
    if gated:
        h = swiglu(x @ p["w_gate"].astype(dt), x @ p["w_up"].astype(dt))
    else:
        h = x @ p["w_in"].astype(dt)
        if "b_in" in p:
            h = h + p["b_in"].astype(dt)
        h = jax.nn.gelu(h) if act == "gelu" else jax.nn.silu(h)
    out = h @ p["w_out"].astype(dt)
    if "b_out" in p:
        out = out + p["b_out"].astype(dt)
    return out


# ---------------------------------------------------------------------------
# embedding / unembedding
# ---------------------------------------------------------------------------

def embed_lookup(table: jax.Array, ids: jax.Array, dtype) -> jax.Array:
    return table.astype(dtype)[ids]


def unembed(table: jax.Array, x: jax.Array) -> jax.Array:
    """Tied unembedding: logits in fp32 for a stable softmax/xent."""
    return jnp.einsum("...d,vd->...v", x.astype(jnp.float32), table.astype(jnp.float32))


def gold_logit(logits: jax.Array, labels: jax.Array) -> jax.Array:
    """logits[..., labels] via mask-sum — partitions cleanly when the vocab
    axis is sharded (take_along_axis would all-gather)."""
    V = logits.shape[-1]
    idx = jax.lax.broadcasted_iota(jnp.int32, logits.shape, logits.ndim - 1)
    hit = idx == labels[..., None]
    return jnp.sum(jnp.where(hit, logits, 0.0), axis=-1)


def cross_entropy(logits: jax.Array, labels: jax.Array, mask: jax.Array | None = None) -> jax.Array:
    """Mean token cross-entropy. logits fp32 (..., V); labels int (...,)."""
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = gold_logit(logits, labels)
    nll = logz - gold
    if mask is not None:
        mask = mask.astype(nll.dtype)
        return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.mean(nll)
