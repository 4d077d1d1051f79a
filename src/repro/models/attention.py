"""Multi-head attention: GQA, causal / sliding-window / bidirectional masks,
RoPE, ring-buffer KV caches for sub-quadratic long-context decode; latent
attention (MLA, with YaRN rope) for training.

The einsum path here is the paper-faithful ("out-of-the-box XLA") baseline.
``repro.kernels.ops`` provides the Pallas flash-attention fast path; model code
routes through :func:`sdpa`, which dispatches on ``repro.runtime.flags``.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.models import layers
from repro.models.config import ModelConfig

Params = Dict[str, Any]


def attention_init(key, cfg: ModelConfig, *, cross: bool = False) -> Params:
    if cfg.is_mla:
        return mla_init(key, cfg)
    d, hd = cfg.d_model, cfg.hd
    q_dim, kv_dim = cfg.n_heads * hd, cfg.n_kv_heads * hd
    kq, kk, kv, ko = jax.random.split(key, 4)
    p: Params = {
        "wq": layers.dense_init(kq, d, q_dim),
        "wk": layers.dense_init(kk, d, kv_dim),
        "wv": layers.dense_init(kv, d, kv_dim),
        "wo": layers.dense_init(ko, q_dim, d, scale=1.0 / (q_dim ** 0.5 * (2 * cfg.n_layers) ** 0.5)),
    }
    if cfg.qkv_bias and not cross:
        p["bq"] = jnp.zeros((q_dim,), jnp.float32)
        p["bk"] = jnp.zeros((kv_dim,), jnp.float32)
        p["bv"] = jnp.zeros((kv_dim,), jnp.float32)
    return p


# ---------------------------------------------------------------------------
# latent attention (MLA, DeepSeek-V2): training path
# ---------------------------------------------------------------------------

def mla_init(key, cfg: ModelConfig) -> Params:
    """Leaves of latent attention without a query latent (``q_lora_rank``
    null): ``wq`` (d, H·(nope+rope)), ``wkv_a`` (d, latent+rope) giving the
    latent c_kv and the rotary key shared by all heads, ``kv_norm`` on the
    latent, ``wkv_b`` (latent, H·(nope+v)) and ``wo`` (H·v, d)."""
    d, H, r = cfg.d_model, cfg.n_heads, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    kq, ka, kb, ko = jax.random.split(key, 4)
    return {
        "wq": layers.dense_init(kq, d, H * (dn + dr)),
        "wkv_a": layers.dense_init(ka, d, r + dr),
        "kv_norm": layers.rmsnorm_init(r),
        "wkv_b": layers.dense_init(kb, r, H * (dn + dv)),
        "wo": layers.dense_init(ko, H * dv, d, scale=1.0 / (
            (H * dv) ** 0.5 * (2 * cfg.n_layers) ** 0.5)),
    }


def _mla_rope(cfg: ModelConfig):
    """(rotary frequencies or None for plain rope, the factor YaRN puts on
    the rotated dims' cos and sin)."""
    f = cfg.rope_scaling_factor
    if f <= 1:
        return None, 1.0
    freqs = layers.yarn_freqs(cfg.qk_rope_head_dim, cfg.rope_theta, f,
                              cfg.rope_original_max, cfg.yarn_beta_fast,
                              cfg.yarn_beta_slow)
    return freqs, (layers.yarn_mscale(f, cfg.yarn_mscale)
                   / layers.yarn_mscale(f, cfg.yarn_mscale_all_dim))


def mla_softmax_scale(cfg: ModelConfig) -> float:
    """``(nope + rope) ** -0.5``, times YaRN's m² for the whole score."""
    m = (layers.yarn_mscale(cfg.rope_scaling_factor, cfg.yarn_mscale_all_dim)
         if cfg.yarn_mscale_all_dim else 1.0)
    return (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5 * m * m


def mla_apply(cfg: ModelConfig, p: Params, x: jax.Array, positions: jax.Array,
              *, causal: bool = True, window: Optional[int] = None,
              segment_ids: Optional[jax.Array] = None) -> jax.Array:
    """Latent attention over a full sequence: q = x·W_q; [c_kv, k_rope] =
    x·W_kv_a; c_kv normed; [k_nope, v] = c_kv·W_kv_b per head; rotary
    positions (YaRN where configured) on q's rope dims and on the one
    shared k_rope; attention at head dims nope+rope (Q/K) and v (V)."""
    B, S, _ = x.shape
    H, r = cfg.n_heads, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    dt = x.dtype
    with jax.named_scope("mla"):
        q = (x @ p["wq"].astype(dt)).reshape(B, S, H, dn + dr)
        kv_a = x @ p["wkv_a"].astype(dt)
        c_kv = layers.rmsnorm(p["kv_norm"], kv_a[..., :r])
        kv = (c_kv @ p["wkv_b"].astype(dt)).reshape(B, S, H, dn + dv)
        freqs, m = _mla_rope(cfg)
        q_rope = layers.apply_rope(q[..., dn:], positions, cfg.rope_theta, freqs)
        k_rope = layers.apply_rope(kv_a[..., None, r:], positions,
                                   cfg.rope_theta, freqs)
        if m != 1.0:
            q_rope, k_rope = q_rope * m, k_rope * m
        q = jnp.concatenate([q[..., :dn], q_rope], axis=-1)
        k = jnp.concatenate([kv[..., :dn],
                             jnp.broadcast_to(k_rope, (B, S, H, dr))], axis=-1)
        v = kv[..., dn:]
    out = sdpa(q, k, v, None, causal=causal, window=window,
               segment_ids=segment_ids, scale=mla_softmax_scale(cfg))
    with jax.named_scope("mla"):
        return out.reshape(B, S, H * dv) @ p["wo"].astype(dt)


def _refuse_mla(cfg: ModelConfig) -> None:
    if cfg.is_mla:
        raise NotImplementedError(
            f"{cfg.name}: latent attention has no decode or prefill cache "
            f"(a latent paged cache); it trains only")


def _mask_bias(q_pos: jax.Array, k_pos: jax.Array, *, causal: bool,
               window: Optional[int], k_valid: Optional[jax.Array] = None) -> jax.Array:
    """(..., Sq, Sk) additive fp32 bias. q_pos/k_pos are absolute positions."""
    qp = q_pos[..., :, None]
    kp = k_pos[..., None, :]
    ok = jnp.ones(jnp.broadcast_shapes(qp.shape, kp.shape), bool)
    if causal:
        ok &= kp <= qp
    if window is not None:
        ok &= kp > qp - window
    if k_valid is not None:
        ok &= k_valid[..., None, :]
    return jnp.where(ok, 0.0, -1e30).astype(jnp.float32)


CHUNKED_THRESHOLD = 32 * 1024 * 1024  # Sq·Sk elements above which we go chunked


def chunked_sdpa(q: jax.Array, k: jax.Array, v: jax.Array, *, causal: bool,
                 window, segment_ids: Optional[jax.Array] = None,
                 bq: int = 512, bk: int = 1024,
                 scale: Optional[float] = None) -> jax.Array:
    """Online-softmax attention in pure jnp (flash attention expressed as a
    rolled ``lax.map``/``lax.scan`` nest): O(Sq·bk) memory instead of O(Sq·Sk),
    which is what lets the 32k-prefill shapes compile without materializing
    the S² score tensor.  ``window`` may be a traced scalar (Hymba's per-layer
    global/SWA mix).  ``segment_ids`` (B, S) restricts attention to equal ids
    (packed sequences) — the same mask the flash kernel and einsum path use."""
    B, Sq, Hq, D = q.shape
    Sk, Hkv, Dv = k.shape[1], k.shape[2], v.shape[3]
    g = Hq // Hkv
    bq = min(bq, Sq)
    bk = min(bk, Sk)
    pad_q = (-Sq) % bq
    pad_k = (-Sk) % bk
    qf = q.astype(jnp.float32) * (D ** -0.5 if scale is None else scale)
    if pad_q:
        qf = jnp.pad(qf, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    if pad_k:
        kf = jnp.pad(kf, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        vf = jnp.pad(vf, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
    nq, nk = qf.shape[1] // bq, kf.shape[1] // bk
    qb = jnp.moveaxis(qf.reshape(B, nq, bq, Hkv, g, D), 1, 0)      # (nq,B,bq,Hkv,g,D)
    kb = jnp.moveaxis(kf.reshape(B, nk, bk, Hkv, D), 1, 0)
    vb = jnp.moveaxis(vf.reshape(B, nk, bk, Hkv, Dv), 1, 0)
    if segment_ids is not None:
        segf = segment_ids.astype(jnp.int32)
        # pad q/k tails with distinct ids so padded rows/cols never pair up
        qsb = jnp.moveaxis(jnp.pad(segf, ((0, 0), (0, pad_q)),
                                   constant_values=-2).reshape(B, nq, bq), 1, 0)
        ksb = jnp.moveaxis(jnp.pad(segf, ((0, 0), (0, pad_k)),
                                   constant_values=-3).reshape(B, nk, bk), 1, 0)
    else:
        qsb = jnp.zeros((nq, B, bq), jnp.int32)
        ksb = jnp.zeros((nk, B, bk), jnp.int32)

    def one_q(args):
        iq, qblk, qsblk = args                                     # qblk (B,bq,Hkv,g,D)
        qpos = iq * bq + jnp.arange(bq)

        def one_k(carry, kin):
            ik, kblk, vblk, ksblk = kin
            m, l, acc = carry
            s = jnp.einsum("bqhgd,bkhd->bhgqk", qblk, kblk)        # (B,Hkv,g,bq,bk)
            kpos = ik * bk + jnp.arange(bk)
            ok = (kpos[None, :] < Sk)
            if causal:
                ok = ok & (kpos[None, :] <= qpos[:, None])
            if window is not None:
                ok = ok & (kpos[None, :] > qpos[:, None] - window)
            okb = ok[None]                                         # (1|B,bq,bk)
            if segment_ids is not None:
                okb = okb & (qsblk[:, :, None] == ksblk[:, None, :])
            s = jnp.where(okb[:, None, None], s, -1e30)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None]) * okb[:, None, None]
            alpha = jnp.exp(m - m_new)
            l = l * alpha + jnp.sum(p, axis=-1)
            acc = acc * alpha[..., None] + jnp.einsum("bhgqk,bkhd->bhgqd", p, vblk)
            return (m_new, l, acc), None

        m0 = jnp.full((B, Hkv, g, bq), -1e30, jnp.float32)
        l0 = jnp.zeros((B, Hkv, g, bq), jnp.float32)
        a0 = jnp.zeros((B, Hkv, g, bq, Dv), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(one_k, (m0, l0, a0),
                                      (jnp.arange(nk), kb, vb, ksb))
        out = acc / jnp.maximum(l, 1e-30)[..., None]               # (B,Hkv,g,bq,D)
        return jnp.moveaxis(out, 3, 1)                             # (B,bq,Hkv,g,D)

    outs = jax.lax.map(one_q, (jnp.arange(nq), qb, qsb))           # (nq,B,bq,...)
    out = jnp.moveaxis(outs, 0, 1).reshape(B, nq * bq, Hq, Dv)
    return out[:, :Sq].astype(q.dtype)


def sdpa(q: jax.Array, k: jax.Array, v: jax.Array, bias: Optional[jax.Array],
         *, causal: bool, window=None,
         segment_ids: Optional[jax.Array] = None,
         scale: Optional[float] = None) -> jax.Array:
    """Scaled dot-product attention with GQA. q:(B,Sq,Hq,D) k:(B,Sk,Hkv,D)
    v:(B,Sk,Hkv,Dv); the softmax scale is ``scale`` (default ``D ** -0.5``).

    Dispatch: Pallas flash kernel (differentiable — training AND prefill take
    it when enabled and the shapes divide the block sizes) → chunked
    online-softmax (large S, no S² materialization) → einsum oracle.

    ``segment_ids`` (B, S) int32 restricts attention to equal ids (packed
    sequences); all three paths share the semantics bit-for-bit.  A ``bias``
    COMPOSES with the synthesized causal/window/segment mask — it no longer
    silently disables it (a caller passing both used to get un-masked
    attention).
    """
    from repro.runtime import flags
    if flags.use_flash_attention() and bias is None:
        from repro.kernels import ops
        if ops.flash_supported(q, k, causal=causal, window=window,
                               segment_ids=segment_ids, v=v):
            return ops.flash_attention(q, k, v, causal=causal, window=window,
                                       segment_ids=segment_ids, scale=scale)
    if bias is None and q.shape[1] * k.shape[1] > CHUNKED_THRESHOLD:
        return chunked_sdpa(q, k, v, causal=causal, window=window,
                            segment_ids=segment_ids, scale=scale)
    B, Sq, Hq, D = q.shape
    Sk, Hkv = k.shape[1], k.shape[2]
    g = Hq // Hkv
    qf = q.astype(jnp.float32) * (D ** -0.5 if scale is None else scale)
    qf = qf.reshape(B, Sq, Hkv, g, D)
    scores = jnp.einsum("bqhgd,bkhd->bhgqk", qf, k.astype(jnp.float32))
    if bias is not None:
        scores = scores + bias[:, None, None, :, :]
    if causal or window is not None or segment_ids is not None:
        # aligned self-attention positions (the flash path's mask semantics)
        qpos = jnp.arange(Sq)[:, None]
        kpos = jnp.arange(Sk)[None, :]
        ok = (kpos <= qpos) if causal else jnp.ones((Sq, Sk), bool)
        if window is not None:
            ok &= kpos > qpos - window
        if segment_ids is not None:
            okb = ok[None] & (segment_ids[:, :, None] == segment_ids[:, None, :])
            scores = jnp.where(okb[:, None, None], scores, -1e30)
        else:
            scores = jnp.where(ok[None, None, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", probs, v.astype(jnp.float32))
    return out.reshape(B, Sq, Hq, v.shape[3]).astype(q.dtype)


def attention_apply(cfg: ModelConfig, p: Params, x: jax.Array, positions: jax.Array,
                    *, causal: bool = True, window: Optional[int] = None,
                    segment_ids: Optional[jax.Array] = None,
                    kv_source: Optional[jax.Array] = None,
                    kv_positions: Optional[jax.Array] = None) -> jax.Array:
    """Full-sequence attention (training / prefill / encoder / cross).

    ``segment_ids`` (B, S) marks packed-document boundaries: attention stays
    within equal ids (self-attention only — cross-attention callers must not
    pass it)."""
    if segment_ids is not None and kv_source is not None:
        raise ValueError("segment_ids only apply to self-attention")
    if cfg.is_mla:
        if kv_source is not None:
            raise NotImplementedError("latent attention is self-attention")
        return mla_apply(cfg, p, x, positions, causal=causal, window=window,
                         segment_ids=segment_ids)
    B, S, d = x.shape
    hd = cfg.hd
    dt = x.dtype
    q = x @ p["wq"].astype(dt)
    src = x if kv_source is None else kv_source
    k = src @ p["wk"].astype(dt)
    v = src @ p["wv"].astype(dt)
    if "bq" in p:
        q, k, v = q + p["bq"].astype(dt), k + p["bk"].astype(dt), v + p["bv"].astype(dt)
    q = q.reshape(B, S, cfg.n_heads, hd)
    Sk = src.shape[1]
    k = k.reshape(B, Sk, cfg.n_kv_heads, hd)
    v = v.reshape(B, Sk, cfg.n_kv_heads, hd)
    kp = kv_positions if kv_positions is not None else positions
    if cfg.pos_embed == "rope" and kv_source is None:
        q = layers.apply_rope(q, positions, cfg.rope_theta)
        k = layers.apply_rope(k, kp, cfg.rope_theta)
    if kv_source is None:
        # self-attention: positions are aligned aranges at every call site, so
        # the mask is synthesized inside sdpa — never a (B, Sq, Sk) bias.
        out = sdpa(q, k, v, None, causal=causal, window=window,
                   segment_ids=segment_ids)
    else:
        out = sdpa(q, k, v, None, causal=False, window=None)  # full cross-attn
    out = out.reshape(B, S, cfg.n_heads * hd)
    return out @ p["wo"].astype(dt)


def attention_prefill(cfg: ModelConfig, p: Params, x: jax.Array, positions: jax.Array,
                      cache: Dict[str, jax.Array], *, window: Optional[int] = None,
                      segment_ids: Optional[jax.Array] = None):
    """Full-sequence causal self-attention that also writes the prompt's
    post-RoPE K/V into the ring cache — the prefill half of serving, one
    parallel forward instead of a per-token decode loop.  Only the last
    ``size`` positions are scattered (slot = pos % size is unique there), so
    ring overwrites stay deterministic.  Returns (out, new_cache).

    ``segment_ids`` carries the batched mixed-length admission mask (id -1
    on right-padded positions, so real tokens never attend into another
    request's pad tail and padded prefills stay on the flash kernel)."""
    _refuse_mla(cfg)
    B, S, d = x.shape
    hd, dt = cfg.hd, x.dtype
    q = x @ p["wq"].astype(dt)
    k = x @ p["wk"].astype(dt)
    v = x @ p["wv"].astype(dt)
    if "bq" in p:
        q, k, v = q + p["bq"].astype(dt), k + p["bk"].astype(dt), v + p["bv"].astype(dt)
    q = q.reshape(B, S, cfg.n_heads, hd)
    k = k.reshape(B, S, cfg.n_kv_heads, hd)
    v = v.reshape(B, S, cfg.n_kv_heads, hd)
    if cfg.pos_embed == "rope":
        q = layers.apply_rope(q, positions, cfg.rope_theta)
        k = layers.apply_rope(k, positions, cfg.rope_theta)
    out = sdpa(q, k, v, None, causal=True, window=window,
               segment_ids=segment_ids)
    size = cache["k"].shape[1]
    keep = min(S, size)
    slots = positions[:, S - keep:] % size
    bidx = jnp.arange(B)[:, None]
    new_cache = {
        "k": cache["k"].at[bidx, slots].set(k[:, S - keep:].astype(cache["k"].dtype)),
        "v": cache["v"].at[bidx, slots].set(v[:, S - keep:].astype(cache["v"].dtype)),
        "pos": cache["pos"].at[bidx, slots].set(positions[:, S - keep:]),
    }
    out = out.reshape(B, S, cfg.n_heads * hd)
    return out @ p["wo"].astype(dt), new_cache


# ---------------------------------------------------------------------------
# decode path (single new token against a KV cache)
# ---------------------------------------------------------------------------

def cache_init(cfg: ModelConfig, batch: int, max_len: int, *, window: Optional[int],
               dtype=None) -> Dict[str, jax.Array]:
    """Ring-buffer KV cache. For sliding-window layers the buffer is only
    ``window`` wide — this is what makes ``long_500k`` decode O(window)."""
    size = max_len if window is None else min(window, max_len)
    dt = dtype or cfg.compute_dtype
    return {
        "k": jnp.zeros((batch, size, cfg.n_kv_heads, cfg.hd), dt),
        "v": jnp.zeros((batch, size, cfg.n_kv_heads, cfg.hd), dt),
        "pos": jnp.zeros((batch, size), jnp.int32) - 1,  # -1 = invalid slot
    }


def _pool_positions(page_table: jax.Array, page_size: int):
    """(kpos, pages) of a gathered pool: logical absolute position of every
    gathered token (-1 where the logical page is unmapped) and the clamped
    physical page indices to gather."""
    n_max = page_table.shape[1]
    logical = jnp.arange(n_max * page_size, dtype=jnp.int32)[None]
    mapped = jnp.repeat(page_table >= 0, page_size, axis=1)
    return jnp.where(mapped, logical, -1), jnp.maximum(page_table, 0)


def attention_decode_paged(cfg: ModelConfig, p: Params, x: jax.Array,
                           ts: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
                           page_table: jax.Array, *,
                           window: Optional[int] = None):
    """One-token attention against a block-paged KV pool.

    x: (B, 1, d); ts: (B,) per-request absolute positions;
    k_pool/v_pool: (n_pages, page_size, Hkv, hd) shared across requests;
    page_table: (B, n_max) physical page per logical page, -1 = unmapped.
    Token j of logical page i sits at position i*page_size + j.  The new K/V
    is scattered through the table (an inactive row whose page is unmapped
    lands on the reserved trash page 0 and is never read); the scheduler
    guarantees the target page is mapped and exclusively owned
    (``PagedKVManager.ensure_writable`` — the copy-on-write boundary).
    Returns (out, k_pool, v_pool)."""
    _refuse_mla(cfg)
    B, _, d = x.shape
    hd, dt = cfg.hd, x.dtype
    ps = k_pool.shape[1]
    q = (x @ p["wq"].astype(dt)).reshape(B, 1, cfg.n_heads, hd)
    knew = x @ p["wk"].astype(dt)
    vnew = x @ p["wv"].astype(dt)
    if "bq" in p:
        q = q + p["bq"].astype(dt).reshape(1, 1, cfg.n_heads, hd)
        knew, vnew = knew + p["bk"].astype(dt), vnew + p["bv"].astype(dt)
    knew = knew.reshape(B, 1, cfg.n_kv_heads, hd)
    vnew = vnew.reshape(B, 1, cfg.n_kv_heads, hd)
    if cfg.pos_embed == "rope":
        q = layers.apply_rope(q, ts[:, None], cfg.rope_theta)
        knew = layers.apply_rope(knew, ts[:, None], cfg.rope_theta)
    pidx = jnp.take_along_axis(page_table, (ts // ps)[:, None], axis=1)[:, 0]
    pidx = jnp.maximum(pidx, 0)
    slot = ts % ps
    k_pool = k_pool.at[pidx, slot].set(knew[:, 0].astype(k_pool.dtype))
    v_pool = v_pool.at[pidx, slot].set(vnew[:, 0].astype(v_pool.dtype))
    from repro.runtime import flags
    if flags.use_flash_decode():
        from repro.kernels import ops
        out = ops.paged_decode_attention(q, k_pool.astype(dt), v_pool.astype(dt),
                                         page_table, ts=ts, window=window)
    else:
        from repro.kernels import ref
        out = ref.paged_decode_attention_reference(
            q, k_pool.astype(dt), v_pool.astype(dt), page_table, ts=ts,
            window=window)
    out = out.reshape(B, 1, cfg.n_heads * hd)
    return out @ p["wo"].astype(dt), k_pool, v_pool


def attention_prefill_paged(cfg: ModelConfig, p: Params, x: jax.Array,
                            positions: jax.Array, valid: jax.Array,
                            k_pool: jax.Array, v_pool: jax.Array,
                            page_table: jax.Array, *,
                            window: Optional[int] = None):
    """Suffix prefill against a block-paged pool: rows are right-padded
    prompt SUFFIXES (a prefix-cache hit skips re-ingesting shared pages).

    x: (B, S, d); positions: (B, S) absolute (= history length + arange);
    valid: (B, S) marks real suffix tokens (padding is routed to the trash
    page).  Suffix K/V is scattered through the page table, then queries
    attend the full gathered history + suffix; the causal mask over absolute
    positions keeps stale bytes in partially-filled tail pages invisible
    (their logical positions exceed every query position).  Returns
    (out, k_pool, v_pool)."""
    _refuse_mla(cfg)
    B, S, d = x.shape
    hd, dt = cfg.hd, x.dtype
    ps, n_max = k_pool.shape[1], page_table.shape[1]
    q = x @ p["wq"].astype(dt)
    k = x @ p["wk"].astype(dt)
    v = x @ p["wv"].astype(dt)
    if "bq" in p:
        q, k, v = q + p["bq"].astype(dt), k + p["bk"].astype(dt), v + p["bv"].astype(dt)
    q = q.reshape(B, S, cfg.n_heads, hd)
    k = k.reshape(B, S, cfg.n_kv_heads, hd)
    v = v.reshape(B, S, cfg.n_kv_heads, hd)
    if cfg.pos_embed == "rope":
        q = layers.apply_rope(q, positions, cfg.rope_theta)
        k = layers.apply_rope(k, positions, cfg.rope_theta)
    ip = jnp.minimum(positions // ps, n_max - 1)      # pad rows may run past
    pg = jnp.take_along_axis(page_table, ip, axis=1)  # the request's pages
    pg = jnp.where(valid, jnp.maximum(pg, 0), 0)
    slot = positions % ps
    k_pool = k_pool.at[pg, slot].set(k.astype(k_pool.dtype))
    v_pool = v_pool.at[pg, slot].set(v.astype(v_pool.dtype))
    kpos, pages = _pool_positions(page_table, ps)
    gk = k_pool[pages].reshape(B, n_max * ps, cfg.n_kv_heads, hd)
    gv = v_pool[pages].reshape(B, n_max * ps, cfg.n_kv_heads, hd)
    bias = _mask_bias(positions, kpos, causal=True, window=window,
                      k_valid=kpos >= 0)
    out = sdpa(q, gk.astype(dt), gv.astype(dt), bias, causal=False, window=None)
    out = out.reshape(B, S, cfg.n_heads * hd)
    return out @ p["wo"].astype(dt), k_pool, v_pool


def attention_decode(cfg: ModelConfig, p: Params, x: jax.Array, t: jax.Array,
                     cache: Dict[str, jax.Array], *, window: Optional[int] = None,
                     cross: bool = False):
    """x: (B, 1, d); t: scalar absolute position. Returns (out, new_cache)."""
    _refuse_mla(cfg)
    B, _, d = x.shape
    hd, dt = cfg.hd, x.dtype
    q = (x @ p["wq"].astype(dt)).reshape(B, 1, cfg.n_heads, hd)
    if "bq" in p:
        q = q + p["bq"].astype(dt).reshape(1, 1, cfg.n_heads, hd)
    if cfg.pos_embed == "rope":
        q = layers.apply_rope(q, jnp.full((B, 1), t, jnp.int32), cfg.rope_theta)
    if cross:
        k, v, kpos = cache["k"], cache["v"], cache["pos"]
        new_cache = cache
    else:
        knew = x @ p["wk"].astype(dt)
        vnew = x @ p["wv"].astype(dt)
        if "bk" in p:
            knew, vnew = knew + p["bk"].astype(dt), vnew + p["bv"].astype(dt)
        knew = knew.reshape(B, 1, cfg.n_kv_heads, hd)
        vnew = vnew.reshape(B, 1, cfg.n_kv_heads, hd)
        if cfg.pos_embed == "rope":
            knew = layers.apply_rope(knew, jnp.full((B, 1), t, jnp.int32), cfg.rope_theta)
        size = cache["k"].shape[1]
        slot = jnp.mod(t, size)  # ring-buffer write
        k = jax.lax.dynamic_update_slice_in_dim(cache["k"], knew.astype(cache["k"].dtype), slot, axis=1)
        v = jax.lax.dynamic_update_slice_in_dim(cache["v"], vnew.astype(cache["v"].dtype), slot, axis=1)
        kpos = jax.lax.dynamic_update_slice_in_dim(
            cache["pos"], jnp.full((B, 1), t, jnp.int32), slot, axis=1)
        new_cache = {"k": k, "v": v, "pos": kpos}
    from repro.runtime import flags
    if flags.use_flash_decode() and not cross:
        from repro.kernels import ops
        out = ops.decode_attention(q, k.astype(dt), v.astype(dt), kpos,
                                   t=t, window=window)
    else:
        valid = kpos >= 0
        if window is not None:
            valid &= kpos > t - window
        bias = _mask_bias(jnp.full((B, 1), t, jnp.int32), kpos, causal=not cross,
                          window=None, k_valid=valid)
        out = sdpa(q, k.astype(dt), v.astype(dt), bias, causal=False, window=None)
    out = out.reshape(B, 1, cfg.n_heads * hd)
    return out @ p["wo"].astype(dt), new_cache
