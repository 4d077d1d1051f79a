"""Decoder-only LM covering dense / MoE / hybrid / xLSTM / VLM families.

Homogeneous stacks (dense, moe, hybrid) use stacked layer params + ``lax.scan``
— this keeps the HLO small, makes remat policies uniform, and is exactly the
layout the pipeline-parallel runtime shards over the ``stage`` axis.
Heterogeneous stacks (xLSTM's mLSTM/sLSTM mix, DeepSeek's first dense layer)
keep those layers unstacked.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import sharding
from repro.models import layers, moe as moe_mod, ssm as ssm_mod, xlstm as xlstm_mod
from repro.models.attention import (attention_init, attention_apply,
                                    attention_decode, attention_decode_paged,
                                    attention_prefill, attention_prefill_paged,
                                    cache_init)
from repro.models.config import ModelConfig

Params = Dict[str, Any]

BIG_WINDOW = 1 << 30  # "no window" sentinel usable as a traced value


# ---------------------------------------------------------------------------
# block init/apply (one homogeneous block; the stack scans this)
# ---------------------------------------------------------------------------

def block_init(key, cfg: ModelConfig, *, kind: str) -> Params:
    k1, k2 = jax.random.split(key)
    if kind == "hymba":
        return ssm_mod.hymba_block_init(key, cfg)
    if kind == "mlstm":
        return xlstm_mod.mlstm_block_init(key, cfg)
    if kind == "slstm":
        return xlstm_mod.slstm_block_init(key, cfg)
    p: Params = {
        "norm1": layers.norm_init(cfg.norm, cfg.d_model),
        "attn": attention_init(k1, cfg),
        "norm2": layers.norm_init(cfg.norm, cfg.d_model),
    }
    if kind == "moe":
        p["moe"] = moe_mod.moe_init(k2, cfg)
    else:
        p["mlp"] = layers.mlp_init(k2, cfg.d_model, cfg.d_ff, gated=cfg.gated_mlp)
    return p


def block_apply(cfg: ModelConfig, p: Params, x: jax.Array, positions: jax.Array,
                *, kind: str, window,
                segment_ids: Optional[jax.Array] = None) -> Tuple[jax.Array, Dict]:
    """Returns (x, MoE readings: ``moe_mod.no_stats()`` for a block without
    experts)."""
    zero = moe_mod.no_stats()
    if kind in ("hymba", "mlstm", "slstm") and segment_ids is not None:
        # recurrent state mixes across the whole row — a segment mask on the
        # attention half alone would silently leak documents into each other
        raise NotImplementedError(
            f"packed-sequence training (segment_ids) is attention-only; "
            f"block kind {kind!r} carries recurrent state across documents")
    if kind == "hymba":
        return ssm_mod.hymba_block_apply(cfg, p, x, positions, window=window), zero
    if kind == "mlstm":
        return xlstm_mod.mlstm_block_apply(cfg, p, x), zero
    if kind == "slstm":
        return xlstm_mod.slstm_block_apply(cfg, p, x), zero
    h = layers.norm_apply(cfg.norm, p["norm1"], x)
    h = attention_apply(cfg, p["attn"], h, positions, causal=True, window=window,
                        segment_ids=segment_ids)
    x = x + h
    # "seq" resolves to the tp axis under sequence parallelism (Korthikanti
    # et al.): the residual/norm sections live S-sharded and XLA converts the
    # TP all-reduces into reduce-scatter + all-gather pairs around them.
    x = sharding.constrain(x, "batch", "seq", None)
    h = layers.norm_apply(cfg.norm, p["norm2"], x)
    if kind == "moe":
        mo, stats = moe_mod.moe_apply(cfg, p["moe"], h)
        return x + mo, stats
    x = x + layers.mlp_apply(p["mlp"], h, gated=cfg.gated_mlp, act=cfg.act)
    x = sharding.constrain(x, "batch", "seq", None)
    return x, zero


def block_decode(cfg: ModelConfig, p: Params, x: jax.Array, t, cache, *, kind: str, window):
    if kind == "hymba":
        return ssm_mod.hymba_block_decode(cfg, p, x, t, cache, window=window)
    if kind == "mlstm":
        return xlstm_mod.mlstm_block_decode(cfg, p, x, cache)
    if kind == "slstm":
        return xlstm_mod.slstm_block_decode(cfg, p, x, cache)
    h = layers.norm_apply(cfg.norm, p["norm1"], x)
    h, kv = attention_decode(cfg, p["attn"], h, t, cache, window=window)
    x = x + h
    h = layers.norm_apply(cfg.norm, p["norm2"], x)
    if kind == "moe":
        mo, _ = moe_mod.moe_apply(cfg, p["moe"], h)
        return x + mo, kv
    return x + layers.mlp_apply(p["mlp"], h, gated=cfg.gated_mlp, act=cfg.act), kv


def block_cache_init(cfg: ModelConfig, batch: int, max_len: int, *, kind: str, window):
    if kind == "hymba":
        return ssm_mod.hymba_cache_init(cfg, batch, max_len, window=window)
    if kind == "mlstm":
        return xlstm_mod.mlstm_state_init(cfg, batch)
    if kind == "slstm":
        return xlstm_mod.slstm_state_init(cfg, batch)
    return cache_init(cfg, batch, max_len, window=window)


# ---------------------------------------------------------------------------
# layer plan: which kinds, which are scanned/stacked
# ---------------------------------------------------------------------------

def layer_plan(cfg: ModelConfig):
    """Returns (scanned_kind | None, n_scanned, [(idx, kind) unstacked prefix]).

    Unstacked layers always come *before* the scanned stack (DeepSeek's dense
    first layer).  xLSTM is fully unstacked (mixed block kinds).
    """
    if cfg.family == "moe":
        pre = [(i, "dense") for i in range(cfg.first_k_dense)]
        return "moe", cfg.n_layers - cfg.first_k_dense, pre
    if cfg.family == "hybrid":
        return "hymba", cfg.n_layers, []
    if cfg.family == "ssm":
        kinds = ["slstm" if i in cfg.slstm_at else "mlstm" for i in range(cfg.n_layers)]
        return None, 0, list(enumerate(kinds))
    return "dense", cfg.n_layers, []


def hymba_global_layers(cfg: ModelConfig):
    return {0, cfg.n_layers // 2, cfg.n_layers - 1}


def layer_windows(cfg: ModelConfig) -> Optional[jax.Array]:
    """Per-scanned-layer attention window (traced through the scan). None if uniform."""
    if cfg.family == "hybrid" and cfg.swa_window is not None:
        g = hymba_global_layers(cfg)
        return jnp.array([BIG_WINDOW if i in g else cfg.swa_window
                          for i in range(cfg.n_layers)], jnp.int32)
    return None


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def lm_init(key, cfg: ModelConfig) -> Params:
    ke, kb, kh, kp = jax.random.split(key, 4)
    scanned_kind, n_scanned, pre = layer_plan(cfg)
    p: Params = {"embed": layers.embed_init(ke, cfg.vocab_size, cfg.d_model)}
    if cfg.pos_embed == "learned":
        p["pos_embed"] = jax.random.normal(kp, (min(cfg.max_position, 32768), cfg.d_model),
                                           jnp.float32) * 0.02
    if pre:
        p["pre_blocks"] = [block_init(jax.random.fold_in(kb, 1000 + i), cfg, kind=k)
                           for i, k in pre]
    if n_scanned:
        # one vmapped block init (bitwise equal to stacking per-layer inits):
        # a jitted init of a deep model compiles one block, not n_scanned
        keys = jax.random.split(kb, n_scanned)
        p["blocks"] = jax.vmap(
            lambda k: block_init(k, cfg, kind=scanned_kind))(keys)
    p["final_norm"] = layers.norm_init(cfg.norm, cfg.d_model)
    if not cfg.tie_embeddings:
        p["lm_head"] = layers.embed_init(kh, cfg.vocab_size, cfg.d_model)
    return p


# ---------------------------------------------------------------------------
# forward (training / prefill)
# ---------------------------------------------------------------------------

def _embed_inputs(cfg: ModelConfig, params: Params, batch: Dict[str, jax.Array]):
    dt = cfg.compute_dtype
    tokens = batch["tokens"]
    x = layers.embed_lookup(params["embed"], tokens, dt)
    if cfg.family == "vlm" and "vision_embeds" in batch:
        nv = batch["vision_embeds"].shape[1]
        x = jnp.concatenate([batch["vision_embeds"].astype(dt), x[:, nv:]], axis=1)
    if cfg.pos_embed == "learned":
        S = x.shape[1]
        x = x + params["pos_embed"][:S].astype(dt)[None]
    return x


def lm_forward(cfg: ModelConfig, params: Params, batch: Dict[str, jax.Array],
               *, remat_policy: str = "full",
               last_only: bool = False) -> Tuple[jax.Array, Dict]:
    """→ (logits fp32 (B,S,V) — or (B,1,V) when ``last_only``, which slices
    the hidden states BEFORE the unembed so the (S,V) matmul is never built —
    MoE readings summed over the layers, ``moe_mod.add_stats``)."""
    x = _embed_inputs(cfg, params, batch)
    B, S, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    # packed batches: attention stays within a document (RoPE is relative, so
    # per-document position resets are unnecessary — scores depend on i-j)
    segment_ids = batch.get("segment_ids")
    x = sharding.constrain(x, "batch", "seq", None)
    scanned_kind, n_scanned, pre = layer_plan(cfg)
    stats = moe_mod.no_stats()

    for (idx, kind), bp in zip(pre, params.get("pre_blocks", [])):
        x, a = block_apply(cfg, bp, x, positions, kind=kind, window=cfg.swa_window,
                           segment_ids=segment_ids)
        stats = moe_mod.add_stats(stats, a)

    if n_scanned:
        windows = layer_windows(cfg)
        uniform_window = cfg.swa_window

        def one_layer(carry, layer_in):
            x, stats = carry
            if windows is None:
                bp = layer_in
                w = uniform_window
            else:
                bp, w = layer_in
            x, a = block_apply(cfg, bp, x, positions, kind=scanned_kind, window=w,
                               segment_ids=segment_ids)
            return (x, moe_mod.add_stats(stats, a)), None

        body = one_layer
        if remat_policy != "none":
            policy = (jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims
                      if remat_policy == "dots"
                      else jax.checkpoint_policies.nothing_saveable)
            body = jax.checkpoint(one_layer, policy=policy, prevent_cse=False)
        xs = params["blocks"] if windows is None else (params["blocks"], windows)
        (x, stats), _ = jax.lax.scan(body, (x, stats), xs)

    if last_only:
        x = x[:, -1:]
    x = layers.norm_apply(cfg.norm, params["final_norm"], x)
    table = params.get("lm_head", params["embed"])
    logits = layers.unembed(table, x)
    logits = sharding.constrain(logits, "batch", None, "tp")  # vocab-sharded xent
    return logits, stats


def lm_loss(cfg: ModelConfig, params: Params, batch: Dict[str, jax.Array],
            *, remat_policy: str = "full") -> Tuple[jax.Array, Dict[str, jax.Array]]:
    logits, stats = lm_forward(cfg, params, batch, remat_policy=remat_policy)
    mask = batch.get("loss_mask")
    if cfg.family == "vlm" and mask is None:
        # vision positions carry no next-token loss
        S = batch["tokens"].shape[1]
        mask = (jnp.arange(S)[None] >= cfg.n_vision_tokens).astype(jnp.float32)
        mask = jnp.broadcast_to(mask, batch["tokens"].shape)
    xent = layers.cross_entropy(logits, batch["labels"], mask)
    loss = xent + cfg.moe_aux_coef * stats["aux"]
    metrics = {"xent": xent, "aux": stats["aux"]}
    if cfg.family == "moe":
        metrics.update(moe_held_tokens=stats["moe_held_tokens"],
                       moe_max_load=stats["moe_max_load"])
    return loss, metrics


def block_prefill(cfg: ModelConfig, p: Params, x: jax.Array, positions: jax.Array,
                  cache, *, kind: str, window,
                  segment_ids: Optional[jax.Array] = None):
    """``block_apply`` + ring-cache population (serving prefill).  Only the
    dense attention kind routes here; MoE (per-token capacity routing) and
    recurrent kinds use the family's decode-scan fallback."""
    assert kind == "dense", kind
    h = layers.norm_apply(cfg.norm, p["norm1"], x)
    h, cache = attention_prefill(cfg, p["attn"], h, positions, cache, window=window,
                                 segment_ids=segment_ids)
    x = x + h
    x = sharding.constrain(x, "batch", "seq", None)
    h = layers.norm_apply(cfg.norm, p["norm2"], x)
    x = x + layers.mlp_apply(p["mlp"], h, gated=cfg.gated_mlp, act=cfg.act)
    return sharding.constrain(x, "batch", "seq", None), cache


def _invalidate_padded_slots(caches, lengths: jax.Array):
    """Set ``pos = -1`` on every cache slot holding a padded position
    (``pos >= length``) so decode's validity mask skips it.  Cache ``pos``
    leaves end in (..., B, size); lengths is (B,)."""
    def fix(c):
        if isinstance(c, dict):
            if "pos" in c:
                pos = c["pos"]
                lim = lengths.reshape((1,) * (pos.ndim - 2) + (-1, 1))
                return dict(c, pos=jnp.where(pos >= lim, -1, pos))
            return {k: fix(v) for k, v in c.items()}
        if isinstance(c, (list, tuple)):
            return type(c)(fix(v) for v in c)
        return c
    return fix(caches)


def lm_prefill(cfg: ModelConfig, params: Params, batch: Dict[str, jax.Array], caches):
    """``lm_forward(last_only=True)`` that also fills the decode caches with
    the prompt's K/V: prompt ingestion becomes one parallel teacher-forced
    forward.  Returns (last-position logits ``(B, V)``, caches).

    ``batch["lengths"]`` (B,), when present, marks right-padded prompts: the
    returned logits come from position ``lengths-1`` and cache slots holding
    padded positions are invalidated (causal masking already keeps the padded
    tail from influencing positions before it).  This is what lets the
    serving scheduler bucket prompt lengths to powers of two and stop
    retracing per distinct length."""
    x = _embed_inputs(cfg, params, batch)
    B, S, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    # batched mixed-length admission: id -1 on padded positions keeps padded
    # prefills masked on every sdpa path (and the flash kernel in particular)
    segment_ids = batch.get("segment_ids")
    x = sharding.constrain(x, "batch", "seq", None)
    scanned_kind, n_scanned, pre = layer_plan(cfg)
    new_caches = dict(caches)

    if pre:
        newpre = []
        for (idx, kind), bp, c in zip(pre, params.get("pre_blocks", []), caches["pre"]):
            x, c = block_prefill(cfg, bp, x, positions, c, kind=kind,
                                 window=cfg.swa_window, segment_ids=segment_ids)
            newpre.append(c)
        new_caches["pre"] = newpre

    if n_scanned:
        def step(x, bc):
            bp, c = bc
            x, c = block_prefill(cfg, bp, x, positions, c, kind=scanned_kind,
                                 window=cfg.swa_window, segment_ids=segment_ids)
            return x, c

        x, newc = jax.lax.scan(step, x, (params["blocks"], caches["blocks"]))
        new_caches["blocks"] = newc

    lengths = batch.get("lengths")
    if lengths is None:
        x_last = x[:, -1:]
    else:
        x_last = x[jnp.arange(B), lengths - 1][:, None]
        new_caches = _invalidate_padded_slots(new_caches, lengths)
    x = layers.norm_apply(cfg.norm, params["final_norm"], x_last)
    table = params.get("lm_head", params["embed"])
    logits = layers.unembed(table, x)
    return logits[:, 0], new_caches


# ---------------------------------------------------------------------------
# block-paged KV pool (serving; see repro.session.kvpool)
# ---------------------------------------------------------------------------

def _require_paged_plan(cfg: ModelConfig):
    scanned_kind, n_scanned, pre = layer_plan(cfg)
    if scanned_kind != "dense" or pre:
        raise NotImplementedError(
            f"paged KV pool requires a pure dense attention stack; "
            f"{cfg.name} has kind={scanned_kind!r} pre={pre}")
    return n_scanned


def lm_paged_pool_init(cfg: ModelConfig, n_pages: int, page_size: int,
                       dtype=None):
    """One shared pool of KV pages for ALL requests: leaves are
    (L, n_pages, page_size, Hkv, hd).  Sliding-window configs keep full
    pools (the window mask is applied at attention time; page reclamation
    past the window is a follow-up)."""
    L = _require_paged_plan(cfg)
    dt = dtype or cfg.compute_dtype
    shape = (L, n_pages, page_size, cfg.n_kv_heads, cfg.hd)
    return {"blocks": {"k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt)}}


def block_decode_paged(cfg: ModelConfig, p: Params, x, ts, pk, pv, page_table,
                       layer, *, window):
    h = layers.norm_apply(cfg.norm, p["norm1"], x)
    h, pk, pv = attention_decode_paged(cfg, p["attn"], h, ts, pk, pv,
                                       page_table, layer, window=window)
    x = x + h
    h = layers.norm_apply(cfg.norm, p["norm2"], x)
    x = x + layers.mlp_apply(p["mlp"], h, gated=cfg.gated_mlp, act=cfg.act)
    return x, pk, pv


def block_prefill_paged(cfg: ModelConfig, p: Params, x, positions, valid,
                        pk, pv, page_table, *, window):
    h = layers.norm_apply(cfg.norm, p["norm1"], x)
    h, pk, pv = attention_prefill_paged(cfg, p["attn"], h, positions, valid,
                                        pk, pv, page_table, window=window)
    x = x + h
    x = sharding.constrain(x, "batch", "seq", None)
    h = layers.norm_apply(cfg.norm, p["norm2"], x)
    x = x + layers.mlp_apply(p["mlp"], h, gated=cfg.gated_mlp, act=cfg.act)
    return sharding.constrain(x, "batch", "seq", None), pk, pv


def lm_paged_decode_step(cfg: ModelConfig, params: Params, token: jax.Array,
                         ts: jax.Array, pool, page_tables):
    """One decode step where every batch row reads/writes KV through its OWN
    page-table row at its OWN position.  token/ts: (B,);
    page_tables: (B, n_max) int32.  → (logits (B, V), pool).

    The stacked pool rides the layer scan as carry: each layer writes its
    new K/V rows and reads its pages in place by layer index, so no layer's
    pool is sliced out or restacked."""
    L = _require_paged_plan(cfg)
    dt = cfg.compute_dtype
    x = layers.embed_lookup(params["embed"], token[:, None], dt)
    if cfg.pos_embed == "learned":
        maxp = params["pos_embed"].shape[0]
        x = x + params["pos_embed"][jnp.minimum(ts, maxp - 1)].astype(dt)[:, None]

    def step(carry, layer_in):
        x, pk, pv = carry
        bp, layer = layer_in
        x, pk, pv = block_decode_paged(cfg, bp, x, ts, pk, pv, page_tables,
                                       layer, window=cfg.swa_window)
        return (x, pk, pv), None

    (x, nk, nv), _ = jax.lax.scan(
        step, (x, pool["blocks"]["k"], pool["blocks"]["v"]),
        (params["blocks"], jnp.arange(L, dtype=jnp.int32)))
    x = layers.norm_apply(cfg.norm, params["final_norm"], x)
    table = params.get("lm_head", params["embed"])
    logits = layers.unembed(table, x)[:, 0]
    return logits, {"blocks": {"k": nk, "v": nv}}


def lm_paged_prefill(cfg: ModelConfig, params: Params,
                     batch: Dict[str, jax.Array], pool, page_tables):
    """Suffix prefill into the paged pool.

    ``batch``: ``tokens`` (B, S) right-padded prompt SUFFIXES,
    ``hist_lens`` (B,) tokens already in the pool via shared prefix pages
    (re-ingestion skipped), ``lengths`` (B,) valid suffix lengths (≥ 1 — the
    scheduler caps sharing at prompt-1 so the first-token logits always have
    a position to come from).  Returns (logits at the last valid suffix
    position (B, V), pool)."""
    _require_paged_plan(cfg)
    tokens = batch["tokens"]
    hist = batch["hist_lens"]
    lengths = batch["lengths"]
    B, S = tokens.shape
    dt = cfg.compute_dtype
    positions = hist[:, None] + jnp.arange(S, dtype=jnp.int32)[None]
    valid = jnp.arange(S, dtype=jnp.int32)[None] < lengths[:, None]
    x = layers.embed_lookup(params["embed"], tokens, dt)
    if cfg.pos_embed == "learned":
        maxp = params["pos_embed"].shape[0]
        x = x + params["pos_embed"][jnp.minimum(positions, maxp - 1)].astype(dt)

    def step(x, layer_in):
        bp, pk, pv = layer_in
        x, pk, pv = block_prefill_paged(cfg, bp, x, positions, valid, pk, pv,
                                        page_tables, window=cfg.swa_window)
        return x, (pk, pv)

    x, (nk, nv) = jax.lax.scan(
        step, x, (params["blocks"], pool["blocks"]["k"], pool["blocks"]["v"]))
    x_last = x[jnp.arange(B), lengths - 1][:, None]
    x_last = layers.norm_apply(cfg.norm, params["final_norm"], x_last)
    table = params.get("lm_head", params["embed"])
    logits = layers.unembed(table, x_last)
    return logits[:, 0], {"blocks": {"k": nk, "v": nv}}


# ---------------------------------------------------------------------------
# decode (one token against caches)
# ---------------------------------------------------------------------------

def lm_cache_init(cfg: ModelConfig, batch: int, max_len: int):
    scanned_kind, n_scanned, pre = layer_plan(cfg)
    windows = layer_windows(cfg)
    caches: Dict[str, Any] = {}
    if pre:
        caches["pre"] = [block_cache_init(cfg, batch, max_len, kind=k,
                                          window=cfg.swa_window)
                         for _, k in pre]
    if n_scanned:
        if windows is None:
            one = lambda i: block_cache_init(cfg, batch, max_len, kind=scanned_kind,
                                             window=cfg.swa_window)
        else:
            g = hymba_global_layers(cfg)
            one = lambda i: block_cache_init(cfg, batch, max_len, kind=scanned_kind,
                                             window=None if i in g else cfg.swa_window)
        # Hymba global vs SWA layers have different KV buffer sizes → can't stack.
        if windows is None:
            stack = [one(i) for i in range(n_scanned)]
            caches["blocks"] = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *stack)
        else:
            caches["hymba"] = [one(i) for i in range(n_scanned)]
    return caches


def lm_decode_step(cfg: ModelConfig, params: Params, token: jax.Array, t: jax.Array,
                   caches) -> Tuple[jax.Array, Any]:
    """token: (B,) int32; t: scalar int32 position. → (logits (B,V), caches)."""
    dt = cfg.compute_dtype
    x = layers.embed_lookup(params["embed"], token[:, None], dt)
    if cfg.pos_embed == "learned":
        maxp = params["pos_embed"].shape[0]
        x = x + params["pos_embed"][jnp.minimum(t, maxp - 1)].astype(dt)[None, None]
    scanned_kind, n_scanned, pre = layer_plan(cfg)
    new_caches = dict(caches)

    if pre:
        newpre = []
        for (idx, kind), bp, c in zip(pre, params.get("pre_blocks", []), caches["pre"]):
            x, c = block_decode(cfg, bp, x, t, c, kind=kind, window=cfg.swa_window)
            newpre.append(c)
        new_caches["pre"] = newpre

    if n_scanned:
        if "hymba" in caches:
            g = hymba_global_layers(cfg)
            newc = []
            for i in range(n_scanned):
                bp = jax.tree_util.tree_map(lambda a: a[i], params["blocks"])
                w = None if i in g else cfg.swa_window
                x, c = block_decode(cfg, bp, x, t, caches["hymba"][i],
                                    kind=scanned_kind, window=w)
                newc.append(c)
            new_caches["hymba"] = newc
        else:
            def step(x, bc):
                bp, c = bc
                x, c = block_decode(cfg, bp, x, t, c, kind=scanned_kind,
                                    window=cfg.swa_window)
                return x, c
            x, newc = jax.lax.scan(step, x, (params["blocks"], caches["blocks"]))
            new_caches["blocks"] = newc

    x = layers.norm_apply(cfg.norm, params["final_norm"], x)
    table = params.get("lm_head", params["embed"])
    logits = layers.unembed(table, x)[:, 0]
    return logits, new_caches


# ---------------------------------------------------------------------------
# family registrations — the decoder-only backbone serves every family whose
# stack is a (possibly heterogeneous) scan of blocks; ``layer_plan`` picks
# the block kinds (attention / moe / mamba / mLSTM / sLSTM) per family.
# ---------------------------------------------------------------------------

from repro.models.registry import ModelFamily, register_family  # noqa: E402


class DecoderOnlyLM(ModelFamily):
    """Token-in / logits-out decoder stack (dense backbone)."""

    def init_params(self, cfg, key):
        return lm_init(key, cfg)

    def loss(self, cfg, params, batch, *, remat_policy="full"):
        return lm_loss(cfg, params, batch, remat_policy=remat_policy)

    def forward(self, cfg, params, batch, *, remat_policy="none", last_only=False):
        logits, _ = lm_forward(cfg, params, batch, remat_policy=remat_policy,
                               last_only=last_only)
        return logits

    def init_cache(self, cfg, params, batch_size, max_len, batch=None):
        return lm_cache_init(cfg, batch_size, max_len)

    def decode_step(self, cfg, params, token, t, caches):
        return lm_decode_step(cfg, params, token, t, caches)

    def prefill_cache(self, cfg, params, batch, caches):
        # Parallel prefill only for pure-attention stacks.  MoE routes per
        # token under capacity limits, so a full-sequence forward drops
        # different tokens than step-by-step decode; recurrent/hybrid kinds
        # have state caches a forward pass never materializes.  Those use the
        # decode-scan fallback (exact decode semantics, one compile).
        if self.supports_padded_prefill(cfg):
            return lm_prefill(cfg, params, batch, caches)
        return super().prefill_cache(cfg, params, batch, caches)

    def supports_padded_prefill(self, cfg):
        # exactly the stacks routed to the parallel (causal-attention)
        # prefill above — the decode-scan fallback ignores batch["lengths"]
        # and would feed pad tokens into state caches
        scanned_kind, _, pre = layer_plan(cfg)
        return scanned_kind == "dense" and all(k == "dense" for _, k in pre)

    def cache_slot_axes(self, cfg, caches):
        axes: Dict[str, Any] = {}
        if "pre" in caches:
            axes["pre"] = jax.tree_util.tree_map(lambda _: 0, caches["pre"])
        if "blocks" in caches:   # stacked (L, B, ...) — slot axis after layers
            axes["blocks"] = jax.tree_util.tree_map(lambda _: 1, caches["blocks"])
        if "hymba" in caches:
            axes["hymba"] = jax.tree_util.tree_map(lambda _: 0, caches["hymba"])
        return axes

    # --- block-paged KV pool (see repro.session.kvpool) ----------------
    def supports_paged_cache(self, cfg):
        # positional K/V lists only: exactly the pure-attention stacks.
        # Recurrent/state families (SSM, hybrid) keep contiguous slot
        # caches — their state is not a list of per-position entries, so a
        # page table has nothing to index; the scheduler gates on this.
        return self.supports_padded_prefill(cfg)

    def init_paged_pool(self, cfg, params, n_pages, page_size):
        return lm_paged_pool_init(cfg, n_pages, page_size)

    def paged_decode_step(self, cfg, params, token, ts, pool, page_tables):
        return lm_paged_decode_step(cfg, params, token, ts, pool, page_tables)

    def paged_prefill(self, cfg, params, batch, pool, page_tables):
        return lm_paged_prefill(cfg, params, batch, pool, page_tables)


class MoELM(DecoderOnlyLM):
    """Routed-FFN variant; routing/EP live in ``repro.models.moe`` blocks."""

    def param_sharding_hints(self, cfg):
        # The expert (E, d, ff) stacks carry an explicit "expert" axis; the
        # router stays replicated so every rank routes identically.  These
        # hints are load-bearing: without them the generic MLP rules would
        # match w_gate/w_up/w_out and mis-shard the expert dim.
        return (
            (r"moe.*\brouter\b$", ("embed", None)),
            (r"moe.*\b(w_gate|w_up)\b$", ("expert", "embed", "tp")),
            (r"moe.*\bw_out\b$", ("expert", "tp", "embed")),
        )


# SSD/mLSTM scan params: per-head decay/skip/dt vectors are tiny and enter
# the selective-scan recurrence elementwise — pinned replicated so no rule
# below them ever tries to split the head dim across tp.
_SSM_SCAN_HINTS = (
    (r"\b(A_log|D|dt_bias)\b$", (None,)),
    (r"\bbc_proj\b$", ("embed", None)),       # B/C/dt projection: state dim whole
    (r"\bconv\b$", (None, "tp")),             # depthwise conv: channels on tp
)


class SSMLM(DecoderOnlyLM):
    """xLSTM stack (mLSTM scan + unstacked sLSTM blocks, see ``xlstm.py``)."""

    def param_sharding_hints(self, cfg):
        return _SSM_SCAN_HINTS


class HybridLM(DecoderOnlyLM):
    """Hymba-style attention+mamba hybrid (``ssm.py`` blocks)."""

    def param_sharding_hints(self, cfg):
        return _SSM_SCAN_HINTS


class VLM(DecoderOnlyLM):
    """LM backbone over concatenated [vision_embeds; tokens] inputs."""

    def supports_paged_cache(self, cfg):
        # the paged suffix prefill is token-only; vision embeddings occupy
        # the leading positions and would be re-embedded as tokens
        return False

    def extra_input_specs(self, cfg, batch_size):
        return {"vision_embeds": jax.ShapeDtypeStruct(
            (batch_size, cfg.n_vision_tokens, cfg.d_model), jnp.float32)}


register_family("transformer", "dense")(DecoderOnlyLM())
register_family("moe")(MoELM())
register_family("ssm")(SSMLM())
register_family("hybrid")(HybridLM())
register_family("vlm")(VLM())
