"""Pipeline parallelism in pjit-land (the TPU-native analogue of
Megatron/DeepSpeed 1F1B over InfiniBand P2P), with the interleaved
virtual-stage schedule (Megatron-LM, arXiv 2104.04473) as a first-class,
configurable object: ``plan.vpp`` chunks per physical stage.

Layout: block params are stacked ``(PP, L/PP, ...)`` for ``vpp=1`` and
``(VPP, PP, L/(PP·VPP), ...)`` for ``vpp>1``, with the stage axis sharded
over the ``pp`` mesh axis (the VPP chunk axis is never sharded — chunks
co-reside on their stage's devices).  The live activation buffer is
``(PP, mbs, S, d)`` with the stage axis sharded the same way.  Each
superstep vmaps the per-stage layer scan and rotates the buffer one stage
forward — XLA lowers the rotation of a stage-sharded axis to a
collective-permute ring, i.e. the P2P stage transfer.

Interleaved rotation: chunk ``c = v·PP + p`` lives on stage ``p``; a
micro-batch loops the stage ring VPP times (chunk c → chunk c+1 is always
one hop to the next stage, wrapping PP-1 → 0).  Micro-batches flow in
rounds of PP (hence ``gas % pp == 0`` for ``vpp>1``): hop ``c`` of
micro-batch ``m = q·PP + r`` runs at superstep

    t(m, c) = q·PP·VPP + (c // PP)·PP + r + (c % PP)

so at superstep ``i`` stage ``p`` processes ``j = i - p`` decomposed as
``q = j // (PP·VPP)``, ``v = (j % (PP·VPP)) // PP``, ``r = j % PP``.
A fresh micro-batch is injected into stage 0 exactly when the wrapped
activation from stage PP-1 has just finished the LAST chunk (its loss is
banked the same superstep), so the shift register never grows.

Bubble structure is explicit: the scan runs ``VPP·GAS + PP - 1`` supersteps
of one chunk (1/VPP of a stage) each, so the compiled HLO contains exactly
the ``(PP-1)/(VPP·GAS+PP-1)`` idle fraction of the interleaved schedule —
``vpp=1`` reproduces the plain ``(PP-1)/(GAS+PP-1)`` schedule (and layout)
bit-for-bit; the dry-run roofline sees the bubble as "wasted" FLOPs.

The backward pass is jax.grad through the scan; XLA schedules the
transposed collective-permutes against compute, which reproduces 1F1B's
overlap behaviour without a hand-written schedule.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import sharding
from repro.core.recipe import ParallelismConfig
from repro.models import layers as L
from repro.models import transformer as T
from repro.models.config import ModelConfig

Params = Dict[str, Any]


def stack_for_pipeline(block_params, pp: int, vpp: int = 1):
    """(L, ...) stacked block params → (PP, L/PP, ...) for ``vpp=1`` or
    (VPP, PP, L/(PP·VPP), ...) for ``vpp>1``.

    Chunk ``c = v·PP + p`` (contiguous layers ``[c·Lc, (c+1)·Lc)``) lands at
    ``[v, p]`` — a plain row-major reshape, so ``vpp=1`` keeps the historic
    2-axis layout (checkpoints stay canonical-unstacked either way)."""
    def re(x):
        l = x.shape[0]
        assert l % (pp * vpp) == 0, \
            f"layers {l} not divisible by pp*vpp={pp}*{vpp}"
        if vpp == 1:
            return x.reshape(pp, l // pp, *x.shape[1:])
        return x.reshape(vpp, pp, l // (pp * vpp), *x.shape[1:])
    return jax.tree_util.tree_map(re, block_params)


def unstack_from_pipeline(block_params, vpp: int = 1):
    """Inverse of :func:`stack_for_pipeline` (collapse the stacking axes)."""
    lead = 3 if vpp > 1 else 2
    def re(x):
        n = 1
        for s in x.shape[:lead]:
            n *= s
        return x.reshape(n, *x.shape[lead:])
    return jax.tree_util.tree_map(re, block_params)


def pipeline_loss(cfg: ModelConfig, params: Params, batch: Dict[str, jax.Array],
                  plan: ParallelismConfig) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Pipelined training loss under the (interleaved) 1F1B superstep scan.

    ``params['blocks']`` leaves are (PP, L/PP, ...) for ``plan.vpp == 1`` and
    (VPP, PP, L/(PP·VPP), ...) for ``plan.vpp > 1``.

    Supported for homogeneous (scan-compatible) stacks: dense / moe / hybrid.
    """
    pp, gas, vpp = plan.pp, plan.gas, plan.vpp
    plan.validate(cfg.n_layers)
    scanned_kind, n_scanned, pre = T.layer_plan(cfg)
    assert n_scanned, f"{cfg.name}: pipeline needs a scanned stack"
    tokens = batch["tokens"]
    Bg, S = tokens.shape
    assert Bg % gas == 0, f"batch {Bg} not divisible by gas={gas}"
    mbs_g = Bg // gas
    dt = cfg.compute_dtype
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (mbs_g, S))

    tok_mb = tokens.reshape(gas, mbs_g, S)
    lab_mb = batch["labels"].reshape(gas, mbs_g, S)
    mask_mb = None
    if batch.get("loss_mask") is not None:
        mask_mb = batch["loss_mask"].reshape(gas, mbs_g, S)
    # packed batches: segment ids are INPUTS, not activations, so they never
    # ride the stage shift register — stage s at superstep i just re-indexes
    # its scheduled micro-batch out of seg_mb below
    seg_mb = None
    if batch.get("segment_ids") is not None:
        seg_mb = batch["segment_ids"].reshape(gas, mbs_g, S)
    vis = batch.get("vision_embeds")

    windows = T.layer_windows(cfg)
    if windows is None:
        win_stages = None
    elif vpp == 1:
        win_stages = windows.reshape(pp, -1)
    else:
        win_stages = windows.reshape(vpp, pp, -1)

    ring = pp * vpp                      # hops per loop × loops = chunk count

    def schedule(j):
        """Superstep-local schedule index ``j = i - p`` → (micro-batch m,
        chunk row v, validity).  Micro-batches flow in rounds of PP."""
        q, rem = j // ring, j % ring
        v = rem // pp
        m = q * pp + rem % pp
        valid = (j >= 0) & (j < gas * vpp)
        return jnp.clip(m, 0, gas - 1), v, valid

    # ---- per-stage computation (vmapped over the stage axis) ----
    def chunk_scan(stage_blocks, win_stage, x, seg):
        def one_layer(carry, layer_in):
            x, aux = carry
            bp = layer_in if win_stage is None else layer_in[0]
            w = cfg.swa_window if win_stage is None else layer_in[1]
            x, a = T.block_apply(cfg, bp, x, positions, kind=scanned_kind, window=w,
                                 segment_ids=seg)
            return (x, aux + a["aux"]), None
        body = one_layer
        if plan.remat_policy != "none":
            pol = (jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims
                   if plan.remat_policy == "dots"
                   else jax.checkpoint_policies.nothing_saveable)
            body = jax.checkpoint(one_layer, policy=pol, prevent_cse=False)
        xs = stage_blocks if win_stage is None else (stage_blocks, win_stage)
        (x, aux), _ = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)), xs)
        return x, aux

    if vpp == 1:
        stage_apply = lambda blocks, wins, v, x, seg: chunk_scan(blocks, wins, x, seg)
    else:
        def stage_apply(chunks, wins, v, x, seg):
            # each physical stage dynamically selects the chunk the schedule
            # assigns it this superstep out of its (VPP, Lc, ...) stack
            blocks = jax.tree_util.tree_map(
                lambda a: jax.lax.dynamic_index_in_dim(a, v, axis=0,
                                                       keepdims=False), chunks)
            win = None if wins is None else jax.lax.dynamic_index_in_dim(
                wins, v, axis=0, keepdims=False)
            return chunk_scan(blocks, win, x, seg)

    if plan.remat_policy == "stage":
        # nested remat: stash ONE activation per (stage, superstep) instead of
        # one per (layer, superstep) — backward recomputes the chunk forward,
        # re-checkpointing per layer, so the transient is a single chunk's
        # layer stash.  Cuts the pipeline's remat memory by layers/chunk ×.
        stage_apply = jax.checkpoint(
            stage_apply, policy=jax.checkpoint_policies.nothing_saveable,
            prevent_cse=False, static_argnums=())
    seg_axis = None if seg_mb is None else 0
    # vmap over the PHYSICAL stage axis: axis 0 of (PP, L/PP, ...) stacks,
    # axis 1 of (VPP, PP, Lc, ...) interleaved stacks; per-stage chunk row v
    blocks_axis = 0 if vpp == 1 else 1
    win_axis = None if win_stages is None else blocks_axis
    # on a mesh the stage axis lives on "pp": say so, so a per-shard kernel
    # (shard_map) inside a stage runs on its own stage's devices only
    rules = sharding.active_rules()
    spmd = ("pp" if rules is not None and rules.mesh.shape.get("pp", 1) > 1
            else None)
    vstage = jax.vmap(stage_apply,
                      in_axes=(blocks_axis, win_axis, 0, 0, seg_axis),
                      spmd_axis_name=spmd)

    def embed_mb(tok, seg):
        x = L.embed_lookup(params["embed"], tok, dt)
        if cfg.family == "vlm" and vis is not None:
            nv = vis.shape[1]
            x = jnp.concatenate([vis.astype(dt), x[:, nv:]], axis=1)
        if cfg.pos_embed == "learned":
            x = x + params["pos_embed"][:S].astype(dt)[None]
        for (idx, kind), bp in zip(pre, params.get("pre_blocks", [])):
            x, _ = T.block_apply(cfg, bp, x, positions, kind=kind,
                                 window=cfg.swa_window, segment_ids=seg)
        return x

    def loss_mb(x, lab, mask):
        x = L.norm_apply(cfg.norm, params["final_norm"], x)
        logits = L.unembed(params.get("lm_head", params["embed"]), x)
        logits = sharding.constrain(logits, "batch", None, "tp")  # vocab-sharded xent
        logz = jax.nn.logsumexp(logits, axis=-1)
        nll = logz - L.gold_logit(logits, lab)
        if mask is not None:
            return jnp.sum(nll * mask), jnp.sum(mask)
        return jnp.sum(nll), jnp.asarray(nll.size, jnp.float32)

    state0 = jnp.zeros((pp, mbs_g, S, cfg.d_model), dt)
    state0 = sharding.constrain(state0, "stage", "batch", "seq", None)
    stage_ids = jnp.arange(pp)

    def superstep(carry, i):
        state, loss_sum, denom, aux_sum = carry
        mb_idx, v_idx, valid = schedule(i - stage_ids)       # (pp,) each
        seg_state = None
        if seg_mb is not None:
            # clipped indices feed stages whose output the valid mask below
            # discards anyway
            seg_state = jnp.take(seg_mb, mb_idx, axis=0)
        x_out, aux = vstage(params["blocks"], win_stages, v_idx, state, seg_state)
        x_out = sharding.constrain(x_out, "stage", "batch", "seq", None)
        aux_sum = aux_sum + jnp.sum(jnp.where(valid, aux, 0.0))
        # last stage: its micro-batch exits the model when it just ran the
        # LAST chunk row (always, for vpp=1) — bank its loss
        lsum, lden = loss_mb(x_out[-1],
                             jax.lax.dynamic_index_in_dim(lab_mb, mb_idx[-1], keepdims=False),
                             None if mask_mb is None else
                             jax.lax.dynamic_index_in_dim(mask_mb, mb_idx[-1], keepdims=False))
        lvalid = (valid[-1] & (v_idx[-1] == vpp - 1)).astype(jnp.float32)
        loss_sum = loss_sum + lvalid * lsum
        denom = denom + lvalid * lden
        # rotate: stage s output → stage s+1 input; the wrap PP-1 → 0 is the
        # chunk loop-around (vpp>1) or a finished micro-batch (replaced below)
        shifted = jnp.roll(x_out, 1, axis=0)
        # inject the next micro-batch into stage 0 exactly when its schedule
        # row restarts at chunk 0 (every superstep for vpp=1)
        m_nxt, v_nxt, _ = schedule(jnp.asarray(i + 1))
        x_in = embed_mb(
            jax.lax.dynamic_index_in_dim(tok_mb, m_nxt, keepdims=False),
            None if seg_mb is None else
            jax.lax.dynamic_index_in_dim(seg_mb, m_nxt, keepdims=False))
        x_in = x_in.astype(dt)
        if vpp > 1:
            x_in = jnp.where(v_nxt == 0, x_in, shifted[0])
        state = shifted.at[0].set(x_in)
        state = sharding.constrain(state, "stage", "batch", "seq", None)
        return (state, loss_sum, denom, aux_sum), None

    # prologue: micro-batch 0 enters stage 0 (chunk 0) before superstep 0
    state0 = state0.at[0].set(
        embed_mb(tok_mb[0], None if seg_mb is None else seg_mb[0]))
    carry = (state0, jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32),
             jnp.zeros((), jnp.float32))
    (state, loss_sum, denom, aux_sum), _ = jax.lax.scan(
        superstep, carry, jnp.arange(vpp * gas + pp - 1))

    xent = loss_sum / jnp.maximum(denom, 1.0)
    aux = aux_sum / gas
    loss = xent + cfg.moe_aux_coef * aux
    return loss, {"xent": xent, "aux": aux}
