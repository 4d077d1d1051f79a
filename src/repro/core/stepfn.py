"""Step-function factory: assembles model, recipe (TP/PP/ZeRO), optimizer and
compression into the jit-able ``train_step`` / ``serve_step`` the launcher,
dry-run, and benchmarks all share."""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import pipeline as pp_mod
from repro.core import sharding as shd
from repro.core import zero
from repro.models.moe import moe_groups
from repro.core.recipe import ParallelismConfig, axis_mapping
from repro.models import api as model_api
from repro.models.config import ModelConfig
from repro.optim import adamw, schedule
from repro.optim.compress import apply_compression, init_error_feedback
from repro.runtime.resilience import ResilienceConfig


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    peak_lr: float = 3e-4
    warmup: int = 200
    total_steps: int = 10000
    adam: adamw.AdamWConfig = dataclasses.field(default_factory=adamw.AdamWConfig)
    compression: Optional[str] = None      # None | bf16 | int8_ef
    resilience: ResilienceConfig = dataclasses.field(
        default_factory=ResilienceConfig)


def _micro_bits(bad) -> jax.Array:
    """(n,) bool mask → float bitmask (exact in fp32 for n ≤ 24) — the
    anomaly-forensics breadcrumb: the loop decodes which micro-batches of a
    skipped step went bad without shipping a vector through the metrics."""
    n = bad.shape[0]
    if n > 24:
        return jnp.float32(0.0)
    return jnp.sum(bad.astype(jnp.float32)
                   * (2.0 ** jnp.arange(n, dtype=jnp.float32)))


def init_rstat() -> Dict[str, jax.Array]:
    """Resilience stats carried in the train state (so they checkpoint,
    reshard, and roll back with everything else): EMA/variance of accepted
    grad-norms, accepted-step count, and the LR re-warm countdown."""
    return {"ema": jnp.zeros((), jnp.float32),
            "var": jnp.zeros((), jnp.float32),
            "n": jnp.zeros((), jnp.int32),
            "rewarm": jnp.zeros((), jnp.int32)}


def init_state(cfg: ModelConfig, plan: ParallelismConfig, key,
               train_cfg: TrainConfig = TrainConfig()) -> Dict[str, Any]:
    params = model_api.init_params(cfg, key)
    if plan.pp > 1 and "blocks" in params:
        params["blocks"] = pp_mod.stack_for_pipeline(params["blocks"], plan.pp,
                                                     plan.vpp)
    state = {"params": params, "opt": adamw.init_opt_state(params),
             "step": jnp.zeros((), jnp.int32), "rstat": init_rstat()}
    if train_cfg.compression == "int8_ef":
        state["ef"] = init_error_feedback(params)
    return state


def state_shardings(cfg: ModelConfig, state, mesh: Mesh, plan: ParallelismConfig):
    """NamedSharding tree mirroring a train state (params + ZeRO opt + step)."""
    p_sh = zero.param_shardings(cfg, state["params"], mesh, plan)
    o_sh = {
        "m": zero.opt_shardings(p_sh, state["params"], mesh, plan),
        "v": zero.opt_shardings(p_sh, state["params"], mesh, plan),
        "step": NamedSharding(mesh, P()),
    }
    out = {"params": p_sh, "opt": o_sh,
           "step": NamedSharding(mesh, P())}
    if "rstat" in state:
        out["rstat"] = jax.tree_util.tree_map(
            lambda _: NamedSharding(mesh, P()), state["rstat"])
    if "ef" in state:
        out["ef"] = zero.opt_shardings(p_sh, state["params"], mesh, plan)
    return out


def batch_shardings(batch_spec, mesh: Mesh):
    """Batch arrays are sharded over the (pod, data) axes on dim 0, falling
    back to fewer axes when the global batch does not divide (e.g. batch 32
    on a 2×32 pod×data world)."""
    import numpy as np
    axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)

    def one(x):
        use = axes
        while use:
            ways = int(np.prod([mesh.shape[a] for a in use]))
            if x.shape[0] % ways == 0 and x.shape[0] >= ways:
                break
            use = use[1:]  # drop the pod axis first
        ax = use if len(use) > 1 else (use[0] if use else None)
        return NamedSharding(mesh, P(ax, *([None] * (len(x.shape) - 1))))

    return jax.tree_util.tree_map(one, batch_spec)


def make_train_step(cfg: ModelConfig, plan: ParallelismConfig,
                    train_cfg: TrainConfig = TrainConfig(),
                    mesh: Optional[Mesh] = None):
    """Returns train_step(state, batch) → (state, metrics)."""
    mapping = axis_mapping(plan)

    def loss_fn(params, batch):
        # under value_and_grad the forward's ops lower as ``jvp(forward)``
        # and the backward's, remat's recompute included, as
        # ``transpose(jvp(forward))``: the phases apart by name stack
        with jax.named_scope("forward"):
            if plan.gather_params_once and mesh is not None:
                # ZeRO-3 + pipeline: one bf16 cast + all-gather of the fp32
                # masters up front; the superstep scan then reuses the gathered
                # copy instead of re-gathering every iteration.  The cast's
                # transpose delivers bf16 gradient accumulation (Table 1's 2 B
                # gradients).
                dtp = cfg.compute_dtype
                nofsdp = dataclasses.replace(plan, zero_stage=min(plan.zero_stage, 1))
                g_sh = zero.param_shardings(cfg, params, mesh, nofsdp)
                params = jax.tree_util.tree_map(
                    lambda x, s: jax.lax.with_sharding_constraint(
                        x.astype(dtp) if x.dtype == jnp.float32 else x, s),
                    params, g_sh)
            if plan.pp > 1:
                return pp_mod.pipeline_loss(cfg, params, batch, plan)
            return model_api.loss_fn(cfg, params, batch, remat_policy=plan.remat_policy)

    n_groups = plan.dp * plan.pods if mesh is not None else 1
    rs = train_cfg.resilience

    # --- skip consensus: how many data-parallel replica groups vote -------
    # ``consensus_replicas`` forces a simulated fleet on any device count
    # (tests, chaos drills); otherwise the replica axis is the real dp·pods
    # extent of the mesh.  The voted path needs per-replica gradient
    # contributions, which the pipeline schedule folds away — pp>1 keeps the
    # single global verdict (identical on every replica under GSPMD anyway).
    n_rep = 1
    if rs.enabled and rs.consensus and plan.pp == 1:
        n_rep = rs.consensus_replicas or (plan.dp * plan.pods
                                          if mesh is not None else 1)

    def grads_and_metrics(params, batch, chaos_scale=None, rstat=None):
        """(loss, metrics, grads, anomaly-aux), honoring ``plan.gas`` on the
        pp=1 path.

        The pipeline folds GAS into its superstep schedule
        (``pipeline_loss``); without a pipeline we scan over micro-batches
        and accumulate gradients in the compute dtype (the paper's Table-1
        "2 B" bf16 gradient buffer), so ``RecipeAdvisor.suggest``'s
        ``min_gas=8`` plans train the effective batch they claim instead of
        silently collapsing to one big micro-batch.

        Anomaly signals ride along at zero extra sync cost: each path also
        returns ``aux = {"usable", "nonfinite_micros"}``.  On the GAS path a
        non-finite micro-batch is masked out of the accumulation (and the
        micro weights renormalized over the survivors) instead of poisoning
        the whole step; ``usable`` goes False only when every micro-batch is
        bad.  ``chaos_scale`` is the fault-injection harness' per-micro
        gradient multiplier (``runtime.chaos.FaultPlan``).

        With ``n_rep > 1`` the consensus path takes over: per-replica
        verdicts voted across the dp axis (``rstat`` supplies the shared
        z-gate baseline)."""
        if n_rep > 1:
            return consensus_grads(params, batch, chaos_scale, rstat)
        if plan.pp > 1 or plan.gas <= 1:
            (loss, metrics), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, batch)
            if chaos_scale is not None:
                s = jnp.prod(chaos_scale.astype(jnp.float32))
                grads = jax.tree_util.tree_map(
                    lambda g: (g * s).astype(g.dtype), grads)
            usable = jnp.isfinite(adamw.global_norm(grads))
            aux = {"usable": usable,
                   "nonfinite_micros": (~usable).astype(jnp.int32),
                   "bad_replicas": jnp.zeros((), jnp.int32),
                   "bad_micro_bits": (~usable).astype(jnp.float32)}
            return loss, metrics, grads, aux
        gas = plan.gas

        # overlap_zero: constrain the accumulator to the ZeRO shard inside the
        # scan so XLA reduce-scatters each micro-batch's contribution behind
        # the NEXT micro-batch's compute, instead of one bulk reduce-scatter
        # exposed at step end (the Frontier async-collective tuning; the cost
        # model's ``t_overlap`` term is the analytic mirror of this).
        micro_constraint = None
        if (plan.overlap_zero and mesh is not None and plan.zero_stage >= 2):
            p_sh = zero.param_shardings(cfg, params, mesh, plan)
            o_sh = zero.opt_shardings(p_sh, params, mesh, plan)
            micro_constraint = lambda g: zero.grad_constraint(g, mesh, plan, o_sh)

        def to_micro(x):
            if x.shape[0] % gas:
                raise ValueError(
                    f"batch dim {x.shape[0]} not divisible by gas={gas}")
            return x.reshape(gas, x.shape[0] // gas, *x.shape[1:])

        micro = jax.tree_util.tree_map(to_micro, batch)
        acc_dt = cfg.compute_dtype

        # token-weighted accumulation: each micro-batch's masked-mean loss
        # and grads are re-weighted by its live-token count, so a sparse
        # micro (packed rows, SFT masks) doesn't get the same vote as a
        # dense one — matching what gas=1 and the pipeline path compute.
        # Weights are normalized to mean 1, so uniform masks reproduce the
        # unweighted accumulation bit-for-bit (and bf16 magnitudes as-is).
        if batch.get("loss_mask") is not None:
            w = jnp.sum(batch["loss_mask"].astype(jnp.float32)
                        .reshape(gas, -1), axis=1)
        else:
            w = jnp.full((gas,), batch["labels"].reshape(gas, -1).shape[1],
                         jnp.float32)
        wn = w * (gas / jnp.maximum(jnp.sum(w), 1.0))

        if chaos_scale is not None:
            chaos_scale = jnp.broadcast_to(
                chaos_scale.astype(jnp.float32), (gas,))
        else:
            chaos_scale = jnp.ones((gas,), jnp.float32)

        def one(g_acc, mb_wn):
            mb, wi, si = mb_wn
            (loss, metrics), g = jax.value_and_grad(
                loss_fn, has_aux=True)(params, mb)
            g = jax.tree_util.tree_map(lambda x: (x * si).astype(x.dtype), g)
            # per-micro finite gate: a single poisoned micro-batch (bad shard,
            # fp blow-up) is dropped from the accumulation instead of taking
            # the whole effective batch down with it
            fin = jnp.isfinite(adamw.global_norm(g))
            g_acc = jax.tree_util.tree_map(
                lambda a, gi: a + jnp.where(fin, (gi * wi).astype(a.dtype),
                                            jnp.zeros((), a.dtype)),
                g_acc, g)
            if micro_constraint is not None:
                g_acc = micro_constraint(g_acc)
            return g_acc, (loss, metrics, fin)

        g0 = jax.tree_util.tree_map(
            lambda p: jnp.zeros(p.shape, acc_dt), params)
        g_acc, (losses, metricses, fins) = jax.lax.scan(
            one, g0, (micro, wn, chaos_scale))
        all_fin = jnp.all(fins)
        wn_live = wn * fins.astype(jnp.float32)
        # bit-exact with the historic unmasked accumulation when every micro
        # is finite (sum(wn) == gas by construction): only a masked step pays
        # the renormalized denominator
        denom = jnp.where(all_fin, jnp.float32(gas),
                          jnp.maximum(jnp.sum(wn_live), 1e-6))
        grads = jax.tree_util.tree_map(
            lambda g: (g / denom).astype(g.dtype), g_acc)
        metrics = jax.tree_util.tree_map(
            lambda x: jnp.sum(jnp.where(fins, x * wn.astype(x.dtype),
                                        jnp.zeros((), x.dtype)), axis=0)
            / denom.astype(x.dtype), metricses)
        loss = jnp.sum(jnp.where(fins, losses * wn, 0.0)) / denom
        usable = jnp.any(fins)
        loss = jnp.where(usable, loss, jnp.float32(jnp.nan))
        aux = {"usable": usable,
               "nonfinite_micros": jnp.sum((~fins).astype(jnp.int32)),
               "bad_replicas": jnp.zeros((), jnp.int32),
               "bad_micro_bits": _micro_bits(~fins)}
        return loss, metrics, grads, aux

    def consensus_grads(params, batch, chaos_scale, rstat):
        """Fleet-voted anomaly verdict (the tentpole of the elastic-recovery
        contract): batch rows are split into the ``n_rep`` data-parallel
        replica shards, each replica accumulates its OWN gradient
        contribution (per-micro finite masking inside, exactly like the GAS
        path), and its local verdict — every micro non-finite, a non-finite
        local norm, or a z/spike outlier against the shared ``rstat``
        baseline — is reduced across the replica axis.  Under GSPMD that
        reduction lowers to the cross-dp collective (the psum the fleet
        needs), so every replica computes the identical voted bit and the
        zero-update decision cannot desync the fleet's collectives.

        A *minority* of bad replicas is masked out of the accumulation with
        survivor-renormalized weights (a divergent replica costs its shard
        of the batch, not the step); the full skip is taken only when the
        vote says no replica survived — or unconditionally on any bad
        replica when ``mask_divergent_replicas`` is off.

        The replica axis is ``vmap``-ed, not scanned: with the batch sharded
        over dp, each replica's gradient stack stays resident on its own
        devices (the local-grads-before-psum layout of a real fleet) and the
        masked ``sum(axis=0)`` at the end is the one cross-replica
        collective.  ``overlap_zero``'s per-micro constraint does not
        compose with the vmap — the step-level ZeRO constraint after
        compression still applies."""
        R, gas = n_rep, max(plan.gas, 1)

        def to_micro(x):
            if x.shape[0] % (R * gas):
                raise ValueError(
                    f"batch dim {x.shape[0]} not divisible by "
                    f"replicas*gas={R}*{gas}")
            return x.reshape(R, gas, x.shape[0] // (R * gas), *x.shape[1:])

        micro = jax.tree_util.tree_map(to_micro, batch)
        acc_dt = cfg.compute_dtype

        # token weights per (replica, micro), normalized to mean 1 over all
        # R·gas micros — same semantics as the GAS path, so uniform masks
        # keep the all-clean denominator at exactly R·gas
        if batch.get("loss_mask") is not None:
            w = jnp.sum(batch["loss_mask"].astype(jnp.float32)
                        .reshape(R, gas, -1), axis=-1)
        else:
            w = jnp.full((R, gas),
                         batch["labels"].reshape(R * gas, -1).shape[1],
                         jnp.float32)
        wn = w * (R * gas / jnp.maximum(jnp.sum(w), 1.0))

        if chaos_scale is not None:
            s = chaos_scale.astype(jnp.float32).reshape(-1)
            if s.size == R * gas:
                scale = s.reshape(R, gas)
            elif s.size == gas:
                scale = jnp.broadcast_to(s[None, :], (R, gas))
            else:
                scale = jnp.broadcast_to(jnp.prod(s), (R, gas))
        else:
            scale = jnp.ones((R, gas), jnp.float32)

        armed = rstat["n"] >= rs.warmup_steps
        std = jnp.sqrt(jnp.maximum(rstat["var"], 1e-12))

        def per_replica(mb_r, wn_r, s_r):
            def one_micro(gr, inp2):
                mb, wi, si = inp2
                (l, met), g = jax.value_and_grad(
                    loss_fn, has_aux=True)(params, mb)
                g = jax.tree_util.tree_map(
                    lambda x: (x * si).astype(x.dtype), g)
                fin = jnp.isfinite(adamw.global_norm(g))
                gr = jax.tree_util.tree_map(
                    lambda a, gi: a + jnp.where(fin, (gi * wi).astype(a.dtype),
                                                jnp.zeros((), a.dtype)),
                    gr, g)
                return gr, (l, met, fin)

            gr0 = jax.tree_util.tree_map(
                lambda p: jnp.zeros(p.shape, acc_dt), params)
            g_r, (ls, mets, fins) = jax.lax.scan(
                one_micro, gr0, (mb_r, wn_r, s_r))

            # local verdict: the norm of this replica's per-micro-average
            # gradient is what this replica would vote on from its own shard
            live_w = jnp.sum(wn_r * fins.astype(jnp.float32))
            norm_r = adamw.global_norm(g_r) / jnp.maximum(live_w, 1e-6)
            z_r = (norm_r - rstat["ema"]) / std
            spike_r = (armed & (z_r > rs.zscore_threshold)
                       & (norm_r > rs.spike_factor * rstat["ema"]))
            bad = (~jnp.any(fins)) | (~jnp.isfinite(norm_r)) | spike_r

            # mask a bad replica's contribution BEFORE the cross-replica
            # reduce so its poison never enters the collective
            good = ~bad
            g_r = jax.tree_util.tree_map(
                lambda x: jnp.where(good, x, jnp.zeros((), x.dtype)), g_r)
            wloss = jnp.sum(jnp.where(fins, ls * wn_r, 0.0))
            wmets = jax.tree_util.tree_map(
                lambda x: jnp.sum(jnp.where(fins, x * wn_r.astype(x.dtype),
                                            jnp.zeros((), x.dtype)), axis=0),
                mets)
            return g_r, wloss, wmets, fins, bad, live_w

        g_all, wlosses, wmets, fins, bad_r, live_ws = jax.vmap(per_replica)(
            micro, wn, scale)

        good_r = ~bad_r
        n_bad = jnp.sum(bad_r.astype(jnp.int32))      # ← the fleet vote
        all_clean = jnp.all(fins) & (n_bad == 0)
        live_w = jnp.sum(jnp.where(good_r, live_ws, 0.0))
        denom = jnp.where(all_clean, jnp.float32(R * gas),
                          jnp.maximum(live_w, 1e-6))
        # the reduce over the replica axis: under GSPMD this IS the psum
        # over the dp mesh axis — the collective the consensus rides
        grads = jax.tree_util.tree_map(
            lambda g: (jnp.sum(g, axis=0) / denom).astype(g.dtype), g_all)
        metrics = jax.tree_util.tree_map(
            lambda x: jnp.sum(jnp.where(good_r, x, jnp.zeros((), x.dtype)),
                              axis=0) / denom.astype(x.dtype), wmets)
        loss = jnp.sum(jnp.where(good_r, wlosses, 0.0)) / denom
        usable = live_w > 0
        loss = jnp.where(usable, loss, jnp.float32(jnp.nan))
        aux = {"usable": usable,
               "nonfinite_micros": jnp.sum((~fins).astype(jnp.int32)),
               "bad_replicas": n_bad,
               "bad_micro_bits": _micro_bits(jnp.any(~fins, axis=0))}
        return loss, metrics, grads, aux

    def train_step(state, batch):
        ctx = shd.axis_rules(mesh, mapping) if mesh is not None else _null_ctx()
        with ctx, _flash_ctx(plan), moe_groups(n_groups):
            batch = dict(batch)
            chaos_scale = batch.pop("_chaos_grad_scale", None)
            rstat = state.get("rstat")
            if rstat is None:
                rstat = init_rstat()
            loss, metrics, grads, aux = grads_and_metrics(
                state["params"], batch, chaos_scale, rstat)

            # global norm, anomaly gate, compression, LR, AdamW and the
            # skip select: the step's third phase
            with jax.named_scope("optimizer"):
                # --- in-step anomaly signals (free: no extra device sync — they
                # return with the metrics the loop already transfers).  On the
                # consensus path every input below is already a fleet-reduced
                # value, so the verdict — and the zero-update it gates — is
                # bit-identical on every replica. ------------------------------
                gnorm = adamw.global_norm(grads)
                finite = aux["usable"] & jnp.isfinite(gnorm)
                armed = rstat["n"] >= rs.warmup_steps
                std = jnp.sqrt(jnp.maximum(rstat["var"], 1e-12))
                z = (gnorm - rstat["ema"]) / std
                z = jnp.where(finite, z, jnp.float32(jnp.inf))
                spike = (armed & (z > rs.zscore_threshold)
                         & (gnorm > rs.spike_factor * rstat["ema"]))
                if rs.enabled:
                    skip = (~finite) | spike
                    if n_rep > 1 and not rs.mask_divergent_replicas:
                        # strict mode: one bad replica vetoes the whole step
                        skip = skip | (aux["bad_replicas"] > 0)
                else:
                    skip = jnp.zeros((), bool)

                # EMA/variance track ACCEPTED steps only (a skipped spike must
                # not drag the baseline toward the anomaly); the re-warm
                # countdown set by the loop's rollback path decrements here
                first = rstat["n"] == 0
                d = jnp.float32(rs.ema_decay)
                ema_new = jnp.where(first, gnorm,
                                    d * rstat["ema"] + (1 - d) * gnorm)
                var_new = jnp.where(first, rstat["var"],
                                    d * rstat["var"]
                                    + (1 - d) * jnp.square(gnorm - rstat["ema"]))
                accept = (~skip) & finite
                new_rstat = {
                    "ema": jnp.where(accept, ema_new, rstat["ema"]),
                    "var": jnp.where(accept, var_new, rstat["var"]),
                    "n": rstat["n"] + accept.astype(jnp.int32),
                    "rewarm": jnp.maximum(rstat["rewarm"] - 1, 0),
                }

                grads, ef = apply_compression(grads, train_cfg.compression, state.get("ef"))
                if mesh is not None and plan.zero_stage >= 2:
                    p_sh = zero.param_shardings(cfg, state["params"], mesh, plan)
                    o_sh = zero.opt_shardings(p_sh, state["params"], mesh, plan)
                    grads = zero.grad_constraint(grads, mesh, plan, o_sh)
                lr = schedule.lr_schedule(state["step"], peak=train_cfg.peak_lr,
                                          warmup=train_cfg.warmup,
                                          total=train_cfg.total_steps)
                lr = lr * schedule.rewarm_factor(rstat["rewarm"], rs.rewarm_steps)
                params, opt, om = adamw.adamw_update(grads, state["opt"], state["params"],
                                                     lr, train_cfg.adam)
                if rs.enabled:
                    # skip → zero-update: keep params/opt (incl. Adam's step/bias
                    # correction) untouched; the data cursor still advances
                    keep = lambda new, old: jnp.where(skip, old, new)
                    params = jax.tree_util.tree_map(keep, params, state["params"])
                    opt = jax.tree_util.tree_map(keep, opt, state["opt"])
                new_state = {"params": params, "opt": opt,
                             "step": state["step"] + 1, "rstat": new_rstat}
                if ef is not None:
                    if rs.enabled:
                        ef = jax.tree_util.tree_map(keep, ef, state["ef"])
                    new_state["ef"] = ef
                metrics = dict(metrics, loss=loss, **om)
                # resilience signals win over om's post-compression grad_norm:
                # the skip gate keyed on the pre-compression norm is the one the
                # loop's policy must see
                metrics.update(
                    grad_norm=gnorm,
                    all_finite=finite.astype(jnp.float32),
                    skipped=skip.astype(jnp.float32),
                    gnorm_z=jnp.where(armed & finite, z, 0.0),
                    nonfinite_micros=aux["nonfinite_micros"].astype(jnp.float32),
                    bad_replicas=aux["bad_replicas"].astype(jnp.float32),
                    n_replicas=jnp.float32(n_rep),
                    bad_micro_bits=aux["bad_micro_bits"],
                    lr=lr)
        return new_state, metrics

    return train_step


def make_eval_step(cfg: ModelConfig, plan: ParallelismConfig,
                   mesh: Optional[Mesh] = None):
    mapping = axis_mapping(plan)

    def eval_step(params, batch):
        ctx = shd.axis_rules(mesh, mapping) if mesh is not None else _null_ctx()
        with ctx, _flash_ctx(plan):
            loss, metrics = model_api.loss_fn(cfg, params, batch, remat_policy="none")
        return metrics

    return eval_step


def make_serve_step(cfg: ModelConfig, plan: ParallelismConfig,
                    mesh: Optional[Mesh] = None):
    """One decode step over a batch of requests (the ``decode_*`` shapes)."""
    mapping = axis_mapping(plan)

    n_groups = plan.dp * plan.pods if mesh is not None else 1

    def serve_step(params, token, t, caches):
        ctx = shd.axis_rules(mesh, mapping) if mesh is not None else _null_ctx()
        with ctx, moe_groups(n_groups):
            logits, caches = model_api.decode_step(cfg, params, token, t, caches)
            nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return nxt, caches

    return serve_step


def make_prefill(cfg: ModelConfig, plan: ParallelismConfig,
                 mesh: Optional[Mesh] = None, *, last_only: bool = False):
    """``last_only`` returns just the final-position logits — what a serving
    prefill actually needs before decode takes over (beyond-paper opt: drops
    the (B, S, V) fp32 logits output and its collective/memory traffic)."""
    mapping = axis_mapping(plan)

    n_groups = plan.dp * plan.pods if mesh is not None else 1

    def prefill(params, batch):
        ctx = shd.axis_rules(mesh, mapping) if mesh is not None else _null_ctx()
        with ctx, _flash_ctx(plan), moe_groups(n_groups):
            logits = model_api.forward(cfg, params, batch, remat_policy="none",
                                       last_only=last_only)
        return logits

    return prefill


def make_prefill_cache(cfg: ModelConfig, plan: ParallelismConfig,
                       mesh: Optional[Mesh] = None):
    """Serving prompt ingestion: the family prefill that also populates the
    decode caches.  (params, batch, caches) → (last-position logits (B, V),
    caches).  One jit covers all prompt lengths (retrace per shape)."""
    mapping = axis_mapping(plan)

    n_groups = plan.dp * plan.pods if mesh is not None else 1

    def prefill_cache(params, batch, caches):
        ctx = shd.axis_rules(mesh, mapping) if mesh is not None else _null_ctx()
        with ctx, _flash_ctx(plan), moe_groups(n_groups):
            return model_api.prefill_cache(cfg, params, batch, caches)

    return prefill_cache


def make_slot_serve_step(cfg: ModelConfig, plan: ParallelismConfig,
                         mesh: Optional[Mesh] = None):
    """Continuous-batching decode: like ``make_serve_step`` but every slot
    (batch row) carries its OWN position ``ts[i]``, so requests at different
    depths decode together in one full-width step.  Implemented by vmapping
    the single-request decode over the family's cache slot axes — no family
    has to know about mixed-position batches."""
    mapping = axis_mapping(plan)

    n_groups = plan.dp * plan.pods if mesh is not None else 1

    def slot_serve_step(params, tokens, ts, caches):
        axes = model_api.cache_slot_axes(cfg, caches)

        def one(tok, t, cache):
            cache = jax.tree_util.tree_map(
                lambda x, a: jnp.expand_dims(x, a), cache, axes)
            logits, cache = model_api.decode_step(cfg, params, tok[None], t, cache)
            cache = jax.tree_util.tree_map(
                lambda x, a: jnp.squeeze(x, axis=a), cache, axes)
            return jnp.argmax(logits[0], axis=-1).astype(jnp.int32), cache

        ctx = shd.axis_rules(mesh, mapping) if mesh is not None else _null_ctx()
        with ctx, moe_groups(n_groups):
            return jax.vmap(one, in_axes=(0, 0, axes),
                            out_axes=(0, axes))(tokens, ts, caches)

    return slot_serve_step


def make_paged_serve_step(cfg: ModelConfig, plan: ParallelismConfig,
                          mesh: Optional[Mesh] = None):
    """Continuous-batching decode against the block-paged KV pool: every
    batch row carries its own position ``ts[i]`` AND its own page-table row,
    so requests share one pool with no per-slot cache copies.  (params,
    tokens (B,), ts (B,), pool, page_tables (B, n_max)) → (next (B,), pool)."""
    mapping = axis_mapping(plan)
    n_groups = plan.dp * plan.pods if mesh is not None else 1

    def paged_serve_step(params, tokens, ts, pool, page_tables):
        ctx = shd.axis_rules(mesh, mapping) if mesh is not None else _null_ctx()
        with ctx, moe_groups(n_groups):
            logits, pool = model_api.paged_decode_step(
                cfg, params, tokens, ts, pool, page_tables)
            nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return nxt, pool

    return paged_serve_step


def make_paged_prefill(cfg: ModelConfig, plan: ParallelismConfig,
                       mesh: Optional[Mesh] = None):
    """Admission prefill into the paged pool: right-padded prompt suffixes
    (prefix-cache hits skip their shared history) with per-row ``hist_lens``
    and ``lengths``.  (params, batch, pool, page_tables) → (logits (B, V),
    pool)."""
    mapping = axis_mapping(plan)
    n_groups = plan.dp * plan.pods if mesh is not None else 1

    def paged_prefill(params, batch, pool, page_tables):
        ctx = shd.axis_rules(mesh, mapping) if mesh is not None else _null_ctx()
        with ctx, _flash_ctx(plan), moe_groups(n_groups):
            return model_api.paged_prefill(cfg, params, batch, pool,
                                           page_tables)

    return paged_prefill


def pool_copy_page(cfg: ModelConfig, pool, src, dst):
    """Device-side page copy (the copy half of copy-on-write): duplicate
    physical page ``src`` into ``dst`` on every pool leaf.  Pool leaves put
    the page axis at 1 — (L, n_pages, page_size, ...) — per the
    ``init_paged_pool`` contract."""
    return jax.tree_util.tree_map(lambda x: x.at[:, dst].set(x[:, src]), pool)


def cache_zero_slot(cfg: ModelConfig, caches, i):
    """Reset request slot ``i`` of batched decode caches to its init state:
    ``pos`` leaves to -1 (no valid entries), everything else to zeros.  The
    scheduler runs this on retire so a freed slot can never leak stale K/V
    or recurrent state into the next admission."""
    axes = model_api.cache_slot_axes(cfg, caches)

    def one(path, x, a):
        is_pos = any(getattr(kp, "key", None) == "pos" for kp in path)
        shape = list(x.shape)
        shape[a] = 1
        fill = jnp.full(shape, -1 if is_pos else 0, x.dtype)
        return jax.lax.dynamic_update_slice_in_dim(x, fill, i, axis=a)

    return jax.tree_util.tree_map_with_path(one, caches, axes)


def cache_take_slot(cfg: ModelConfig, caches, i):
    """Slice request slot ``i`` out of batched decode caches (slot-width 1)."""
    axes = model_api.cache_slot_axes(cfg, caches)
    return jax.tree_util.tree_map(
        lambda x, a: jax.lax.dynamic_slice_in_dim(x, i, 1, axis=a), caches, axes)


def cache_slice_slots(cfg: ModelConfig, caches, start: int, width: int):
    """Slice ``width`` consecutive request slots out of batched decode caches
    (the scheduler derives narrower admission-prefill templates from one
    full-width template instead of holding one per width)."""
    axes = model_api.cache_slot_axes(cfg, caches)
    return jax.tree_util.tree_map(
        lambda x, a: jax.lax.slice_in_dim(x, start, start + width, axis=a),
        caches, axes)


def cache_insert_slot(cfg: ModelConfig, caches, slot_caches, i):
    """Write slot-width-1 ``slot_caches`` (a fresh prefill, or a reset) into
    slot ``i`` of batched caches — finished requests free their slot and
    queued requests are admitted mid-flight through here."""
    axes = model_api.cache_slot_axes(cfg, caches)
    return jax.tree_util.tree_map(
        lambda x, s, a: jax.lax.dynamic_update_slice_in_dim(
            x, s.astype(x.dtype), i, axis=a),
        caches, slot_caches, axes)


def _flash_ctx(plan: ParallelismConfig):
    """Thread the recipe's flash block-size override (autotuning hook) down
    to ``kernels.ops`` for the duration of a step trace."""
    if plan.flash_bq or plan.flash_bk:
        from repro.runtime import flags
        return flags.flag_ctx(flash_block_q=plan.flash_bq,
                              flash_block_k=plan.flash_bk)
    return _null_ctx()


class _null_ctx:
    def __enter__(self):
        return None

    def __exit__(self, *a):
        return False
