"""``InferenceSession`` — the serving side of the recipe in one object.

Owns the compute-dtype params, family-aware cache init (ring-buffer KV /
SSM states / cross-KV), the jitted prefill and decode steps, and a batched
greedy ``generate()``: prompts are ingested through the cache-populating
prefill (one teacher-forced forward for attention stacks, one decode scan
for recurrent ones) and mixed-length workloads delegate to the
continuous-batching scheduler (``repro.session.scheduler``).
``abstract=True`` composes over ShapeDtypeStructs and exposes
``lower_prefill`` / ``lower_decode`` for the dry-run's compile-only cells.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Sequence, Union

import jax
import jax.numpy as jnp

from repro.core import stepfn
from repro.core.recipe import ParallelismConfig
from repro.launch import plans as plans_mod
from repro.models import api as model_api
from repro.models.config import ModelConfig


class InferenceSession:
    def __init__(self, cfg: ModelConfig, params, *,
                 plan: Optional[ParallelismConfig] = None,
                 mesh=None, abstract: bool = False):
        self.cfg = cfg
        self.params = params
        self.plan = plan if plan is not None else ParallelismConfig()
        self.mesh = mesh
        self.abstract = abstract
        self.family = model_api.family_of(cfg)
        self._serve_step = None
        self._prefill: Dict[bool, Any] = {}
        self._prefill_cache_step = None
        self._slot_step = None
        self._insert_slot = None
        self._take_slot = None
        self._zero_slot = None
        self._paged_prefill_step = None
        self._paged_slot_step = None
        self._pool_copy_page = None
        self.last_stats = None  # ServingStats of the most recent serve()

    # ------------------------------------------------------------------
    # construction
    # ------------------------------------------------------------------
    @classmethod
    def from_recipe(cls, arch: Union[str, ModelConfig], *,
                    reduced: bool = False,
                    plan: Optional[ParallelismConfig] = None,
                    mesh=None, seed: int = 0,
                    abstract: bool = False) -> "InferenceSession":
        """Fresh (random-init) weights in compute dtype — the serving driver
        and dry-run path."""
        from repro.session.train import resolve_config
        cfg = resolve_config(arch, reduced=reduced)

        def mk(key):
            p = model_api.init_params(cfg, key)
            return jax.tree_util.tree_map(
                lambda x: x.astype(cfg.compute_dtype), p)

        key = jax.random.PRNGKey(seed)
        params = jax.eval_shape(mk, key)
        if not abstract:
            # jitted: each leaf is cast as it is made (the fp32 and
            # compute-dtype trees never coexist) and lands on its own shards
            out_sh = (plans_mod.serve_param_sharding(params, mesh)
                      if mesh is not None else None)
            params = jax.jit(mk, out_shardings=out_sh)(key)
        return cls(cfg, params, plan=plan, mesh=mesh, abstract=abstract)

    @classmethod
    def from_params(cls, cfg: ModelConfig, params, *,
                    plan: Optional[ParallelismConfig] = None,
                    mesh=None) -> "InferenceSession":
        """Adopt existing weights (e.g. ``TrainSession.to_inference()``)."""
        return cls(cfg, params, plan=plan, mesh=mesh)

    # ------------------------------------------------------------------
    # serving steps
    # ------------------------------------------------------------------
    def init_cache(self, batch_size: int, max_len: int, batch=None):
        """Family-aware decode caches; non-token inputs (encdec frames) are
        stubbed through the family's ``serve_batch`` hook when absent."""
        return model_api.init_cache(self.cfg, self.params, batch_size,
                                    max_len, batch)

    @property
    def serve_step(self):
        """Jitted one-token decode: (params, token, t, caches) → (next, caches)."""
        if self._serve_step is None:
            # NOT donated: callers may legitimately step twice from one
            # caches state (the new slot_step, scheduler-only, does donate)
            self._serve_step = jax.jit(
                stepfn.make_serve_step(self.cfg, self.plan, self.mesh))
        return self._serve_step

    def prefill(self, batch, *, last_only: bool = True):
        """Teacher-forced full-sequence forward (the prefill phase)."""
        if last_only not in self._prefill:
            self._prefill[last_only] = jax.jit(
                stepfn.make_prefill(self.cfg, self.plan, self.mesh,
                                    last_only=last_only))
        return self._prefill[last_only](self.params, batch)

    @property
    def prefill_cache_step(self):
        """Jitted cache-populating prefill:
        (params, batch, caches) → (last-position logits (B, V), caches)."""
        if self._prefill_cache_step is None:
            self._prefill_cache_step = jax.jit(
                stepfn.make_prefill_cache(self.cfg, self.plan, self.mesh))
        return self._prefill_cache_step

    @property
    def slot_step(self):
        """Jitted per-slot-position decode (continuous batching):
        (params, tokens (B,), ts (B,), caches) → (next (B,), caches)."""
        if self._slot_step is None:
            self._slot_step = jax.jit(
                stepfn.make_slot_serve_step(self.cfg, self.plan, self.mesh),
                donate_argnums=(3,))   # caches are reassigned every step
        return self._slot_step

    @property
    def insert_slot(self):
        """Jitted slot insert: (caches, slot_caches, i) → caches with the
        width-1 ``slot_caches`` written into request slot ``i``.  ``caches``
        is donated (callers rebind it) — the lowering auditor's donation pass
        confirmed the alias, so admission updates in place instead of copying
        the whole cache."""
        if self._insert_slot is None:
            cfg = self.cfg
            self._insert_slot = jax.jit(
                lambda caches, slot, i: stepfn.cache_insert_slot(
                    cfg, caches, slot, i),
                donate_argnums=(0,))
        return self._insert_slot

    @property
    def take_slot(self):
        """Jitted slot slice: (caches, i) → width-1 caches of request slot
        ``i`` (the scheduler splits batched admission prefills with this)."""
        if self._take_slot is None:
            cfg = self.cfg
            self._take_slot = jax.jit(
                lambda caches, i: stepfn.cache_take_slot(cfg, caches, i))
        return self._take_slot

    @property
    def zero_slot(self):
        """Jitted slot reset: (caches, i) → caches with request slot ``i``
        zeroed (positions → -1).  Retire uses this so freed slots never hold
        stale K/V."""
        if self._zero_slot is None:
            cfg = self.cfg
            self._zero_slot = jax.jit(
                lambda caches, i: stepfn.cache_zero_slot(cfg, caches, i),
                donate_argnums=(0,))
        return self._zero_slot

    # ------------------------------------------------------------------
    # block-paged KV pool steps (repro.session.kvpool)
    # ------------------------------------------------------------------
    def init_paged_pool(self, n_pages: int, page_size: int):
        """Device-side KV page pool, leaves (layers, n_pages, page_size,
        n_kv_heads, head_dim) in compute dtype (page 0 is the trash page)."""
        return model_api.init_paged_pool(self.cfg, self.params, n_pages,
                                         page_size)

    @property
    def paged_prefill_step(self):
        """Jitted suffix prefill through page tables:
        (params, batch, pool, page_tables) → (last-valid logits (B, V), pool).
        ``batch`` = tokens (B, S) right-padded suffixes + hist_lens (B,) +
        lengths (B,)."""
        if self._paged_prefill_step is None:
            self._paged_prefill_step = jax.jit(
                stepfn.make_paged_prefill(self.cfg, self.plan, self.mesh),
                donate_argnums=(2,))   # the pool is rebound every call
        return self._paged_prefill_step

    @property
    def paged_slot_step(self):
        """Jitted per-slot-position decode through page tables:
        (params, tokens (B,), ts (B,), pool, page_tables) → (next (B,), pool)."""
        if self._paged_slot_step is None:
            self._paged_slot_step = jax.jit(
                stepfn.make_paged_serve_step(self.cfg, self.plan, self.mesh),
                donate_argnums=(3,))
        return self._paged_slot_step

    @property
    def pool_copy_page(self):
        """Jitted COW page copy: (pool, src, dst) → pool with physical page
        ``src`` copied over ``dst`` in every layer."""
        if self._pool_copy_page is None:
            cfg = self.cfg
            self._pool_copy_page = jax.jit(
                lambda pool, src, dst: stepfn.pool_copy_page(
                    cfg, pool, src, dst),
                donate_argnums=(0,))
        return self._pool_copy_page

    def generate(self, prompts, max_new_tokens, *,
                 stop_token: Optional[int] = None,
                 n_slots: Optional[int] = None):
        """Greedy decode.

        Uniform mode (2-D ``prompts`` array + int ``max_new_tokens``): one
        batched cache-populating prefill ingests the prompts, then argmax
        decode — returns ``(B, prompt_len + max_new_tokens)`` token ids
        (after ``stop_token`` a row is padded with it).

        Mixed-length mode (a list of prompts, or per-request
        ``max_new_tokens``): delegates to the continuous-batching scheduler
        and returns a list of per-request 1-D token arrays (stats land in
        ``self.last_stats``)."""
        if isinstance(prompts, (list, tuple)) or \
                isinstance(max_new_tokens, (list, tuple)):
            outs, _ = self.serve(prompts, max_new_tokens,
                                 stop_token=stop_token, n_slots=n_slots)
            return outs
        prompts = jnp.asarray(prompts, jnp.int32)
        if max_new_tokens <= 0:
            return prompts
        B, P = prompts.shape
        max_len = P + max_new_tokens
        caches = self.init_cache(B, max_len)
        logits, caches = self.prefill_cache_step(
            self.params, {"tokens": prompts}, caches)
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        cols = [prompts, tok[:, None]]
        done = (tok == stop_token) if stop_token is not None else None
        for t in range(P, max_len - 1):
            if done is not None and bool(done.all()):
                cols.append(jnp.full((B, max_len - 1 - t), stop_token, jnp.int32))
                break
            nxt, caches = self.serve_step(self.params, tok, jnp.int32(t), caches)
            if done is not None:
                nxt = jnp.where(done, jnp.int32(stop_token), nxt)
                done = done | (nxt == stop_token)
            tok = nxt
            cols.append(tok[:, None])
        return jnp.concatenate(cols, axis=1)

    def serve(self, prompts: Sequence, max_new_tokens, *,
              stop_token: Optional[int] = None,
              n_slots: Optional[int] = None,
              max_len: Optional[int] = None,
              bucket_prefills: bool = True,
              paged: bool = False,
              page_size: int = 16,
              n_pages: Optional[int] = None,
              prefix_sharing: bool = True,
              scheduler: Optional["ContinuousBatchingScheduler"] = None):
        """Continuous-batching serve of a mixed-length request set.
        Returns (list of per-request 1-D token arrays in submit order,
        ``ServingStats``).

        ``bucket_prefills`` pads admission prefills to power-of-two prompt
        lengths (masked — outputs are unchanged) so a mixed-length workload
        compiles O(log max_len) prefill shapes instead of one per distinct
        prompt length; it is automatically disabled for families whose
        prefill cannot mask padding (recurrent/state caches).

        ``paged=True`` serves from the block-paged KV pool
        (``repro.session.kvpool``): per-request page tables over shared
        physical pages, prefix-cache reuse of identical prompt prefixes, and
        copy-on-write growth — greedy outputs stay token-identical to the
        fixed-slot path.  Pass a previously returned ``scheduler`` to keep
        its prefix cache warm across calls."""
        import numpy as np
        from repro.session.scheduler import (ContinuousBatchingScheduler,
                                             RequestQueue, ServingStats)
        prompts = [np.asarray(p, np.int32).reshape(-1) for p in prompts]
        if isinstance(max_new_tokens, (list, tuple)):
            mnt = [int(m) for m in max_new_tokens]
        else:
            mnt = [int(max_new_tokens)] * len(prompts)
        if len(mnt) != len(prompts):
            raise ValueError(
                f"{len(prompts)} prompts but {len(mnt)} max_new_tokens")
        if not prompts:
            self.last_stats = ServingStats()
            return [], self.last_stats
        if n_slots is None:
            n_slots = min(4, len(prompts))
        if max_len is None:
            max_len = max(len(p) + m for p, m in zip(prompts, mnt))
        queue = RequestQueue()
        rids = [queue.submit(p, m, stop_token=stop_token)
                for p, m in zip(prompts, mnt)]
        sched = scheduler if scheduler is not None else \
            ContinuousBatchingScheduler(self, n_slots=n_slots,
                                        max_len=max_len,
                                        bucket_prefills=bucket_prefills,
                                        paged=paged, page_size=page_size,
                                        n_pages=n_pages,
                                        prefix_sharing=prefix_sharing)
        outputs, stats = sched.run(queue)
        self.last_stats = stats
        return [outputs[r] for r in rids], stats

    # ------------------------------------------------------------------
    # dry-run (compile-only) lowering
    # ------------------------------------------------------------------
    def _require_abstract_mesh(self):
        if not (self.abstract and self.mesh is not None):
            raise RuntimeError("lowering needs abstract=True and a mesh")

    def lower_prefill(self, batch_specs, *, last_only: bool = False):
        """Lower the sharded prefill for abstract ``batch_specs``."""
        self._require_abstract_mesh()
        params_sh = plans_mod.serve_param_sharding(self.params, self.mesh)
        batch_sh = stepfn.batch_shardings(batch_specs, self.mesh)
        fn = stepfn.make_prefill(self.cfg, self.plan, self.mesh,
                                 last_only=last_only)
        jitted = jax.jit(fn, in_shardings=(params_sh, batch_sh))
        return jitted.lower(self.params, batch_specs)

    def lower_decode(self, batch_size: int, cache_len: int):
        """Lower one sharded decode step against a ``cache_len`` cache."""
        self._require_abstract_mesh()
        params_sh = plans_mod.serve_param_sharding(self.params, self.mesh)
        cache_shapes = jax.eval_shape(
            lambda p: model_api.init_cache(self.cfg, p, batch_size, cache_len),
            self.params)
        cache_sh = plans_mod.cache_shardings(
            cache_shapes, self.mesh, global_batch=batch_size, cache_len=cache_len)
        tok = jax.ShapeDtypeStruct((batch_size,), jnp.int32)
        t = jax.ShapeDtypeStruct((), jnp.int32)
        tok_sh = jax.NamedSharding(self.mesh, jax.sharding.PartitionSpec(
            plans_mod.batch_sharding(self.mesh, batch_size)))
        fn = stepfn.make_serve_step(self.cfg, self.plan, self.mesh)
        jitted = jax.jit(fn, in_shardings=(params_sh, tok_sh, None, cache_sh),
                         out_shardings=(tok_sh, cache_sh), donate_argnums=(3,))
        return jitted.lower(self.params, tok, t, cache_shapes)

    def prefill_input_specs(self, batch_size: int, seq_len: int) -> Dict[str, Any]:
        """Abstract prefill batch: tokens + the family's extra inputs."""
        specs = {"tokens": jax.ShapeDtypeStruct((batch_size, seq_len), jnp.int32)}
        specs.update(self.family.extra_input_specs(self.cfg, batch_size))
        return specs

    def __repr__(self) -> str:  # pragma: no cover - debugging aid
        kind = "abstract" if self.abstract else "live"
        return f"<InferenceSession {self.cfg.name} ({kind}) plan={self.plan}>"
