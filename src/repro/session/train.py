"""``TrainSession`` — the single object that owns a training lifecycle.

It resolves the architecture config, applies the paper's recipe
(``ParallelismConfig`` + ``RecipeAdvisor`` checks), builds the train state
and its shardings, jits the train step, owns the deterministic data
pipeline, and runs the fault-tolerant checkpointed loop.  The five drivers
that used to re-compose these pieces by hand now all go through here.

Typical use::

    sess = TrainSession.from_recipe("granite_3_2b", reduced=True,
                                    train_cfg=stepfn.TrainConfig(total_steps=50),
                                    data_cfg=DataConfig(seq_len=128, global_batch=8))
    out = sess.run(ckpt_dir="/tmp/ckpt")          # → {state, history, ...}
    inf = sess.to_inference()                     # serve the trained weights

``abstract=True`` builds the same composition over ``ShapeDtypeStruct``
stand-ins (no memory, no compute) — the dry-run lowers/compiles from it.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Union

import jax
import numpy as np

from repro import configs as cfg_mod
from repro.checkpoint.elastic import canonicalize_state
from repro.core import stepfn
from repro.core.recipe import ParallelismConfig, RecipeAdvisor
from repro.data import DataConfig, make_dataset
from repro.data.pipeline import add_modality_inputs
from repro.models.config import ModelConfig
from repro.runtime.train_loop import LoopConfig, run_training


def resolve_config(arch: Union[str, ModelConfig], *, reduced: bool = False) -> ModelConfig:
    cfg = cfg_mod.get_config(arch) if isinstance(arch, str) else arch
    return cfg.reduced() if reduced else cfg


class TrainSession:
    def __init__(self, cfg: ModelConfig, *,
                 plan: Optional[ParallelismConfig] = None,
                 train_cfg: Optional[stepfn.TrainConfig] = None,
                 data_cfg: Optional[DataConfig] = None,
                 mesh=None, seed: int = 0,
                 abstract: bool = False, donate: bool = True,
                 advisor: Optional[RecipeAdvisor] = None):
        self.cfg = cfg
        self.plan = plan if plan is not None else ParallelismConfig()
        self.train_cfg = train_cfg if train_cfg is not None else stepfn.TrainConfig()
        self.data_cfg = data_cfg
        self.mesh = mesh
        self.abstract = abstract
        if self.plan.pp > 1:
            self.plan.validate(cfg.n_layers)   # pp·vpp layout + gas%pp rules
        # the paper's §7 checklist, evaluated once at composition time; the
        # data-aware packing hint is folded in when the dataset materializes
        self._advisor = advisor or RecipeAdvisor()
        self.advice: Dict[str, str] = self._advisor.check(
            self.plan, n_layers=cfg.n_layers)

        key = jax.random.PRNGKey(seed)

        def init(k):
            return stepfn.init_state(cfg, self.plan, k, self.train_cfg)

        if abstract:
            self.state = jax.eval_shape(init, key)
            self.train_step = None       # composed per-lowering in .lower()
        else:
            # one jitted init that writes each shard straight to its device:
            # no chip ever holds state that is not its own
            out_sh = None
            if mesh is not None:
                out_sh = stepfn.state_shardings(
                    cfg, jax.eval_shape(init, key), mesh, self.plan)
            self.state = jax.jit(init, out_shardings=out_sh)(key)
            step = stepfn.make_train_step(cfg, self.plan, self.train_cfg, mesh)
            self.train_step = jax.jit(step, donate_argnums=(0,) if donate else ())
        self._donate = donate

        self._dataset = None
        self._batch_cache: Dict[int, Any] = {}
        self._eval_step = None
        self._next_step = 0

    # ------------------------------------------------------------------
    # construction
    # ------------------------------------------------------------------
    @classmethod
    def from_recipe(cls, arch: Union[str, ModelConfig], *,
                    reduced: bool = False,
                    plan: Optional[ParallelismConfig] = None,
                    train_cfg: Optional[stepfn.TrainConfig] = None,
                    data_cfg: Optional[DataConfig] = None,
                    mesh=None, seed: int = 0,
                    abstract: bool = False, donate: bool = True) -> "TrainSession":
        """The one public entry point: architecture name (or config) + recipe
        → a fully-composed training session."""
        cfg = resolve_config(arch, reduced=reduced)
        return cls(cfg, plan=plan, train_cfg=train_cfg, data_cfg=data_cfg,
                   mesh=mesh, seed=seed, abstract=abstract, donate=donate)

    # ------------------------------------------------------------------
    # data pipeline (deterministic, resumable: batch = f(seed, step))
    # ------------------------------------------------------------------
    @property
    def dataset(self):
        if self._dataset is None:
            if self.abstract:
                raise RuntimeError("abstract sessions have no data pipeline")
            dc = self.data_cfg or DataConfig(seq_len=256, global_batch=32)
            self._dataset = make_dataset(dc, self.cfg)
            if not dc.pack_documents:
                # data-aware advice: sample one batch, estimate the mean
                # EOS-delimited document length, and suggest packing when
                # rows are mostly shorter documents (advice only — never
                # changes what the session trains on)
                from repro.data.pipeline import estimate_mean_doc_len
                sample = self._dataset.batch(0)
                self.advice.update(self._advisor.check(
                    self.plan, data_cfg=dc,
                    mean_doc_len=estimate_mean_doc_len(
                        sample["tokens"], dc.eos_id)))
        return self._dataset

    def batches(self, step: int):
        """Batch for ``step`` with modality inputs attached (one-slot cache —
        the restart path may re-request the same step)."""
        if step not in self._batch_cache:
            self._batch_cache.clear()
            b = add_modality_inputs(self.dataset.batch(step), self.cfg, step,
                                    self.dataset.cfg.seed)
            if self.mesh is not None:
                b = jax.device_put(b, stepfn.batch_shardings(b, self.mesh))
            self._batch_cache[step] = b
        return self._batch_cache[step]

    # ------------------------------------------------------------------
    # stepping / running
    # ------------------------------------------------------------------
    def step(self, batch=None):
        """One optimizer step; pulls the next pipeline batch when none given."""
        if self.abstract:
            raise RuntimeError("abstract sessions cannot step; use .lower()")
        if batch is None:
            batch = self.batches(self._next_step)
        self.state, metrics = self.train_step(self.state, batch)
        self._next_step += 1
        return metrics

    def run(self, steps: Optional[int] = None, *,
            ckpt_dir=None, ckpt_every: int = 50,
            log_every: Optional[int] = None, keep_ckpts: int = 3,
            async_ckpt: bool = True, fail_at_step: Optional[int] = None,
            chaos=None, fleet=None, ckpt_retry=None,
            tracker=None, log=print) -> Dict[str, Any]:
        """Fault-tolerant training to ``steps`` (default: the schedule length):
        restore → train → periodic atomic checkpoint → preemption handling,
        with the resilience policy from ``train_cfg.resilience`` (the same
        config the jitted step's skip gate was built with, so the two halves
        of the contract stay in sync).

        ``tracker`` is any ``session.tracker.Tracker`` (e.g. ``JsonlTracker``);
        every logged step's metrics stream through it.  ``chaos`` is a
        ``runtime.chaos.FaultPlan``; ``fail_at_step`` is the deprecated
        spelling of ``FaultPlan(crash_at=...)`` and is folded into it.

        ``fleet`` is a ``runtime.fleet.FleetController``: the loop feeds it
        heartbeats and, on replica loss or a persistent straggler, re-plans
        elastically — the session hands the loop a ``make_step`` factory so
        the re-plan arm can re-jit the step for the shrunk plan; the
        session's ``plan``/``train_step`` are updated to the final plan on
        the way out."""
        if self.abstract:
            raise RuntimeError("abstract sessions cannot run; use .lower()")
        if self._next_step:
            raise RuntimeError(
                "run() restarts the data schedule at step 0 — don't mix manual "
                "step() with run() in one session; use a fresh session (resume "
                "happens via ckpt_dir) or keep stepping manually")
        if fail_at_step is not None:
            from repro.runtime.chaos import FaultPlan
            chaos = chaos if chaos is not None else FaultPlan()
            chaos.crash_at = fail_at_step
        total = steps if steps is not None else self.train_cfg.total_steps
        loop_cfg = LoopConfig(
            total_steps=total, ckpt_every=ckpt_every,
            ckpt_dir=str(ckpt_dir) if ckpt_dir else None,
            log_every=log_every if log_every is not None else max(1, total // 20),
            keep_ckpts=keep_ckpts, async_ckpt=async_ckpt)
        def make_step(new_plan):
            # re-jit for a shrunk plan (the elastic re-plan arm); the new
            # step reads the SAME resilience config, so the consensus gate
            # re-derives its replica count from the new plan
            step = stepfn.make_train_step(self.cfg, new_plan, self.train_cfg,
                                          self.mesh)
            return jax.jit(step,
                           donate_argnums=(0,) if self._donate else ())

        out = run_training(self.state, self.train_step, self.batches, loop_cfg,
                           plan=self.plan, log=log, tracker=tracker,
                           resilience=self.train_cfg.resilience,
                           chaos=chaos, fleet=fleet,
                           make_step=make_step if fleet is not None else None,
                           ckpt_retry=ckpt_retry)
        self.state = out["state"]
        if out.get("replans"):
            self.plan = out["plan"]
            self.train_step = make_step(self.plan)
        self._next_step = total
        return out

    def evaluate(self, batch):
        """Loss/metrics on one batch without touching optimizer state."""
        if self._eval_step is None:
            self._eval_step = jax.jit(
                stepfn.make_eval_step(self.cfg, self.plan, self.mesh))
        return self._eval_step(self.state["params"], batch)

    # ------------------------------------------------------------------
    # hand-offs
    # ------------------------------------------------------------------
    def lower(self, batch_specs):
        """Abstract-mode: lower the train step for ``batch_specs`` — sharded
        on this session's mesh (the dry-run's compile-only path), or for the
        default device without one (sizing a run from
        ``compile().memory_analysis()`` before any state exists)."""
        if not self.abstract:
            raise RuntimeError("lower() needs abstract=True")
        step = stepfn.make_train_step(self.cfg, self.plan, self.train_cfg, self.mesh)
        if self.mesh is None:
            return jax.jit(step, donate_argnums=(0,)).lower(self.state,
                                                            batch_specs)
        state_sh = stepfn.state_shardings(self.cfg, self.state, self.mesh, self.plan)
        batch_sh = stepfn.batch_shardings(batch_specs, self.mesh)
        jitted = jax.jit(step, in_shardings=(state_sh, batch_sh),
                         out_shardings=(state_sh, None), donate_argnums=(0,))
        return jitted.lower(self.state, batch_specs)

    def to_inference(self, *, plan: Optional[ParallelismConfig] = None,
                     mesh=None) -> "InferenceSession":
        """Hand the trained weights to serving (canonical layer layout,
        compute-dtype cast)."""
        from repro.session.infer import InferenceSession
        params = canonicalize_state(self.state, self.plan)["params"]
        params = jax.tree_util.tree_map(
            lambda x: x.astype(self.cfg.compute_dtype), params)
        return InferenceSession.from_params(self.cfg, params, plan=plan, mesh=mesh)

    @property
    def n_params(self) -> int:
        return sum(int(np.prod(x.shape))
                   for x in jax.tree_util.tree_leaves(self.state["params"]))

    def __repr__(self) -> str:  # pragma: no cover - debugging aid
        kind = "abstract" if self.abstract else "live"
        return (f"<TrainSession {self.cfg.name} ({kind}) plan={self.plan} "
                f"params={self.n_params / 1e6:.1f}M>")
