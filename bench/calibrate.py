#!/usr/bin/env python3
"""Readings that set a cell's correctness limits, at the cell's own size.

    python bench/calibrate.py --workload <cell> --seeds 1,2,3 --what program
    python bench/calibrate.py --workload <cell> --seeds 1,2,3 \
        --what control,half_batch,no_exchange

``--what`` is a comma-separated list.  ``program`` reads the numbers the
benchmark compares from the program's own checked steps (training) or one
serve call at the cell's load (serving), one seed after another in this one
process; for serving it also reads the control, at each position the gap
of the token fp8 puts first.  In training each further entry adds the same
numbers under ``<entry>_<name>``, held against the same f32 reference:
``control``, the reference computed in fp8 in the program's place;
``half_batch``, the program with the second half of every batch's rows
masked out of the loss, the mean taken over the rest; ``no_exchange``, the
program with the pipeline's stage ring left out; ``state_unchanged``, the
program with a step that returns its state as it got it.  Each seed prints
one JSON line.  The lower reading of a number is the largest the program
gives; the upper is the smallest the control (or a fault) gives.  Not run
by the benchmark itself.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def state_unchanged():
    """Every train step returns the state it was given.  Returns what
    undoes it."""
    from repro.core import stepfn
    orig = stepfn.make_train_step

    def frozen(*a, **kw):
        step = orig(*a, **kw)
        return lambda state, batch: (state, step(state, batch)[1])
    stepfn.make_train_step = frozen
    return lambda: setattr(stepfn, "make_train_step", orig)


def half_batch():
    """Every train step counts only the first half of its batch's rows: the
    others are masked out of the loss, whose mean is over the rest (the
    rows stay, so a pipeline still splits the batch into its micro-batches).
    Returns what undoes it."""
    from repro.core import stepfn
    orig = stepfn.make_train_step

    def broken(*a, **kw):
        step = orig(*a, **kw)

        def half(state, batch):
            n = batch["loss_mask"].shape[0] // 2
            mask = batch["loss_mask"].at[n:].set(0.0)
            return step(state, dict(batch, loss_mask=mask))
        return half
    stepfn.make_train_step = broken
    return lambda: setattr(stepfn, "make_train_step", orig)


def no_exchange():
    """The pipeline's stage ring left out: each stage takes its own output
    back in place of the stage before it's (``jnp.roll`` in
    ``core/pipeline.py`` is the collective-permute between stages).
    Returns what undoes it."""
    import jax.numpy as jnp
    from repro.core import pipeline

    class NoRoll:
        def __getattr__(self, name):
            return getattr(jnp, name)

        @staticmethod
        def roll(x, shift, axis=None):
            return x
    pipeline.jnp = NoRoll()
    return lambda: setattr(pipeline, "jnp", jnp)


FAULTS = {"state_unchanged": state_unchanged, "half_batch": half_batch,
          "no_exchange": no_exchange}


def readings(job, what) -> dict:
    """The numbers of each of ``what``: the program's (``program``), and
    in training those of a control or fault under ``<what>_<name>``, every
    reading held against one f32 reference."""
    from bench import correct, harness
    if job.mix["driver"] == "train":
        from bench.drivers import train
        out = {}
        if "program" in what:
            sess, _, program = train.setup(job)
            harness.free(sess.state)
        host = train.host_batches(job, train.CHECK_STEPS)
        ref = train.reference(job, host)
        if "program" in what:
            out.update(correct.train_numbers(program, ref))
        for w in what:
            if w == "control":
                other = train.reference(job, host, "fp8")
            elif w in FAULTS:
                undo = FAULTS[w]()
                try:
                    sess, _, other = train.setup(job)
                finally:
                    undo()
                harness.free(sess.state)
            else:
                continue
            out.update({f"{w}_{k}": v for k, v in
                        correct.train_numbers(other, ref).items()})
        return out
    from bench.drivers import serve
    inf = serve.build(job)
    done = [serve.call(job, inf, 0)]
    harness.free(inf.params)
    _, failed, seqs = serve.sample(job, done)
    gaps = serve.reference(job, seqs, ("f32", "fp8"))
    return {"logit_gap": correct.widest(gaps["served"]),
            "control_logit_gap": correct.widest(gaps["fp8"]),
            "failed": (failed, "")}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--what", default="program",
                    help="comma-separated: program, control, half_batch, "
                         "no_exchange, state_unchanged")
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import os
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", str(ROOT / ".jax_cache"))
    import jax
    from bench import harness, spec
    from bench.run import Job
    jax.config.update("jax_compilation_cache_dir",
                      os.environ["JAX_COMPILATION_CACHE_DIR"])
    what = args.what.split(",")
    bench = spec.benchmark()
    counter = harness.CompileCounter()
    for seed in (int(s) for s in args.seeds.split(",")):
        job = Job(bench, args.workload, seed, 0, False, jax.devices(), counter)
        nums = readings(job, what)
        print(json.dumps({"seed": seed, "what": args.what,
                          **{k: v[0] for k, v in nums.items()},
                          "at": {k: v[1] for k, v in nums.items()}}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
