#!/usr/bin/env python3
"""Readings that set a cell's correctness limits, at the cell's own size.

    python bench/calibrate.py --workload <cell> --seeds 1,2,3 --what program
    python bench/calibrate.py --workload <cell> --seeds 1,2,3 --what control
    python bench/calibrate.py --workload <cell> --seeds 1,2,3 --what half_batch

``program`` reads the numbers the benchmark compares from the program's own
checked steps (training) or one serve call at the cell's load (serving),
one seed after another in this one process; for serving it also reads the
control, at each position the gap of the token fp8 puts first.  ``control`` reads them from
the reference computed in fp8 in the program's place.  ``half_batch``
(training) reads them from the program with every step given only the
first half of its rows.  Each seed prints one JSON line.  The lower
reading of a number is the largest the program gives; the upper is the
smallest the control (or a fault) gives.  Not run by the benchmark itself.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def half_batch():
    """Every train step sees only the first half of its batch's rows."""
    from repro.core import stepfn
    orig = stepfn.make_train_step

    def broken(*a, **kw):
        step = orig(*a, **kw)

        def half(state, batch):
            n = batch["tokens"].shape[0] // 2
            return step(state, {k: v[:n] for k, v in batch.items()})
        return half
    stepfn.make_train_step = broken


def readings(job, what: str) -> dict:
    from bench import correct, harness
    if job.mix["driver"] == "train":
        from bench.drivers import train
        sess, host, program = train.setup(job)
        harness.free(sess.state)
        ref = train.reference(job, host)
        if what == "control":
            program = train.reference(job, host, "fp8")
        return {k: v for k, v in correct.train_numbers(program, ref).items()}
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
    ap.add_argument("--what", choices=("program", "control", "half_batch"),
                    default="program")
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import os
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", str(ROOT / ".jax_cache"))
    import jax
    from bench import harness, spec
    from bench.run import Job
    jax.config.update("jax_compilation_cache_dir",
                      os.environ["JAX_COMPILATION_CACHE_DIR"])
    if args.what == "half_batch":
        half_batch()
    bench = spec.benchmark()
    counter = harness.CompileCounter()
    for seed in (int(s) for s in args.seeds.split(",")):
        job = Job(bench, args.workload, seed, 0, False, jax.devices(), counter)
        nums = readings(job, args.what)
        print(json.dumps({"seed": seed, "what": args.what,
                          **{k: v[0] for k, v in nums.items()},
                          "at": {k: v[1] for k, v in nums.items()}}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
