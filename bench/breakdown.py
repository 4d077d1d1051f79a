#!/usr/bin/env python3
"""Run one cell traced, as ``run.py --trace 1`` does, and print what the
program's own names say of the trace (``named.py``).

    python bench/breakdown.py --workload <cell> --seed <n> --seconds <s> \
        [--keep DIR]

After run.py's lines it prints one JSON object, ``{"named": {...}}``: for
training, device ms per step of each phase of the step (forward, backward
with remat's recompute, optimizer, and the unscoped rest) beside busy ms per
step, and chip-idle ms per step inside the ``train.*`` spans; for serving,
chip-idle ms per decode step inside the ``serve.*`` spans, the median and
the longest gap between a request's tokens and the share of the window
spent admitting (from the scheduler's ``ServingStats``, over the whole
window); for both, each Pallas
kernel's device ms per step by phase, idle ms per step inside each span,
each span's mean length, and the longest idle stretches by span.
``--keep`` copies the trace into that directory.
"""

from __future__ import annotations

import argparse
import json
import shutil
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=int, required=True)
    ap.add_argument("--keep", type=Path)
    args = ap.parse_args(argv)

    import run                                    # bench/run.py
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import harness, named, trace
    from bench.drivers import serve as serve_driver, train as train_driver

    seen = {"stats": []}

    class KeptTracer(harness.Tracer):
        def reduce(self):
            path = trace.find_xplane(self.dir)
            seen["named"] = named.reduce(path)
            if args.keep:
                args.keep.mkdir(parents=True, exist_ok=True)
                shutil.copy(path, args.keep / f"{args.workload}."
                            f"{args.seed}.xplane.pb")
            return super().reduce()

    def keep_ctx(real):
        def run_cell(job):
            res = real(job)
            seen["ctx"] = res["ctx"]
            return res
        return run_cell

    real_call = serve_driver.call

    def call(job, inf, i, warmup=False):
        out = real_call(job, inf, i, warmup)
        if not warmup:
            seen["stats"].append(out[3])
        return out

    # run.py deletes the trace once reduced and keeps no ServingStats:
    # these wrappers keep both for this process, and run.py stays as it is
    harness.Tracer = KeptTracer
    serve_driver.call = call
    train_driver.run = keep_ctx(train_driver.run)
    serve_driver.run = keep_ctx(serve_driver.run)
    rc = run.main(["--workload", args.workload, "--seed", str(args.seed),
                   "--seconds", str(args.seconds), "--trace", "1"])
    if rc == 0:
        print(json.dumps({"named": named.summary(
            seen["named"], seen["ctx"], seen["stats"])}), flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main())
