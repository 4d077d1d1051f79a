"""The four-chip training cell rehearsed on four virtual CPU devices, at
tiny widths and 4 layers (``tiny.job``): one whole run through the cell's
harness, mesh, reference and limits, with the timed path whole or broken.

    XLA_FLAGS=--xla_force_host_platform_device_count=4 JAX_PLATFORMS=cpu \\
        python -m bench.tests.mesh_rehearsal <case>

Cases: ``sound``; ``state_unchanged``, ``half_batch``, ``no_exchange`` (the
faults of ``calibrate.py``); ``fp8_control`` (the reference in fp8 in the
program's place, judged by the cell's limits).  The last line of stdout
is ``{"correct": ..., "checks": ...}``.  The device count is fixed when
JAX starts, so ``test_mesh.py`` runs each case in a process of its own."""

from __future__ import annotations

import json
import sys


def main(case: str) -> dict:
    import jax
    from bench import calibrate, correct
    from bench.drivers import train
    from bench.run import execute
    from bench.tests import tiny

    if len(jax.devices()) < 4:
        raise SystemExit(f"needs 4 devices, JAX finds {len(jax.devices())}")
    job = tiny.job(tiny.TRAIN_MESH, n_layers=4)
    if case == "fp8_control":
        host = train.host_batches(job, train.CHECK_STEPS)
        ref = train.reference(job, host)
        ok, checks = correct.judge(correct.train_numbers(
            train.reference(job, host, "fp8"), ref), job.limits)
        return {"correct": bool(ok), "checks": checks}
    if case != "sound":
        calibrate.FAULTS[case]()
    out = execute(job)
    return {"correct": out["correct"], "checks": out["checks"]}


if __name__ == "__main__":
    from bench.tests import conftest  # noqa: F401  (puts the repo on the path)
    print(json.dumps(main(sys.argv[1])))
