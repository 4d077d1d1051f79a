#!/usr/bin/env python3
"""Run one benchmark cell on the chips of this machine.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json``) names a configuration and a traffic mix, whose
files under ``bench/`` say what to build and which driver runs it.  The
last line of stdout is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics, or with ``--trace 1``
its per-layer ones), ``device``, with a trace ``breakdown``, and last
``checks``: each number the correctness comparison read, beside its limit
(also the last lines of stderr).  Without a TPU, or with fewer chips than
the cell asks for, it exits non-zero and prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
CACHE_DIR = ROOT / ".jax_cache"


class Job:
    """One run of one cell: what its driver module needs, and the set-up
    clock and measured window it reports through."""

    def __init__(self, bench: dict, workload: str, seed: int, seconds: int,
                 trace: bool, devices, counter, *, conf=None, mix=None,
                 limits=None):
        from bench import spec
        self.bench, self.workload = bench, workload
        self.cell = spec.cell(workload, bench)
        self.conf = conf or spec.config_file(self.cell["config"], bench)
        self.mix = mix or spec.traffic(self.cell["traffic"])
        self.limits = limits or spec.limits(workload)
        self.model_cfg = spec.model_config(self.conf)
        self.plan = spec.plan(self.conf.get("train", {}))
        self.seed, self.seconds, self.trace = seed, seconds, trace
        self.devices = devices
        self.counter = counter
        self.setup_s = None
        self.window_obj = None
        self.tracer = None

    def setup_done(self) -> None:
        self.setup_s = time.perf_counter() - T_START

    @contextlib.contextmanager
    def window(self):
        from bench import harness
        win = Window()
        tracer = None
        if self.trace:
            # the traced stretch: the mix's cap, else the configuration's
            # (four chips' events of a whole window take minutes to collect)
            tracer = harness.Tracer(self.workload, self.mix.get(
                "trace_seconds", self.conf.get("trace_seconds")))
        self.counter.count = 0
        self.counter.armed = True
        try:
            with (tracer or contextlib.nullcontext()):
                win.start()
                yield win
                win.stop()
        finally:
            self.counter.armed = False
        if self.counter.count:
            raise RuntimeError(f"{self.counter.count} compilations inside the "
                               f"measured window")
        self.window_obj = win
        self.tracer = tracer


class Window:
    def start(self):
        self.t0 = time.perf_counter()
        self.seconds = None

    def elapsed(self) -> float:
        return time.perf_counter() - self.t0

    def stop(self):
        self.seconds = self.elapsed()


def execute(job) -> dict:
    """Drive the cell and assemble the result line (without the device
    check, which ``main`` does)."""
    from bench import correct, peaks, spec
    driver = importlib.import_module(f"bench.drivers.{job.mix['driver']}")
    res = driver.run(job)
    ok, checks = correct.judge(res["numbers"], job.limits)
    dev = job.devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": job.cell["chips"],
              "memory_peak_bytes": res["memory_peak_bytes"]}
    out = {"correct": bool(ok), "attempted": int(res["attempted"]),
           "failed": int(res["failed"])}
    wanted = spec.metrics_for(job.bench, job.workload, job.trace)
    metrics = {}
    if not job.trace:
        vals = dict(res["e2e"], setup_s=job.setup_s)
        for m in wanted:
            metrics[m["name"]] = {"value": vals[m["name"]], "unit": m["unit"]}
    else:
        t0 = time.perf_counter()
        red = job.tracer.reduce()
        print(f"trace reduced in {time.perf_counter() - t0:.1f} s",
              file=sys.stderr)
        ctx = dict(res["ctx"], trace=red, peak=peaks.peak(dev.device_kind),
                   cfg=job.model_cfg)
        for m in wanted:
            v = spec.metric_reader(m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        device["busy_s"] = red.mean_busy_s
        device["window_s"] = red.window_s
        out["breakdown"] = {"device_ops": red.top_ops(10),
                            "idle_gaps": red.idle_gaps[:10]}
    out["metrics"] = metrics
    out["device"] = device
    out["checks"] = {k: {"value": c["value"], "limit": c["limit"]}
                     for k, c in checks.items()}
    print(f"setup_s {job.setup_s!r}; window_s {job.window_obj.seconds!r}; "
          f"ctx {json.dumps({k: v for k, v in res['ctx'].items()})}",
          file=sys.stderr)
    correct.report(checks)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=int, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    import jax
    from bench import harness, spec
    bench = spec.benchmark()
    chips = spec.cell(args.workload, bench)["chips"]
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < chips:
        print(f"bench: the cell needs {chips} TPU chip(s); JAX finds "
              f"{len(devices)} {devices[0].platform} device(s). Nothing was "
              f"run.", file=sys.stderr)
        return 2
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    job = Job(bench, args.workload, args.seed, args.seconds, bool(args.trace),
              devices, harness.CompileCounter())
    out = execute(job)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
