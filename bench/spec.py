"""Everything the harness reads from disk, found by name.

A cell of ``BENCHMARK.json`` names a configuration and a traffic mix; each is
a data file of its own under ``bench/`` (``configs/<name>.json``,
``traffic/<name>.json``), and each cell's correctness limits sit in
``limits/<cell>.json``.  A configuration names its plain reference
(``reference/<name>.py``), a mix its driver (``drivers/<name>.py``).
Per-layer metrics are readers in ``metrics/<metric>.py``.  Adding a cell, a configuration, a mix or a metric
is adding files and entries; no file here changes for it.
"""

from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark() -> dict:
    return load_json(ROOT / "BENCHMARK.json")


def cell(name: str, bench: dict | None = None) -> dict:
    bench = bench if bench is not None else benchmark()
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def config_file(name: str, bench: dict | None = None) -> dict:
    bench = bench if bench is not None else benchmark()
    for c in bench["configs"]:
        if c["name"] == name:
            return load_json(ROOT / c["file"])
    raise KeyError(f"no config {name!r} in BENCHMARK.json")


def traffic(name: str) -> dict:
    return load_json(BENCH / "traffic" / f"{name}.json")


def limits(workload: str) -> dict:
    return load_json(BENCH / "limits" / f"{workload}.json")


def metrics_for(bench: dict, workload: str, trace: bool) -> list:
    """The metric entries this cell reports: its end-to-end metrics, or
    with a trace its per-layer ones (a metric without ``workloads`` is
    reported wherever the end-to-end metric it moves is)."""
    e2e = [m for m in bench["end_to_end"]
           if workload in m.get("workloads", [workload])]
    if not trace:
        return e2e
    mine = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"]
            if workload in m.get("workloads", [workload] if m["moves"] in mine
                                 else [])]


def model_config(conf: dict):
    """The program's ``ModelConfig`` from a configuration file's ``model``
    group (its keys are ``ModelConfig`` fields)."""
    from repro.models.config import ModelConfig
    fields = {f.name for f in dataclasses.fields(ModelConfig)}
    unknown = set(conf["model"]) - fields
    if unknown:
        raise KeyError(f"configuration keys that ModelConfig lacks: {unknown}")
    return ModelConfig(**conf["model"])


def plan(conf: dict):
    from repro.core.recipe import ParallelismConfig
    return ParallelismConfig(**conf.get("plan", {}))


def reference(conf: dict):
    """The plain reference module a configuration file names
    (``reference/<name>.py``)."""
    return importlib.import_module(f"bench.reference.{conf['reference']}")


def metric_reader(name: str):
    """The ``read(ctx)`` function of ``metrics/<name>.py``."""
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
