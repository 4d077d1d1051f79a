import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (architecture × input shape) on
the production mesh (single-pod 16×16 and multi-pod 2×16×16), print
``memory_analysis()`` / ``cost_analysis()``, parse collective bytes from the
compiled HLO, and persist one JSON per cell for the roofline table.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch granite_3_2b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod] [--out results/dryrun]
"""

import argparse
import json
import time
import traceback
from pathlib import Path

import dataclasses

from repro import configs as cfg_mod
from repro.core.cost_model import active_params, model_flops_per_token
from repro.core.recipe import ParallelismConfig
from repro.launch import plans as plans_mod
from repro.launch import shapes as shapes_mod
from repro.launch.hlo_analysis import analyze_module, collective_bytes
from repro.launch.mesh import make_production_mesh, make_recipe_mesh
from repro.models.config import ModelConfig
from repro.session import InferenceSession, TrainSession


def _train_artifacts(cfg: ModelConfig, plan: ParallelismConfig, mesh, shape):
    """(lowered, aux-info) for a train_step cell — an abstract TrainSession
    composes state shapes, shardings and the sharded step; we just lower."""
    from repro.runtime import flags
    sess = TrainSession.from_recipe(cfg, plan=plan, mesh=mesh, abstract=True)
    lowered = sess.lower(shapes_mod.train_input_specs(cfg, shape))
    tokens = shape.global_batch * shape.seq_len
    # flash-trained attention carries the recompute-style backward multiplier
    useful = model_flops_per_token(
        cfg, shape.seq_len, flash_backward=flags.use_flash_attention()) * tokens
    return lowered, {"model_flops": useful}


def _serve_artifacts(cfg: ModelConfig, plan: ParallelismConfig, mesh, shape,
                     *, prefill_last_only: bool = False):
    """(lowered, aux) for serve_step (decode) or prefill cells."""
    B = shape.global_batch
    sess = InferenceSession.from_recipe(cfg, plan=plan, mesh=mesh, abstract=True)
    if shape.kind == "prefill":
        lowered = sess.lower_prefill(sess.prefill_input_specs(B, shape.seq_len),
                                     last_only=prefill_last_only)
        useful = 2.0 * active_params(cfg) * B * shape.seq_len
        return lowered, {"model_flops": useful}
    # decode: one token against a KV/state cache of seq_len
    lowered = sess.lower_decode(B, shape.seq_len)
    useful = 2.0 * active_params(cfg) * B
    return lowered, {"model_flops": useful}


def _lint_cell(rec: dict, hlo: str, cfg, plan, mesh, kind: str,
               verbose: bool) -> None:
    """``--lint``: run the HLO-level audit passes over an already-compiled
    dry-run cell (collectives vs plan; donation/jaxpr passes need the richer
    contexts ``repro.launch.lint`` builds, so they stay there)."""
    from repro.analysis.context import LintContext
    from repro.analysis.registry import run_passes
    ctx = LintContext(cell=f"{rec['arch']}__{rec['shape']}__{rec['mesh']}",
                      cfg=cfg, plan=plan, mesh=mesh, kind=kind,
                      lower_fn=lambda: None)
    ctx._cache["hlo"] = hlo              # already compiled — reuse the text
    report = run_passes(ctx)
    rec["lint"] = report.to_json()
    worst = report.worst()
    if verbose:
        print(report.format_text())
    rec["lint_worst"] = worst.name if worst is not None else None


def run_cell(arch: str, shape_name: str, *, multi_pod: bool, out_dir: Path,
             verbose: bool = True, sp: bool = False,
             prefill_last_only: bool = False, remat: str = None,
             gather_once: bool = False, tag: str = "",
             lint: bool = False) -> dict:
    cfg = cfg_mod.get_config(arch)
    shape = shapes_mod.SHAPES[shape_name]
    ok, why = shapes_mod.applicable(cfg, shape)
    mesh_tag = ("multipod" if multi_pod else "pod") + (f"-{tag}" if tag else "")
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_tag,
           "status": "skip", "reason": why,
           "variant": {"sp": sp,
                       "prefill_last_only": prefill_last_only, "remat": remat}}
    if not ok:
        if verbose:
            print(f"[dryrun] {arch} × {shape_name}: SKIP ({why})")
        return rec

    plan = plans_mod.make_plan(arch, cfg, shape, multi_pod=multi_pod)
    if sp:
        plan = dataclasses.replace(plan, sequence_parallel=True)
    if remat:
        plan = dataclasses.replace(plan, remat_policy=remat)
    if gather_once:
        plan = dataclasses.replace(plan, gather_params_once=True)
    if plan.pp > 1 or plan.tp != 16:
        mesh = make_recipe_mesh(pp=plan.pp, tp=plan.tp, multi_pod=multi_pod)
    else:
        mesh = make_production_mesh(multi_pod=multi_pod)

    t0 = time.time()
    try:
        with mesh:
            if shape.kind == "train":
                lowered, aux = _train_artifacts(cfg, plan, mesh, shape)
            else:
                lowered, aux = _serve_artifacts(
                    cfg, plan, mesh, shape, prefill_last_only=prefill_last_only)
            compiled = lowered.compile()
        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis() or {}
        hlo = compiled.as_text()
        coll = collective_bytes(hlo)          # body-once (raw) counts
        # Pallas kernels are opaque custom-calls: credit the flash matmuls
        # analytically (fwd + recompute-style bwd for train cells), spread
        # uniformly over the per-layer flash call sites.  Only valid when
        # flash attention is the sole Pallas kernel in the module — other
        # kernel flags would add custom-calls this can't tell apart.
        from repro.launch import hlo_analysis as _ha
        from repro.runtime import flags as _flags
        cc_flops = None
        if _flags.use_flash_attention() and cfg.family != "ssm" and not (
                _flags.use_fused_rmsnorm() or _flags.use_flash_decode()):
            fwd = _ha.flash_attention_flops(
                shape.global_batch, cfg.n_heads, shape.seq_len, shape.seq_len,
                cfg.hd, causal=True, window=cfg.swa_window, backward=False)
            if shape.kind == "train":
                # fwd + delta/dQ/dKV bwd kernels; remat re-emits the forward
                remat = plan.remat_policy != "none"
                total = fwd * (3.5 + (2.0 if remat else 1.0))
                per_call = total / (5 if remat else 4)
            else:
                per_call = fwd
            per_call /= mesh.devices.size
            cc_flops = {"tpu_custom_call": per_call, "MosaicTPU": per_call}
        walk = analyze_module(hlo, custom_call_flops=cc_flops)  # trip-weighted
        if lint:
            _lint_cell(rec, hlo, cfg, plan, mesh, shape.kind, verbose)
        t1 = time.time()
        rec.update({
            "status": "ok",
            "plan": {"tp": plan.tp, "pp": plan.pp, "dp": plan.dp,
                     "pods": plan.pods, "mbs": plan.mbs, "gas": plan.gas,
                     "zero": plan.zero_stage},
            "devices": mesh.devices.size,
            "compile_s": round(t1 - t0, 1),
            "memory": {
                "argument_bytes": mem.argument_size_in_bytes,
                "output_bytes": mem.output_size_in_bytes,
                "temp_bytes": mem.temp_size_in_bytes,
                "alias_bytes": mem.alias_size_in_bytes,
                "peak_per_device": mem.argument_size_in_bytes
                + mem.output_size_in_bytes + mem.temp_size_in_bytes
                - mem.alias_size_in_bytes,
            },
            "cost_raw": {"flops_per_device": cost.get("flops", 0.0),
                         "bytes_per_device": cost.get("bytes accessed", 0.0)},
            # trip-count-weighted per-device totals (see hlo_analysis.py)
            "hlo": {
                "flops_per_device": walk["flops"],
                "bytes_per_device": walk["bytes"],
                "collective_bytes_per_device": walk["collective_total"],
                "collectives": {k: walk[k] for k in
                                ("all-reduce", "all-gather", "reduce-scatter",
                                 "all-to-all", "collective-permute")},
            },
            "collectives_raw": coll,
            "model_flops": aux["model_flops"],
        })
        if verbose:
            m = rec["memory"]
            print(f"[dryrun] {arch} × {shape_name} × {mesh_tag}: OK "
                  f"({rec['compile_s']}s) peak/dev="
                  f"{m['peak_per_device']/2**30:.2f}GiB "
                  f"flops/dev={walk['flops']:.3g} "
                  f"coll/dev={walk['collective_total']/2**20:.1f}MiB")
    except Exception as e:  # noqa: BLE001 — failures are data here
        rec.update({"status": "fail", "error": f"{type(e).__name__}: {e}",
                    "traceback": traceback.format_exc()[-2000:]})
        if verbose:
            print(f"[dryrun] {arch} × {shape_name} × {mesh_tag}: FAIL {e}")
    out_dir.mkdir(parents=True, exist_ok=True)
    with open(out_dir / f"{arch}__{shape_name}__{mesh_tag}.json", "w") as f:
        json.dump(rec, f, indent=1, default=str)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--sp", action="store_true", help="sequence parallelism")
    ap.add_argument("--prefill-last-only", action="store_true")
    ap.add_argument("--remat", default=None, choices=[None, "none", "dots", "full", "stage"])
    ap.add_argument("--gather-once", action="store_true")
    ap.add_argument("--serve-tp", type=int, default=None,
                    help="override serving TP degree (head-aligned hillclimb)")
    ap.add_argument("--tag", default="", help="suffix for result filenames")
    ap.add_argument("--lint", action="store_true",
                    help="run the lowering-audit HLO passes over each cell "
                         "(full audit incl. jaxpr/kernels: repro.launch.lint)")
    args = ap.parse_args()

    out_dir = Path(args.out)
    if args.serve_tp:
        from repro.launch import plans as _plans
        for a in cfg_mod.ARCH_IDS:
            _plans.SERVE_TP[a] = args.serve_tp
    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    results = []
    if args.all:
        pairs = shapes_mod.cells({a: cfg_mod.get_config(a) for a in cfg_mod.ASSIGNED})
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        pairs = [(args.arch, args.shape)]
    for arch, shape in pairs:
        for mp in meshes:
            results.append(run_cell(
                arch, shape, multi_pod=mp, out_dir=out_dir, sp=args.sp,
                prefill_last_only=args.prefill_last_only,
                remat=args.remat, gather_once=args.gather_once, tag=args.tag,
                lint=args.lint))
    n_ok = sum(r["status"] == "ok" for r in results)
    n_skip = sum(r["status"] == "skip" for r in results)
    n_fail = sum(r["status"] == "fail" for r in results)
    print(f"[dryrun] done: {n_ok} ok, {n_skip} skip, {n_fail} fail "
          f"of {len(results)} cells")
    return 1 if n_fail else 0


if __name__ == "__main__":
    raise SystemExit(main())
