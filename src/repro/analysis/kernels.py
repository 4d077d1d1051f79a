"""Pallas kernel validator: static checks over captured grid specs.

``capture_pallas_calls`` monkeypatches ``pl.pallas_call`` with a recorder
that *does not run the kernel* — it records (grid, BlockSpecs, out shapes,
scalar-prefetch values, dimension semantics) and returns zeros of
``out_shape``, so even a deliberately broken spec captures cleanly and the
driver code around the kernel (transposes, padding) still traces.

Checks per captured call:

* **block divisibility** — every blocked dim must divide its array dim
  (Pallas pads silently; these kernels assume exact tiling, and a misdivided
  block reads garbage into the masked softmax).
* **index-map bounds** — evaluating the index map over the whole grid, every
  block offset must land inside the array.
* **grid coverage** — the union of output block indices must cover every
  output tile, else some tiles are never written (stale VMEM).
* **write races** — two grid points mapping to the same output tile while
  differing in a ``parallel`` grid dim race; revisits are only legal along
  ``arbitrary`` dims (the accumulation sweep).
"""

from __future__ import annotations

import contextlib
import dataclasses
import itertools
from typing import Any, List, Optional, Sequence, Tuple

import numpy as np

from repro.analysis.findings import Finding, Severity

# full-grid sweeps are capped; past this we check a deterministic sample of
# grid points and skip the coverage proof (can't prove coverage on a sample)
_MAX_GRID_POINTS = 65536


@dataclasses.dataclass
class KernelArg:
    name: str                         # in0, in1, ... / out0, ...
    shape: Tuple[int, ...]            # declared array shape
    block_shape: Optional[Tuple[Optional[int], ...]]
    index_map: Optional[Any]          # callable(*grid_ids, *scalar_args)


@dataclasses.dataclass
class KernelCapture:
    kernel: str                       # kernel function name
    grid: Tuple[int, ...]
    in_args: List[KernelArg]
    out_args: List[KernelArg]
    num_scalar_prefetch: int = 0
    scalar_values: Tuple[Any, ...] = ()   # concrete prefetch arrays
    dimension_semantics: Optional[Tuple[str, ...]] = None


def _specs_of(obj) -> list:
    if obj is None:
        return []
    return list(obj) if isinstance(obj, (list, tuple)) else [obj]


@contextlib.contextmanager
def capture_pallas_calls(records: List[KernelCapture]):
    """Record every ``pl.pallas_call`` spec reached inside the block, stubbing
    out the kernel execution (returns zeros of ``out_shape``)."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    orig = pl.pallas_call

    def recorder(kernel, *, out_shape=None, grid=None, grid_spec=None,
                 in_specs=None, out_specs=None, scratch_shapes=(),
                 compiler_params=None, interpret=False, **kw):
        nsp = 0
        if grid_spec is not None:
            grid = tuple(grid_spec.grid)
            in_specs = _specs_of(grid_spec.in_specs)
            out_specs = _specs_of(grid_spec.out_specs)
            nsp = int(getattr(grid_spec, "num_scalar_prefetch", 0) or 0)
        else:
            grid = tuple(grid) if grid is not None else ()
            in_specs = _specs_of(in_specs)
            out_specs = _specs_of(out_specs)
        sem = None
        if compiler_params is not None:
            sem = getattr(compiler_params, "dimension_semantics", None)
            if sem is None and isinstance(compiler_params, dict):
                sem = compiler_params.get("mosaic", {}).get(
                    "dimension_semantics")
        out_shapes = _specs_of(out_shape)
        kname = getattr(kernel, "func", kernel)    # unwrap functools.partial
        kname = getattr(kname, "__name__", str(kernel))

        def stub(*inputs):
            scalars = []
            for x in inputs[:nsp]:
                try:
                    scalars.append(np.asarray(x))
                except Exception:  # noqa: BLE001 — traced prefetch value
                    scalars = []
                    break
            scalars = tuple(scalars)
            arrs = inputs[nsp:]
            cap = KernelCapture(
                kernel=kname, grid=grid,
                in_args=[KernelArg(
                    f"in{i}", tuple(a.shape),
                    tuple(s.block_shape) if s is not None and
                    s.block_shape is not None else None,
                    s.index_map if s is not None else None)
                    for i, (s, a) in enumerate(zip(in_specs, arrs))],
                out_args=[KernelArg(
                    f"out{i}", tuple(o.shape),
                    tuple(s.block_shape) if s is not None and
                    s.block_shape is not None else None,
                    s.index_map if s is not None else None)
                    for i, (s, o) in enumerate(zip(out_specs, out_shapes))],
                num_scalar_prefetch=nsp, scalar_values=scalars,
                dimension_semantics=tuple(sem) if sem else None)
            records.append(cap)
            zeros = [jnp.zeros(o.shape, o.dtype) for o in out_shapes]
            if out_shape is None:
                return None
            if isinstance(out_shape, (list, tuple)):
                return type(out_shape)(zeros) if isinstance(out_shape, list) \
                    else tuple(zeros)
            return zeros[0]

        return stub

    pl.pallas_call = recorder
    try:
        yield records
    finally:
        pl.pallas_call = orig


# ---------------------------------------------------------------------------
# checks
# ---------------------------------------------------------------------------

def _grid_points(grid: Tuple[int, ...]):
    """(points, sampled?) — full cartesian sweep, or a deterministic sample
    (all axis-aligned edges) past the cap."""
    total = int(np.prod(grid)) if grid else 0
    if total <= _MAX_GRID_POINTS:
        return list(itertools.product(*[range(g) for g in grid])), False
    pts = set()
    base = tuple(0 for _ in grid)
    pts.add(base)
    for d, g in enumerate(grid):
        for v in range(g):
            p = list(base)
            p[d] = v
            pts.add(tuple(p))
            q = [x - 1 for x in grid]
            q[d] = v
            pts.add(tuple(q))
    return sorted(pts), True


def _eval_map(arg: KernelArg, pt: Sequence[int],
              scalars: Tuple[Any, ...]) -> Optional[Tuple[int, ...]]:
    if arg.index_map is None:
        return tuple(0 for _ in (arg.block_shape or arg.shape))
    idx = arg.index_map(*pt, *scalars)
    if not isinstance(idx, tuple):
        idx = (idx,)
    return tuple(int(i) for i in idx)


def check_kernel(cap: KernelCapture, *,
                 pass_name: str = "kernels") -> List[Finding]:
    out: List[Finding] = []
    pts, sampled = _grid_points(cap.grid)
    sem = cap.dimension_semantics or tuple("arbitrary" for _ in cap.grid)
    maps_checkable = (cap.num_scalar_prefetch == 0
                      or len(cap.scalar_values) == cap.num_scalar_prefetch)
    if not maps_checkable:
        out.append(Finding(
            pass_name=pass_name, code="scalar-values-unavailable",
            severity=Severity.INFO, where=cap.kernel,
            message="scalar-prefetch values were traced at capture time; "
                    "index-map bounds/coverage not evaluated"))
    if sampled:
        out.append(Finding(
            pass_name=pass_name, code="grid-sampled", severity=Severity.INFO,
            where=cap.kernel,
            message=f"grid {cap.grid} exceeds {_MAX_GRID_POINTS} points; "
                    f"bounds checked on an edge sample, coverage not proven"))

    for arg in (*cap.in_args, *cap.out_args):
        where = f"{cap.kernel}/{arg.name}"
        if arg.block_shape is None:
            continue
        bs = tuple(b if b is not None else s
                   for b, s in zip(arg.block_shape, arg.shape))
        if len(bs) != len(arg.shape):
            out.append(Finding(
                pass_name=pass_name, code="block-rank-mismatch",
                severity=Severity.ERROR, where=where,
                message=f"block_shape {arg.block_shape} has rank "
                        f"{len(bs)} but the array is rank "
                        f"{len(arg.shape)} ({arg.shape})"))
            continue
        for d, (b, s) in enumerate(zip(bs, arg.shape)):
            if b <= 0 or s % b:
                out.append(Finding(
                    pass_name=pass_name, code="block-not-divisible",
                    severity=Severity.ERROR, where=f"{where}[{d}]",
                    message=f"block dim {d} = {b} does not divide array dim "
                            f"{s} (shape {arg.shape}, block "
                            f"{arg.block_shape}) — Pallas would pad and the "
                            f"kernel reads out-of-range data"))

        if not maps_checkable:
            continue
        # bounds over the (possibly sampled) grid
        oob = 0
        first_bad = None
        visited = {}
        for pt in pts:
            try:
                idx = _eval_map(arg, pt, cap.scalar_values)
            except Exception as e:  # noqa: BLE001 — map itself is broken
                out.append(Finding(
                    pass_name=pass_name, code="index-map-error",
                    severity=Severity.ERROR, where=where,
                    message=f"index map raised at grid point {pt}: "
                            f"{type(e).__name__}: {e}"))
                oob = -1
                break
            if len(idx) != len(bs):
                out.append(Finding(
                    pass_name=pass_name, code="index-map-rank",
                    severity=Severity.ERROR, where=where,
                    message=f"index map returned {len(idx)} indices for a "
                            f"rank-{len(bs)} block at grid point {pt}"))
                oob = -1
                break
            bad = any(i < 0 or (i + 1) * b > s + (b - s % b) % b
                      for i, b, s in zip(idx, bs, arg.shape))
            # exact bound when divisible: block index must satisfy
            # (i+1)*b <= s; the expression above degrades to that
            if bad:
                oob += 1
                first_bad = first_bad or (pt, idx)
            visited.setdefault(idx, pt)
        if oob > 0:
            pt, idx = first_bad
            out.append(Finding(
                pass_name=pass_name, code="index-out-of-bounds",
                severity=Severity.ERROR, where=where,
                message=f"{oob}/{len(pts)} grid points map outside the "
                        f"array: e.g. grid {pt} → block {idx} with block "
                        f"{bs} in shape {arg.shape}"))

        if arg.name.startswith("out") and oob == 0:
            # coverage: every output tile written at least once
            if not sampled:
                tiles = int(np.prod([s // b for b, s in zip(bs, arg.shape)
                                     if b]))
                if len(visited) < tiles:
                    out.append(Finding(
                        pass_name=pass_name, code="uncovered-output-tile",
                        severity=Severity.ERROR, where=where,
                        message=f"grid writes {len(visited)} of {tiles} "
                                f"output tiles — unwritten tiles hold stale "
                                f"memory"))
            # races: same tile from two points differing in a parallel dim
            race = None
            for pt in pts:
                idx = _eval_map(arg, pt, cap.scalar_values)
                prev = visited.get(idx)
                if prev is not None and prev != pt:
                    for d, (a, b2) in enumerate(zip(prev, pt)):
                        if a != b2 and d < len(sem) and sem[d] == "parallel":
                            race = (prev, pt, idx, d)
                            break
                if race:
                    break
            if race:
                prev, pt, idx, d = race
                out.append(Finding(
                    pass_name=pass_name, code="write-race",
                    severity=Severity.ERROR, where=where,
                    message=f"grid points {prev} and {pt} both write output "
                            f"tile {idx} but differ in grid dim {d} declared "
                            f"'parallel' — unordered writes race"))
    return out


# ---------------------------------------------------------------------------
# the repo's kernel surfaces, captured at representative shapes
# ---------------------------------------------------------------------------

def default_kernel_captures(cfg=None) -> List[KernelCapture]:
    """Capture the flash fwd+bwd and (paged) decode kernels at small
    representative shapes derived from ``cfg`` (falls back to a generic GQA
    shape).  Calls the un-jitted entry points so nothing lands in jit caches
    and scalar-prefetch values stay concrete."""
    import jax
    import jax.numpy as jnp
    from repro.kernels import decode_attention as da
    from repro.kernels import flash_attention as fa

    B, S, bq, bk = 2, 256, 128, 128
    Hq = max(2, int(getattr(cfg, "n_heads", 4) or 4)) if cfg else 4
    Hkv = int(getattr(cfg, "n_kv_heads", Hq) or Hq) if cfg else 2
    if Hq % Hkv:
        Hkv = Hq
    D = int(getattr(cfg, "hd", 16) or 16) if cfg else 16

    key = jax.random.PRNGKey(0)
    q = jax.random.normal(key, (B, S, Hq, D), jnp.float32)
    k = jax.random.normal(key, (B, S, Hkv, D), jnp.float32)
    v = jax.random.normal(key, (B, S, Hkv, D), jnp.float32)

    records: List[KernelCapture] = []
    with capture_pallas_calls(records):
        o, lse = fa._forward(q, k, v, None, True, None, bq, bk, None,
                             D ** -0.5, False)
        fa._backward(q, k, v, None, o, lse, jnp.ones_like(o),
                     True, None, bq, bk, None, D ** -0.5, False)

        Sc, bkd = 512, 128
        kc = jax.random.normal(key, (B, Sc, Hkv, D), jnp.float32)
        vc = jax.random.normal(key, (B, Sc, Hkv, D), jnp.float32)
        kpos = jnp.broadcast_to(jnp.arange(Sc, dtype=jnp.int32), (B, Sc))
        qd = q[:, :1]
        da.decode_attention.__wrapped__(qd, kc, vc, kpos,
                                        t=jnp.int32(Sc - 1), window=None,
                                        bk=bkd, interpret=False)

        n_pages, ps, n_max = 8, 64, 4
        kp = jax.random.normal(key, (n_pages, ps, Hkv, D), jnp.float32)
        vp = jax.random.normal(key, (n_pages, ps, Hkv, D), jnp.float32)
        pt = jnp.tile(jnp.arange(n_max, dtype=jnp.int32)[None], (B, 1))
        ts = jnp.full((B,), ps * n_max - 1, jnp.int32)
        da.paged_decode_attention.__wrapped__(qd, kp, vp, pt, ts=ts,
                                              window=None, interpret=False)
    return records


class PallasKernelPass:
    name = "kernels"
    requires = ("kernels",)

    def run(self, ctx) -> List[Finding]:
        out: List[Finding] = []
        for cap in ctx.kernels:
            out.extend(check_kernel(cap, pass_name=self.name))
        if not ctx.kernels:
            out.append(Finding(
                pass_name=self.name, code="no-kernels-captured",
                severity=Severity.INFO, where="capture",
                message="no pallas_call reached during capture"))
        return out


from repro.analysis.registry import register_pass  # noqa: E402

register_pass(PallasKernelPass)
