"""Logical-axis sharding: the recipe's placement rules in one place.

The paper's recipe is *placement*: TP collectives on the fast intra-node
domain, PP across nodes, ZeRO-DP across the slowest domain.  We express that
as logical axis names on parameters/activations, resolved against whatever
physical mesh the launcher built.  Everything no-ops when no rules are
installed (CPU unit tests).
"""

from __future__ import annotations

import re
import threading
from contextlib import contextmanager
from typing import Any, Dict, Optional, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

_state = threading.local()


def _rules() -> Optional["AxisRules"]:
    return getattr(_state, "rules", None)


class AxisRules:
    """Maps logical axis names → physical mesh axis names (or None).

    Mesh-resilient: axes absent from the mesh are dropped, and the recipe's
    "tp" resolves to the raw production mesh's "model" axis when the logical
    (pod, data, pp, tp) factorization has not been applied."""

    ALIASES = {"tp": "model"}

    def __init__(self, mesh: Mesh, mapping: Dict[str, Any]):
        self.mesh = mesh
        self.mapping = dict(mapping)

    def _present(self, ax):
        """Filter/alias one mesh-axis name (or tuple) against the mesh."""
        if ax is None:
            return None
        if isinstance(ax, (tuple, list)):
            out = tuple(a for a in (self._present(x) for x in ax) if a is not None)
            return out if out else None
        if ax in self.mesh.axis_names:
            return ax
        alias = self.ALIASES.get(ax)
        if alias and alias in self.mesh.axis_names:
            return alias
        return None

    def resolve(self, logical: Tuple[Optional[str], ...]) -> P:
        phys = []
        used = set()
        for name in logical:
            if name is None:
                phys.append(None)
                continue
            ax = self._present(self.mapping.get(name))
            if ax is None:
                phys.append(None)
            elif isinstance(ax, (tuple, list)):
                ax = tuple(a for a in ax if a not in used)
                used.update(ax)
                phys.append(ax if len(ax) > 1 else (ax[0] if ax else None))
            else:
                if ax in used:
                    phys.append(None)
                else:
                    used.add(ax)
                    phys.append(ax)
        return P(*phys)


@contextmanager
def axis_rules(mesh: Mesh, mapping: Dict[str, Any]):
    old = _rules()
    _state.rules = AxisRules(mesh, mapping)
    try:
        yield _state.rules
    finally:
        _state.rules = old


def active_rules() -> Optional[AxisRules]:
    """The axis rules installed by the enclosing ``axis_rules`` block (None
    outside one): code that must place work per shard — Pallas kernels, which
    XLA cannot partition — reads the mesh from here."""
    return _rules()


def logical(*names: Optional[str]) -> Tuple[Optional[str], ...]:
    return names


def constrain(x: jax.Array, *names: Optional[str]) -> jax.Array:
    """Apply a with_sharding_constraint if axis rules are installed."""
    r = _rules()
    if r is None:
        return x
    spec = r.resolve(tuple(names))
    return jax.lax.with_sharding_constraint(x, NamedSharding(r.mesh, spec))


# ---------------------------------------------------------------------------
# parameter partition rules (path-regex → logical axes)
# ---------------------------------------------------------------------------

# Order matters: first match wins.  Family-specific placements (MoE expert
# tensors, SSM scan params) live on ``ModelFamily.param_sharding_hints`` and
# are consulted *before* this list via ``extra_rules``.  Axis names:
#   "tp"    — tensor-parallel (fast domain; paper's TP ≤ node rule)
#   "fsdp"  — ZeRO-3 parameter sharding axis (the data axis)
#   "stage" — pipeline stage axis (leading axis of stacked block params)
#   "layers"— scanned layer axis (never sharded)
PARAM_RULES = [
    (r"\bembed\b$", ("tp", "embed")),                       # (V, d) vocab-sharded
    (r"\blm_head\b$", ("tp", "embed")),
    (r"\bpos_embed\b$", (None, "embed")),
    (r"\bwq\b$|\bwk\b$|\bwv\b$", ("embed", "tp")),
    (r"\bwo\b$", ("tp", "embed")),
    (r"\bbq\b$|\bbk\b$|\bbv\b$", ("tp",)),
    (r"\bw_gate\b$|\bw_up\b$|\bw_in\b$", ("embed", "tp")),  # MLP in-proj: d_ff sharded
    (r"\bw_out\b$", ("tp", "embed")),                       # MLP out-proj
    (r"\bb_in\b$", ("tp",)),
    (r"\bb_out\b$", ("embed",)),
    (r"\bin_proj\b$", ("embed", "tp")),                     # SSM / xLSTM
    (r"\bbc_proj\b$", ("embed", None)),
    (r"\bout_proj\b$", ("tp", "embed")),
    (r"\bconv\b$", (None, "tp")),
    (r"\b(A_log|D|dt_bias|b_igate|b_fgate)\b$", (None,)),
    (r"\bw_igate\b$|\bw_fgate\b$", ("embed", None)),
    (r"\b(rz|ri|rf|ro)\b$", (None, None, None)),            # sLSTM recurrent (block-diag)
    (r"\b(wz|wi|wf|wo_s)\b$", ("embed", "tp")),
    (r"\b(bz|bi|bf|bo)\b$", (None,)),
    (r"\bscale\b$|\bbias\b$", (None,)),                     # norms
]


def spec_for_path(path: str, shape: Tuple[int, ...], *, stacked_axes: int = 0,
                  extra_rules: Tuple = ()) -> Tuple[Optional[str], ...]:
    """Logical axes for a parameter; ``stacked_axes`` leading axes are
    (layers) / (stage, layers) / (chunks, stage, layers) from scan, pipeline,
    and interleaved virtual-stage stacking respectively.  ``extra_rules``
    (family ``param_sharding_hints``) are matched before ``PARAM_RULES``."""
    prefix: Tuple[Optional[str], ...] = ()
    if stacked_axes == 1:
        prefix = ("layers",)
    elif stacked_axes == 2:
        prefix = ("stage", "layers")
    elif stacked_axes == 3:
        prefix = ("chunks", "stage", "layers")
    for pat, axes in tuple(extra_rules) + tuple(PARAM_RULES):
        if re.search(pat, path):
            axes = tuple(axes)
            if len(axes) + len(prefix) < len(shape):  # e.g. (E,d,ff) expert leaves
                axes = (None,) * (len(shape) - len(prefix) - len(axes)) + axes
            return prefix + axes[: len(shape) - len(prefix)]
    return prefix + (None,) * (len(shape) - len(prefix))


def tree_logical_specs(params, *, stacked_axes_fn=None, extra_rules: Tuple = ()):
    """Mirror tree of logical-axis tuples for a parameter pytree."""
    def visit(path, leaf):
        pstr = "/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in path)
        sa = stacked_axes_fn(pstr) if stacked_axes_fn else 0
        return spec_for_path(pstr, leaf.shape, stacked_axes=sa,
                             extra_rules=extra_rules)
    return jax.tree_util.tree_map_with_path(visit, params)


def sanitize(ns: NamedSharding, shape: Tuple[int, ...], mesh: Mesh) -> NamedSharding:
    """Drop partitioning on dims the mesh axes do not divide (odd vocab sizes,
    head counts like 14/25/40 vs a 16-wide tp axis, ...)."""
    parts = list(ns.spec) + [None] * (len(shape) - len(ns.spec))
    fixed = []
    for dim, p in zip(shape, parts):
        if p is None:
            fixed.append(None)
            continue
        axes = p if isinstance(p, tuple) else (p,)
        ways = 1
        for a in axes:
            ways *= mesh.shape[a]
        fixed.append(p if (dim % ways == 0 and dim >= ways) else None)
    return NamedSharding(mesh, P(*fixed))


def resolve_tree(specs, mesh: Mesh, mapping: Dict[str, Any], shapes_tree=None):
    """Logical-axis tree → NamedSharding tree (divisibility-sanitized when
    a matching tree of array shapes is supplied)."""
    rules = AxisRules(mesh, mapping)
    if shapes_tree is None:
        return jax.tree_util.tree_map(
            lambda ax: NamedSharding(mesh, rules.resolve(ax)),
            specs, is_leaf=lambda x: isinstance(x, tuple))
    return jax.tree_util.tree_map(
        lambda ax, leaf: sanitize(NamedSharding(mesh, rules.resolve(ax)),
                                  leaf.shape, mesh),
        specs, shapes_tree, is_leaf=lambda x: isinstance(x, tuple))
