"""Lowering-auditor unit tests (single device).

Covers the findings/baseline model, the pass registry, the HLO parsing the
collective/donation audits stand on, and — for each pass family — one clean
run over the repo's real artifacts plus one *seeded violation* the pass must
catch (the CI gate's ``--prove-gate`` contract in miniature).
"""

import dataclasses
import json

import jax
import jax.numpy as jnp
import pytest

from repro import configs as cfg_mod
from repro.analysis import (Finding, Report, Severity, load_baseline,
                            registered_passes, run_passes, save_baseline)
from repro.analysis.context import DonationInfo, LintContext
from repro.analysis.kernels import (KernelArg, KernelCapture,
                                    capture_pallas_calls, check_kernel,
                                    default_kernel_captures)
from repro.analysis.memory import audit_donation, f32_dot_findings
from repro.analysis.recompile import (ProbeSpec, RecompileHazardPass,
                                      probe_shape_dependence)


def _cfg(arch="granite_3_2b", dtype="bfloat16"):
    return dataclasses.replace(cfg_mod.get_config(arch).reduced(), dtype=dtype)


# ---------------------------------------------------------------------------
# findings / baseline model
# ---------------------------------------------------------------------------

def test_fingerprint_stable_across_messages():
    a = Finding(pass_name="p", code="c", severity=Severity.WARNING,
                message="saw 123 bytes", where="opt/m/w")
    b = Finding(pass_name="p", code="c", severity=Severity.ERROR,
                message="saw 456 bytes this time", where="opt/m/w")
    assert a.fingerprint == b.fingerprint          # message/severity excluded
    c = Finding(pass_name="p", code="c", severity=Severity.WARNING,
                message="", where="opt/v/w")
    assert a.fingerprint != c.fingerprint          # where included


def test_baseline_suppression_and_gate(tmp_path):
    rep = Report("cell")
    rep.add(Finding(pass_name="p", code="x", severity=Severity.WARNING,
                    message="m", where="a"))
    rep.add(Finding(pass_name="p", code="y", severity=Severity.ERROR,
                    message="m", where="b"))
    assert len(rep.active(Severity.WARNING)) == 2
    path = tmp_path / "baseline.json"
    save_baseline(path, {"cell": [rep.findings[0].fingerprint]})
    rep.apply_baseline(load_baseline(path)["cell"])
    active = rep.active(Severity.WARNING)
    assert [f.code for f in active] == ["y"]       # x suppressed, y still gates
    assert rep.worst() == Severity.ERROR


def test_baseline_missing_file_is_empty(tmp_path):
    assert load_baseline(tmp_path / "nope.json") == {}


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

def test_builtin_passes_registered_in_order():
    names = registered_passes()
    for expected in ("collectives", "donation", "dtype", "replication",
                     "kernels", "recompile"):
        assert expected in names


def test_run_passes_skips_unavailable_and_reports_crashes():
    from repro.analysis.registry import LintPass, register_pass

    class Boom(LintPass):
        name = "boom-test"
        requires = ("cfg",)

        def run(self, ctx):
            raise RuntimeError("kapow")

    register_pass(Boom)
    ctx = LintContext(cell="t", cfg=_cfg())       # no hlo/jaxpr/kernels
    rep = run_passes(ctx, names=["donation", "boom-test"])
    # donation skipped silently (no artifacts); the crash gates as ERROR
    codes = [(f.pass_name, f.code, f.severity) for f in rep.findings]
    assert codes == [("boom-test", "pass-crashed", Severity.ERROR)]


# ---------------------------------------------------------------------------
# HLO parsing (the substrate under collectives/donation)
# ---------------------------------------------------------------------------

def test_collective_ops_and_aliases_from_real_module():
    from repro.launch.hlo_analysis import (collective_ops, collective_summary,
                                           input_output_aliases)
    donated = {"w": jnp.ones((64, 64), jnp.float32),
               "b": jnp.ones((64,), jnp.float32)}
    lowered = jax.jit(
        lambda s, x: ({"w": s["w"] + x.sum(), "b": s["b"] * 2.0}, x.mean()),
        donate_argnums=(0,)).lower(donated, jnp.ones((8,), jnp.float32))
    hlo = lowered.compile().as_text()
    aliases = input_output_aliases(hlo)
    assert {a.param_number for a in aliases} == {0, 1}
    assert collective_ops(hlo) == []               # single device: none
    assert collective_summary([]) == {}


def test_entry_parameter_bytes():
    from repro.launch.hlo_analysis import entry_parameter_bytes
    lowered = jax.jit(lambda a, b: a @ b).lower(
        jnp.ones((4, 8), jnp.float32), jnp.ones((8, 2), jnp.float32))
    pb = entry_parameter_bytes(lowered.compile().as_text())
    assert pb.get(0) == 4 * 8 * 4 and pb.get(1) == 8 * 2 * 4


# ---------------------------------------------------------------------------
# donation audit
# ---------------------------------------------------------------------------

def test_donation_clean_on_aliased_jit():
    state = {"w": jnp.ones((64, 64), jnp.float32)}
    lowered = jax.jit(lambda s, x: ({"w": s["w"] + x},),
                      donate_argnums=(0,)).lower(
        state, jnp.ones((64, 64), jnp.float32))
    hlo = lowered.compile().as_text()
    assert audit_donation(hlo, DonationInfo(argnums=(0,), trees=(state,))) == []


def test_donation_dropped_is_error():
    # the donated tree never reaches an output — nothing can alias
    state = {"w": jnp.ones((64, 64), jnp.float32)}
    lowered = jax.jit(lambda s, x: (x * 2.0,), donate_argnums=(0,)).lower(
        state, jnp.ones((8,), jnp.float32))
    hlo = lowered.compile().as_text()
    fs = audit_donation(hlo, DonationInfo(argnums=(0,), trees=(state,)))
    assert [f.code for f in fs] == ["donation-dropped"]
    assert fs[0].severity == Severity.ERROR


def test_donation_precise_per_leaf_path():
    # two donated leaves, one unaliased (returned transposed ≠ same layout is
    # still aliasable, so use a genuinely dropped leaf instead)
    state = {"a": jnp.ones((64, 64), jnp.float32),
             "b": jnp.ones((32, 32), jnp.float32)}
    x = jnp.ones((64, 64), jnp.float32)
    lowered = jax.jit(lambda s, x: ({"a": s["a"] + x},),
                      donate_argnums=(0,)).lower(state, x)
    hlo = lowered.compile().as_text()
    di = DonationInfo(argnums=(0,), trees=(state,), all_args=(state, x))
    fs = audit_donation(hlo, di)
    assert any(f.code in ("unaliased-donation", "donation-shortfall")
               for f in fs)


def test_infer_session_slot_donations_alias():
    """The continuous-batching donation sites promised in session/infer.py
    must actually alias (the audit that motivated donating insert_slot)."""
    from repro.core import stepfn
    from repro.session import InferenceSession
    cfg = cfg_mod.get_config("granite_3_2b").reduced()
    sess = InferenceSession.from_recipe(cfg)
    caches = sess.init_cache(2, 32)
    slot = sess.init_cache(1, 32)
    for name, fn, argnums, args in [
        ("zero_slot", lambda c, i: stepfn.cache_zero_slot(cfg, c, i),
         (0,), (caches, 0)),
        ("insert_slot", lambda c, s, i: stepfn.cache_insert_slot(cfg, c, s, i),
         (0,), (caches, slot, 0)),
    ]:
        hlo = jax.jit(fn, donate_argnums=argnums).lower(
            *args).compile().as_text()
        fs = audit_donation(hlo, DonationInfo(argnums=argnums, trees=(caches,)))
        assert fs == [], (name, [f.render() for f in fs])


# ---------------------------------------------------------------------------
# dtype audit
# ---------------------------------------------------------------------------

def test_f32_dot_flagged_on_bf16_path():
    jx = jax.make_jaxpr(lambda a, b: a @ b)(
        jnp.zeros((32, 64)), jnp.zeros((64, 16)))
    fs = f32_dot_findings(jx, _cfg())
    assert [f.code for f in fs] == ["f32-upcast-dot"]


def test_f32_dot_ignored_on_f32_config_and_vocab_dim():
    a, b = jnp.zeros((32, 64)), jnp.zeros((64, 16))
    jx = jax.make_jaxpr(lambda a, b: a @ b)(a, b)
    assert f32_dot_findings(jx, _cfg(dtype="float32")) == []
    cfg = _cfg()
    v = jnp.zeros((32, cfg.vocab_size))
    jxv = jax.make_jaxpr(lambda h, w: h @ w)(
        jnp.zeros((4, 32)), v)                    # logits head: allowlisted
    assert f32_dot_findings(jxv, cfg) == []


def test_mixed_precision_dot_not_flagged():
    # bf16 operands with f32 accumulation is the *correct* pattern
    jx = jax.make_jaxpr(
        lambda a, b: jax.lax.dot_general(
            a, b, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32))(
        jnp.zeros((8, 8), jnp.bfloat16), jnp.zeros((8, 8), jnp.bfloat16))
    assert f32_dot_findings(jx, _cfg()) == []


def test_f32_dot_found_inside_scan():
    def f(xs, w):
        def body(c, x):
            return c, x @ w
        return jax.lax.scan(body, 0.0, xs)[1]
    jx = jax.make_jaxpr(f)(jnp.zeros((3, 8, 8)), jnp.zeros((8, 8)))
    assert [f_.code for f_ in f32_dot_findings(jx, _cfg())] == ["f32-upcast-dot"]


# ---------------------------------------------------------------------------
# kernel validator
# ---------------------------------------------------------------------------

def test_real_kernels_validate_clean():
    caps = default_kernel_captures(_cfg())
    assert {c.kernel for c in caps} >= {"_fwd_kernel", "_decode_kernel",
                                        "_paged_decode_kernel"}
    for cap in caps:
        assert check_kernel(cap) == [], (cap.kernel,
                                         [f.render() for f in check_kernel(cap)])


def test_kernel_seeded_violations():
    cap = KernelCapture(
        kernel="seeded", grid=(4,),
        in_args=[KernelArg("in0", (100,), (32,), lambda i: (i,))],
        out_args=[KernelArg("out0", (128,), (32,), lambda i: (0,))],
        num_scalar_prefetch=0, scalar_values=(),
        dimension_semantics=("parallel",))
    codes = {f.code for f in check_kernel(cap)}
    assert codes == {"block-not-divisible", "uncovered-output-tile",
                     "write-race"}


def test_kernel_out_of_bounds_and_rank():
    oob = KernelCapture(
        kernel="oob", grid=(4,), in_args=[],
        out_args=[KernelArg("out0", (64,), (32,), lambda i: (i,))],
        num_scalar_prefetch=0, scalar_values=(), dimension_semantics=None)
    assert {f.code for f in check_kernel(oob)} == {"index-out-of-bounds"}
    rank = KernelCapture(
        kernel="rank", grid=(2,), in_args=[
            KernelArg("in0", (8, 8), (8,), lambda i: (i,))],
        out_args=[], num_scalar_prefetch=0, scalar_values=(),
        dimension_semantics=None)
    assert {f.code for f in check_kernel(rank)} == {"block-rank-mismatch"}


def test_kernel_squeezed_dim_is_one_element():
    """A None block dim (squeezed out of the kernel, as the paged decode
    kernel's layer axis) indexes single elements: every layer of a stack
    of 3 is in bounds, a fourth is not."""
    def cap(n):
        return KernelCapture(
            kernel="squeezed", grid=(n,), in_args=[
                KernelArg("in0", (3, 8, 128), (None, 8, 128),
                          lambda i: (i, 0, 0))],
            out_args=[], num_scalar_prefetch=0, scalar_values=(),
            dimension_semantics=None)
    assert check_kernel(cap(3)) == []
    assert {f.code for f in check_kernel(cap(4))} == {"index-out-of-bounds"}


def test_capture_does_not_execute_kernel():
    from jax.experimental import pallas as pl
    ran = []

    def kernel(x_ref, o_ref):
        ran.append(True)           # must never run under capture
        o_ref[...] = x_ref[...]

    records = []
    with capture_pallas_calls(records):
        out = pl.pallas_call(
            kernel, out_shape=jax.ShapeDtypeStruct((8, 128), jnp.float32),
            grid=(2,),
            in_specs=[pl.BlockSpec((4, 128), lambda i: (i, 0))],
            out_specs=pl.BlockSpec((4, 128), lambda i: (i, 0)),
        )(jnp.ones((8, 128), jnp.float32))
    assert not ran and out.shape == (8, 128)
    assert len(records) == 1 and records[0].grid == (2,)
    assert check_kernel(records[0]) == []


# ---------------------------------------------------------------------------
# recompilation-hazard pass
# ---------------------------------------------------------------------------

def test_probe_detects_python_value_shape():
    diff = probe_shape_dependence(
        lambda x, n: x[:n],
        [(jax.ShapeDtypeStruct((8,), jnp.float32), 3),
         (jax.ShapeDtypeStruct((8,), jnp.float32), 5)])
    assert diff is not None and not diff.startswith("raise:")


def test_probe_clean_on_shape_transparent_fn():
    assert probe_shape_dependence(
        lambda x, t: x * t,
        [(jax.ShapeDtypeStruct((8,), jnp.float32), 3),
         (jax.ShapeDtypeStruct((8,), jnp.float32), 5)]) is None


def test_recompile_pass_severities():
    bad = ProbeSpec(name="bad", fn=lambda x, n: x[:n],
                    variants=[(jax.ShapeDtypeStruct((8,), jnp.float32), 3),
                              (jax.ShapeDtypeStruct((8,), jnp.float32), 5)])
    ok = ProbeSpec(name="ok", fn=lambda x, t: x + t,
                   variants=[(jax.ShapeDtypeStruct((8,), jnp.float32), 1),
                             (jax.ShapeDtypeStruct((8,), jnp.float32), 2)])
    bounded = ProbeSpec(name="bucketed", fn=lambda x, n: x[:n], bounded=True,
                        variants=[(jax.ShapeDtypeStruct((8,), jnp.float32), 2),
                                  (jax.ShapeDtypeStruct((8,), jnp.float32), 4)])
    ctx = LintContext(cell="t", entry_points=[bad, ok, bounded])
    fs = RecompileHazardPass().run(ctx)
    by_name = {f.where: f for f in fs}
    assert by_name["bad"].code == "shape-depends-on-python-value"
    assert by_name["bad"].severity == Severity.ERROR
    assert "ok" not in by_name
    assert by_name["bucketed"].severity == Severity.INFO


def test_repo_entry_points_are_shape_transparent():
    """The stepfn serve/eval/cache-slot surfaces must not specialize shapes
    on Python values (t, slot indices) — the serve loop passes them per call."""
    from repro.analysis.recompile import default_entry_points
    from repro.core.recipe import ParallelismConfig
    cfg = cfg_mod.get_config("granite_3_2b").reduced()
    ctx = LintContext(cell="t", entry_points=default_entry_points(
        cfg, ParallelismConfig()))
    fs = RecompileHazardPass().run(ctx)
    errors = [f for f in fs if f.severity >= Severity.WARNING]
    assert errors == [], [f.render() for f in errors]


# ---------------------------------------------------------------------------
# family sharding hints
# ---------------------------------------------------------------------------

def test_param_sharding_hints_take_precedence():
    from repro.core.sharding import spec_for_path
    hints = ((r"\bw_gate\b$", ("expert", None, "tp")),)
    assert spec_for_path("moe/w_gate", (4, 8, 16)) == (None, "embed", "tp") \
        or spec_for_path("moe/w_gate", (4, 8, 16)) is not None
    assert spec_for_path("moe/w_gate", (4, 8, 16), extra_rules=hints) == \
        ("expert", None, "tp")


def test_moe_family_hints_shard_expert_axis():
    from repro.core import zero
    from repro.core.recipe import ParallelismConfig
    from repro.models import api as model_api
    cfg = cfg_mod.get_config("olmoe_1b_7b").reduced()
    hints = model_api.family_of(cfg).param_sharding_hints(cfg)
    assert any("expert" in axes for _, axes in hints)
    params = jax.eval_shape(
        lambda k: model_api.init_params(cfg, k), jax.random.PRNGKey(0))
    from jax.sharding import Mesh
    import numpy as np
    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    sh = zero.param_shardings(cfg, params, mesh, ParallelismConfig())
    assert jax.tree_util.tree_structure(sh) == \
        jax.tree_util.tree_structure(params)


def test_dense_family_has_no_hints():
    from repro.models import api as model_api
    cfg = cfg_mod.get_config("granite_3_2b").reduced()
    assert model_api.family_of(cfg).param_sharding_hints(cfg) == ()


def test_ssm_hints_pin_scan_params_replicated():
    from repro.core.sharding import spec_for_path
    from repro.models import api as model_api
    cfg = cfg_mod.get_config("hymba_15b").reduced()
    hints = model_api.family_of(cfg).param_sharding_hints(cfg)
    assert spec_for_path("blocks/ssm/A_log", (4,), extra_rules=hints) == (None,)


# ---------------------------------------------------------------------------
# prove-gate (the CI seeded-violation smoke, single-device subset)
# ---------------------------------------------------------------------------

def test_prove_gate_passes():
    from repro.analysis.cli import prove_gate
    assert prove_gate(log=lambda *a, **k: None) == 0


def test_lint_report_json_roundtrip():
    rep = Report("cell", meta={"arch": "x"})
    rep.add(Finding(pass_name="p", code="c", severity=Severity.INFO,
                    message="m", where="w", data={"n": 1}))
    j = json.loads(json.dumps(rep.to_json()))
    assert j["cell"] == "cell" and j["findings"][0]["code"] == "c"
