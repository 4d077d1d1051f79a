"""Distribution tests that need multiple devices: run in subprocesses with
XLA_FLAGS=--xla_force_host_platform_device_count=8 (the main test process
keeps the default single device, per the assignment)."""

import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parent.parent


def _run(code: str, devices: int = 8) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = str(ROOT / "src")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env, timeout=500)
    assert out.returncode == 0, f"stderr:\n{out.stderr[-3000:]}"
    return out.stdout


def test_pipeline_rotation_lowers_to_collective_permute():
    out = _run("""
        import jax, numpy as np, re
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        from repro.configs import get_config
        from repro.models import init_params
        from repro.core.pipeline import pipeline_loss, stack_for_pipeline
        from repro.core.recipe import ParallelismConfig
        from repro.core import sharding as shd
        cfg = get_config("granite_3_2b").reduced()
        key = jax.random.PRNGKey(0)
        params = init_params(cfg, key)
        plan = ParallelismConfig(pp=2, tp=2, dp=2, gas=4)
        mesh = Mesh(np.array(jax.devices()).reshape(2,2,2), ("data","pp","tp"))
        pparams = dict(params, blocks=stack_for_pipeline(params["blocks"], 2))
        B, S = 8, 32
        batch = {"tokens": jax.random.randint(key, (B,S), 0, cfg.vocab_size),
                 "labels": jax.random.randint(key, (B,S), 0, cfg.vocab_size)}
        mapping = {"tp":"tp","stage":"pp","batch":"data","expert":"tp",
                   "layers":None,"embed":None,"seq":None}
        def loss(p, b):
            with shd.axis_rules(mesh, mapping):
                return pipeline_loss(cfg, p, b, plan)[0]
        with mesh:
            c = jax.jit(jax.grad(loss),
                        in_shardings=(None, NamedSharding(mesh, P("data")))
                        ).lower(pparams, batch).compile()
        hlo = c.as_text()
        assert "collective-permute" in hlo, "stage rotation must be a permute"
        print("PERMUTES", hlo.count("collective-permute"))
    """)
    assert "PERMUTES" in out


def test_train_step_numerics_match_under_sharding():
    """Sharded (dp=4, tp=2) train step produces the same loss as 1-device."""
    out = _run("""
        import jax, numpy as np, jax.numpy as jnp
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        from repro.configs import get_config
        from repro.core import stepfn
        from repro.core.recipe import ParallelismConfig
        cfg = get_config("granite_3_2b").reduced()
        key = jax.random.PRNGKey(0)
        B, S = 8, 32
        batch = {"tokens": jax.random.randint(key, (B,S), 0, cfg.vocab_size),
                 "labels": jax.random.randint(key, (B,S), 0, cfg.vocab_size)}
        # single-device reference
        plan0 = ParallelismConfig()
        st0 = stepfn.init_state(cfg, plan0, key)
        _, m0 = jax.jit(stepfn.make_train_step(cfg, plan0))(st0, batch)
        # sharded: dp=4 × tp=2 with ZeRO-1
        mesh = Mesh(np.array(jax.devices()).reshape(4,2), ("data","model"))
        plan = ParallelismConfig(tp=2, dp=4, zero_stage=1)
        # rename axes to the recipe's names via a 4-axis view
        mesh = Mesh(np.array(jax.devices()).reshape(1,4,1,2), ("pod","data","pp","tp"))
        st = stepfn.init_state(cfg, plan, key)
        sh = stepfn.state_shardings(cfg, st, mesh, plan)
        bsh = stepfn.batch_shardings(batch, mesh)
        with mesh:
            step = jax.jit(stepfn.make_train_step(cfg, plan, mesh=mesh),
                           in_shardings=(sh, bsh), out_shardings=(sh, None))
            _, m1 = step(st, batch)
        a, b = float(m0["loss"]), float(m1["loss"])
        assert abs(a - b) < 1e-4, (a, b)
        print("LOSS_MATCH", a, b)
    """)
    assert "LOSS_MATCH" in out


def test_zero3_params_actually_sharded():
    out = _run("""
        import jax, numpy as np
        from jax.sharding import Mesh
        from repro.configs import get_config
        from repro.core import stepfn
        from repro.core.recipe import ParallelismConfig
        cfg = get_config("granite_3_2b").reduced()
        mesh = Mesh(np.array(jax.devices()).reshape(1,8,1,1), ("pod","data","pp","tp"))
        plan = ParallelismConfig(dp=8, zero_stage=3)
        st = jax.eval_shape(lambda k: stepfn.init_state(cfg, plan, k),
                            jax.random.PRNGKey(0))
        sh = stepfn.state_shardings(cfg, st, mesh, plan)
        # ZeRO-3: the big mlp weights must carry the data axis
        spec = sh["params"]["blocks"]["mlp"]["w_gate"].spec
        flat = [a for part in spec if part for a in
                (part if isinstance(part, tuple) else (part,))]
        assert "data" in flat, spec
        # ZeRO-1 invariant: optimizer moments sharded too
        ospec = sh["opt"]["m"]["blocks"]["mlp"]["w_gate"].spec
        oflat = [a for part in ospec if part for a in
                 (part if isinstance(part, tuple) else (part,))]
        assert "data" in oflat, ospec
        print("ZERO3_OK")
    """)
    assert "ZERO3_OK" in out


def test_recipe_mesh_factorization():
    out = _run("""
        import numpy as np, jax
        from jax.sharding import Mesh
        from repro.core.recipe import ParallelismConfig, factorize_production_mesh
        base = Mesh(np.array(jax.devices()).reshape(2, 4), ("data", "model"))
        plan = ParallelismConfig(tp=2, pp=2, dp=2)
        m = factorize_production_mesh(base, plan)
        assert dict(m.shape) == {"pod":1, "data":2, "pp":2, "tp":2}, m.shape
        # TP must be innermost: consecutive device ids share a tp group
        ids = np.vectorize(lambda d: d.id)(m.devices)
        assert ids[0,0,0,1] == ids[0,0,0,0] + 1
        print("MESH_OK")
    """)
    assert "MESH_OK" in out


def test_plan_mesh_flash_session_matches_one_device():
    """A pp=2 x tp=2 session on the mesh built from four local devices, with
    the Pallas flash kernel per shard (segment ids on), takes the same first
    step as the one-device session, and its state lands sharded."""
    out = _run("""
        import dataclasses, jax, pytest
        from repro.configs import get_config
        from repro.core import stepfn
        from repro.core.recipe import ParallelismConfig
        from repro.data import DataConfig
        from repro.launch.mesh import make_plan_mesh
        from repro.runtime import flags
        from repro.session import TrainSession
        cfg = dataclasses.replace(get_config("granite_3_2b").reduced(),
                                  n_layers=4)
        plan4 = ParallelismConfig(pp=2, tp=2, gas=4)
        mesh = make_plan_mesh(plan4)
        assert dict(mesh.shape) == {"pod": 1, "data": 1, "pp": 2, "tp": 2}
        with pytest.raises(ValueError):
            make_plan_mesh(ParallelismConfig(tp=2), jax.devices())
        dc = DataConfig(seq_len=128, global_batch=8, pack_documents=True)
        tc = stepfn.TrainConfig(total_steps=2, warmup=1)
        def first(plan, mesh):
            s = TrainSession.from_recipe(cfg, plan=plan, mesh=mesh,
                                         train_cfg=tc, data_cfg=dc, seed=0)
            w = s.state["params"]["blocks"]["mlp"]["w_gate"]
            h = s.run(1, log_every=1, log=lambda m: None)["history"][0]
            return float(h["loss"]), float(h["grad_norm"]), w
        with flags.flag_ctx(flash_attention=True):
            l4, g4, w4 = first(plan4, mesh)
            l1, g1, _ = first(ParallelismConfig(gas=4), None)
        assert len(w4.sharding.device_set) == 4, w4.sharding
        assert abs(l4 - l1) <= 1e-4 * abs(l1), (l4, l1)
        assert abs(g4 - g1) <= 1e-3 * abs(g1), (g4, g1)
        print("PLAN_MESH_OK", l4, l1, g4, g1)
    """, devices=4)
    assert "PLAN_MESH_OK" in out


def test_consensus_skip_bitwise_identical_across_replicas():
    """ISSUE-9 acceptance: one divergent replica's gradient on a real dp>=2
    mesh must yield the IDENTICAL vote on every replica — survivors update,
    the divergent shard is masked, and every device holds bit-identical
    params afterwards.  An all-replicas-bad step must skip fleet-wide with
    params frozen on every shard."""
    out = _run("""
        import jax, numpy as np, jax.numpy as jnp
        from jax.sharding import Mesh
        from repro.configs import get_config
        from repro.core import stepfn
        from repro.core.recipe import ParallelismConfig
        cfg = get_config("granite_3_2b").reduced()
        key = jax.random.PRNGKey(0)
        B, S, R = 4, 32, 2
        mesh = Mesh(np.array(jax.devices()).reshape(1,2,1,1),
                    ("pod","data","pp","tp"))
        plan = ParallelismConfig(dp=2, zero_stage=1)
        batch = {"tokens": jax.random.randint(key, (B,S), 0, cfg.vocab_size),
                 "labels": jax.random.randint(key, (B,S), 0, cfg.vocab_size),
                 "_chaos_grad_scale": jnp.ones((R,), jnp.float32)}
        st = stepfn.init_state(cfg, plan, key)
        sh = stepfn.state_shardings(cfg, st, mesh, plan)
        bsh = stepfn.batch_shardings(batch, mesh)
        with mesh:
            step = jax.jit(stepfn.make_train_step(cfg, plan, mesh=mesh),
                           in_shardings=(sh, bsh), out_shardings=(sh, None))
            def poisoned(bad):
                s = np.ones((R,), np.float32); s[list(bad)] = np.nan
                return dict(batch, _chaos_grad_scale=jnp.asarray(s))
            def shards_equal(state):
                w = state["params"]["blocks"]["mlp"]["w_gate"]
                raw = [np.asarray(s.data) for s in w.addressable_shards]
                return all(np.array_equal(raw[0], r) for r in raw[1:])
            # one divergent replica: masked, not skipped, either way round
            for bad in ([0], [1]):
                st2, m = step(st, poisoned(bad))
                assert float(m["skipped"]) == 0.0, bad
                assert float(m["bad_replicas"]) == 1.0, bad
                assert float(m["n_replicas"]) == 2.0
                assert shards_equal(st2), "replicas must agree bitwise"
            # all replicas bad: fleet-wide skip, params frozen on all shards
            before = np.asarray(
                st["params"]["blocks"]["mlp"]["w_gate"].addressable_shards[0].data)
            st3, m = step(st, poisoned([0, 1]))
            assert float(m["skipped"]) == 1.0
            assert float(m["bad_replicas"]) == 2.0
            assert shards_equal(st3)
            after = np.asarray(
                st3["params"]["blocks"]["mlp"]["w_gate"].addressable_shards[0].data)
            assert np.array_equal(before, after), "skip must freeze params"
        print("CONSENSUS_BITWISE_OK")
    """, devices=2)
    assert "CONSENSUS_BITWISE_OK" in out
