"""Compile the main path's Pallas kernels for a described TPU v5e, no chip
attached: the TPU compiler refuses what interpret mode accepts (blocks whose
two minor dims are not (8k, 128k) or the array's own, unsupported layouts),
so these compiles guard the chip path on every CPU run.

Shapes are granite_3_2b's (Hq=32, Hkv=8, head_dim=64) at seq 2048, and the
serving decode shapes (8 slots, page size 128).  Kernels are called with
``interpret=False``: the dispatch layer would pick interpret mode from the
CPU backend this process runs on.  The topology is described inside a
module fixture (never at import, so every xdist worker collects the same
tests and only the worker given this file loads the TPU library).
"""

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.decode_attention import (decode_attention,
                                            paged_decode_attention)
from repro.kernels.flash_attention import flash_attention
from repro.kernels.rmsnorm import rmsnorm

B, S, HQ, HKV, D = 2, 2048, 32, 8, 64
SLOTS, CACHE, PAGE, N_PAGES = 8, 2048, 128, 129


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compiled_text(fn, *specs) -> str:
    return jax.jit(fn).lower(*specs).compile().as_text()


@pytest.mark.parametrize("packed", [False, True], ids=["plain", "segment_ids"])
def test_flash_fwd_bwd_compiles(one_chip, packed):
    """At the training cell's shapes (batch 4) and the tiles and GQA fold
    that ``ops._flash_blocks`` picks for them."""
    from repro.kernels import ops
    q = _spec((4, S, HQ, D), jnp.bfloat16, one_chip)
    kv = _spec((4, S, HKV, D), jnp.bfloat16, one_chip)
    seg = _spec((4, S), jnp.int32, one_chip)
    bq, bk, gf = ops._flash_blocks(S, S, g=HQ // HKV, D=D, packed=packed)

    def loss(q, k, v, seg):
        out = flash_attention(q, k, v, segment_ids=seg if packed else None,
                              causal=True, bq=bq, bk=bk, gf=gf,
                              interpret=False)
        return out.astype(jnp.float32).sum()

    hlo = _compiled_text(jax.grad(loss, argnums=(0, 1, 2)), q, kv, kv, seg)
    # forward, delta preprocess, dQ sweep, dK/dV sweep, each by its name
    assert hlo.count("custom_call_target=\"tpu_custom_call\"") == 4
    for part in ("fwd", "delta", "dq", "dkv"):
        assert re.search(rf"%flash_attention_{part}(\.\d+)? = ", hlo), part


@pytest.mark.parametrize("dv", [128, 192], ids=["dv128", "dv192"])
def test_flash_mla_head_dims_compile(one_chip, dv):
    """Latent attention's shapes (deepseek_v2_lite_ep8): 16 heads, Q/K 192
    wide and V 128 (or as wide as Q/K), packed rows of 8192, the YaRN
    softmax scale, at the tiles ``ops._flash_blocks`` picks for them."""
    from repro.kernels import ops
    Sm, H, Dqk = 8192, 16, 192
    q = _spec((2, Sm, H, Dqk), jnp.bfloat16, one_chip)
    v = _spec((2, Sm, H, dv), jnp.bfloat16, one_chip)
    seg = _spec((2, Sm), jnp.int32, one_chip)
    bq, bk, gf = ops._flash_blocks(Sm, Sm, g=1, D=Dqk, Dv=dv, packed=True)

    def loss(q, k, v, seg):
        out = flash_attention(q, k, v, segment_ids=seg, causal=True, bq=bq,
                              bk=bk, gf=gf, scale=0.1147, interpret=False)
        return out.astype(jnp.float32).sum()

    hlo = _compiled_text(jax.grad(loss, argnums=(0, 1, 2)), q, q, v, seg)
    for part in ("fwd", "delta", "dq", "dkv"):
        assert re.search(rf"%flash_attention_{part}(\.\d+)? = ", hlo), part


def test_expert_gmm_compiles(one_chip):
    """The held experts' grouped matmul, forward and backward, at one MoE
    layer of deepseek_v2_lite_ep8 (16384 tokens, top-6, 8 held experts,
    hidden 2048, expert width 1408) on its worst-case row layout."""
    from repro.kernels import grouped_matmul as gm
    from repro.models.moe import layout_rows
    M, E, d, ff = layout_rows(16384, 6, 8), 8, 2048, 1408
    x = _spec((M, d), jnp.bfloat16, one_chip)
    w = _spec((E, d, ff), jnp.float32, one_chip)
    te = _spec((M // gm.TILE_M,), jnp.int32, one_chip)
    n = _spec((1,), jnp.int32, one_chip)
    sizes = _spec((E,), jnp.int32, one_chip)

    def loss(x, w, te, n, sizes):
        y = gm.expert_matmul(x, w, te, n, sizes, False)
        return y.astype(jnp.float32).sum()

    hlo = _compiled_text(jax.value_and_grad(loss, argnums=(0, 1)), x, w, te,
                         n, sizes)
    for name in ("expert_gmm", "expert_gmm_dx", "expert_gmm_dw"):
        assert re.search(rf"%{name}(\.\d+)? = ", hlo), name


def test_decode_compiles(one_chip):
    q = _spec((SLOTS, 1, HQ, D), jnp.bfloat16, one_chip)
    kv = _spec((SLOTS, CACHE, HKV, D), jnp.bfloat16, one_chip)
    kpos = _spec((SLOTS, CACHE), jnp.int32, one_chip)
    t = _spec((), jnp.int32, one_chip)
    hlo = _compiled_text(
        lambda q, k, v, kpos, t: decode_attention(q, k, v, kpos, t=t, bk=512,
                                                  interpret=False),
        q, kv, kv, kpos, t)
    assert "tpu_custom_call" in hlo
    assert re.search(r"%decode_attention(\.\d+)? = ", hlo)


def test_paged_decode_compiles(one_chip):
    q = _spec((SLOTS, 1, HQ, D), jnp.bfloat16, one_chip)
    pool = _spec((N_PAGES, PAGE, HKV, D), jnp.bfloat16, one_chip)
    table = _spec((SLOTS, CACHE // PAGE), jnp.int32, one_chip)
    ts = _spec((SLOTS,), jnp.int32, one_chip)
    hlo = _compiled_text(
        lambda q, kp, vp, pt, ts: paged_decode_attention(
            q, kp, vp, pt, ts=ts, interpret=False),
        q, pool, pool, table, ts)
    assert "tpu_custom_call" in hlo
    assert re.search(r"%paged_decode_attention(\.\d+)? = ", hlo)


def test_rmsnorm_compiles(one_chip):
    x = _spec((B * S, 2048), jnp.bfloat16, one_chip)
    scale = _spec((2048,), jnp.float32, one_chip)
    hlo = _compiled_text(lambda x, s: rmsnorm(x, s, interpret=False), x, scale)
    assert "tpu_custom_call" in hlo
