"""Time the held experts' grouped matmul on one TPU chip: the Pallas
``expert_gmm`` kernels against ``jax.lax.ragged_dot`` on the same rows.

    python3 scripts/expert_matmul_race.py [--reps 5]

Shapes are one MoE layer of the `deepseek_v2_lite_ep8.train.pack8k` cell:
2 x 8192 tokens routed top-6 over 64 experts by a random router, of which
8 are held (experts 0-7), hidden 2048, expert width 1408, bf16; the rows
are the layer's own layout (`models/moe.expert_layout`: worst-case rows,
each expert padded to whole tiles).  For each it times the SwiGLU of the
held experts forward and backward (`jax.grad` with respect to the rows and
the three weights) and prints one JSON line: milliseconds per call (median
of `--reps` rounds), and the largest gap of the Pallas path's output and
gradients from `ragged_dot`'s over the rows that hold pairs.  Exits 2
without a TPU.
"""

from __future__ import annotations

import argparse
import json
import statistics
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.kernels import grouped_matmul as gm  # noqa: E402
from repro.models import layers, moe  # noqa: E402

T, K, ROUTER, HELD, D, FF = 16384, 6, 64, 8, 2048, 1408


def _time(fn, args, reps: int) -> float:
    jax.block_until_ready(fn(*args))
    rounds = []
    for _ in range(reps):
        t = time.perf_counter()
        jax.block_until_ready([fn(*args) for _ in range(5)])
        rounds.append((time.perf_counter() - t) / 5)
    return 1e3 * statistics.median(rounds)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--reps", type=int, default=5)
    a = ap.parse_args()
    if jax.default_backend() != "tpu":
        print("no TPU", file=sys.stderr)
        return 2
    key = jax.random.PRNGKey(0)
    logits = jax.random.normal(key, (T, ROUTER))
    _, gate_idx = jax.lax.top_k(logits, K)
    lay = jax.jit(moe.expert_layout, static_argnums=1)(gate_idx, HELD)
    M = lay["row_pair"].shape[0]
    live = (jnp.arange(M) < lay["n_tiles"][0] * gm.TILE_M)[:, None]
    ks = jax.random.split(jax.random.fold_in(key, 1), 5)
    xs = jnp.where(lay["row_pair"][:, None] < T * K,
                   jax.random.normal(ks[0], (M, D), jnp.bfloat16), 0)
    w = {"w_gate": jax.random.normal(ks[1], (HELD, D, FF)) / D ** 0.5,
         "w_up": jax.random.normal(ks[2], (HELD, D, FF)) / D ** 0.5,
         "w_out": jax.random.normal(ks[3], (HELD, FF, D)) / FF ** 0.5}
    cot = jax.random.normal(ks[4], (M, D), jnp.bfloat16)
    padded = ((lay["sizes"] + gm.TILE_M - 1) // gm.TILE_M) * gm.TILE_M

    def ragged(p, xs):
        def mm(x, w):
            return jax.lax.ragged_dot(x, w.astype(x.dtype), padded)
        return mm(layers.swiglu(mm(xs, p["w_gate"]), mm(xs, p["w_up"])),
                  p["w_out"])

    def pallas(p, xs):
        return moe.expert_ffn(p, xs, lay)

    def grads(ffn):
        def loss(p, xs):
            y = jnp.where(live, ffn(p, xs), 0).astype(jnp.float32)
            return jnp.sum(y * cot)
        return jax.jit(jax.value_and_grad(loss, argnums=(0, 1)))

    out = {"rows": int(M), "live_rows": int(lay["n_tiles"][0] * gm.TILE_M),
           "sizes": [int(s) for s in lay["sizes"]]}
    res = {}
    for name, ffn in (("pallas", pallas), ("ragged_dot", ragged)):
        f = grads(ffn)
        out[f"{name}_fwd_bwd_ms"] = _time(f, (w, xs), a.reps)
        fwd = jax.jit(lambda p, xs, ffn=ffn: jnp.where(live, ffn(p, xs), 0))
        out[f"{name}_fwd_ms"] = _time(fwd, (w, xs), a.reps)
        res[name] = (fwd(w, xs), f(w, xs)[1])
    (y0, g0), (y1, g1) = res["pallas"], res["ragged_dot"]

    def gap(a, b):
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        return float(jnp.max(jnp.abs(a - b)) / jnp.maximum(jnp.max(jnp.abs(b)),
                                                           1e-30))
    out["gap_out"] = gap(y0, y1)
    held = (lay["row_pair"] < T * K)[:, None]     # rows no kernel writes
    out["gap_grads"] = max([gap(x, y) for x, y in zip(
        jax.tree_util.tree_leaves(g0[0]), jax.tree_util.tree_leaves(g1[0]))]
        + [gap(jnp.where(held, g0[1], 0), jnp.where(held, g1[1], 0))])
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
