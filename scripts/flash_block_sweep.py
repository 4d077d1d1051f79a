"""Time the flash kernels on one TPU chip over a grid of tile shapes.

    python3 scripts/flash_block_sweep.py [--out chiprun_out/flash_sweep.jsonl]
        [--shapes 256,512,4 ...]

Shapes are one layer of the `granite_3_2b_d8.train.pack2k` benchmark cell:
batch 4 x 2048, 32 query heads on 8 KV heads of 64, bf16, causal, with the
cell's 8 packed-document layouts (`bench/traffic/pack2k.json`) and without
segment ids.  For every (bq, bk, gf) it times the forward alone and the
forward plus backward (`jax.grad`), each over the 8 layouts, and prints one
JSON line per shape: milliseconds per call, medians of `--reps` rounds.
`--shapes` replaces the default grid of (bq, bk, gf).  Exits 2 without a
TPU.
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
sys.path.insert(0, str(ROOT))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from bench import traffic  # noqa: E402
from repro.kernels.flash_attention import flash_attention  # noqa: E402

B, S, HQ, HKV, D = 4, 2048, 32, 8, 64
SHAPES = [(bq, bk, gf) for bq in (128, 256, 512) for bk in (128, 256, 512)
          for gf in (1, 2, 4) if gf * bq <= 1024]


def _time(fn, args_list, reps: int) -> float:
    for args in args_list:                     # compile and warm every layout
        jax.block_until_ready(fn(*args))
    rounds = []
    for _ in range(reps):
        t = time.perf_counter()
        outs = [fn(*args) for args in args_list]
        jax.block_until_ready(outs)
        rounds.append((time.perf_counter() - t) / len(args_list))
    return 1e3 * statistics.median(rounds)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="chiprun_out/flash_sweep.jsonl")
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--shapes", nargs="*", default=None,
                    help="bq,bk,gf triples; default: every shape of SHAPES")
    a = ap.parse_args()
    if jax.default_backend() != "tpu":
        print("no TPU", file=sys.stderr)
        return 2
    mix = json.loads((ROOT / "bench/traffic/pack2k.json").read_text())
    segs = [jnp.asarray(traffic.train_batch(mix, B, 100, 0, i)["segment_ids"])
            for i in range(mix["distinct_batches"])]
    key = jax.random.PRNGKey(0)
    q = jax.random.normal(key, (B, S, HQ, D), jnp.bfloat16)
    k = jax.random.normal(jax.random.fold_in(key, 1), (B, S, HKV, D),
                          jnp.bfloat16)
    v = jax.random.normal(jax.random.fold_in(key, 2), (B, S, HKV, D),
                          jnp.bfloat16)
    shapes = ([tuple(map(int, x.split(","))) for x in a.shapes]
              if a.shapes else SHAPES)
    out = Path(a.out)
    out.parent.mkdir(parents=True, exist_ok=True)
    with out.open("a") as f:
        for packed in (True, False):
            layouts = [(q, k, v, s) for s in segs] if packed else [(q, k, v, None)]
            for bq, bk, gf in shapes:
                kw = dict(causal=True, bq=bq, bk=bk, gf=gf)

                def fwd(q, k, v, seg):
                    return flash_attention(q, k, v, segment_ids=seg, **kw)

                def loss(q, k, v, seg):
                    return fwd(q, k, v, seg).astype(jnp.float32).sum()

                row = {"packed": packed, "bq": bq, "bk": bk, "gf": gf,
                       "device": jax.devices()[0].device_kind}
                try:
                    row["fwd_ms"] = _time(jax.jit(fwd), layouts, a.reps)
                    row["fwd_bwd_ms"] = _time(
                        jax.jit(jax.grad(loss, argnums=(0, 1, 2))), layouts,
                        a.reps)
                except Exception as e:  # noqa: BLE001 — record and go on
                    row["error"] = f"{type(e).__name__}: {str(e)[:200]}"
                line = json.dumps(row)
                print(line, flush=True)
                f.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
