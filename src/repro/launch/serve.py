"""Batched serving driver — a thin CLI over ``InferenceSession``.

Static batch (prefill + autoregressive decode with ring-buffer KV caches):

  PYTHONPATH=src python -m repro.launch.serve --arch granite_3_2b --reduced \
      --batch 4 --prompt-len 32 --gen 32

Request-stream mode (continuous batching: mixed-length requests through the
slot scheduler, finished requests free their slot mid-flight):

  PYTHONPATH=src python -m repro.launch.serve --arch granite_3_2b --reduced \
      --stream 16 --slots 4 --prompt-len 32 --gen 32
"""

from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.runtime.compile_cache import enable_compile_cache
from repro.session import InferenceSession


def run_static(sess, args):
    cfg = sess.cfg
    prompts = jax.random.randint(jax.random.PRNGKey(0),
                                 (args.batch, args.prompt_len), 0, cfg.vocab_size)
    t0 = time.time()
    toks = sess.generate(prompts, args.gen)
    dt = time.time() - t0
    n_new = toks.shape[1] - args.prompt_len
    print(f"[serve] {cfg.name}: generated {n_new} tokens × batch {args.batch} "
          f"in {dt:.2f}s ({args.batch * n_new / dt:.1f} tok/s)")
    print("[serve] sample:", np.asarray(toks[0, args.prompt_len:args.prompt_len + 16]))
    return toks


def run_stream(sess, args):
    """Mixed-length synthetic request stream through the continuous-batching
    scheduler: prompt lengths cycle through a few buckets (so prefill compiles
    amortize) and decode budgets vary widely (the static-batch worst case)."""
    cfg = sess.cfg
    rng = np.random.RandomState(0)
    plen_buckets = sorted({max(4, args.prompt_len // 2), args.prompt_len})
    prompts, gens = [], []
    for r in range(args.stream):
        plen = plen_buckets[r % len(plen_buckets)]
        prompts.append(rng.randint(0, cfg.vocab_size, size=plen).astype(np.int32))
        gens.append(int(rng.randint(1, args.gen + 1)))
    t0 = time.time()
    outs, stats = sess.serve(prompts, gens, n_slots=args.slots,
                             paged=args.paged, page_size=args.page_size)
    dt = time.time() - t0
    print(f"[serve] {cfg.name}: {stats.requests} requests "
          f"({sum(gens)} tokens) through {args.slots} slots in {dt:.2f}s "
          f"({stats.generated_tokens / dt:.1f} tok/s, compiles included)")
    print(f"[serve] {stats}")
    if args.paged:
        print(f"[serve] pool: {stats.pool_pages} pages of {stats.page_size} "
              f"(occupancy {stats.pool_occupancy:.2f}), prefix hits "
              f"{stats.prefix_hits} (rate {stats.prefix_hit_rate:.2f})")
    for p, o in zip(prompts[:4], outs[:4]):
        print(f"[serve] P={len(p)} → {o[len(p):len(p) + 8]}")
    return outs


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--stream", type=int, default=0, metavar="N",
                    help="serve N mixed-length requests via continuous batching")
    ap.add_argument("--slots", type=int, default=4,
                    help="scheduler slot count (stream mode)")
    ap.add_argument("--paged", action="store_true",
                    help="serve from the block-paged KV pool with "
                         "copy-on-write prefix sharing (stream mode)")
    ap.add_argument("--page-size", type=int, default=16,
                    help="tokens per KV page (with --paged)")
    args = ap.parse_args(argv)

    sess = InferenceSession.from_recipe(args.arch, reduced=args.reduced, seed=0)
    if args.stream:
        return run_stream(sess, args)
    return run_static(sess, args)


if __name__ == "__main__":
    enable_compile_cache()
    main()
