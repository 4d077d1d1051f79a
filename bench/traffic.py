"""The one generator of inputs: it reads a traffic mix's parameters and the
seed, and nothing else.

Every seed gets the same work: the sizes (document lengths, prompt and
output lengths) are fixed quantiles of the mix's distributions, and their
order comes from the mix (its ``order_seed``), because the order decides
work too (which attention tiles a packed row skips, how a serve call's slots
drain).  The run's seed draws the token ids only.  Two runs of one seed see
identical inputs.
"""

from __future__ import annotations

import math

import numpy as np


def rng(seed: int, *stream: int) -> np.random.Generator:
    """A generator for (seed, stream...) that takes seeds beyond 32 bits."""
    return np.random.Generator(np.random.Philox(
        key=[seed & (2 ** 64 - 1), hash(tuple(stream)) & (2 ** 64 - 1)]))


def quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


# --------------------------------------------------------------------------
# packed documents for training
# --------------------------------------------------------------------------

def doc_lengths(mix: dict) -> list:
    """One row's documents: ``docs_per_row`` quantiles of a geometric
    (exponential) length with mean ``doc_mean``, and one more document that
    fills the row to ``seq_len``."""
    S, k, m = mix["seq_len"], mix["docs_per_row"], mix["doc_mean"]
    lens = [max(1, int(round(-m * math.log(1 - q)))) for q in quantiles(k)]
    rest = S - sum(lens)
    if rest < 0:
        raise ValueError(f"documents {lens} overflow a row of {S}")
    return lens + ([rest] if rest else [])


def train_batch(mix: dict, batch: int, vocab: int, seed: int, index: int) -> dict:
    """Batch ``index`` of a packed-document stream: (batch, seq_len) tokens,
    next-token labels, segment ids, and a loss mask that drops each
    document's last position (its label would be the next document's
    first token)."""
    S = mix["seq_len"]
    lens = doc_lengths(mix)
    rows = rng(seed, 1, index).integers(1, vocab, (batch, S + 1),
                                        dtype=np.int64).astype(np.int32)
    seg = np.empty((batch, S), np.int32)
    mask = np.ones((batch, S), np.float32)
    for r in range(batch):
        order = rng(mix["order_seed"], 1, index, r).permutation(len(lens))
        pos = 0
        for d, j in enumerate(order):
            seg[r, pos:pos + lens[j]] = d
            pos += lens[j]
            mask[r, pos - 1] = 0.0
    return {"tokens": rows[:, :-1], "labels": rows[:, 1:],
            "segment_ids": seg, "loss_mask": mask}


# --------------------------------------------------------------------------
# request sets for serving
# --------------------------------------------------------------------------

def _sizes(spec: dict, n: int, step: int = 1) -> np.ndarray:
    """``n`` quantiles of a length distribution, each rounded to the
    nearest ``1 + step * k``."""
    q = quantiles(n)
    lo, hi = spec["min"], spec["max"]
    if spec["dist"] == "loguniform":
        x = np.exp(np.log(lo) + (np.log(hi) - np.log(lo)) * q)
    elif spec["dist"] == "uniform":
        x = lo + (hi - lo) * q
    else:
        raise ValueError(f"unknown size distribution {spec['dist']!r}")
    return 1 + step * np.round((x - 1) / step).astype(int)


def request_sizes(mix: dict, warmup: bool = False) -> list:
    """(prompt length, output length) of each request of a call: the same
    for every seed and every call.  Prompts and outputs are paired by the
    mix's ``order_seed`` and sent longest output first, so each slot that
    frees takes the longest request left and the slots finish together, as
    in the body of a long dataset run.

    Output lengths are ``1 + k * j`` for the mix's ``warmup_divisor`` k.  A
    request holds its slot for ``max_new - 1`` decode steps after its
    prefill, so with ``warmup=True`` (outputs ``1 + j``) every slot frees k
    times sooner, in the same order and with the same ties: the scheduler
    admits the same requests in the same groups, and the call runs every
    prefill and decode shape of a full call in a k-th of its decode steps."""
    n, k = mix["requests_per_call"], mix["warmup_divisor"]
    prompts = _sizes(mix["prompt"], n)
    outputs = _sizes(mix["output"], n, k)[
        rng(mix["order_seed"], 2).permutation(n)]
    order = np.argsort(-outputs, kind="stable")
    if warmup:
        outputs = 1 + (outputs - 1) // k
    return [(int(prompts[i]), int(outputs[i])) for i in order]


def max_len(mix: dict) -> int:
    """The per-slot length every call is served with: its longest
    request's prompt and output."""
    return max(p + m for p, m in request_sizes(mix))


def serve_call(mix: dict, vocab: int, seed: int, call: int,
               warmup: bool = False):
    """Call ``call`` of a run: (prompts, max_new_tokens).  Only the token
    ids differ from call to call and from seed to seed."""
    sizes = request_sizes(mix, warmup)
    g = rng(seed, 4, call)
    prompts = [g.integers(1, vocab, p, dtype=np.int64).astype(np.int32)
               for p, _ in sizes]
    return prompts, [m for _, m in sizes]
