"""The benchmark's own operation and byte counts, from shapes alone.

Counted is the work the algorithm requires, never what an implementation
happens to do: no recomputation (remat, a flash backward that rebuilds the
scores), no padding of the head dim, and causal attention only over the
(query, key) pairs of one document.  A multiply-add is two operations.
"""

from __future__ import annotations

from typing import Iterable

import numpy as np

BF16_BYTES = 2
F32_BYTES = 4


def layer_matmul_params(cfg) -> int:
    """Weights of one dense decoder layer that enter a matrix multiply:
    q/k/v/o projections and the (gated) MLP."""
    d, hd = cfg.d_model, cfg.hd
    q, kv = cfg.n_heads * hd, cfg.n_kv_heads * hd
    mlp = (3 if cfg.gated_mlp else 2) * d * cfg.d_ff
    return d * (q + 2 * kv) + q * d + mlp


def matmul_params(cfg) -> int:
    """Every layer plus the LM head (the tied table used as the unembed);
    the embedding lookup is a gather and does no multiply."""
    return cfg.n_layers * layer_matmul_params(cfg) + cfg.vocab_size * cfg.d_model


def causal_pairs(doc_lengths: Iterable[int]) -> int:
    """(query, key) pairs causal attention inside each document visits."""
    n = np.asarray(list(doc_lengths), np.int64)
    return int(np.sum(n * (n + 1) // 2))


def segment_lengths(segment_ids: np.ndarray) -> list:
    """Document lengths of a packed batch (B, S): runs of equal ids."""
    out = []
    for row in np.asarray(segment_ids):
        cuts = np.flatnonzero(np.diff(row)) + 1
        out.extend(np.diff(np.concatenate([[0], cuts, [row.size]])).tolist())
    return out


def attention_fwd_flops(cfg, pairs: int) -> int:
    """One layer's forward attention: QK^T and PV over ``pairs`` pairs."""
    return 2 * 2 * pairs * cfg.hd * cfg.n_heads


def train_flops(cfg, tokens: int, pairs: int) -> int:
    """Model operations of one training pass (forward + backward) over
    ``tokens`` tokens whose documents hold ``pairs`` causal pairs:
    6 per matmul weight per token, plus attention at three times its
    forward (forward, and the two products of the backward per product)."""
    return (6 * matmul_params(cfg) * tokens
            + 3 * cfg.n_layers * attention_fwd_flops(cfg, pairs))


def flash_train_work(cfg, batch: int, seq: int, pairs: int):
    """(operations, bytes) flash attention must do for one training pass of
    every layer: forward QK^T and PV, backward dV, dP, dQ and dK (six
    products over the causal pairs of each document), and one read or write
    of Q, K, V, O, dO, dQ, dK, dV in bf16 and of the fp32 log-sum-exp."""
    ops = 6 * 2 * pairs * cfg.hd * cfg.n_heads
    q_like = batch * seq * cfg.n_heads * cfg.hd * BF16_BYTES      # Q O dO dQ
    kv_like = batch * seq * cfg.n_kv_heads * cfg.hd * BF16_BYTES   # K V dK dV
    lse = batch * cfg.n_heads * seq * F32_BYTES
    return cfg.n_layers * ops, cfg.n_layers * (4 * q_like + 4 * kv_like + lse)


def roofline_seconds(ops: float, nbytes: float, peak: dict):
    """(least time, what bounds it) on a chip with ``peak``'s rates."""
    t_ops = ops / peak["bf16_flops_per_s"]
    t_bytes = nbytes / peak["hbm_bytes_per_s"]
    return (t_ops, "flops") if t_ops >= t_bytes else (t_bytes, "bytes")

