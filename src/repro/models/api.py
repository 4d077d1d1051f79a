"""Unified model API: one entry point per lifecycle stage, dispatched through
the pluggable family registry (``repro.models.registry``).

``init_params``  → fp32 master parameter pytree
``loss_fn``      → (loss, metrics) for a training batch
``forward``      → logits for a full sequence (prefill)
``init_cache``   → decode caches (KV rings / SSM states / cross-KV)
``decode_step``  → one-token autoregressive step

New families register themselves with ``@register_family("<name>")`` and are
picked up here (and by every session/driver) with zero dispatch changes.
"""

from __future__ import annotations

from typing import Any, Dict

import jax

# imported for their registration side effects (each module registers its
# families at import time)
from repro.models import encdec, transformer  # noqa: F401
from repro.models.config import ModelConfig
from repro.models.registry import (  # noqa: F401 — re-exported public surface
    ModelFamily, family_of, get_family, register_family, registered_families,
)


def init_params(cfg: ModelConfig, key) -> Any:
    return family_of(cfg).init_params(cfg, key)


def loss_fn(cfg: ModelConfig, params, batch: Dict[str, jax.Array],
            *, remat_policy: str = "full"):
    return family_of(cfg).loss(cfg, params, batch, remat_policy=remat_policy)


def forward(cfg: ModelConfig, params, batch: Dict[str, jax.Array],
            *, remat_policy: str = "none", last_only: bool = False):
    return family_of(cfg).forward(cfg, params, batch,
                                  remat_policy=remat_policy, last_only=last_only)


def init_cache(cfg: ModelConfig, params, batch_size: int, max_len: int,
               batch: Dict[str, jax.Array] | None = None):
    fam = family_of(cfg)
    if batch is None:
        batch = fam.serve_batch(cfg, batch_size)
    return fam.init_cache(cfg, params, batch_size, max_len, batch)


def decode_step(cfg: ModelConfig, params, token: jax.Array, t: jax.Array, caches):
    return family_of(cfg).decode_step(cfg, params, token, t, caches)


def prefill_cache(cfg: ModelConfig, params, batch: Dict[str, jax.Array], caches):
    """Ingest a prompt into decode caches → (last-position logits, caches)."""
    return family_of(cfg).prefill_cache(cfg, params, batch, caches)


def cache_slot_axes(cfg: ModelConfig, caches):
    """Pytree of ints: the request ('slot') axis of every cache leaf."""
    return family_of(cfg).cache_slot_axes(cfg, caches)


def supports_paged_cache(cfg: ModelConfig) -> bool:
    return family_of(cfg).supports_paged_cache(cfg)


def paged_decode_step(cfg: ModelConfig, params, token, ts, pool, page_tables):
    return family_of(cfg).paged_decode_step(cfg, params, token, ts, pool,
                                            page_tables)


def paged_prefill(cfg: ModelConfig, params, batch: Dict[str, jax.Array],
                  pool, page_tables):
    return family_of(cfg).paged_prefill(cfg, params, batch, pool, page_tables)


class Model:
    """Convenience OO wrapper used by examples and the serving loop."""

    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg
        self.family = family_of(cfg)

    def init(self, key):
        return init_params(self.cfg, key)

    def loss(self, params, batch, **kw):
        return loss_fn(self.cfg, params, batch, **kw)

    def forward(self, params, batch, **kw):
        return forward(self.cfg, params, batch, **kw)

    def init_cache(self, params, batch_size, max_len, batch=None):
        return init_cache(self.cfg, params, batch_size, max_len, batch)

    def decode_step(self, params, token, t, caches):
        return decode_step(self.cfg, params, token, t, caches)


def build_model(cfg: ModelConfig) -> Model:
    return Model(cfg)
