"""Mixture-of-Experts FFN: a router over every expert of the layer, and the
experts held here.

A layer holds experts ``[0, n_experts)`` of the ``n_router`` its router
scores (all of them unless ``router_experts`` says more): it computes its
own experts' part of the result for the tokens routed to them, as one
chip of an expert-parallel deployment does before the exchange.

Two paths:

  * **dropless** (no mesh of more than one device): the router in f32,
    softmax and greedy top-k over every expert, then the (token, slot)
    pairs whose expert is held, laid out by a stable counting sort into
    row tiles of one expert each, sized for the worst case so that no pair
    is ever dropped; a grouped matmul (``kernels/grouped_matmul.py``) over
    the held experts; the gate-weighted sum back to the tokens.  Dispatch
    and combine are gathers both ways (custom VJPs), never scatters.
  * **GShard** capacity dispatch (a mesh is active, where the expert axis
    may be sharded): one-hot einsums that XLA turns into all-to-alls;
    tokens past ``capacity_factor`` are dropped there.

The shared experts run on every token on both.
"""

from __future__ import annotations

import contextlib
import contextvars
import functools
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.core import sharding
from repro.models import layers
from repro.models.config import ModelConfig

Params = Dict[str, Any]

# GShard-style dispatch groups: tokens are routed within groups of
# T/num_groups tokens, with capacity computed per group.  The launcher sets
# this to the data-parallel world size so each group is exactly one data
# shard — dispatch/combine tensors then stay shard-local instead of scaling
# with the GLOBAL batch (which is what blows up memory at 256-way meshes).
_moe_groups: contextvars.ContextVar[int] = contextvars.ContextVar("moe_groups",
                                                                  default=1)


@contextlib.contextmanager
def moe_groups(n: int):
    tok = _moe_groups.set(max(1, int(n)))
    try:
        yield
    finally:
        _moe_groups.reset(tok)


def moe_init(key, cfg: ModelConfig) -> Params:
    d, ff, E = cfg.d_model, cfg.moe_d_ff, cfg.n_experts
    kr, kg, ku, ko, ks = jax.random.split(key, 5)
    p: Params = {
        "router": layers.dense_init(kr, d, cfg.n_router, scale=0.02),
        "w_gate": jax.random.truncated_normal(kg, -3, 3, (E, d, ff), jnp.float32) / (d ** 0.5),
        "w_up": jax.random.truncated_normal(ku, -3, 3, (E, d, ff), jnp.float32) / (d ** 0.5),
        "w_out": jax.random.truncated_normal(ko, -3, 3, (E, ff, d), jnp.float32) / (ff ** 0.5),
    }
    if cfg.n_shared_experts:
        p["shared"] = layers.mlp_init(ks, d, cfg.n_shared_experts * ff, gated=True)
    return p


def no_stats() -> Dict[str, jax.Array]:
    """What a layer without experts adds to the step's MoE readings."""
    z = jnp.zeros((), jnp.float32)
    return {"aux": z, "moe_held_tokens": z, "moe_max_load": z}


def add_stats(a: Dict[str, jax.Array], b: Dict[str, jax.Array]):
    """Readings of two layers: balance losses and held pairs add, the load
    is the busier layer's."""
    return {"aux": a["aux"] + b["aux"],
            "moe_held_tokens": a["moe_held_tokens"] + b["moe_held_tokens"],
            "moe_max_load": jnp.maximum(a["moe_max_load"], b["moe_max_load"])}


def _route(cfg: ModelConfig, p: Params, xt: jax.Array):
    """Router over every expert in f32: (probs (..., n_router), gates and
    expert ids of the greedy top-k (..., k)).  Gates are renormalized only
    with ``norm_topk_prob``, then scaled by ``routed_scaling_factor``."""
    logits = jnp.einsum("...d,de->...e", xt.astype(jnp.float32),
                        p["router"].astype(jnp.float32),
                        precision=jax.lax.Precision.HIGHEST)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_w, gate_idx = jax.lax.top_k(probs, cfg.top_k)
    if cfg.norm_topk_prob:
        gate_w = gate_w / jnp.maximum(gate_w.sum(-1, keepdims=True), 1e-9)
    return probs, gate_w * cfg.routed_scaling_factor, gate_idx


def _balance(probs: jax.Array, gate_idx: jax.Array, rows: int) -> jax.Array:
    """Switch/DeepSeek balance loss ``Σ_e f_e · P_e`` over the whole router,
    per each of ``rows`` equal runs of tokens, then their mean: ``f_e`` is
    the share of the run's top-k picks that go to e times n_router,
    ``P_e`` e's mean probability.  probs (T, E_r), gate_idx (T, k)."""
    T, E = probs.shape
    k = gate_idx.shape[-1]
    picks = jax.nn.one_hot(gate_idx, E, dtype=jnp.float32).sum(1)  # (T, E)
    f = picks.reshape(rows, T // rows, E).mean(1) * (E / k)
    P = probs.reshape(rows, T // rows, E).mean(1)
    return jnp.mean(jnp.sum(f * P, -1))


def _mesh_active() -> bool:
    r = sharding.active_rules()
    return r is not None and r.mesh.size > 1


def moe_apply(cfg: ModelConfig, p: Params, x: jax.Array
              ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """x: (B, S, d) → (out (B, S, d), readings): ``aux`` the balance loss
    (per row with ``moe_seq_aux``), ``moe_held_tokens`` the (token, slot)
    pairs sent to held experts, ``moe_max_load`` the busiest held
    expert's pairs over the held experts' mean."""
    B, S, d = x.shape
    with jax.named_scope("moe.route"):
        probs, gate_w, gate_idx = _route(cfg, p, x.reshape(B * S, d))
        aux = _balance(probs, gate_idx, B if cfg.moe_seq_aux else 1)
    if _mesh_active():
        if cfg.n_router != cfg.n_experts:
            raise NotImplementedError(
                "a share of the experts runs only off a mesh (no ep axis)")
        out, sizes = _moe_gshard(cfg, p, x, gate_w, gate_idx)
    else:
        out, sizes = _moe_dropless(cfg, p, x, gate_w, gate_idx)
    if "shared" in p:
        with jax.named_scope("moe.shared"):
            out = out + layers.mlp_apply(p["shared"], x, gated=True)
    held = jnp.sum(sizes).astype(jnp.float32)
    mean = held / sizes.shape[0]
    load = jnp.where(held > 0, jnp.max(sizes) / jnp.maximum(mean, 1e-9), 0.0)
    return out, {"aux": aux, "moe_held_tokens": held,
                 "moe_max_load": load.astype(jnp.float32)}


# ---------------------------------------------------------------------------
# dropless path: counting sort, grouped matmul, gathers both ways
# ---------------------------------------------------------------------------

def _pick(rows: jax.Array, dest: jax.Array, s: int) -> jax.Array:
    """Slot ``s``'s row of every token (zeros for a pair held elsewhere,
    whose ``dest`` is the row past ``rows``): (T, d), one slot at a time so
    that no (T, k, d) array is ever built."""
    return _rows(rows, dest[:, s]).astype(jnp.float32)


def _rows(x: jax.Array, idx: jax.Array) -> jax.Array:
    """``x[idx]`` with zeros where ``idx`` is past ``x``'s rows."""
    return jnp.take(x, idx, axis=0, mode="fill", fill_value=0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _dispatch(xt, row_pair, dest, k):
    """Rows of the expert layout: row r holds token ``row_pair[r] // k``
    (zeros where ``row_pair[r]`` is past the pairs).  Its VJP sums each
    token's rows back by ``dest`` ((T, k): the row of each (token, slot)
    pair, the row past the layout for a pair held elsewhere): a gather
    too."""
    P = dest.size
    return _rows(xt, jnp.where(row_pair < P, row_pair // k, xt.shape[0]))


def _dispatch_fwd(xt, row_pair, dest, k):
    return _dispatch(xt, row_pair, dest, k), dest


def _dispatch_bwd(k, dest, dxs):
    dxt = sum(_pick(dxs, dest, s) for s in range(k))
    return dxt.astype(dxs.dtype), None, None


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@jax.custom_vjp
def _combine(ys, gate, dest, row_pair):
    """Each token's gate-weighted sum of its pairs' rows: out[t] =
    Σ_s gate[t, s] · ys[dest[t, s]] (nothing for a pair held elsewhere).
    Its VJP gathers each row's token gradient by ``row_pair``."""
    out = sum(gate[:, s, None] * _pick(ys, dest, s)
              for s in range(gate.shape[1]))
    return out.astype(ys.dtype)


def _combine_fwd(ys, gate, dest, row_pair):
    return _combine(ys, gate, dest, row_pair), (ys, gate, dest, row_pair)


def _combine_bwd(res, dout):
    ys, gate, dest, row_pair = res
    T, k = gate.shape
    P = T * k
    valid = row_pair < P
    w_row = _rows(gate.reshape(P), row_pair)
    dys = (_rows(dout, jnp.where(valid, row_pair // k, T)).astype(jnp.float32)
           * w_row[:, None]).astype(ys.dtype)
    g = dout.astype(jnp.float32)
    dgate = jnp.stack([jnp.sum(g * _pick(ys, dest, s), -1) for s in range(k)],
                      axis=1)
    return dys, dgate, None, None


_combine.defvjp(_combine_fwd, _combine_bwd)


def layout_rows(tokens: int, top_k: int, held: int) -> int:
    """Rows of the expert layout, for the worst case: every token sends
    min(k, held) pairs here, and each held expert pads up to one tile."""
    from repro.kernels.grouped_matmul import TILE_M
    need = tokens * min(top_k, held) + held * (TILE_M - 1)
    return -(-need // TILE_M) * TILE_M


def expert_layout(gate_idx: jax.Array, held: int):
    """Where each (token, slot) pair goes in the expert rows, by a stable
    counting sort on the expert id: pairs of held experts in expert order,
    each expert's rows padded to whole row tiles, the rows sized by
    :func:`layout_rows` so that no pair is ever dropped.  gate_idx (T, k)
    → dict: ``dest`` (T, k) each pair's row (the row past the layout for
    a pair held elsewhere), ``row_pair`` (M,) each row's pair ``t·k + s``
    (``T·k`` for an empty row), ``sizes`` (held,) pairs per expert, and
    the grouped matmul's ``tile_expert`` and ``n_tiles``."""
    from repro.kernels import grouped_matmul as gm
    T, k = gate_idx.shape
    P = T * k
    M = layout_rows(T, k, held)
    eid = gate_idx.reshape(P)
    onehot = (eid[:, None] == jnp.arange(held)[None]).astype(jnp.int32)
    sizes = jnp.sum(onehot, 0)
    rank = jnp.sum((jnp.cumsum(onehot, 0) - 1) * onehot, -1)
    start, tile_expert, n_tiles = gm.tile_layout(sizes, M)
    dest = jnp.where(eid < held, start[jnp.minimum(eid, held - 1)] + rank, M)
    row_pair = jnp.full((M,), P, jnp.int32).at[dest].set(
        jnp.arange(P, dtype=jnp.int32), mode="drop")
    return {"dest": dest.reshape(T, k), "row_pair": row_pair, "sizes": sizes,
            "tile_expert": tile_expert, "n_tiles": n_tiles}


def expert_ffn(p: Params, xs: jax.Array, lay: dict) -> jax.Array:
    """The held experts' SwiGLU on the expert rows: a grouped matmul each
    for gate, up and down (``expert_gmm*`` kernels)."""
    from repro.kernels import grouped_matmul as gm
    from repro.kernels.ops import _interpret

    def mm(a, w):
        return gm.expert_matmul(a, w, lay["tile_expert"], lay["n_tiles"],
                                lay["sizes"], _interpret())
    return mm(layers.swiglu(mm(xs, p["w_gate"]), mm(xs, p["w_up"])),
              p["w_out"])


def _moe_dropless(cfg: ModelConfig, p: Params, x: jax.Array, gate_w, gate_idx):
    B, S, d = x.shape
    T, k = B * S, cfg.top_k
    with jax.named_scope("moe.route"):
        lay = expert_layout(gate_idx, cfg.n_experts)
        xs = _dispatch(x.reshape(T, d), lay["row_pair"], lay["dest"], k)
    with jax.named_scope("moe.experts"):
        ys = expert_ffn(p, xs, lay)
    with jax.named_scope("moe.combine"):
        gate = jnp.where(gate_idx < cfg.n_experts, gate_w, 0.0)
        out = _combine(ys, gate, lay["dest"], lay["row_pair"])
    return out.reshape(B, S, d), lay["sizes"]


# ---------------------------------------------------------------------------
# GShard capacity dispatch (a mesh is active)
# ---------------------------------------------------------------------------

def _moe_gshard(cfg: ModelConfig, p: Params, x: jax.Array, gate_w, gate_idx):
    """Grouped dispatch: tokens are split into G groups (G = DP world size
    when launched under a mesh).  Routing, capacity and the dispatch/combine
    one-hots all carry a leading G axis sharded over the data axes, so every
    tensor is local to its shard; the expert einsums contract over the
    tp-sharded expert axis (EP → all-to-alls there only).  Pairs past an
    expert's capacity are dropped."""
    B, S, d = x.shape
    E, k = cfg.n_experts, cfg.top_k
    dt = x.dtype
    T = B * S
    G = _moe_groups.get()
    if T % G:
        G = 1
    Tg = T // G
    xt = sharding.constrain(x.reshape(G, Tg, d), "batch", None, None)
    gate_w = gate_w.reshape(G, Tg, k)
    one_hot_all = jax.nn.one_hot(gate_idx.reshape(G, Tg, k), E,
                                 dtype=jnp.float32)                # (G,Tg,k,E)
    capacity = int(max(k, round(Tg * k / E * cfg.capacity_factor)))
    capacity = min(capacity, Tg)

    # position of each (token, slot) within its expert queue, per group
    flat_onehot = one_hot_all.reshape(G, Tg * k, E)
    pos_in_expert = jnp.cumsum(flat_onehot, axis=1) - flat_onehot   # (G,Tg*k,E)
    pos = jnp.sum(pos_in_expert * flat_onehot, axis=-1).reshape(G, Tg, k)
    keep = pos < capacity                                      # (G,Tg,k)

    pos_oh = jax.nn.one_hot(jnp.where(keep, pos, capacity).astype(jnp.int32),
                            capacity, dtype=jnp.float32)        # (G,Tg,k,C)
    # combine (G,Tg,E,C); dispatch derived from it (one big tensor, not two —
    # the GShard trick; both in the compute dtype)
    combine = jnp.einsum("gtke,gtkc->gtec",
                         one_hot_all * (gate_w * keep)[..., None], pos_oh).astype(dt)
    dispatch = (combine > 0).astype(dt)

    xe = jnp.einsum("gtec,gtd->gecd", dispatch, xt)             # (G,E,C,d)
    xe = sharding.constrain(xe, "batch", "expert", None, None)
    h = layers.swiglu(jnp.einsum("gecd,edf->gecf", xe, p["w_gate"].astype(dt)),
                      jnp.einsum("gecd,edf->gecf", xe, p["w_up"].astype(dt)))
    ye = jnp.einsum("gecf,efd->gecd", h, p["w_out"].astype(dt))  # (G,E,C,d)
    ye = sharding.constrain(ye, "batch", "expert", None, None)
    out = jnp.einsum("gtec,gecd->gtd", combine, ye).reshape(B, S, d)
    sizes = jnp.sum(one_hot_all, axis=(0, 1, 2))
    return out, sizes
