"""Plain reference of DeepSeek-V2-Lite's decoder at one chip's share of an
expert-parallel deployment: embedding; ``first_k_dense_replace`` pre-norm
blocks of latent attention (MLA) and a SwiGLU MLP; then blocks of MLA and
an expert layer whose router scores every expert of the layer but whose
result holds only the experts held here (ids ``[0, n_experts)``), plus the
shared experts; a final norm and an untied head over the vocabulary
slice.  Written from the configuration file alone, in float32 at the
highest matmul precision, one row at a time and one layer at a time, one
attention head at a time, with no kernel, cache or batching; it imports
nothing of the program.  One chip only.

Departures from the published description (DeepSeek-V2, arXiv:2405.04434,
and its config.json), each shared with the program:

- the expert layer's result is the held experts' part alone, as one chip
  of EP 8 computes it before the exchange; the router keeps its 64
  outputs and top-6;
- the rotary channels are the two halves of the 64 rope dims (HF's
  checkpoint interleaves them: a fixed permutation of those columns);
- positions count along the packed row, as the dense cells do;
- the balance loss (``seq_aux``) is taken per packed row with the
  configuration's coefficient (the config gives none);
- every held expert is computed on every token and weighted by its gate
  (zero where the token did not pick it): the same sum as routing.

``precision="fp8"`` is the control: both operands of every matrix product
(the router's included) rounded to float8 e4m3 with one scale a tensor.
"""

from __future__ import annotations

import functools
import math
import sys
import time
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference.dense import _decay_mask, _ops, layer_norms, nested

BF16_BYTES = 2
F32_BYTES = 4


def param_shapes(m: dict) -> Dict[str, tuple]:
    """Canonical shapes by the program's leaf names: the leading dense
    layers unstacked (``pre_blocks/<i>/``), the expert layers stacked."""
    d, V, H = m["d_model"], m["vocab_size"], m["n_heads"]
    r, dn, dr, dv = (m["kv_lora_rank"], m["qk_nope_head_dim"],
                     m["qk_rope_head_dim"], m["v_head_dim"])
    E, ff = m["n_experts"], m["moe_d_ff"]
    Er = m.get("router_experts") or E
    nd = m["first_k_dense"]
    L = m["n_layers"] - nd
    attn = {"attn/wq": (d, H * (dn + dr)), "attn/wkv_a": (d, r + dr),
            "attn/kv_norm/scale": (r,), "attn/wkv_b": (r, H * (dn + dv)),
            "attn/wo": (H * dv, d), "norm1/scale": (d,), "norm2/scale": (d,)}
    out = {"embed": (V, d), "lm_head": (V, d), "final_norm/scale": (d,)}
    for i in range(nd):
        for n, s in attn.items():
            out[f"pre_blocks/{i}/{n}"] = s
        out.update({f"pre_blocks/{i}/mlp/w_gate": (d, m["d_ff"]),
                    f"pre_blocks/{i}/mlp/w_up": (d, m["d_ff"]),
                    f"pre_blocks/{i}/mlp/w_out": (m["d_ff"], d)})
    for n, s in attn.items():
        out[f"blocks/{n}"] = (L,) + s
    sh = m["n_shared_experts"] * ff
    out.update({"blocks/moe/router": (L, d, Er),
                "blocks/moe/w_gate": (L, E, d, ff),
                "blocks/moe/w_up": (L, E, d, ff),
                "blocks/moe/w_out": (L, E, ff, d),
                "blocks/moe/shared/w_gate": (L, d, sh),
                "blocks/moe/shared/w_up": (L, d, sh),
                "blocks/moe/shared/w_out": (L, sh, d)})
    return out


def _yarn(m: dict):
    """(rotary frequencies (rope/2,), the factor on rope's cos and sin,
    the softmax scale), from DeepSeek-V2's YaRN formulas."""
    dim, theta = m["qk_rope_head_dim"], m.get("rope_theta", 10000.0)
    f = m.get("rope_scaling_factor", 0.0)
    base = 1.0 / theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    scale = (m["qk_nope_head_dim"] + dim) ** -0.5

    def msc(ms):
        return 1.0 if f <= 1 else 0.1 * ms * math.log(f) + 1.0
    if f <= 1:
        return jnp.asarray(base, jnp.float32), 1.0, scale

    def turns_dim(turns):
        return (dim * math.log(m["rope_original_max"] / (turns * 2 * math.pi))
                / (2 * math.log(theta)))
    lo = max(math.floor(turns_dim(m.get("yarn_beta_fast", 32.0))), 0)
    hi = min(math.ceil(turns_dim(m.get("yarn_beta_slow", 1.0))), dim - 1)
    hi = hi + 0.001 if lo == hi else hi
    ramp = np.clip((np.arange(dim // 2) - lo) / (hi - lo), 0.0, 1.0)
    freqs = base / f * ramp + base * (1.0 - ramp)
    mall = m.get("yarn_mscale_all_dim", 0.0)
    if mall:
        scale *= msc(mall) ** 2
    return (jnp.asarray(freqs, jnp.float32),
            msc(m.get("yarn_mscale", 1.0)) / msc(mall), scale)


def _rope(x, freqs, factor):
    """(S, H, D) rotated by position, the two halves of D together."""
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * freqs
    sin, cos = jnp.sin(ang)[:, None], jnp.cos(ang)[:, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           -1) * factor


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _attention(m, mm, p, h, ok, rope, eps):
    S = h.shape[0]
    H, r = m["n_heads"], m["kv_lora_rank"]
    dn, dv = m["qk_nope_head_dim"], m["v_head_dim"]
    freqs, factor, scale = rope
    q = mm("sd,de->se", h, p["wq"]).reshape(S, H, -1)
    kv_a = mm("sd,de->se", h, p["wkv_a"])
    c = _rms(kv_a[:, :r], p["kv_norm"]["scale"], eps)
    kv = mm("sr,re->se", c, p["wkv_b"]).reshape(S, H, dn + dv)
    k_rope = _rope(kv_a[:, None, r:], freqs, factor)
    q = jnp.concatenate([q[..., :dn], _rope(q[..., dn:], freqs, factor)], -1)
    k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(
        k_rope, (S, H, k_rope.shape[-1]))], -1)
    v = kv[..., dn:]

    @jax.checkpoint
    def head(qkv):
        qh, kh, vh = qkv
        s = jnp.where(ok, mm("qd,kd->qk", qh, kh) * scale, -jnp.inf)
        return mm("qk,kd->qd", jax.nn.softmax(s, axis=-1), vh)

    o = jax.lax.map(head, (q.transpose(1, 0, 2), k.transpose(1, 0, 2),
                           v.transpose(1, 0, 2)))          # (H, S, dv)
    return mm("se,ed->sd", o.transpose(1, 0, 2).reshape(S, H * dv), p["wo"])


def _swiglu(mm, p, h):
    u = jax.nn.silu(mm("sd,df->sf", h, p["w_gate"])) * mm("sd,df->sf", h,
                                                          p["w_up"])
    return mm("sf,fd->sd", u, p["w_out"])


def _experts(m, mm, p, h):
    """(held experts' part + shared experts, the row's balance term)."""
    S = h.shape[0]
    E, k = m["n_experts"], m["top_k"]
    probs = jax.nn.softmax(mm("sd,de->se", h, p["router"]), axis=-1)
    Er = probs.shape[-1]
    gw, gi = jax.lax.top_k(probs, k)
    if m.get("norm_topk_prob", True):
        gw = gw / jnp.sum(gw, -1, keepdims=True)
    gw = gw * m.get("routed_scaling_factor", 1.0)
    gates = jnp.einsum("sk,ske->se", gw, jax.nn.one_hot(gi, E))  # held only
    picks = jnp.sum(jax.nn.one_hot(gi, Er), (0, 1))
    balance = jnp.sum(picks * Er / (S * k) * jnp.mean(probs, 0))
    u = (jax.nn.silu(mm("sd,edf->sef", h, p["w_gate"]))
         * mm("sd,edf->sef", h, p["w_up"]))
    y = mm("sef,efd->sed", u, p["w_out"])
    out = jnp.einsum("se,sed->sd", gates, y,
                     precision=jax.lax.Precision.HIGHEST)
    return out + _swiglu(mm, p["shared"], h), balance


def forward(m: dict, w: dict, tokens, segment_ids, precision: str = "f32",
            eps: float = 1e-6):
    """(logits (S, V), balance terms summed over the expert layers) of one
    row; attention is causal and stays within equal ``segment_ids``."""
    mm = _ops(precision)
    pos = jnp.arange(tokens.shape[0])
    ok = ((pos[None, :] <= pos[:, None])
          & (segment_ids[:, None] == segment_ids[None, :]))
    rope = _yarn(m)

    def layer(x, p, moe):
        x = x + _attention(m, mm, p["attn"], _rms(x, p["norm1"]["scale"], eps),
                           ok, rope, eps)
        h = _rms(x, p["norm2"]["scale"], eps)
        if not moe:
            return x + _swiglu(mm, p["mlp"], h), jnp.zeros((), jnp.float32)
        y, bal = _experts(m, mm, p["moe"], h)
        return x + y, bal

    x = w["embed"][tokens]
    for i in range(m["first_k_dense"]):
        x, _ = jax.checkpoint(functools.partial(layer, moe=False))(
            x, w["pre_blocks"][str(i)])
    x, bal = jax.lax.scan(jax.checkpoint(functools.partial(layer, moe=True)),
                          x, w["blocks"])
    x = _rms(x, w["final_norm"]["scale"], eps)
    return mm("sd,vd->sv", x, w["lm_head"]), jnp.sum(bal)


def _row_loss(m, precision, eps, w, tokens, labels, seg, mask, aux_weight):
    """Σ masked nll of the row plus ``aux_weight`` times its balance."""
    logits, bal = forward(m, w, tokens, seg, precision, eps)
    nll = jax.nn.logsumexp(logits, -1) - jnp.take_along_axis(
        logits, labels[:, None], -1)[:, 0]
    return jnp.sum(nll * mask) + aux_weight * bal


def train_readings(m: dict, opt: dict, batches: List[dict], seed: int,
                   precision: str = "f32", eps: float = 1e-6,
                   devices=None) -> dict:
    """Three AdamW steps from the seed's weights on ``batches``, as
    ``dense.train_readings`` takes them: each step's loss (mean masked
    cross-entropy plus ``moe_aux_coef`` times the rows' mean balance), the
    per-layer norms of the first step's clipped gradient, and of the
    weights' change after the last step."""
    from bench import weights as wmod
    if devices is not None and len(devices) > 1:
        raise ValueError("the MLA/MoE reference runs on one chip")
    shapes = param_shapes(m)
    w = nested(wmod.make_canonical(shapes, seed, jnp.float32))
    row_grad = jax.jit(jax.value_and_grad(functools.partial(
        _row_loss, m, precision, eps)))
    add = jax.jit(lambda a, b: jax.tree_util.tree_map(jnp.add, a, b),
                  donate_argnums=0)
    decay = _decay_mask(w, opt)
    b1, b2, coef = opt["b1"], opt["b2"], m.get("moe_aux_coef", 0.01)
    mom = jax.tree_util.tree_map(lambda x: np.zeros(x.shape, np.float32), w)
    vel = jax.tree_util.tree_map(lambda x: np.zeros(x.shape, np.float32), w)

    @functools.partial(jax.jit, static_argnums=5, donate_argnums=(0, 1, 2))
    def update(p, a, b, g, lr_t, dec):
        t, lr = lr_t
        a = b1 * a + (1 - b1) * g
        b = b2 * b + (1 - b2) * g * g
        u = (a / (1 - b1 ** t)) / (jnp.sqrt(b / (1 - b2 ** t)) + opt["eps"])
        if dec:
            u = u + opt["weight_decay"] * p
        return p - lr * u, a, b

    losses, grad_norms = [], None
    t0 = time.perf_counter()
    with jax.default_matmul_precision("highest"):
        for step, b in enumerate(batches):
            rows = b["tokens"].shape[0]
            n = float(np.sum(b["loss_mask"]))
            tot, g = 0.0, None
            for r in range(rows):
                lr_, gr = row_grad(w, *(jnp.asarray(b[k][r]) for k in
                                        ("tokens", "labels", "segment_ids",
                                         "loss_mask")),
                                   jnp.float32(n * coef / rows))
                tot += float(lr_)
                g = gr if g is None else add(g, gr)
                del gr
            losses.append(tot / n)
            leaves = jax.tree_util.tree_leaves(g)
            gn = float(jnp.sqrt(sum(jnp.sum(jnp.square(x)) for x in leaves))) / n
            clip = min(1.0, opt["grad_clip"] / (gn + 1e-9)) / n
            g = jax.tree_util.tree_map(lambda x: x * clip, g)
            if step == 0:
                grad_norms = layer_norms(g)
            lr = opt["peak_lr"] * min(1.0, (step + 1.0) / max(1, opt["warmup"]))
            lr_t = (jnp.float32(step + 1), jnp.float32(lr))
            flat_w, tree = jax.tree_util.tree_flatten(w)
            out_w, out_m, out_v = [], [], []
            for p, a, bb, gg, dec in zip(flat_w, jax.tree_util.tree_leaves(mom),
                                         jax.tree_util.tree_leaves(vel),
                                         jax.tree_util.tree_leaves(g),
                                         jax.tree_util.tree_leaves(decay)):
                p, a, bb = update(p, jnp.asarray(a), jnp.asarray(bb), gg, lr_t,
                                  bool(dec))
                out_w.append(p)
                out_m.append(np.asarray(a))
                out_v.append(np.asarray(bb))
                del a, bb
            w = jax.tree_util.tree_unflatten(tree, out_w)
            mom = jax.tree_util.tree_unflatten(tree, out_m)
            vel = jax.tree_util.tree_unflatten(tree, out_v)
            del g, leaves
    del mom, vel
    w0 = nested(wmod.make_canonical(shapes, seed, jnp.float32))
    delta = layer_norms(jax.tree_util.tree_map(jnp.subtract, w, w0))
    print(f"reference mla_moe ({precision}): {len(batches)} steps in "
          f"{time.perf_counter() - t0:.1f} s", file=sys.stderr)
    return {"loss": losses, "grad": grad_norms, "delta": delta}


# --------------------------------------------------------------------------
# the benchmark's counts for this block (bench/flops.py's rules: the work
# the algorithm requires, no recomputation, no padding; a multiply-add is
# two operations)
# --------------------------------------------------------------------------

def _mla_params(cfg) -> int:
    d, H, r = cfg.d_model, cfg.n_heads, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    return (d * H * (dn + dr) + d * (r + dr) + r * H * (dn + dv)
            + H * dv * d)


def expert_slots(cfg, tokens: int) -> float:
    """(token, slot) pairs the held experts expect: each token's top-k
    picks, the held share of the router's experts."""
    return tokens * cfg.top_k * cfg.n_experts / cfg.n_router


def train_flops(cfg, tokens: int, pairs: int) -> float:
    """Model operations of one training pass over ``tokens`` tokens whose
    documents hold ``pairs`` causal pairs: 6 per matmul weight per token
    (MLA projections in every layer, the dense layers' MLP, the shared
    experts and the router in the expert layers, the head over the
    vocabulary slice), 6 per expert weight per expected held slot, and
    attention at three times its forward (QK^T at the q/k head dim, PV at
    V's)."""
    d, ff = cfg.d_model, cfg.moe_d_ff
    nd = cfg.first_k_dense
    nm = cfg.n_layers - nd
    per_token = (cfg.n_layers * _mla_params(cfg) + nd * 3 * d * cfg.d_ff
                 + nm * (3 * d * cfg.n_shared_experts * ff + d * cfg.n_router)
                 + cfg.vocab_size * d)
    experts = nm * 3 * d * ff * expert_slots(cfg, tokens)
    attn_fwd = 2 * pairs * cfg.n_heads * (
        cfg.qk_nope_head_dim + cfg.qk_rope_head_dim + cfg.v_head_dim)
    return 6 * per_token * tokens + 6 * experts + 3 * cfg.n_layers * attn_fwd


def flash_train_work(cfg, batch: int, seq: int, pairs: int):
    """(operations, bytes) of flash attention in one training pass of every
    layer: QK^T, dP... six products over the causal pairs, three at the
    q/k head dim (QK^T, dQ, dK) and three at V's (PV, dV, dP); Q dQ K dK at
    the q/k head dim, O dO V dV at V's, in bf16, and the fp32 log-sum-exp,
    each moved once."""
    H = cfg.n_heads
    dqk = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
    dv = cfg.v_head_dim
    ops = 2 * pairs * H * (3 * dqk + 3 * dv)
    rows = batch * seq * H
    nbytes = rows * (4 * dqk + 4 * dv) * BF16_BYTES + rows * F32_BYTES
    return cfg.n_layers * ops, cfg.n_layers * nbytes
