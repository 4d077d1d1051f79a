"""Plain reference of a dense decoder: embedding, pre-norm blocks of GQA
attention with rotary positions and a (gated) MLP, a final norm and an
unembedding by the embedding table.  Written from the configuration file
alone, in float32 at the highest matmul precision, one row (or sequence) at
a time and one layer at a time, so that it fits beside nothing.  It imports
nothing of the program.  Over several chips its weights, gradients and
Adam's moments are split across them (``spread``), and each product runs
split as GSPMD places it; on one chip they stay whole.

``precision="fp8"`` is the control: the same computation with both operands
of every matrix product rounded to float8 e4m3 with one scale per tensor,
the step below the bf16 the configurations compute in.
"""

from __future__ import annotations

import math
import sys
import time
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

F8_MAX = 448.0


def param_shapes(m: dict) -> Dict[str, tuple]:
    """Canonical shapes of the weights, by name (layers stacked first)."""
    L, d, V, ff = m["n_layers"], m["d_model"], m["vocab_size"], m["d_ff"]
    hd = m.get("head_dim") or d // m["n_heads"]
    q, kv = m["n_heads"] * hd, m["n_kv_heads"] * hd
    out = {"embed": (V, d),
           "blocks/attn/wq": (L, d, q), "blocks/attn/wk": (L, d, kv),
           "blocks/attn/wv": (L, d, kv), "blocks/attn/wo": (L, q, d),
           "blocks/norm1/scale": (L, d), "blocks/norm2/scale": (L, d),
           "blocks/mlp/w_out": (L, ff, d), "final_norm/scale": (d,)}
    if m.get("gated_mlp", True):
        out.update({"blocks/mlp/w_gate": (L, d, ff), "blocks/mlp/w_up": (L, d, ff)})
    else:
        out["blocks/mlp/w_in"] = (L, d, ff)
    if m.get("norm", "rmsnorm") == "layernorm":
        out.update({"blocks/norm1/bias": (L, d), "blocks/norm2/bias": (L, d),
                    "final_norm/bias": (d,)})
    if not m.get("tie_embeddings", True):
        out["lm_head"] = (V, d)
    return out


def spread(shapes: Dict[str, tuple], devices):
    """``{name: sharding}`` of the reference's weights on ``devices``, or
    None on one device.  Each matrix is split over a 1-D mesh of the devices
    along the axis a Megatron split cuts: the input of ``wo`` and ``w_out``,
    the output of every other matrix (the embedding's d_model).  The layer
    axis stays whole, as the layer scan slices it; vectors, and an axis the
    devices do not divide, stay whole too."""
    if len(devices) == 1:
        return None
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    mesh = Mesh(np.asarray(devices), ("d",))

    def one(name, shape):
        inner = shape[1:] if name.startswith("blocks/") else shape
        axis = -2 if name.rsplit("/", 1)[-1] in ("wo", "w_out") else -1
        spec = [None] * len(shape)
        if len(inner) >= 2 and shape[axis] % len(devices) == 0:
            spec[axis] = "d"
        return NamedSharding(mesh, P(*spec))

    return {n: one(n, s) for n, s in shapes.items()}


def _q8(x):
    """Round to float8 e4m3 with one scale for the tensor."""
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / F8_MAX
    y = (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
    return x + jax.lax.stop_gradient(y - x)


def _ops(precision: str):
    q = _q8 if precision == "fp8" else (lambda x: x)
    if precision not in ("f32", "fp8"):
        raise ValueError(precision)

    def mm(eq, a, b):
        return jnp.einsum(eq, q(a.astype(jnp.float32)), q(b.astype(jnp.float32)),
                          precision=jax.lax.Precision.HIGHEST)
    return mm


def _norm(m: dict, p: dict, x, eps: float):
    if m.get("norm", "rmsnorm") == "layernorm":
        mu = jnp.mean(x, -1, keepdims=True)
        var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
        return (x - mu) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * p["scale"]


def _rope(x, theta: float):
    """Rotary positions on (S, H, D): the two halves of D rotate together."""
    S, _, D = x.shape
    freqs = 1.0 / theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs
    sin, cos = jnp.sin(ang)[:, None], jnp.cos(ang)[:, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def forward(m: dict, w: dict, tokens, segment_ids, precision: str = "f32",
            eps: float = 1e-6):
    """Logits (S, V) of one row of ``tokens`` (S,); attention is causal and
    stays within equal ``segment_ids``."""
    mm = _ops(precision)
    d = m["d_model"]
    H, Hkv = m["n_heads"], m["n_kv_heads"]
    hd = m.get("head_dim") or d // H
    g = H // Hkv
    S = tokens.shape[0]
    rope = m.get("pos_embed", "rope") == "rope"
    gelu = m.get("act", "silu") == "gelu"
    pos = jnp.arange(S)
    ok = (pos[None, :] <= pos[:, None]) & (segment_ids[:, None] == segment_ids[None, :])
    f32 = lambda t: jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), t)

    def layer(x, p):
        p = f32(p)
        h = _norm(m, p["norm1"], x, eps)
        q = mm("sd,de->se", h, p["attn"]["wq"]).reshape(S, H, hd)
        k = mm("sd,de->se", h, p["attn"]["wk"]).reshape(S, Hkv, hd)
        v = mm("sd,de->se", h, p["attn"]["wv"]).reshape(S, Hkv, hd)
        if rope:
            q, k = _rope(q, m.get("rope_theta", 10000.0)), _rope(k, m.get("rope_theta", 10000.0))
        k = jnp.repeat(k, g, axis=1)
        v = jnp.repeat(v, g, axis=1)
        s = mm("qhd,khd->hqk", q, k) / math.sqrt(hd)
        s = jnp.where(ok[None], s, -jnp.inf)
        a = jax.nn.softmax(s, axis=-1)
        o = mm("hqk,khd->qhd", a, v).reshape(S, H * hd)
        x = x + mm("se,ed->sd", o, p["attn"]["wo"])
        h = _norm(m, p["norm2"], x, eps)
        mlp = p["mlp"]
        if "w_gate" in mlp:
            u = jax.nn.silu(mm("sd,df->sf", h, mlp["w_gate"])) * mm("sd,df->sf", h, mlp["w_up"])
        else:
            u = mm("sd,df->sf", h, mlp["w_in"])
            u = jax.nn.gelu(u) if gelu else jax.nn.silu(u)
        return x + mm("sf,fd->sd", u, mlp["w_out"]), None

    x = w["embed"][tokens].astype(jnp.float32)
    x, _ = jax.lax.scan(jax.checkpoint(layer), x, w["blocks"])
    x = _norm(m, f32(w["final_norm"]), x, eps)
    table = w.get("lm_head", w["embed"])
    return mm("sd,vd->sv", x, table)


def nested(flat: Dict[str, jax.Array]) -> dict:
    out: dict = {}
    for name, x in flat.items():
        node = out
        parts = name.split("/")
        for part in parts[:-1]:
            node = node.setdefault(part, {})
        node[parts[-1]] = x
    return out


def _row_loss(m, precision, eps, w, tokens, labels, seg, mask):
    logits = forward(m, w, tokens, seg, precision, eps)
    nll = jax.nn.logsumexp(logits, -1) - jnp.take_along_axis(
        logits, labels[:, None], -1)[:, 0]
    return jnp.sum(nll * mask)


def layer_norms(tree) -> Dict[str, np.ndarray]:
    """Per-layer norm of every leaf of a nested tree (one value for a leaf
    that is not stacked)."""
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    out = {}
    for path, x in flat:
        name = "/".join(str(getattr(k, "key", k)) for k in path)
        x = x.astype(jnp.float32)
        if name.startswith("blocks/"):
            out[name] = np.asarray(jnp.sqrt(jnp.sum(
                jnp.square(x.reshape(x.shape[0], -1)), axis=1)))
        else:
            out[name] = np.asarray(jnp.sqrt(jnp.sum(jnp.square(x))))[None]
    return out


def train_readings(m: dict, opt: dict, batches: List[dict], seed: int,
                   precision: str = "f32", eps: float = 1e-6,
                   devices=None) -> dict:
    """Three AdamW steps from the seed's weights on ``batches``: each step's
    loss (mean over the loss mask), the per-layer norms of the first step's
    clipped gradient, and of the weights' change after the last step.
    Weights, gradients and moments are split over ``devices`` (default:
    the first device alone)."""
    import functools
    from bench import weights as wmod
    shapes = param_shapes(m)
    layout = spread(shapes, devices or jax.devices()[:1])
    w = nested(wmod.make_canonical(shapes, seed, jnp.float32, layout))
    out_sh = None
    if layout is not None:
        whole = jax.sharding.NamedSharding(
            next(iter(layout.values())).mesh, jax.sharding.PartitionSpec())
        out_sh = (whole, nested(layout))     # gradients split as the weights
    row_grad = jax.jit(jax.value_and_grad(functools.partial(
        _row_loss, m, precision, eps)), out_shardings=out_sh)
    add = jax.jit(lambda a, b: jax.tree_util.tree_map(jnp.add, a, b),
                  donate_argnums=0)
    decay = _decay_mask(w, opt)
    b1, b2 = opt["b1"], opt["b2"]
    # Adam's moments wait on the host while the gradients are on one chip;
    # over several they stay on the devices, split as the weights are
    if layout is None:
        zeros, home = (lambda x: np.zeros(x.shape, np.float32)), np.asarray
    else:
        zeros = lambda x: jnp.zeros(x.shape, jnp.float32, device=x.sharding)
        home = lambda x: x
    mom = jax.tree_util.tree_map(zeros, w)
    vel = jax.tree_util.tree_map(zeros, w)

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
    t0, t_rows = time.perf_counter(), 0.0
    with jax.default_matmul_precision("highest"):
        for step, b in enumerate(batches):
            tot, g = 0.0, None
            t_r = time.perf_counter()
            for r in range(b["tokens"].shape[0]):
                lr_, gr = row_grad(w, *(jnp.asarray(b[k][r]) for k in
                                        ("tokens", "labels", "segment_ids", "loss_mask")))
                tot += float(lr_)
                g = gr if g is None else add(g, gr)
                del gr
            t_rows += time.perf_counter() - t_r
            n = float(np.sum(b["loss_mask"]))
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
                p, a, bb = update(p, jax.device_put(a, p.sharding),
                                  jax.device_put(bb, p.sharding), gg, lr_t,
                                  bool(dec))
                out_w.append(p)
                out_m.append(home(a))
                out_v.append(home(bb))
                del a, bb
            w = jax.tree_util.tree_unflatten(tree, out_w)
            mom = jax.tree_util.tree_unflatten(tree, out_m)
            vel = jax.tree_util.tree_unflatten(tree, out_v)
            del g, leaves
    del mom, vel
    w0 = nested(wmod.make_canonical(shapes, seed, jnp.float32, layout))
    delta = layer_norms(jax.tree_util.tree_map(jnp.subtract, w, w0))
    print(f"reference ({precision}): {len(batches)} steps in "
          f"{time.perf_counter() - t0:.1f} s, of them row gradients "
          f"{t_rows:.1f} s", file=sys.stderr)
    return {"loss": losses, "grad": grad_norms, "delta": delta}


def _decay_mask(w, opt):
    """Which weights AdamW decays, as the configuration states it: every
    array of ``decay_min_ndim`` or more dimensions as the program stores it
    (the stacked per-layer norm gains included)."""
    return jax.tree_util.tree_map(lambda x: x.ndim >= opt["decay_min_ndim"], w)


def serve_gaps(m: dict, seed: int, seqs: List[tuple], dtype,
               precisions=("f32",), eps: float = 1e-6) -> Dict[str, list]:
    """For each (tokens, prompt_len) in ``seqs``: the gap by which each
    served token's reference logit lies below the reference's best, and for
    every other precision the gap of the token that precision puts first.
    Weights are the seed's, in the ``dtype`` they are served in."""
    import functools
    from bench import weights as wmod
    w = nested(wmod.make_canonical(param_shapes(m), seed, dtype))
    out = {p: [] for p in ("served",) + tuple(x for x in precisions if x != "f32")}
    fwd = {p: jax.jit(functools.partial(forward, m, precision=p, eps=eps))
           for p in ("f32",) + tuple(precisions)}
    with jax.default_matmul_precision("highest"):
        for tokens, plen in seqs:
            toks = jnp.asarray(tokens, jnp.int32)
            seg = jnp.zeros_like(toks)
            ref = fwd["f32"](w, toks, seg)[plen - 1:-1]
            best = jnp.max(ref, -1)
            served = toks[plen:]
            out["served"].append(np.asarray(
                best - jnp.take_along_axis(ref, served[:, None], -1)[:, 0]))
            for p in out:
                if p == "served":
                    continue
                pick = jnp.argmax(fwd[p](w, toks, seg)[plen - 1:-1], -1)
                out[p].append(np.asarray(
                    best - jnp.take_along_axis(ref, pick[:, None], -1)[:, 0]))
    return out
