"""Weights made from the seed by the benchmark, never by the program.

The program's own initializer decides nothing here: the benchmark draws
every leaf of the program's parameter tree from the seed, on the device, in
one jitted call, and the reference draws the same values in the same way.
Each leaf is drawn at its canonical shape (layers first, as a plain stack
of layers) from a key folded from the seed and the leaf's path, then laid
out in the shape the program stores it in.
"""

from __future__ import annotations

import math
import zlib

import jax
import jax.numpy as jnp
import numpy as np

EMBED_STD = 0.02
BIASES = {"bias", "bq", "bk", "bv", "b_in", "b_out"}


def seed_key(seed: int, stream: int = 0):
    """A threefry key from any non-negative integer below 2**62: benchmark
    seeds exceed 32 bits, and ``stream`` separates further draws."""
    if not 0 <= seed < 2 ** 62:
        raise ValueError(f"seed {seed} out of range")
    data = np.array([(seed >> 32) ^ (stream << 30), seed & 0xFFFFFFFF],
                    np.uint32)
    return jax.random.wrap_key_data(data, impl="threefry2x32")


def path_name(path) -> str:
    return "/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in path)


def canonical_shape(name: str, shape, n_layers: int):
    """A stacked-layer leaf as (n_layers, ...) whatever the program's stage
    layout folds the layer axis into."""
    shape = tuple(shape)
    if not name.startswith("blocks/"):
        return shape
    for k in range(1, len(shape) + 1):
        if math.prod(shape[:k]) == n_layers:
            return (n_layers,) + shape[k:]
    raise ValueError(f"{name}{shape}: no leading dims multiply to "
                     f"{n_layers} layers")


def draw(key, name: str, shape, dtype):
    """One leaf: norm gains 1, biases 0, the embedding N(0, 0.02²), every
    other matrix N(0, 1/fan_in) with fan_in its second-to-last dim."""
    leaf = name.rsplit("/", 1)[-1]
    if leaf == "scale":
        return jnp.ones(shape, dtype)
    if leaf in BIASES:
        return jnp.zeros(shape, dtype)
    k = jax.random.fold_in(key, zlib.crc32(name.encode()))
    std = EMBED_STD if leaf == "embed" else 1.0 / math.sqrt(shape[-2])
    return (jax.random.normal(k, shape, jnp.float32) * std).astype(dtype)


def make(abstract_tree, seed: int, n_layers: int, dtype, shardings=None):
    """The benchmark's weights in the layout of ``abstract_tree`` (a tree of
    shapes, e.g. from ``jax.eval_shape`` of the program's init), in
    ``dtype``, placed by ``shardings`` — one jitted call on the device."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(abstract_tree)
    names = [path_name(p) for p, _ in leaves]
    shapes = [tuple(x.shape) for _, x in leaves]

    def build(key):
        out = []
        for name, shape in zip(names, shapes):
            x = draw(key, name, canonical_shape(name, shape, n_layers), dtype)
            out.append(x.reshape(shape))
        return jax.tree_util.tree_unflatten(treedef, out)

    return jax.jit(build, out_shardings=shardings)(seed_key(seed))


def make_canonical(names_shapes, seed: int, dtype, shardings=None):
    """The same draws for the reference: ``{name: canonical shape}`` →
    ``{name: array}``, each leaf moved to ``shardings[name]`` (where given)
    as soon as it is drawn."""
    key = seed_key(seed)
    place = ((lambda n, x: x) if shardings is None
             else (lambda n, x: jax.device_put(x, shardings[n])))
    return {n: place(n, draw(key, n, s, dtype))
            for n, s in names_shapes.items()}
