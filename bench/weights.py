"""Random weights from a seed, made by the benchmark, not by the program.

Every leaf of the parameter tree is drawn from its own key: the root key
of the seed folded with a checksum of the leaf's path, and for a leaf
stacked over layers, folded again with the layer index.  So one layer of
one leaf can be drawn on its own, bit for bit as it sits inside the whole
tree, which is how ``reference.py`` rebuilds the weights layer by layer
without holding the model and without taking anything from the program.

By default the values follow the program's own initialisation:
projections and embeddings normal with standard deviation 0.02; the spike
boundaries at their initial threshold 0.01 and log-scale 0.  Norm scales
and biases, which the program initialises to zero, are drawn small and
non-zero here (standard deviation 0.1 and 0.02), so the comparison with
the reference also covers how they are applied.

A configuration's ``weights`` entry (``init`` below) changes that, as a
trained model would differ from a fresh one: ``fan_in_gain`` draws the
named projections with standard deviation ``gain / sqrt(fan_in)`` (the
leaf's first dimension), and ``spike_scale`` sets the named boundaries'
code range ``exp(log_scale)``, as training calibrates it to the values
that cross the boundary.
"""
from __future__ import annotations

import math
import zlib

import jax
import jax.numpy as jnp

#: leaves stacked over layers live under this key, with a leading layer dim
STACKED = "units"
_NORMS = {"ln", "ln2", "final_ln"}
_BIASES = {"bq", "bk", "bv"}


def root_key(seed_lo, seed_hi):
    """Key of a seed split into two 31-bit halves (seeds may exceed 32
    bits; the halves may be traced values)."""
    return jax.random.fold_in(jax.random.PRNGKey(seed_lo), seed_hi)


def split_seed(seed: int) -> tuple[int, int]:
    return seed & 0x7FFFFFFF, (seed >> 31) & 0x7FFFFFFF


def leaf_key(root, path: str):
    return jax.random.fold_in(root, zlib.crc32(path.encode()) & 0x7FFFFFFF)


def draw(key, path: str, shape, dtype, init=None):
    """The value of one leaf (or one layer of a stacked leaf)."""
    init = init or {}
    parts = path.split("/")
    name = parts[-1]
    if name == "theta":
        return jnp.full(shape, 0.01, dtype)
    if name == "log_scale":
        scale = init.get("spike_scale", {}).get(parts[-2], 1.0)
        return jnp.full(shape, math.log(scale), dtype)
    gain = init.get("fan_in_gain", {}).get(name)
    if gain is not None:
        std = gain / math.sqrt(shape[0])
    else:
        std = 0.1 if name in _NORMS else 0.02
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def draw_layer(root, path: str, layer, shape, dtype, init=None):
    """Layer ``layer`` of the stacked leaf at ``path`` (shape without the
    layer dim)."""
    return draw(jax.random.fold_in(leaf_key(root, path), layer), path,
                shape, dtype, init)


def path_str(path) -> str:
    return "/".join(str(getattr(p, "key", p)) for p in path)


def make_params(structs, shardings, seed: int, init=None):
    """The whole parameter tree for ``structs`` (a tree of
    ``ShapeDtypeStruct``), made on the device in one jitted call and
    placed by ``shardings``.  The seed enters as data, so every seed runs
    the same compiled program."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(structs)

    def gen(lo, hi):
        root = root_key(lo, hi)
        out = []
        for path, s in flat:
            p = path_str(path)
            if p.startswith(STACKED + "/"):
                out.append(jax.vmap(
                    lambda l, p=p, s=s: draw_layer(root, p, l, s.shape[1:],
                                                   s.dtype, init))(
                    jnp.arange(s.shape[0], dtype=jnp.int32)))
            else:
                out.append(draw(leaf_key(root, p), p, s.shape, s.dtype,
                                init))
        return jax.tree_util.tree_unflatten(treedef, out)

    lo, hi = split_seed(seed)
    return jax.jit(gen, out_shardings=shardings)(jnp.int32(lo),
                                                 jnp.int32(hi))
