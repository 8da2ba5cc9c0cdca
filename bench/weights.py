"""Weights made by the benchmark from ``--seed``, on the device, in one call.

The program under test takes these in place of its own initialisation, and
the reference makes the same ones, so neither reads the other's state.
Each leaf gets its own key (the seed's key folded with the leaf's index in
the tree): matrices are normal over the square root of their fan-in,
embedding and output head normal times 0.02, and norm scales normal times
0.1, so a norm applied as ``x * scale`` instead of ``x * (1 + scale)``
shows.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def key_data(seed: int) -> np.ndarray:
    """Two uint32 words from any whole-number seed (beyond 32 bits too)."""
    return np.random.SeedSequence(int(seed)).generate_state(2, np.uint32)


def _leaf_name(path) -> str:
    last = path[-1]
    return str(getattr(last, "key", getattr(last, "name", last)))


def _init_leaf(key, name: str, shape, dtype):
    x = jax.random.normal(key, shape, jnp.float32)
    if name in ("embed", "lm_head"):
        x = x * 0.02
    elif name.startswith("w") and len(shape) >= 2:
        x = x / np.sqrt(shape[-2])
    else:
        x = x * 0.1
    return x.astype(dtype)


def init_fn(template):
    """``f(key_data) -> params`` shaped like ``template`` (a pytree of arrays
    or ShapeDtypeStructs)."""
    paths, treedef = jax.tree_util.tree_flatten_with_path(template)

    def make(kd):
        key = jax.random.wrap_key_data(kd, impl="threefry2x32")
        leaves = [_init_leaf(jax.random.fold_in(key, i), _leaf_name(p),
                             leaf.shape, leaf.dtype)
                  for i, (p, leaf) in enumerate(paths)]
        return jax.tree_util.tree_unflatten(treedef, leaves)
    return make


def _stacked(path) -> bool:
    """A leaf under ``blocks`` holds one slice per layer on its leading axis."""
    return any(_leaf_name((k,)) == "blocks" for k in path)


def leaf_norms(tree):
    """Norms of the tree's leaves as one float32 vector.  Each layer's share
    of a stacked block leaf counts as a leaf of its own, as it would in a
    model that keeps one tree per layer."""
    out = []
    for path, x in jax.tree_util.tree_flatten_with_path(tree)[0]:
        sq = jnp.square(x.astype(jnp.float32))
        if _stacked(path):
            out.append(jnp.sqrt(jnp.sum(sq.reshape(sq.shape[0], -1), axis=1)))
        else:
            out.append(jnp.sqrt(jnp.sum(sq))[None])
    return jnp.concatenate(out)


def leaf_names(tree):
    """'/'-joined key paths of the leaves that ``leaf_norms`` reads, a
    stacked block leaf's layers as ``<path>[<layer>]``."""
    names = []
    for path, x in jax.tree_util.tree_flatten_with_path(tree)[0]:
        name = "/".join(_leaf_name((k,)) for k in path)
        names += ([f"{name}[{i}]" for i in range(x.shape[0])]
                  if _stacked(path) else [name])
    return names
