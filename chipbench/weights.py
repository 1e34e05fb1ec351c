"""Seeded weights, made on the device in one jitted call.

The benchmark owns the weights: ``make_params`` draws every leaf of the
served model's parameter tree from the run's seed, in the dtype the
model serves it in, and the engines are handed that tree (see
``program.install_weights``).  The plain reference reads the same draws
through its architecture module's view of the tree (``arch/<model_type>.py``
``canonical_layer`` / ``canonical_head``), so it takes nothing the program
made.

Scales: matrices N(0, 1/fan_in), the embedding N(0, 0.02^2), and every
RMSNorm gain 1 + N(0, 0.1^2).  The program stores a norm gain as its
offset from 1, so the leaf holds the N(0, 0.1^2) draw; random gains make
a dropped or misplaced norm visible to the comparison.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

NORM_LEAVES = ("ln1", "ln2", "final_norm", "q_norm", "k_norm")
NORM_SD = 0.1
EMBED_SD = 0.02


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any whole number up to 64 bits, keeping every
    bit (``PRNGKey`` alone overflows above 2**31 - 1)."""
    seed = int(seed) % (1 << 64)
    return jax.random.wrap_key_data(
        jnp.asarray([seed >> 32, seed & 0xFFFFFFFF], jnp.uint32))


def _leaf_name(path) -> str:
    return "/".join(str(getattr(p, "key", p)) for p in path)


@functools.partial(jax.jit, static_argnums=0)
def _draw(layout, key):
    shapes, treedef = layout
    leaves = []
    for i, (name, shape, dtype) in enumerate(shapes):
        k = jax.random.fold_in(key, i)
        last = name.rsplit("/", 1)[-1]
        if last in NORM_LEAVES:
            scale = NORM_SD
        elif last == "embed":
            scale = EMBED_SD
        else:
            scale = 1.0 / float(np.sqrt(shape[-2]))
        leaves.append(jax.random.normal(k, shape, dtype) * jnp.asarray(
            scale, dtype))
    return jax.tree_util.tree_unflatten(treedef, leaves)


def layout_of(shape_tree) -> tuple:
    """Hashable (names, shapes, dtypes) + treedef of a parameter tree of
    ``ShapeDtypeStruct``s: the static key of the jitted draw."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(shape_tree)
    shapes = tuple((_leaf_name(p), tuple(a.shape), jnp.dtype(a.dtype).name)
                   for p, a in flat)
    return shapes, treedef


def make_params(shape_tree, seed: int):
    """Every leaf of ``shape_tree`` drawn from ``seed``, on the default
    device, in one compiled program."""
    return _draw(layout_of(shape_tree), seed_key(seed))
