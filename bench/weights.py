"""Seeded model weights, made by the benchmark and handed to the program.

Each leaf is drawn from a key folded from the run's seed and the leaf's
path; a leaf stacked over layers (under ``blocks``) draws layer ``l``
from that key folded with ``l``.  So the reference can draw any single
layer again, in float32, without the program and without the others.

The program says which leaves exist and their dtypes (the shapes of its
own ``init_params``); the values, and the rule for each kind of leaf,
are the benchmark's.  Norm scales, SSM decays and skips are random
around their usual values, so a reference that drops one of them
disagrees with the program.
"""

from __future__ import annotations

import math
import zlib
from typing import Any, Tuple

import jax
import jax.numpy as jnp

_NORMS = ("pre_norm", "mlp_norm", "final_norm", "q_norm", "k_norm", "norm")
_OUT_PROJ = ("wo", "out_proj", "shared_wo")


def seed_key(seed: int) -> jax.Array:
    """A key for any whole-number seed, also past 32 bits."""
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0x7FFFFFFF)


def _path_key(seed: int, path: str) -> jax.Array:
    return jax.random.fold_in(seed_key(seed), zlib.crc32(path.encode()) >> 1)


def draw(key: jax.Array, path: str, shape: Tuple[int, ...],
         n_layers: int) -> jax.Array:
    """float32 values of one leaf (or one layer of a stacked leaf)."""
    name = path.rsplit("/", 1)[-1]
    if name in _NORMS:
        return 0.1 * jax.random.normal(key, shape, jnp.float32)
    if name == "A_log":
        return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0))
    if name == "D":
        return 1.0 + 0.1 * jax.random.normal(key, shape, jnp.float32)
    if name == "dt_bias":
        return -2.0 + 0.3 * jax.random.normal(key, shape, jnp.float32)
    scale = 0.02
    if name in _OUT_PROJ:
        scale /= math.sqrt(2 * n_layers)
    return scale * jax.random.truncated_normal(key, -2.0, 2.0, shape,
                                               jnp.float32)


def leaf_path(kp) -> str:
    return "/".join(str(getattr(k, "key", k)) for k in kp)


def is_stacked(path: str) -> bool:
    return path.startswith("blocks/")


def layer_values(seed: int, path: str, layer: int, shape: Tuple[int, ...],
                 dtype, n_layers: int) -> jax.Array:
    """Layer ``layer`` of a stacked leaf, as the program holds it
    (rounded to ``dtype``), returned in float32."""
    key = jax.random.fold_in(_path_key(seed, path), layer)
    return draw(key, path, shape, n_layers).astype(dtype).astype(jnp.float32)


def leaf_values(seed: int, path: str, shape: Tuple[int, ...], dtype,
                n_layers: int) -> jax.Array:
    """A whole leaf in ``dtype``: stacked leaves layer by layer."""
    if not is_stacked(path):
        return draw(_path_key(seed, path), path, shape,
                    n_layers).astype(dtype)
    keys = jax.vmap(lambda l: jax.random.fold_in(_path_key(seed, path), l))(
        jnp.arange(shape[0]))
    return jax.vmap(lambda k: draw(k, path, shape[1:], n_layers))(
        keys).astype(dtype)


def build(shapes: Any, seed: int, n_layers: int):
    """Every leaf of ``shapes`` (a pytree of ShapeDtypeStruct); call it
    inside a jitted function."""
    return jax.tree_util.tree_map_with_path(
        lambda kp, s: leaf_values(seed, leaf_path(kp), tuple(s.shape),
                                  s.dtype, n_layers), shapes)


def make(shapes: Any, seed: int, n_layers: int):
    """:func:`build` on the device, in one jitted call."""
    return jax.jit(lambda: build(shapes, seed, n_layers))()
