"""Weights from the seed, made by the benchmark and handed to both sides.

The program under test and the plain reference get the same numbers, and
neither gets them from the other: one jitted call draws every leaf of a
parameter tree (given only as shapes) from ``--seed``, on the device, in the
type the program holds its parameters in.  A leaf's draw depends on the
seed and on its path, never on the order of the leaves.
"""

from __future__ import annotations

import zlib
from typing import Any

import jax
import jax.numpy as jnp

PyTree = Any

WEIGHT_STD = 0.02   # the initializer_range the configurations publish
BIAS_STD = 0.02     # biases and layer-norm offsets are drawn too, so that
SCALE_STD = 0.1     # their gradients and their use are both exercised


def seed_key(seed: int):
    """A key for any whole number: the driver's seeds pass 2**31."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    return jax.random.fold_in(
        jax.random.key(seed & 0x7FFFFFFF), seed >> 31)


def _path_str(path) -> str:
    return "/".join(str(getattr(k, "key", getattr(k, "name", k)))
                    for k in path)


def _draw(key, path: str, shape, dtype):
    leaf_key = jax.random.fold_in(key, zlib.crc32(path.encode()) & 0x7FFFFFFF)
    last = path.rsplit("/", 1)[-1]
    noise = jax.random.normal(leaf_key, shape, jnp.float32)
    if last == "scale":
        value = 1.0 + SCALE_STD * noise
    elif "bias" in last:
        value = BIAS_STD * noise
    else:
        value = WEIGHT_STD * noise
    return value.astype(dtype)


def draw_params(key, abstract_params: PyTree) -> PyTree:
    """Traceable: the whole tree from ``seed_key(seed)``.  The key is an
    argument and not a constant of the program, so one compiled program
    serves every seed (and is found in the compile cache again)."""
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: _draw(key, _path_str(path), leaf.shape, leaf.dtype),
        abstract_params)


def make_params(seed: int, abstract_params: PyTree, shardings=None) -> PyTree:
    fn = jax.jit(lambda key: draw_params(key, abstract_params),
                 out_shardings=shardings)
    return fn(seed_key(seed))
