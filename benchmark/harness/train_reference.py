"""Follows a training run's first steps with the plain reference and
compares what the timed step produced with it, leaf by leaf."""

from __future__ import annotations

import statistics
from typing import Any, Dict, List

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from benchmark.harness import weights
from benchmark.reference import adamw


def leaf_norms(tree) -> Dict[str, jax.Array]:
    flat = jax.tree_util.tree_leaves_with_path(tree)
    return {weights._path_str(path): jnp.sqrt(jnp.sum(jnp.square(
        leaf.astype(jnp.float32)))) for path, leaf in flat}


def _spread(mesh: Mesh, shape) -> NamedSharding:
    """Shard a leaf over the reference's devices along its largest
    dimension that divides: placement only, the arithmetic is unchanged."""
    n = mesh.size
    dims = [i for i, s in enumerate(shape) if s % n == 0 and s >= n]
    if n == 1 or not dims:
        return NamedSharding(mesh, P())
    axis = max(dims, key=lambda i: shape[i])
    return NamedSharding(mesh, P(*([None] * axis + ["d"])))


def follow(ref, dot, cfg: Dict[str, Any], opt: Dict[str, Any], seed: int,
           abstract_params, host_batches: List[Dict[str, np.ndarray]],
           rows_per_block: int, devices) -> Dict[str, Any]:
    """Losses of each step; of the first gradient the norm before clipping
    and the leaf norms after it; and leaf norms of the parameters' change
    over ``len(host_batches)`` steps."""
    mesh = Mesh(np.asarray(devices), ("d",))
    f32 = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, jnp.float32), abstract_params)
    shardings = jax.tree.map(lambda s: _spread(mesh, s.shape), f32)
    params0 = weights.make_params(seed, f32, shardings)
    rows = next(iter(host_batches[0].values())).shape[0]
    if rows % rows_per_block:
        raise ValueError(f"{rows} rows do not split into blocks of "
                         f"{rows_per_block}")
    blocks = rows // rows_per_block
    row_sharding = NamedSharding(
        mesh, P(None, "d") if rows_per_block % mesh.size == 0 else P())

    def step(params, state, batch):
        def one_block(carry, block):
            acc, total = carry
            value, grads = jax.value_and_grad(
                lambda p: ref.loss(dot, cfg, p, block))(params)
            acc = jax.tree.map(lambda a, g: a + g / blocks, acc, grads)
            return (acc, total + value / blocks), None

        zero = jax.tree.map(jnp.zeros_like, params)
        (grads, loss), _ = jax.lax.scan(
            one_block, (zero, jnp.zeros((), jnp.float32)), batch)
        grads, norm = adamw.clip_by_global_norm(
            grads, float(opt["clip_global_norm"]))
        new_params, new_state = adamw.update(opt, params, state, grads)
        return new_params, new_state, loss, norm, leaf_norms(grads)

    step = jax.jit(step, donate_argnums=(1,))
    if len(host_batches) > int(opt["warmup_steps"]):
        raise ValueError("the reference follows warm-up steps only")
    params, state = params0, adamw.init(params0)
    losses, grad_norms, global_norm = [], None, None
    for host in host_batches:
        batch = {k: jax.device_put(
            v.reshape((blocks, rows_per_block) + v.shape[1:]), row_sharding)
            for k, v in host.items()}
        new_params, state, loss, norm, norms = step(params, state, batch)
        if params is not params0:
            jax.tree.map(lambda x: x.delete(), params)
        params = new_params
        losses.append(float(loss))
        if grad_norms is None:
            grad_norms = {k: float(v) for k, v in norms.items()}
            global_norm = float(norm)
    delta = jax.jit(lambda a, b: leaf_norms(
        jax.tree.map(jnp.subtract, a, b)))(params, params0)
    return {"losses": losses, "grad_norms": grad_norms,
            "grad_global_norm": global_norm,
            "delta_norms": {k: float(v) for k, v in delta.items()}}


def worst_leaf_gap(program: Dict[str, float], reference: Dict[str, float]):
    """The widest gap between the program's norm of a leaf and the
    reference's, against the reference's norm of that leaf or of the median
    leaf, whichever is larger (some leaves' gradients are all but zero).
    Returns (gap, leaf)."""
    if set(program) != set(reference):
        raise ValueError(
            "leaf sets differ: "
            f"{sorted(set(program) ^ set(reference))[:6]}")
    floor = statistics.median(reference.values())
    gaps = {k: abs(program[k] - reference[k]) / max(reference[k], floor, 1e-30)
            for k in reference}
    leaf = max(gaps, key=gaps.get)
    return gaps[leaf], leaf
