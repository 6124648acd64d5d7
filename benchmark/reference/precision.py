"""How a reference multiplies matrices.

``Exact`` is the reference proper: float32 operands, and float32
arithmetic on the TPU too (``precision="highest"``; by default a TPU
multiplies float32 in bfloat16 passes).

``Fp8`` is the control of a bfloat16 configuration: the same computation
with every matrix multiplication done on float8 operands, the nearest
precision below bfloat16 and the step a later PR would be tempted by.  It
follows the usual float8 recipe: one scale per tensor from its largest
magnitude, e4m3 for the forward operands, e5m2 for the gradient that flows
back into the two backward products, float32 accumulation.  The comparison
that decides ``correct`` has to reject it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def _exact_einsum(spec, a, b):
    return jnp.einsum(spec, a.astype(jnp.float32), b.astype(jnp.float32),
                      precision=jax.lax.Precision.HIGHEST,
                      preferred_element_type=jnp.float32)


class Exact:
    name = "float32-highest"

    def einsum(self, spec, a, b):
        return _exact_einsum(spec, a, b)


def _round_to(x, dtype):
    """``x`` rounded to a float8 type under one scale for the tensor."""
    x = x.astype(jnp.float32)
    top = float(jnp.finfo(dtype).max)
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / top
    return (x / scale).astype(dtype).astype(jnp.float32) * scale


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _fp8_einsum(spec, a, b):
    return _exact_einsum(spec, _round_to(a, jnp.float8_e4m3fn),
                         _round_to(b, jnp.float8_e4m3fn))


def _fp8_fwd(spec, a, b):
    qa = _round_to(a, jnp.float8_e4m3fn)
    qb = _round_to(b, jnp.float8_e4m3fn)
    return _exact_einsum(spec, qa, qb), (qa, qb)


def _fp8_bwd(spec, saved, g):
    qa, qb = saved
    _, vjp = jax.vjp(lambda x, y: _exact_einsum(spec, x, y), qa, qb)
    return vjp(_round_to(g, jnp.float8_e5m2))


_fp8_einsum.defvjp(_fp8_fwd, _fp8_bwd)


class Fp8(Exact):
    name = "float8 (e4m3 forward, e5m2 backward)"

    def einsum(self, spec, a, b):
        return _fp8_einsum(spec, a, b)


BY_NAME = {"exact": Exact, "fp8": Fp8}
