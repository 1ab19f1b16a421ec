"""What the plain references share: the seed's key and the controls'
rounding. Nothing of the program."""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax


def seed_key(seed: int):
    """A PRNG key from any non-negative seed, also above 2**31."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


# (exponent bits, mantissa bits, largest finite value) of the types a
# control may round to, as ``lax.reduce_precision`` takes them
FORMATS = {"bfloat16": (8, 7, None), "float8_e4m3fn": (4, 3, 240.0)}


def round_to(x, name: str):
    """``x`` rounded to the values the type ``name`` holds, kept in
    float32. By ``lax.reduce_precision``: a convert to the narrow type
    and back is an identity the TPU compiler may remove ("excess
    precision"; the float8 round trip read no different from none, my
    chip run, PR 24). The 8-bit type is scaled per tensor, as a float8
    matrix product would be: the largest magnitude lands on the type's
    largest value."""
    exponent, mantissa, largest = FORMATS[name]
    if largest is None:
        return lax.reduce_precision(x, exponent, mantissa)
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / largest
    return lax.reduce_precision(x / scale, exponent, mantissa) * scale
