"""The precision control: the plain reference with every matrix product
taken in the nearest precision below the configuration's bfloat16, that
is float8 (e4m3: 3 mantissa bits, exponents -6 to 8, largest 448) operands
with one scale per tensor and float32 accumulation.  The rounding is
emulated in float32, so it runs on any chip, and is straight-through:
gradients flow as if it were not there, as in float8 training."""
from __future__ import annotations

import jax
import jax.numpy as jnp

E4M3_MAX = 448.0
MANTISSA_BITS = 3
MIN_EXPONENT = -6


def round_e4m3(y):
    """Round float32 values in [-448, 448] to the nearest e4m3 value."""
    mag = jnp.abs(y)
    e = jnp.floor(jnp.log2(jnp.where(mag > 0, mag, 1.0)))
    step = jnp.exp2(jnp.maximum(e, MIN_EXPONENT) - MANTISSA_BITS)
    return jnp.clip(jnp.round(y / step) * step, -E4M3_MAX, E4M3_MAX)


def to_fp8(x):
    x = x.astype(jnp.float32)
    scale = jnp.max(jnp.abs(x)) / E4M3_MAX
    scale = jnp.where(scale > 0, scale, 1.0)
    q = round_e4m3(x / scale) * scale
    return x + jax.lax.stop_gradient(q - x)


def fp8_matmul(spec, a, b):
    return jnp.einsum(spec, to_fp8(a), to_fp8(b),
                      precision=jax.lax.Precision.HIGHEST,
                      preferred_element_type=jnp.float32)
