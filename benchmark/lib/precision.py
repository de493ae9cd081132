"""Matrix products of the plain references: float32 at ``highest``
precision, or — the control — with both operands rounded to fp8."""

from __future__ import annotations


def _fp8(x):
    """Round to e4m3 (4 significant bits, subnormal floor 2**-9) under a
    per-tensor scale that puts the largest magnitude at 448."""
    import jax
    import jax.numpy as jnp
    amax = jax.lax.stop_gradient(jnp.maximum(jnp.max(jnp.abs(x)), 1e-30))
    scale = 448.0 / amax
    y = x * scale
    _, e = jnp.frexp(jax.lax.stop_gradient(y))
    step = jnp.maximum(jnp.ldexp(jnp.ones_like(y), e - 4), 2.0 ** -9)
    q = jnp.round(y / step) * step / scale
    # straight-through: the rounding passes the gradient as it is
    return x + jax.lax.stop_gradient(q - x)


def make_ein(quant: str = ""):
    import jax
    import jax.numpy as jnp

    def ein(spec, a, b):
        if quant == "fp8":
            a, b = _fp8(a), _fp8(b)
        elif quant:
            raise ValueError(f"unknown control precision {quant!r}")
        return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST,
                          preferred_element_type=jnp.float32)

    return ein
