"""The reference's side of a training round: plain Adam over the trainable
leaves, followed step by step, and the norms the comparison reads."""

from __future__ import annotations

import numpy as np

ADAM_B1, ADAM_B2, ADAM_EPS = 0.9, 0.999, 1e-8


def follow(loss_fn, trainable: dict, frozen: dict, batches: list,
           learning_rate: float) -> dict:
    """Run ``len(batches)`` Adam steps from ``trainable``.

    ``loss_fn(trainable, frozen, x, y)`` is the family's plain loss.
    Returns each step's loss, the first step's gradient and
    the change of each leaf over all the steps (host numpy)."""
    import jax
    import jax.numpy as jnp

    # the frozen weights go in as an argument: closed over, they would be
    # baked into the program as constants, gigabytes of them on the host
    @jax.jit
    def step(p, m, v, t, x, y, frozen):
        loss, g = jax.value_and_grad(loss_fn)(p, frozen, x, y)
        m = jax.tree.map(lambda a, b: ADAM_B1 * a + (1 - ADAM_B1) * b, m, g)
        v = jax.tree.map(lambda a, b: ADAM_B2 * a + (1 - ADAM_B2) * b * b,
                         v, g)
        c1, c2 = 1 - ADAM_B1 ** t, 1 - ADAM_B2 ** t
        p = jax.tree.map(
            lambda w, a, b: w - learning_rate * (a / c1)
            / (jnp.sqrt(b / c2) + ADAM_EPS), p, m, v)
        return p, m, v, loss, g

    p = trainable
    m = jax.tree.map(jnp.zeros_like, p)
    v = jax.tree.map(jnp.zeros_like, p)
    losses, first_grad = [], None
    for i, (x, y) in enumerate(batches):
        p, m, v, loss, g = step(p, m, v, jnp.float32(i + 1),
                                jnp.asarray(x), jnp.asarray(y), frozen)
        losses.append(float(loss))
        if first_grad is None:
            first_grad = jax.tree.map(np.asarray, g)
        del g
    return {"losses": losses, "first_grad": first_grad,
            "final": jax.tree.map(np.asarray, p),
            "initial": jax.tree.map(np.asarray, trainable)}
