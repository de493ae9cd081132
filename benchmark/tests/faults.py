"""Faults planted under the timed path, for the tests that must see
``correct`` come out false. Never reached by a measuring run: the command
line has no way to name a fault."""

from __future__ import annotations

import numpy as np


def plant(fault: str, ops, train):
    if fault == "state_unchanged":
        return _unchanged(ops), train
    if fault == "half_batch":
        return ops, _HalfBatches(train)
    if fault in ("", "token_altered"):      # planted on the client's side
        return ops, train
    raise ValueError(f"unknown fault {fault!r}")


def _unchanged(ops):
    """A step that returns its state unchanged: the round ships what it
    was sent."""
    import jax
    import jax.numpy as jnp
    real = ops.train

    def train(dataset, params_cfg, **kwargs):
        before = jax.tree.map(jnp.copy, ops.variables)   # inputs are donated
        out = real(dataset, params_cfg, **kwargs)
        ops.variables = before
        out.variables = ops.get_variables()
        return out

    ops.train = train
    return ops


class _HalfBatches:
    """Half of every batch left out; the loss is the mean over the rest."""

    def __init__(self, dataset):
        self._dataset = dataset
        self.x, self.y, self.seed = dataset.x, dataset.y, dataset.seed

    def __len__(self):
        return len(self._dataset)

    @property
    def size(self):
        return len(self._dataset)

    def batches(self, *args, **kwargs):
        return self._dataset.batches(*args, **kwargs)

    def infinite_batches(self, batch_size, *args, **kwargs):
        for x, y in self._dataset.infinite_batches(batch_size, *args,
                                                   **kwargs):
            keep = max(1, len(x) // 2)
            yield x[:keep], y[:keep]


def alter_tokens(tokens: np.ndarray, vocab: int) -> np.ndarray:
    """One served token altered where the client reads it."""
    out = np.array(tokens)
    out[len(out) // 2] = (out[len(out) // 2] + 1 + vocab // 2) % vocab
    return out
