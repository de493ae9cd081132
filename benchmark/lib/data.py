"""Inputs made from ``--seed``: token rows, images, labels. Host numpy only,
so the benchmark's parent (which may not touch a JAX backend), the recipe in
the chip-holding process and the reference all draw the same arrays."""

from __future__ import annotations

import numpy as np


def loader_order(n_rows: int, seed: int) -> np.ndarray:
    """Row order of epoch 0 as a loader seeded with ``seed`` shuffles it
    (``numpy.random.default_rng((seed, epoch)).shuffle``): the order in
    which the program's first local steps see the rows."""
    idx = np.arange(n_rows)
    np.random.default_rng((int(seed), 0)).shuffle(idx)
    return idx


def lm_rows(vocab: int, shape: dict, seed: int):
    """(train x, train y, test x, test y): ``local_steps * batch`` distinct
    rows of ``seq`` token ids uniform over the vocabulary; the target is
    the next token."""
    n_train = int(shape["local_steps"]) * int(shape["batch"])
    n_test = int(shape.get("test_rows", shape["batch"]))
    rng = np.random.default_rng([int(seed), 0xDA7A])
    x = rng.integers(0, vocab, (n_train + n_test, int(shape["seq"])),
                     dtype=np.int32)
    y = np.roll(x, -1, axis=1)
    return x[:n_train], y[:n_train], x[n_train:], y[n_train:]


def image_rows(cfg: dict, shape: dict, seed: int):
    """(train x, train y, test x, test y): float32 images and class ids."""
    n_train = int(shape["local_steps"]) * int(shape["batch"])
    n_test = int(shape.get("test_rows", shape["batch"]))
    side, ch = int(cfg["image_size"]), int(cfg["num_channels"])
    rng = np.random.default_rng([int(seed), 0x1A6E])
    x = rng.standard_normal((n_train + n_test, side, side, ch),
                            dtype=np.float32)
    y = rng.integers(0, int(cfg["num_labels"]), (n_train + n_test,),
                     dtype=np.int32)
    return x[:n_train], y[:n_train], x[n_train:], y[n_train:]


def batches_in_order(x, y, shape: dict, seed: int):
    """The first ``local_steps`` batches as the loader yields them."""
    order = loader_order(len(x), seed)
    b = int(shape["batch"])
    return [(x[order[i * b:(i + 1) * b]], y[order[i * b:(i + 1) * b]])
            for i in range(int(shape["local_steps"]))]


def lm_batches(vocab: int, shape: dict, seed: int):
    x, y, _, _ = lm_rows(vocab, shape, seed)
    return batches_in_order(x, y, shape, seed)


def image_batches(cfg: dict, shape: dict, seed: int):
    x, y, _, _ = image_rows(cfg, shape, seed)
    return batches_in_order(x, y, shape, seed)
