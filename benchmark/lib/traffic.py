"""The one generator of serving traffic: a traffic file's parameters and
``--seed`` in, one stream of requests out, which the clients share: each
takes the stream's next request when its reply arrives. The stream is the
fixed block of (prompt length, output length) pairs over and over, each
pass in another order and with other token ids drawn from the seed, so that
every seed offers the same work: whole blocks, and one partial one at the
window's end."""

from __future__ import annotations

import threading

import numpy as np


def request_block(traffic: dict) -> list:
    """The fixed block of (prompt_len, out_len) pairs: prompt lengths in
    the grid's stated proportions (largest remainders), output lengths
    evenly spread over [out_min, out_max], paired by the block's own seed."""
    n = int(traffic["block"])
    grid = [int(g) for g in traffic["prompt_grid"]]
    weights = np.asarray(traffic["prompt_weights"], float)
    exact = weights / weights.sum() * n
    counts = np.floor(exact).astype(int)
    for i in np.argsort(-(exact - counts))[: n - counts.sum()]:
        counts[i] += 1
    prompts = np.repeat(grid, counts)
    outs = np.rint(np.linspace(int(traffic["out_min"]),
                               int(traffic["out_max"]), n)).astype(int)
    rng = np.random.default_rng(int(traffic.get("block_seed", 0)))
    rng.shuffle(outs)
    return [(int(p), int(o)) for p, o in zip(prompts, outs)]


def requests(traffic: dict, vocab: int, seed: int):
    """Endless stream of (prompt tokens, out_len)."""
    block = request_block(traffic)
    rng = np.random.default_rng([int(seed), 0x5E7])
    while True:
        for i in rng.permutation(len(block)):
            plen, olen = block[i]
            yield rng.integers(0, vocab, (plen,), dtype=np.int32), olen


class SharedStream:
    """``requests`` behind a lock: the clients' threads take turns."""

    def __init__(self, traffic: dict, vocab: int, seed: int):
        self._stream = requests(traffic, vocab, seed)
        self._lock = threading.Lock()

    def take(self):
        with self._lock:
            return next(self._stream)
