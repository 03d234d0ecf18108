"""Inputs made from ``--seed``: the token batches a traffic file describes,
and the key the weights are drawn from.

A traffic file (``bench/traffic/<name>.json``) holds the job's geometry and
data: ``seq``, ``mini_batch``, ``num_microbatches``, ``mesh``, ``remat``,
``tokens`` (the token distribution; ``"uniform"`` draws every id of the
vocabulary alike) and ``optimizer``. Every seed gets the same sizes; only
the token ids change.
"""
from __future__ import annotations

import numpy as np

DISTRIBUTIONS = ("uniform",)


def _seed_words(seed: int) -> int:
    return int(seed) % (1 << 64)


def weight_key_data(seed: int) -> int:
    """A 31-bit integer for ``jax.random.PRNGKey``, drawn from ``seed``."""
    ss = np.random.SeedSequence([_seed_words(seed), 0x5EED])
    return int(ss.generate_state(1, np.uint32)[0] >> 1)


class Tokens:
    """Token batches of one traffic mix under one seed. ``batch(n, step)``
    has the signature the program's input pipeline calls a dataset with;
    step ``i`` of the run always gets the same rows."""

    def __init__(self, traffic: dict, vocab_size: int, seed: int):
        if traffic["tokens"] not in DISTRIBUTIONS:
            raise ValueError(f"unknown token distribution {traffic['tokens']!r}")
        self.seq = int(traffic["seq"])
        self.vocab_size = int(vocab_size)
        self.seed = _seed_words(seed)

    def batch(self, batch_size: int, step: int):
        rng = np.random.default_rng([self.seed, 1, int(step)])
        toks = rng.integers(0, self.vocab_size, (batch_size, self.seq + 1),
                            dtype=np.int32)
        return {"tokens": toks[:, :-1], "labels": toks[:, 1:].copy()}
