"""Seeded inputs: every random choice of a run is drawn here from ``--seed``.

Each purpose draws from a stream of its own
(``np.random.SeedSequence([seed, stream])``), so adding a draw to one stream
never moves another.  A training run draws two keys: the weights' and the
one its steps fold their step index into.
"""
from __future__ import annotations

import numpy as np

STREAMS = {"weights": 1, "train": 2}


def rng(seed: int, stream: str) -> np.random.Generator:
    return np.random.default_rng(
        np.random.SeedSequence([int(seed) % 2 ** 64, STREAMS[stream]]))


def key_words(seed: int, stream: str) -> np.ndarray:
    """A raw threefry key (two uint32 words) for ``stream``."""
    return rng(seed, stream).integers(0, 2 ** 32, size=2, dtype=np.uint32)
