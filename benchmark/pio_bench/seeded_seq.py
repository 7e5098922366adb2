"""What a sequence configuration draws from ``--seed`` beyond
``seeded.py``: every user's history as one CSR array.  NumPy only.

Lengths are lognormal (``median``, ``sigma``) clipped to ``[lo, hi]``, drawn
independently per user; item ids are uniform over the catalog (every
vocabulary row is an item).
"""

from __future__ import annotations

import numpy as np

from pio_bench import seeded

STREAM_HISTORY_LENGTHS = 11
STREAM_HISTORY_ITEMS = 12


class Histories:
    """``indptr`` (users + 1,) int64 and ``items`` (sum of lengths,) int32,
    oldest event first; also the history provider the sequence template
    reads through (``recent_items`` / ``recent_indices``)."""

    def __init__(self, indptr: np.ndarray, items: np.ndarray):
        self.indptr, self.items = indptr, items

    def of(self, user: int, limit: int) -> np.ndarray:
        lo, hi = self.indptr[user], self.indptr[user + 1]
        return self.items[max(lo, hi - limit):hi]

    # users are named u<index>, items i<index> (engines/latent_moe_sequence)
    def recent_indices(self, user: str, limit: int, item_map=None):
        if user[:1] != "u" or not user[1:].isdigit():
            return self.items[:0]
        index = int(user[1:])
        if index >= len(self.indptr) - 1:
            return self.items[:0]
        return self.of(index, limit)

    def recent_items(self, user: str, limit: int) -> list:
        return [f"i{j}" for j in self.recent_indices(user, limit)]


def make_histories(seed: int, users: int, items: int, spec: dict) -> Histories:
    """Plain seeded draws, one per user: ``clip(rint(exp(normal)))``."""
    lengths = np.clip(
        np.rint(np.exp(seeded.rng(seed, STREAM_HISTORY_LENGTHS).normal(
            np.log(spec["median"]), spec["sigma"], users))),
        spec["min"], spec["max"]).astype(np.int64)
    indptr = np.zeros(users + 1, np.int64)
    np.cumsum(lengths, out=indptr[1:])
    ids = seeded.rng(seed, STREAM_HISTORY_ITEMS).integers(
        0, items, int(indptr[-1]), dtype=np.int32)
    return Histories(indptr, ids)
