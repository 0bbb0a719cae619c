"""Seeded single-writer typing sessions, generated a round at a time
for every document at once (numpy, no per-document loop).

Each document types and backspaces in runs at a cursor that jumps at
the start of some runs, the shape of the Eg-walker paper's sequential
editing traces (arXiv 2409.14252). A run types with a probability that
depends on where the live length stands against a band, so the length
rises into the band and then holds there. Every op is one character.
"""
from __future__ import annotations

import numpy as np

LETTERS = np.array(list("abcdefghijklmnopqrstuvwxyz"))


class TypingSessions:
    def __init__(self, seed: int, n_docs: int, params: dict):
        self.rng = np.random.default_rng(seed)
        self.p = params
        self.length = np.zeros(n_docs, np.int64)
        self.cursor = np.zeros(n_docs, np.int64)
        self.typing = np.ones(n_docs, bool)
        self.run_left = np.zeros(n_docs, np.int64)

    def step(self) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
        """One op per document: (is_insert, position, letter)."""
        p, rng = self.p, self.rng
        n = len(self.length)
        new = self.run_left == 0
        jump = new & (rng.random(n) < p["jump_prob"])
        self.cursor = np.where(
            jump, (rng.random(n) * (self.length + 1)).astype(np.int64),
            self.cursor)
        p_type = np.where(self.length < p["band_lo"], p["type_below"],
                          np.where(self.length > p["band_hi"],
                                   p["type_above"], p["type_inside"]))
        self.typing = np.where(new, rng.random(n) < p_type, self.typing)
        self.run_left = np.where(
            new, rng.geometric(1.0 / p["mean_run"], n), self.run_left)
        ins = self.typing | (self.cursor == 0)
        pos = np.where(ins, self.cursor, self.cursor - 1)
        letter = LETTERS[rng.integers(0, 26, n)]
        self.cursor = np.where(ins, self.cursor + 1, self.cursor - 1)
        self.length += np.where(ins, 1, -1)
        self.run_left -= 1
        return ins, pos, letter

    def round(self, k: int) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
        """The next ``k`` ops of every document, as [docs, k] arrays."""
        steps = [self.step() for _ in range(k)]
        return tuple(np.stack([s[i] for s in steps], axis=1)
                     for i in range(3))
