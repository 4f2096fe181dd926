"""The event stream of a run, drawn from the seed in numpy blocks.

A configuration's ``generator`` names its kind and parameters; the kind is
a file, ``generators/<kind>.py``, with ``types(gen)`` (the event types, in
the order their indices take) and ``draw(stream, n)`` (the next ``n``
events as columns).  The same seed gives the same events however many
blocks a run draws.  The program sees only the raw dict events.
"""
from __future__ import annotations

from typing import Dict, List

import numpy as np

from . import spec

BLOCK = 1 << 15


def rng_for(seed: int) -> np.random.Generator:
    """Any whole number is a seed, negative and past 64 bits included."""
    return np.random.default_rng(int(seed) % (1 << 64))


class Stream:
    """The event stream of one run: columns grown block by block.

    ``columns`` holds one numpy array per attribute plus ``type`` as an
    index into ``type_names``; :meth:`raw` gives event ``i`` as the dict the
    service takes.  A generator keeps what carries from block to block (a
    clock) in ``carry``.
    """

    def __init__(self, gen: dict, seed: int, root: str = spec.CHIP_DIR):
        self.gen = gen
        self.rng = rng_for(seed)
        self._kind = spec.load_generator(gen["kind"], root)
        self.type_names: List[str] = list(self._kind.types(gen))
        self.columns: Dict[str, np.ndarray] = {}
        self._lists: Dict[str, list] = {}
        self.carry: Dict[str, float] = {}
        self.n = 0

    def grow(self, n: int) -> None:
        """Draw blocks until the stream holds at least ``n`` events."""
        while self.n < n:
            block = self._kind.draw(self, BLOCK)
            for k, v in block.items():
                self.columns[k] = (v if k not in self.columns
                                   else np.concatenate([self.columns[k], v]))
                self._lists.setdefault(k, []).extend(
                    [self.type_names[c] for c in v.tolist()] if k == "type"
                    else v.tolist())
            self.n += BLOCK

    def raw(self, i: int) -> dict:
        if i >= self.n:
            self.grow(i + 1)
        return {k: col[i] for k, col in self._lists.items()}

    def select(self, idx: np.ndarray) -> Dict[str, np.ndarray]:
        """The columns of events ``idx`` (the accepted ones, in order)."""
        return {k: v[idx] for k, v in self.columns.items()}
