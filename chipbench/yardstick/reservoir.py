"""The seeded sample of a closed window's answers that the check reads.

The window does not know in advance how many inputs it will complete, so
the sample is drawn as they complete, by reservoir sampling: the first
``size`` completed inputs fill the slots in order, and each later one
replaces a slot drawn uniformly, with the probability that keeps every
input completed so far equally likely to be held. At any point the slots
hold ``min(size, completed)`` inputs drawn uniformly without replacement
from every input completed so far, whatever the window's length.

The draws follow Algorithm L (Li, ACM TOMS 20(4), 1994), which jumps
from one admitted input to the next by a random skip, so a panel costs
the host a few operations for each input it admits and one comparison
besides.

A window draws one panel's admissions at a time (:meth:`Reservoir.draw`).
Where two inputs of one panel draw the same slot, the later replaces the
earlier, as the one-by-one algorithm does, so only the later is written.
The answers themselves stay on the device in a fixed-size buffer; this
object keeps what the host needs to check them: each slot's pool column
and the activity mask the program reported for it.
"""

from __future__ import annotations

import math

import numpy as np


class Reservoir:
    def __init__(self, size: int, seed: int):
        self.size = size
        self.rng = np.random.default_rng([seed, 7])
        self.seen = 0  # inputs completed and offered to the sample
        self.cols = np.zeros(size, np.int64)  # pool column held by each slot
        self.masks = np.zeros(size, bool)  # the program's mask bit of each slot
        self._w = math.exp(math.log(self._uniform()) / size)
        self._next = size - 1  # the last input admitted so far (0-based)
        self._skip()

    @property
    def filled(self) -> int:
        return min(self.size, self.seen)

    def _uniform(self) -> float:
        return 1.0 - self.rng.random()  # on (0, 1]: its log is finite

    def _skip(self) -> None:
        """Move ``_next`` to the next input past the fill to be admitted."""
        self._next += math.floor(math.log(self._uniform()) / math.log1p(-self._w)) + 1

    def draw(self, width: int) -> tuple[np.ndarray, np.ndarray]:
        """``(columns, slots)``: which of the next ``width`` completed
        inputs enter the sample, by their column in the panel (ascending),
        and the slot each is written to (no slot twice)."""
        start, end = self.seen, self.seen + width
        self.seen = end
        taken = {}  # slot -> panel column; a later column replaces an earlier
        for i in range(start, min(end, self.size)):
            taken[i] = i - start
        while self._next < end:
            taken[int(self.rng.integers(self.size))] = self._next - start
            self._w *= math.exp(math.log(self._uniform()) / self.size)
            self._skip()
        if not taken:
            return _NONE, _NONE
        slots = np.fromiter(taken, np.int64, len(taken))
        cols = np.fromiter(taken.values(), np.int64, len(taken))
        order = np.argsort(cols)
        return cols[order], slots[order]

    def admit(self, cols, slots, first: int, mask) -> None:
        """Record what the drawn ``slots`` now hold: the inputs at panel
        columns ``cols`` of a panel whose first input is pool column
        ``first``, and their bits of the panel's activity ``mask``."""
        self.cols[slots] = first + cols
        self.masks[slots] = mask[cols]


_NONE = np.zeros(0, np.int64)
