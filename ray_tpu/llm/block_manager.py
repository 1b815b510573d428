"""Host-side KV block accounting: free list + refcounts.

Reference parity: the role of vLLM's BlockSpaceManager under ray.llm
(allocation, refcounted copy-free prefix sharing). Device-side layout and
kernels live in :mod:`ray_tpu.models.paged`; this class is pure host
bookkeeping — nothing here touches an array.

Block 0 is reserved as the scratch block: free slots and padded prefill
tails write there, so it is never allocatable.

A block is ``block_size`` positions of whatever the family caches by
position: keys and values per head, or latent rows. What a family keeps per
sequence and not per position (a recurrent state, a convolution tail) lives
by slot beside the pool and is none of this class's business: it is sized by
``max_slots``, reset by the prefill that starts a sequence, and so neither
allocated nor freed.

A family whose layers are of two kinds (``models/paged.py``, a second table
kind that keeps a window) has a second part of the pool, for the layers that attend only the
last ``window`` positions, and :class:`WindowBlocks` keeps its books: a
``BlockManager`` of its own and a table a slot beside the slot's other one.
A block of a window layer is ``block_size`` positions of keys and values of
those layers alone. It is allocated just before the program that writes its
first position is launched (a prefill chunk's blocks together, a decode step's
next block every ``block_size`` tokens), and freed, while the request runs,
just before the launch of the first program none of whose queries sees any of
its positions: query ``i`` sees key ``j`` where ``i - j < window``, so before a
program whose first query is at ``p`` the blocks wholly at or below ``p -
window`` go back to the free list and the table points at the scratch block
there. The device runs programs in the order they were launched, so a step
still in flight has read a block before whoever is given it next writes it.
"""

from __future__ import annotations

import numpy as np


class BlockManager:
    def __init__(self, num_blocks: int):
        if num_blocks < 2:
            raise ValueError("need at least 2 blocks (block 0 is scratch)")
        self.num_blocks = num_blocks
        # LIFO free list: recently freed blocks are re-used first (their
        # pool pages are warmest).
        self._free = list(range(num_blocks - 1, 0, -1))
        self._rc: dict[int, int] = {}

    @property
    def free_blocks(self) -> int:
        return len(self._free)

    @property
    def used_blocks(self) -> int:
        return (self.num_blocks - 1) - len(self._free)

    def can_alloc(self, n: int) -> bool:
        return n <= len(self._free)

    def alloc(self, n: int) -> list[int]:
        """n fresh blocks at refcount 1; raises if the pool is short —
        callers gate on :meth:`can_alloc` for admission control."""
        if n > len(self._free):
            raise RuntimeError(
                f"KV pool exhausted: want {n}, have {len(self._free)}"
            )
        ids = [self._free.pop() for _ in range(n)]
        for b in ids:
            self._rc[b] = 1
        return ids

    def incref(self, ids) -> None:
        for b in ids:
            self._rc[b] += 1

    def decref(self, ids) -> list[int]:
        """Drop one reference per id; blocks reaching zero return to the
        free list. Returns the freed ids."""
        freed = []
        for b in ids:
            rc = self._rc[b] - 1
            if rc == 0:
                del self._rc[b]
                self._free.append(b)
                freed.append(b)
            else:
                self._rc[b] = rc
        return freed

    def refcount(self, block: int) -> int:
        return self._rc.get(block, 0)


class WindowBlocks:
    """The blocks of one layer kind that keeps the last ``window`` positions
    only, by slot (module docstring). ``tables`` [slots, W] is the engine's
    block table of the kind, written here and handed to the programs by the
    engine: entry ``i`` is the block of positions ``[i block, (i + 1) block)``,
    0 (the scratch block) where none is held. A slot never holds more than
    ``per_slot`` blocks (``models.paged.window_blocks_a_slot`` of the window
    and the longest prefill program), so ``slots * per_slot`` blocks (and the
    scratch block) never run out: nothing waits for a window block."""

    def __init__(self, window: int, per_slot: int, block_size: int, tables: np.ndarray):
        self.window, self.block_size, self.tables = window, block_size, tables
        self.per_slot = per_slot
        self.mgr = BlockManager(tables.shape[0] * per_slot + 1)
        # Of each slot: the blocks it holds, oldest first, and the table
        # entries they stand at, [lo, hi).
        self._held = [[] for _ in range(tables.shape[0])]
        self.lo = np.zeros(tables.shape[0], np.int64)
        self.hi = np.zeros(tables.shape[0], np.int64)
        self.released = 0  # blocks given back while their request ran

    def advance(self, slot: int, first_query: int, upto: int) -> None:
        """Before the launch of a program that writes positions up to ``upto``
        (exclusive) of ``slot`` and whose first query stands at
        ``first_query``: give back the blocks no query sees any more, take
        those the writes need."""
        bs = self.block_size
        dead = min(max(first_query - self.window + 1, 0) // bs, int(self.hi[slot]))
        lo, held = int(self.lo[slot]), self._held[slot]
        if dead > lo:
            self.mgr.decref(held[: dead - lo])
            del held[: dead - lo]
            self.tables[slot, lo:dead] = 0
            self.released += dead - lo
            self.lo[slot] = dead
        need, hi = -(-upto // bs), int(self.hi[slot])
        if need > hi:
            new = self.mgr.alloc(need - hi)
            held.extend(new)
            self.tables[slot, hi:need] = new
            self.hi[slot] = need

    def release(self, slot: int) -> None:
        """The slot's request has ended: every block it still holds."""
        self.mgr.decref(self._held[slot])
        self._held[slot] = []
        self.tables[slot] = 0
        self.lo[slot] = self.hi[slot] = 0

    @property
    def held_blocks(self) -> int:
        return self.mgr.used_blocks

    @property
    def full_retention_blocks(self) -> int:
        """What the slots would hold if nothing had been given back."""
        return int(self.hi.sum())
