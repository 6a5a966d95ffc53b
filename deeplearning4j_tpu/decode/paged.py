"""Paged KV cache: a BlockPool of fixed-size token blocks + block tables.

The slab cache pays `[slots, capacity, H, Dh]` of HBM per attention layer
whether or not any request uses its capacity — a 2k-capacity slot serving a
40-token chat strands 98% of its bytes, the same stranded-capacity math the
ZeRO sharding work attacked for optimizer state (arXiv 2004.13336). The
paged layout stops paying for unused tokens:

  pool   [num_blocks, block_size, H, Dh]   one allocation, all slots
  table  [slots, capacity//block_size] i32 logical block j of slot s lives
                                           in pool block table[s, j]

Token t of a slot lives at (table[s, t // block_size], t % block_size), so
a gather of the slot's table row reconstructs its contiguous K/V — that is
`kernels.flash_attention.flash_decode_paged`. The table is a plain int32
ARRAY OPERAND of the decode step (replicated on a mesh; the pool itself
keeps head-sharding), never a shape: requests of any length mix in one
executable, and the zero-steady-state-recompile contract survives paging.

Block 0 is a reserved SCRATCH block: unallocated table entries and the pad
chunks of a prefill bucket all point there, so out-of-range writes land in
a block nobody reads (every read is masked by the per-slot length vector)
instead of needing in-trace bounds checks.

Everything stateful here is HOST-SIDE and owned by the scheduler loop
thread: `BlockPool` hands out physical block ids (`alloc`/`free`), the
scheduler writes table rows, and admission may OVERSUBSCRIBE the pool —
admit more requests than the pool could back at full length — with a
watermark-triggered preempt of the youngest slot when growth runs dry
(the preempted request keeps its partial tokens and re-prefills
prompt+partial on re-admission; see DecodeScheduler)."""
from __future__ import annotations

import numpy as np


class PoolExhausted(RuntimeError):
    """Allocation failed: fewer free blocks than requested. The scheduler
    answers by preempting the youngest slot (watermark policy), never by
    failing the request."""


def blocks_for(n_tokens, block_size):
    """Physical blocks needed to hold n_tokens."""
    return -(-int(n_tokens) // int(block_size))


class BlockPool:
    """Host-side free-list allocator over the pool's physical blocks.

    Block 0 is never handed out (the scratch block). Allocation is
    all-or-nothing; `defrag()` re-sorts the free list so future allocations
    prefer low block ids, keeping the pool's high-water mark (and the HBM
    working set a real allocator would page) compact after churn."""

    def __init__(self, num_blocks, block_size):
        num_blocks = int(num_blocks)
        block_size = int(block_size)
        if block_size < 1 or (block_size & (block_size - 1)):
            raise ValueError(f"block_size must be a power of two, got "
                             f"{block_size}")
        if num_blocks < 2:
            raise ValueError("need >= 2 blocks (block 0 is scratch)")
        self.num_blocks = num_blocks
        self.block_size = block_size
        # pop() takes from the tail: descending order -> lowest id first
        self._free = list(range(num_blocks - 1, 0, -1))
        self.high_water = 0          # max blocks ever simultaneously held

    @property
    def capacity_blocks(self):
        """Allocatable blocks (scratch excluded)."""
        return self.num_blocks - 1

    @property
    def free_blocks(self):
        return len(self._free)

    @property
    def used_blocks(self):
        return self.capacity_blocks - len(self._free)

    def utilization(self):
        """Allocated fraction of the allocatable pool (the scheduler's
        `stats()["paged"]["utilization"]`)."""
        return self.used_blocks / max(self.capacity_blocks, 1)

    def alloc(self, n):
        """n physical block ids, or PoolExhausted with the pool untouched."""
        n = int(n)
        if n < 0:
            raise ValueError(f"alloc({n})")
        if n > len(self._free):
            raise PoolExhausted(
                f"need {n} blocks, {len(self._free)} free "
                f"(pool {self.capacity_blocks})")
        out = [self._free.pop() for _ in range(n)]
        self.high_water = max(self.high_water, self.used_blocks)
        return out

    def free(self, blocks):
        """Return blocks to the pool (double-free and scratch are errors)."""
        for b in blocks:
            b = int(b)
            if b <= 0 or b >= self.num_blocks:
                raise ValueError(f"block {b} is not allocatable")
            if b in self._free:
                raise ValueError(f"double free of block {b}")
            self._free.append(b)

    def defrag(self):
        """Re-sort the free list so the next allocations take the lowest
        block ids — after heavy churn the live set packs toward the front
        of the pool (the indirection makes physical compaction unnecessary;
        this keeps the id space, and a real allocator's page set, tight)."""
        self._free.sort(reverse=True)

    def reset(self):
        self._free = list(range(self.num_blocks - 1, 0, -1))
        self.high_water = 0


def make_table(slots, max_blocks):
    """All-scratch block table [slots, max_blocks] int32 (logical block j of
    slot s -> physical block table[s, j]; 0 = unallocated/scratch)."""
    return np.zeros((int(slots), int(max_blocks)), np.int32)
