"""Paged KV-cache accounting: fixed-size blocks + per-slot block tables.

Parity target: the radix/paged KV cache the reference inherits from SGLang
(areal/engine/sglang_remote.py:22 — the server side reserves KV in pages,
not worst-case dense rows). The dense [slots, context_length] layout of
rounds 1-4 reserved 100% of worst-case KV upfront: at 32k context x 64
slots that is the whole HBM budget even when every live sequence is short.

TPU-first shape: one pool tensor [L, n_blocks, block_size, nKV*hd] per
K/V: a row holds its kv heads side by side, which is the page the paged
kernel DMAs, so no program ever relays the pool (the `[..., nKV, hd]`
minor pair this module used to name cost a relayout of a layer's whole
slice per layer per token on the v5e). Host-tier entries and the
migration wire keep the logical `[L, nb, block_size, nKV, hd]` shape.
Block tables are HOST-side numpy (the scheduler thread owns them; the
jitted kernels receive the relevant table slice as a traced operand each
dispatch, so table mutation never recompiles anything). Decode attends
DIRECTLY over the pool through the block table (ops/paged_attention.py)
and each step's KV write is a scatter of the single (layer, block,
offset) row into the pool the chunk carries whole — no copy of the pool
or of a layer's slice (tests/test_pool_in_place.py).

`version` is a monotonic mutation counter: every table write (ensure
growth, free, fork) bumps it, so the engine can skip re-uploading the
table slice for steady-state chunks where nothing moved.

Sharing: a prefix fork ALIASES the donor's full blocks (refcount bump — a
table write, no data movement) and device-copies only the one partial
block at the shared boundary. Aliased blocks are never written: decode
writes at position >= slot length >= the shared-prefix boundary, and the
boundary block is always the copied one.

Block 0 is a reserved null block: unallocated table entries point at it,
so uniform-width gathers of short slots read (masked) garbage instead of
stealing a live block's rows.

A mixed stack (window and full layers, models/qwen2.py) keeps TWO kinds of
cache side by side. The full layers have the pool above, over the full
layers alone. A window layer never reads past its window, so its rows live
in a fixed RING of pages a slot (`WindowRing`): a second pool
`[window layers, 1 + slots * pages, block_size, nKV*hd]` indexed by slot,
outside the allocator, read through a `[slots, pages]` table with the window
in the mask. A fork aliases the full layers' blocks as above and COPIES the
donor's ring pages; what a ring has been written past, it no longer holds,
so a donor serves a prefix only while `WindowRing.holds` says so.
"""

from __future__ import annotations

from collections import OrderedDict
from dataclasses import dataclass, field
from typing import Any

import numpy as np


class PoolDry(Exception):
    """No free blocks; the caller should reclaim (evict parked KV, drop
    donor registrations, preempt) and retry or fall back."""


class KVBlockAllocator:
    """Host-side block accounting for one decode engine.

    Not thread-safe by itself — the decode scheduler thread is the only
    mutator (pause_generation quiesces it before weight swaps touch KV).
    """

    def __init__(self, n_slots: int, n_blocks: int, block_size: int,
                 max_blocks_per_slot: int):
        assert n_blocks >= max_blocks_per_slot + 1, (
            "pool must fit one full-context request plus the null block: "
            f"n_blocks={n_blocks} max_blocks_per_slot={max_blocks_per_slot}"
        )
        self.block_size = int(block_size)
        self.n_blocks = int(n_blocks)
        self.max_blocks_per_slot = int(max_blocks_per_slot)
        # refcount[0] (null block) is pinned so it can never be allocated
        self.refcount = np.zeros(n_blocks, dtype=np.int32)
        self.refcount[0] = 1
        self._free: list[int] = list(range(n_blocks - 1, 0, -1))
        self.tables = np.zeros((n_slots, max_blocks_per_slot), dtype=np.int32)
        self.nblocks = np.zeros(n_slots, dtype=np.int32)
        # bumped on every table mutation; consumers cache uploads against it
        self.version = 0

    # -- queries --------------------------------------------------------
    @property
    def free_blocks(self) -> int:
        return len(self._free)

    @property
    def usable_blocks(self) -> int:
        return self.n_blocks - 1

    def blocks_for(self, tokens: int) -> int:
        return max(-(-int(tokens) // self.block_size), 0)

    def allocated_tokens(self) -> int:
        """Distinct blocks in use x block_size (aliased blocks count once)."""
        return int((self.refcount[1:] > 0).sum()) * self.block_size

    def fragmentation_blocks(self) -> int:
        """Free blocks that cannot back another max-context admission: the
        remainder after whole max_blocks_per_slot reservations. Paged
        allocation needs no contiguity, so this is the only structural
        waste a full-context request can observe."""
        return len(self._free) % self.max_blocks_per_slot

    def table_slice(self, nb: int) -> np.ndarray:
        """[n_slots, nb] table head for a bucketed gather (copy — the
        caller feeds it to a dispatch while the scheduler may mutate)."""
        return self.tables[:, :nb].copy()

    def row(self, slot: int, nb: int) -> np.ndarray:
        return self.tables[slot, :nb].copy()

    # -- mutation -------------------------------------------------------
    def _alloc(self, n: int) -> list[int] | None:
        if n > len(self._free):
            return None
        out = [self._free.pop() for _ in range(n)]
        for b in out:
            self.refcount[b] = 1
        return out

    def free_slot(self, slot: int) -> None:
        nb = int(self.nblocks[slot])
        if nb:
            self.version += 1
        for j in range(nb):
            b = int(self.tables[slot, j])
            if b == 0:
                continue
            self.refcount[b] -= 1
            if self.refcount[b] == 0:
                self._free.append(b)
        self.tables[slot, :] = 0
        self.nblocks[slot] = 0

    def ensure(self, slot: int, tokens: int) -> bool:
        """Grow the slot's table to cover `tokens` KV rows. False = pool
        dry (caller reclaims/preempts and retries)."""
        target = min(self.blocks_for(tokens), self.max_blocks_per_slot)
        cur = int(self.nblocks[slot])
        if target <= cur:
            return True
        got = self._alloc(target - cur)
        if got is None:
            return False
        self.tables[slot, cur:target] = got
        self.nblocks[slot] = target
        self.version += 1
        return True

    def fork(self, src: int, dst: int, covered: int) -> tuple[int, int] | None:
        """Point dst at src's first `covered` tokens of KV.

        Full blocks below the boundary are aliased (refcount++); the
        partial boundary block is freshly allocated and must be
        device-copied by the caller — returns (src_block, dst_block) for
        that copy, or None when the boundary is block-aligned. src == dst
        is a no-op (in-place reuse of a retired donor slot). Raises
        PoolDry (with the aliases rolled back) when the boundary block
        cannot be allocated.
        """
        if src == dst:
            return None
        self.free_slot(dst)
        full = covered // self.block_size
        partial = covered % self.block_size
        for j in range(full):
            b = int(self.tables[src, j])
            self.tables[dst, j] = b
            if b != 0:
                self.refcount[b] += 1
        self.nblocks[dst] = full
        self.version += 1
        if partial:
            got = self._alloc(1)
            if got is None:
                # roll back the aliases; caller reclaims or falls back
                self.free_slot(dst)
                raise PoolDry("no block for the fork boundary")
            self.tables[dst, full] = got[0]
            self.nblocks[dst] = full + 1
            return int(self.tables[src, full]), got[0]
        return None


class WindowRing:
    """Host-side account of the window layers' ring pool of one engine.

    Slot r owns pool blocks `1 + r*pages .. 1 + r*pages + pages - 1`
    (block 0 is the null block inactive slots write to); the row of
    position p sits in page `(p // block_size) % pages`. Nothing is
    allocated or freed: what has to be tracked is how far each slot's ring
    has been WRITTEN (`hi`, one past the highest position), because a
    write at p overwrites position p - pages * block_size. A slot's ring
    holds the window of a request that continues at `covered` (the rows
    `covered - window + 1 .. covered - 1`) only while nothing past
    `covered - window + pages * block_size` has been written."""

    def __init__(self, n_slots: int, window: int, block_size: int):
        from areal_tpu.models.qwen2 import ring_pages

        self.window = int(window)
        self.block_size = int(block_size)
        self.pages = ring_pages(window, block_size)
        self.n_blocks = 1 + int(n_slots) * self.pages
        self.hi = np.zeros(n_slots, dtype=np.int64)

    def blocks(self, slot: int) -> np.ndarray:
        """The slot's ring blocks, by page: int32 [pages]."""
        return (1 + slot * self.pages + np.arange(self.pages)).astype(np.int32)

    def note_written(self, slots, lengths) -> None:
        """Rows up to `lengths` (exclusive) have been (or are about to be:
        dispatched chunks) written into `slots`' rings."""
        self.hi[slots] = np.maximum(self.hi[slots], lengths)

    def reset(self, slot: int, length: int = 0) -> None:
        """The slot's ring was rewritten from scratch (a prefill, a fork)
        and now holds the rows below `length`."""
        self.hi[slot] = length

    def holds(self, slot: int, covered: int) -> bool:
        """Whether the slot's ring still has every row a request continuing
        at `covered` reads first: its window's rows below `covered`."""
        oldest = max(covered - self.window + 1, 0)
        return covered <= self.hi[slot] <= oldest + self.pages * self.block_size


class StateSlots:
    """Host-side account of the linear layers' state pool of one engine.

    A linear layer keeps no rows of keys and values but one recurrent state
    a slot (`[linear layers, 1 + slots, ...]`, row 0 the null slot inactive
    slots name): every token a slot's chunk computes is folded into it and
    cannot be taken out again. So where a paged row stays valid for good and
    a ring page for a window, a state is good for exactly one length: the
    number of tokens it has absorbed (`count`; dispatched chunks included,
    whether or not their tokens are kept). A slot's state can seed a request
    that continues at `covered` only when it has absorbed exactly that
    many: a donor that has decoded one token further, or a slot whose
    run-ahead chunk ran past where the request stopped, cannot."""

    def __init__(self, n_slots: int):
        self.count = np.zeros(n_slots, dtype=np.int64)

    @staticmethod
    def row(slot: int) -> int:
        """The slot's row in the state pool."""
        return 1 + int(slot)

    def note_written(self, slots, lengths) -> None:
        """Tokens up to `lengths` (exclusive) have been (or are about to be:
        dispatched chunks) folded into `slots`' states."""
        self.count[slots] = np.maximum(self.count[slots], lengths)

    def reset(self, slot: int, length: int = 0) -> None:
        """The slot's state was written from scratch (a prefill, a fork, a
        reset to zero) and now holds `length` tokens."""
        self.count[slot] = length

    def holds(self, slot: int, covered: int) -> bool:
        """Whether the slot's state is the state after exactly `covered`
        tokens."""
        return int(self.count[slot]) == int(covered)


@dataclass
class HostKVEntry:
    """One offloaded slot's KV: the slot's first `nb` pool blocks gathered
    into `[L, nb, block_size, nKV, hd]` K/V buffers, plus the resume
    metadata the admission path needs to promote it without a prefill.

    `k`/`v` may still be device arrays with their device→host copies in
    flight (copy_to_host_async started at offload); `HostKVStore`
    materialises them to host numpy behind a small pending window — the
    same double-buffering shape as `core/weight_transfer.iter_prefetched`.
    """

    rid: str
    k: Any
    v: Any
    nb: int
    covered: int  # tokens the blocks actually hold ([0, covered) valid)
    tokens: list[int]  # the covered token ids, for the exact-resume check
    rope_delta: int  # mrope offset restored at promotion (vision slots)
    base_key: np.ndarray  # the slot's sampling base key (uint32 [2]) —
    # restored at promotion so the resumed stream keeps sampling with
    # fold_in(original_key, position): bit-identical to never-evicted
    # Weight version the KV was computed under. Local entries can never go
    # stale (weight installs clear the store), but a MIGRATED entry can
    # race a weight commit on the receiving replica — `match` rejects a
    # version-mismatched entry as an honest miss rather than resuming a
    # stream the new policy never produced (extends PR 7's install-flush
    # tombstone rule across replicas). -1 = unknown (legacy callers).
    weight_version: int = -1
    # int8 pools (kv_dtype="int8"): the per-(row, head) f32 scale blocks
    # gathered alongside the data blocks ([L, nb, nKV, block_size] each).
    # None on the fp path. The quantized bytes + scales travel AS-IS
    # through offload, promotion, export and migration — no hop ever
    # requantizes, so a promoted/imported stream reads the exact bytes
    # the original scatter wrote.
    ks: Any = None
    vs: Any = None
    # which pool scheme produced k/v ("fp" | "int8"); migration rejects a
    # mismatch with the receiving engine as a tombstoned honest miss
    kv_dtype: str = "fp"
    # Fleet-KV-fabric content keys of the entry's COMPLETE blocks
    # (core/kv_fabric.chain_keys over `tokens` at the pool block size,
    # salted with weight_version/kv_dtype). Indexed by the store so
    # `match_blocks` can serve a matching prefix run to ANY request,
    # regardless of rid. Empty when the fabric is off (legacy entries).
    block_keys: tuple[int, ...] = ()
    ts: float = 0.0
    nbytes: int = 0
    pending: bool = field(default=False, repr=False)

    @property
    def meta_only(self) -> bool:
        """Identity-only entry (cheap drain): resume metadata without KV
        bytes — the blocks are re-fetchable from the fleet or recomputed
        by an honest prefill. Never serves block matches."""
        return self.k is None

    def materialize(self) -> None:
        """Finish the device→host copy (blocks only if still in flight)
        and drop the device references."""
        if self.pending and self.k is not None:
            self.k = np.asarray(self.k)
            self.v = np.asarray(self.v)
            if self.ks is not None:
                self.ks = np.asarray(self.ks)
                self.vs = np.asarray(self.vs)
        self.pending = False


class HostKVStore:
    """Host-RAM tier under the paged pool: a byte-budgeted block store
    keyed by rid, with its own LRU.

    Eviction paths that used to DROP parked/preempted slots' blocks (and
    pay a full re-prefill at resume) offload them here instead; promotion
    allocates fresh device blocks and uploads the stored bytes — turning
    `kv_pool_tokens` from a hard capacity wall into a working-set knob
    (the recompute-vs-communicate tradeoff LlamaRL/Podracer resolve by
    keeping actor state resident across interruptions; parity surface:
    SGLang HiCache / vLLM CPU KV offload).

    NOT thread-safe by itself: the decode engine serialises every access
    under its `_host_lock` (rank 25 — between `_weight_lock` and
    `_metrics_lock` in the engine's OrderedLock hierarchy).

    Counters (`swap_out_bytes_total`, `swap_in_bytes_total`, `hits`,
    `misses`, `evictions`, `rejected_puts`, `reprefill_tokens_avoided`)
    feed the engine's `get_metrics()`; a "miss" is an exact-resume lookup
    whose entry was dropped (LRU / weight-install clear, tracked through a
    bounded tombstone set) or went stale (prompt diverged) — fresh
    requests that were never offloaded do not count.
    """

    def __init__(
        self,
        budget_bytes: int,
        block_nbytes: int,
        block_size: int,
        pending_window: int = 2,
        tombstone_cap: int = 1024,
    ):
        assert budget_bytes > 0 and block_nbytes > 0 and block_size > 0
        self.budget_bytes = int(budget_bytes)
        self.block_nbytes = int(block_nbytes)  # K+V bytes per pool block
        self.block_size = int(block_size)
        self.bytes_used = 0
        self._entries: OrderedDict[str, HostKVEntry] = OrderedDict()
        # rids whose entries were dropped (LRU / clear): a later resume
        # lookup for one of these is an honest host-tier MISS. Bounded
        # FIFO so the set cannot grow with traffic.
        self._tombstones: OrderedDict[str, None] = OrderedDict()
        self._tombstone_cap = int(tombstone_cap)
        # offload entries whose device→host copies may still be in
        # flight, oldest first; materialised once more than
        # `pending_window` are outstanding (iter_prefetched's shape)
        self._pending: list[str] = []
        self._pending_window = max(int(pending_window), 0)
        # fleet-KV-fabric block index: content key -> (rid, ordinal) of a
        # resident entry holding that block. First writer wins (identical
        # keys mean identical bytes, so any one copy serves); meta-only
        # entries are never indexed (no bytes to serve).
        self._block_index: dict[int, tuple[str, int]] = {}
        # counters (engine snapshots under its _host_lock)
        self.swap_out_bytes_total = 0
        self.swap_in_bytes_total = 0
        self.hits = 0
        self.misses = 0
        self.evictions = 0
        self.rejected_puts = 0
        self.reprefill_tokens_avoided = 0
        # entries dropped at lookup because their weight_version no longer
        # matches the engine's (migration raced a weight commit); each is
        # also counted in `misses` — the split exists for observability
        self.version_rejects = 0

    def __len__(self) -> int:
        return len(self._entries)

    def rids(self) -> list[str]:
        """Resident entry rids, LRU-first (drain/migration enumerates
        these to stream every host-resident session to a survivor)."""
        return list(self._entries)

    def resident_tokens(self) -> int:
        return sum(e.covered for e in self._entries.values())

    def occupancy(self) -> float:
        return self.bytes_used / self.budget_bytes if self.budget_bytes else 0.0

    def tombstone(self, rid: str) -> None:
        """Mark `rid` known-but-unusable (e.g. a version-rejected import):
        its next exact-resume lookup counts an honest miss instead of
        silently falling through to a fresh-request re-prefill."""
        self._tombstone(rid)

    # -- internals ------------------------------------------------------
    def _tombstone(self, rid: str) -> None:
        self._tombstones[rid] = None
        self._tombstones.move_to_end(rid)
        while len(self._tombstones) > self._tombstone_cap:
            self._tombstones.popitem(last=False)

    def _unindex(self, e: HostKVEntry) -> None:
        for key in e.block_keys:
            owner = self._block_index.get(key)
            if owner is not None and owner[0] == e.rid:
                del self._block_index[key]

    def _index(self, e: HostKVEntry) -> None:
        if e.meta_only:
            return
        for i, key in enumerate(e.block_keys):
            self._block_index.setdefault(key, (e.rid, i))

    def _drop(self, rid: str, tombstone: bool) -> None:
        e = self._entries.pop(rid, None)
        if e is None:
            return
        self.bytes_used -= e.nbytes
        self._unindex(e)
        if rid in self._pending:
            self._pending.remove(rid)
        if tombstone:
            self._tombstone(rid)

    def _drain_pending(self, keep: int) -> None:
        while len(self._pending) > keep:
            rid = self._pending.pop(0)
            e = self._entries.get(rid)
            if e is not None:
                e.materialize()

    # -- offload (swap-out) --------------------------------------------
    def put(self, entry: HostKVEntry) -> bool:
        """Admit an offloaded slot's KV, LRU-evicting other entries to
        fit. False (counted in `rejected_puts`) when the entry alone
        exceeds the budget — the caller falls back to dropping the
        blocks, exactly the pre-tier behavior."""
        from areal_tpu.core import fault_injection

        # D2H offload seam: an abort models the host copy failing — the
        # engine catches it and degrades to drop-and-reprefill
        fault_injection.fire("kv.swap_out", rid=entry.rid)
        # meta-only entries (cheap drain) carry identity, not KV: charge a
        # nominal token-list footprint so they LRU out under pressure
        # without competing with real block bytes
        entry.nbytes = (
            entry.nb * self.block_nbytes
            if not entry.meta_only
            else 64 + 4 * len(entry.tokens)
        )
        if entry.nbytes > self.budget_bytes:
            # tombstoned: this rid's resume will look here and must count
            # as an honest miss (the KV is about to be dropped)
            self._tombstone(entry.rid)
            self.rejected_puts += 1
            return False
        self._drop(entry.rid, tombstone=False)  # replace, not duplicate
        while self.bytes_used + entry.nbytes > self.budget_bytes:
            lru_rid = next(iter(self._entries))
            self._drop(lru_rid, tombstone=True)
            self.evictions += 1
        self._entries[entry.rid] = entry
        self._entries.move_to_end(entry.rid)
        self.bytes_used += entry.nbytes
        self._index(entry)
        if entry.pending:
            self._pending.append(entry.rid)
            self._drain_pending(self._pending_window)
        self.swap_out_bytes_total += entry.nbytes
        return True

    # -- promotion (swap-in) -------------------------------------------
    def match(
        self,
        rid: str,
        covered: int,
        tokens: list[int],
        weight_version: int | None = None,
    ) -> bool:
        """Exact-resume peek: does an entry cover precisely `tokens`?
        Counts a MISS (and drops the stale entry) when the rid was
        offloaded but can no longer serve this resume; counts nothing for
        rids that were never offloaded. `weight_version` (the engine's
        current version) additionally rejects entries whose KV was
        computed under different weights — a migrated entry racing a
        weight commit must re-prefill under the new policy, not resume a
        stream it never produced."""
        e = self._entries.get(rid)
        if e is None:
            if rid in self._tombstones:
                del self._tombstones[rid]
                self.misses += 1
            return False
        if e.meta_only:
            # identity-only (cheap drain): no bytes to promote — the
            # engine claims the sampling key separately and rebuilds the
            # blocks via fabric fetch or an honest re-prefill
            return False
        if (
            weight_version is not None
            and e.weight_version >= 0
            and e.weight_version != weight_version
        ):
            self._drop(rid, tombstone=False)
            self.misses += 1
            self.version_rejects += 1
            return False
        if e.covered == covered and e.tokens == tokens:
            return True
        # prompt diverged (edited/truncated): the cache cannot serve it
        self._drop(rid, tombstone=False)
        self.misses += 1
        return False

    def take(self, rid: str) -> HostKVEntry | None:
        """Pop the entry for promotion (host bytes materialised). The
        caller reports the outcome: `note_hit` after a successful device
        upload, or `restore` if promotion failed (pool dry) so a later
        pass can retry."""
        from areal_tpu.core import fault_injection

        # swap-in seam: an abort models the host→device promotion dying
        # before any state moved — the engine treats it as a miss and
        # falls back to a full re-prefill
        fault_injection.fire("kv.swap_in", rid=rid)
        e = self._entries.pop(rid, None)
        if e is None:
            return None
        self.bytes_used -= e.nbytes
        self._unindex(e)
        if rid in self._pending:
            self._pending.remove(rid)
        e.materialize()
        return e

    def note_hit(self, entry: HostKVEntry) -> None:
        self.hits += 1
        self.swap_in_bytes_total += entry.nbytes
        self.reprefill_tokens_avoided += entry.covered

    def restore(self, entry: HostKVEntry) -> None:
        """Undo a `take` whose promotion could not get device blocks."""
        self.bytes_used += entry.nbytes
        self._entries[entry.rid] = entry
        self._index(entry)
        self._entries.move_to_end(entry.rid, last=False)  # retry soon: MRU-protect others

    # -- fleet KV fabric (content-addressed block lookups) --------------
    def peek(self, rid: str) -> HostKVEntry | None:
        """Entry by rid without counters or LRU movement (the engine
        inspects meta-only drained entries before deciding the ladder)."""
        return self._entries.get(rid)

    def match_blocks(
        self, chain: list[int], min_blocks: int = 1
    ) -> tuple[HostKVEntry, int] | None:
        """Longest content-keyed prefix run the store can serve: the
        largest n with chain[n-1] indexed -> (entry, n). Chained keys are
        position-binding, so a key match at position n-1 implies the
        entry's first n blocks hold exactly the request's first n*B
        tokens — no token comparison needed. No hit/miss counting here:
        fabric attribution is the engine's (a block match must not
        inflate the rid-resume hit rate)."""
        for n in range(len(chain), max(0, min_blocks - 1), -1):
            owner = self._block_index.get(chain[n - 1])
            if owner is None:
                continue
            rid, ordinal = owner
            e = self._entries.get(rid)
            # ordinal must agree with the chain position (anything else
            # is a 64-bit collision between different-length prefixes)
            if e is None or e.meta_only or ordinal != n - 1 or e.nb < n:
                continue
            e.materialize()
            return e, n
        return None

    def fabric_keys(self) -> list[int]:
        """Resident (serveable) content keys, for the /metrics digest."""
        return list(self._block_index)

    # -- lifecycle ------------------------------------------------------
    def flush_pending(self) -> None:
        self._drain_pending(0)

    def clear(self) -> int:
        """Drop everything (weight installs: KV from old weights must not
        seed generation under new ones — same rule as parked KV). Each
        dropped rid is tombstoned, so its resume counts as a miss."""
        n = len(self._entries)
        for rid in list(self._entries):
            self._drop(rid, tombstone=True)
        self._pending.clear()
        self._block_index.clear()
        return n
