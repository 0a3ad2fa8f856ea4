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

What a slot's cache IS follows from the model, and `SlotCache` is the one
place that says so: which pools exist, what a slot holds in each, and what
follows (what is cached at admission, what a prefill scatters through,
whether a slot can still seed a request, what a fork aliases and what it
copies, a row's bytes, which mechanisms cannot be served). The engine asks
it and tests no kind itself. Beside the paged pool above (all a uniform stack
has, a bare array a side) there are: for window layers a fixed RING of pages
a slot (`WindowRing`), a second pool outside the allocator that a fork
copies; for linear layers one recurrent STATE a slot (`StateSlots`), good for
exactly the length it has absorbed; for latent attention ONE row a token and
layer through the same table and allocator, with no V side (beside linear
layers, Kimi-Linear, a slot holds BOTH: the latent rows of its attention
layers and the state of its linear ones, and every answer below is the two
kinds' together); and under a block mask (`block_length` > 1) the paged pool
valid a whole block at a time.
"""

from __future__ import annotations

from collections import OrderedDict
from dataclasses import dataclass, field
from typing import Any

import jax
import jax.numpy as jnp
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


# -- the device copies a fork or a reset takes (jitted a `SlotCache`) ---------


def fork_block(kq, vq, src_b, dst_b):
    """Copy ONE block of the block-table-addressed pool: a fork boundary's
    partial block. Prefix forks are block-table aliasing on the host; the only
    device work left is this copy, against the O(prefix) memcpy of a dense
    cache and the prefill both replace."""

    # tree-mapped so int8 operands copy the scale block through the same
    # block ids as the data block (fp: bare arrays)
    def cp(pool):
        blk = jnp.take(pool, src_b[None], axis=1)
        return pool.at[:, dst_b[None]].set(blk)

    def one(pool):
        if isinstance(pool, dict):
            # a dict of pools: the boundary block is the paged pool's (a
            # latent model's one pool; its V side holds nothing); a ring is
            # copied whole (`fork_ring`)
            kind = "latent" if "latent" in pool else "full"
            return {**pool, kind: cp(pool[kind])} if pool else pool
        return jax.tree.map(cp, pool)

    return one(kq), one(vq)


def fork_ring(kq, vq, src_b, dst_b):
    """Copy one slot's ring pages onto another's: the window layers' rows
    cannot be aliased, each slot writes over its own."""

    def cp(pool):
        ring = pool["window"]
        return {**pool, "window": ring.at[:, dst_b].set(ring[:, src_b])}

    return cp(kq), cp(vq)


def fork_state(kq, vq, src_row, dst_row):
    """Copy one slot's recurrent state onto another's: a state cannot be
    aliased, each slot folds its own tokens into its own."""
    state = {
        name: a.at[:, dst_row].set(a[:, src_row])
        for name, a in kq["state"].items()
    }
    return {**kq, "state": state}, vq


def zero(kq, vq, row):
    """A slot's recurrent state back to zero: a request that starts with no
    prefill decodes from an empty state."""
    state = {name: a.at[:, row].set(0) for name, a in kq["state"].items()}
    return {**kq, "state": state}, vq


# -- what each cache cannot serve ----------------------------------------------

# What a mechanism may need of a slot's cache, in a refusal's words.
_NEEDS = {
    "scales": "rows by kv head with a scale pool beside them",
    "one_pool": "all a slot has cached in one bare pool behind the block table",
    "any_cut": "a prefix that is whole when cut at any token",
    "next_token": "a forward that predicts the next token",
    "queries": "a read that scores several queries a slot",
    "take_back": "a step that a rejected draft can take back",
    "draft_rows": "room for a verify chunk's rows past its oldest query's window",
    "kv_heads": "a kv-head axis to shard",
    "int8_projections": "projections with an int8 form",
    "image_rows": "a place for image rows",
}

# mechanism -> (its setting in words when (config, vision tower, int8
# weights) ask for it, what it needs of the cache)
MECHANISMS = {
    "kv_dtype": (lambda c, v, w: c.kv_dtype != "fp" and f"kv_dtype={c.kv_dtype!r}",
                 ("scales",)),
    "host tier": (lambda c, v, w: float(c.kv_host_pool_mb) > 0
                  and "kv_host_pool_mb > 0 (the host tier)", ("one_pool", "any_cut")),
    "migration": (lambda c, v, w: getattr(c, "role", "unified") != "unified"
                  and f"role={c.role!r} (export and import of parked KV: migration)",
                  ("one_pool", "any_cut")),
    "spec_decode": (lambda c, v, w: c.spec_decode != "off"
                    and f"spec_decode={c.spec_decode!r}",
                    ("next_token", "queries", "take_back", "draft_rows")),
    "tensor_parallel_size": (lambda c, v, w: int(c.tensor_parallel_size) > 1
                             and f"tensor_parallel_size={c.tensor_parallel_size}",
                             ("kv_heads",)),
    "weight_dtype": (lambda c, v, w: w and f"weight_dtype={c.weight_dtype!r}",
                     ("int8_projections",)),
    "vision": (lambda c, v, w: v and "a vision tower", ("image_rows",)),
}


def _ring_draft_room(cache: "SlotCache", config) -> str | None:
    from areal_tpu.models.qwen2 import ring_slack

    slack = ring_slack(cache.ring.window, cache.ring.block_size)
    if config is None or int(config.spec_k) <= slack:
        return None
    return (f"a verify chunk of {int(config.spec_k) + 1} rows (spec_k="
            f"{config.spec_k}) does not fit the ring's {slack} rows of slack")


# kind of cache -> what it is ("is") and, by need, why it lacks it (a function
# of (cache, config) where that depends on a number)
KINDS = {
    "pools": {
        "is": "a mixed stack, layers of more than one kind: a dict of pools",
        "scales": "a pool of a dict has no scale pool",
        "one_pool": "a mixed stack's parked KV is a paged pool and a ring, a "
                    "recurrent state or a latent pool; the host tier and the "
                    "migration wire carry one paged pool of K and V alone",
    },
    "window": {
        "is": "a ring of pages a slot for the window layers",
        "draft_rows": _ring_draft_room,
    },
    "state": {
        "is": "a recurrent state a slot for the linear layers",
        "take_back": "a rejected draft would have to roll each slot's "
                     "recurrent state back",
    },
    "latent": {
        "is": "latent attention: one cached row a token",
        "queries": "the absorbed attention scores one query a slot; a verify "
                   "chunk has several",
        "kv_heads": "the latent pool has no kv-head axis to shard",
        "int8_projections": "the low-rank projections have no int8 form",
        "image_rows": "no latent model with a vision tower is known",
    },
    "block": {
        "is": "generation by diffusion over blocks of positions",
        "scales": "the block step through an int8 pool has not been held to "
                  "the reference",
        "any_cut": "this cache is valid at block boundaries of the mask only; "
                   "the host tier, a prefill-only park and the migration wire "
                   "cover prompt[:-1], which ends inside a block",
        "next_token": "a verify chunk drafts the NEXT token of a causal model; "
                      "a block is denoised in place",
        "image_rows": "image rows have no block mask",
    },
}


class SlotCache:
    """What a slot's cache is, for one engine and one model: the pools it
    consists of, the host-side accounts of what each slot holds in them
    (`alloc` always; `ring` with window layers, `state` with linear layers),
    and every decision that follows from the kinds present. One class: a
    uniform stack is the case with the paged pool alone. Like the accounts,
    mutated by the decode scheduler thread alone."""

    def __init__(self, cfg, *, slots: int, block_size: int, n_blocks: int,
                 max_blocks_per_slot: int, kv_dtype, quant: bool = False,
                 cache_sharding=None, scale_sharding=None):
        self.cfg = cfg
        self.block_length = cfg.block_length_
        layers = cfg.cache_layers if cfg.mixed else {}
        self.alloc = KVBlockAllocator(slots, n_blocks, block_size, max_blocks_per_slot)
        self.ring = (WindowRing(slots, cfg.sliding_window, block_size)
                     if layers.get("window") else None)
        self.state = StateSlots(slots) if layers.get("state") else None
        self._accounts = [a for a in (self.ring, self.state) if a is not None]
        self.kinds = tuple(kind for kind, there in (
            ("pools", cfg.mixed), ("window", self.ring), ("state", self.state),
            ("latent", cfg.latent), ("block", self.block_length > 1)) if there)
        # PHYSICAL bytes: an int8 pool stores 1 byte an element and one f32
        # scale a (row, head); every byte counter downstream (host budget,
        # swap and migration totals) derives from these
        self._pool_dtype = jnp.dtype(jnp.int8 if quant else kv_dtype)
        elem = self._pool_dtype.itemsize
        if cfg.latent:
            self._row_lanes = cfg.latent_row_lanes
            self.row_nbytes = self._row_lanes * elem
        else:
            self._row_lanes = cfg.num_key_value_heads * cfg.head_dim_
            self.row_nbytes = 2 * cfg.num_key_value_heads * (
                cfg.head_dim_ * elem + (4 if quant else 0))
        # (layers, blocks) of each pool of a dict of pools, by kind; None: a
        # bare array a side. `_paged` names the one the block table addresses
        blocks = {"full": n_blocks, "latent": n_blocks,
                  "window": self.ring.n_blocks if self.ring else 0}
        self._pools = {kind: (len(ls), blocks[kind]) for kind, ls in layers.items()
                       if ls and kind in blocks} if cfg.mixed else None
        self._paged = "latent" if cfg.latent else "full" if cfg.mixed else None
        paged_layers = len(layers[self._paged]) if cfg.mixed else cfg.num_hidden_layers
        self.block_nbytes = paged_layers * self.alloc.block_size * self.row_nbytes
        # the recurrent state a slot and recurrent layer, row 0 the null slot:
        # what a slot keeps for a layer is the model's to say
        # (`ModelConfig.slot_state_shapes`), float32 whatever the cache's dtype
        self._state_shapes = {
            name: ((len(layers["state"]), 1 + slots, *shape),
                   jnp.dtype(jnp.float32 if name == "S" else kv_dtype))
            for name, shape in cfg.slot_state_shapes.items()
        } if self.state else {}
        # bytes one linear layer's state update moves for one slot: its state
        # and convolution rows, once in and once out
        self.state_update_nbytes = 2 * sum(
            int(np.prod(shape[2:])) * dtype.itemsize
            for shape, dtype in self._state_shapes.values())
        self._scales = (cfg.num_hidden_layers, n_blocks, cfg.num_key_value_heads,
                        self.alloc.block_size) if quant else None
        self._shardings = cache_sharding, scale_sharding
        self._copies: dict[str, Any] = {}  # jitted at first use
        # whether a request may fork the head of a donor's prefix and prefill
        # the rest (the suffix prefill reads the donor's rows at the cut
        # through one bare pool), and whether a prefix may be found by CONTENT
        # (the fleet KV fabric: it ends where its blocks of the pool end)
        self.shares_partial_prefix = self.content_addressed = not self.lacks(
            ("one_pool", "any_cut"))

    def _copy(self, fn):
        """The copy program `fn`, jitted, the pools donated."""
        if fn.__name__ not in self._copies:
            self._copies[fn.__name__] = jax.jit(fn, donate_argnums=(0, 1))
        return self._copies[fn.__name__]

    def new_pools(self):
        """(K side, V side, K scales, V scales), zeroed on the device. A side
        is a bare `[L, n_blocks, block_size, nKV*hd]` array for a uniform
        stack; for a stack of several kinds a dict, `{"full", "window"}` by
        the layers there are or `{"latent"}` with an EMPTY V side, the state
        `{"S", "conv"}` riding in the K-side dict (beside `full` or beside
        `latent`) so that every pool program carries it donated. Scales (an int8 pool's, else None) are f32
        `[L, n_blocks, nKV, block_size]`: the kv-head axis precedes
        block_size so a Pallas scale block is (1, 1, bs) with the page size
        on the lane dim."""
        cache_sharding, scale_sharding = self._shardings

        def pool(layers, blocks):
            shape = (layers, blocks, self.alloc.block_size, self._row_lanes)
            return jax.device_put(jnp.zeros(shape, self._pool_dtype), cache_sharding)

        def side():
            if self._pools is None:
                return pool(self.cfg.num_hidden_layers, self.alloc.n_blocks)
            return {kind: pool(*shape) for kind, shape in self._pools.items()}

        k, v = side(), {} if self._paged == "latent" else side()
        if self.state is not None:
            # placed from the start (whole on every chip of the mesh), as a
            # program's output is: a state first seen unplaced made the first
            # prefill program compile a second time when it next ran
            place = (lambda a: a) if cache_sharding is None else (
                lambda a: jax.device_put(a, jax.sharding.NamedSharding(
                    cache_sharding.mesh, jax.sharding.PartitionSpec())))
            k["state"] = {name: place(jnp.zeros(shape, dtype))
                          for name, (shape, dtype) in self._state_shapes.items()}
        k_scale, v_scale = (
            jax.device_put(jnp.zeros(self._scales, jnp.float32), scale_sharding)
            if self._scales else None for _ in range(2))
        return k, v, k_scale, v_scale

    # -- what a slot holds -------------------------------------------------
    def cover(self, n: int) -> int:
        """Of a sequence of `n` tokens, how many have their rows cached when
        it is admitted: all but the last, which the chunk's first step takes
        as its input; under a block mask the whole blocks, the rest being
        the revealed head of the first block it denoises."""
        B = self.block_length
        return (n // B) * B if B > 1 else n - 1

    def generated(self, rows: int, prompt_len: int) -> int:
        """Of a slot's `rows` cached rows, how many lie past its prompt's: a
        causal model's rows lag its tokens by one, a block's are its tokens."""
        return rows - prompt_len + (self.block_length == 1)

    def projection_exact(self, config) -> bool:
        """Whether the rows the engine projects for the chunks it has
        dispatched are the rows they will have written: a chunk of token
        steps writes its depth for every live slot. Not under a block mask
        (a diffusion chunk projects its depth and takes back the blocks it
        did not commit), and not for a scheduler that drafts (`config`'s
        `spec_decode`: a verify chunk projects its width and takes back what
        was rejected; its draftless passes dispatch plain chunks, which are
        exact, but a scheduler that may have either in flight is kept out
        whole)."""
        return self.block_length == 1 and config.spec_decode == "off"

    def tables(self, slot: int, nb: int):
        """What a prefill program scatters a slot's rows through: its
        block-table row, and with it the slot's ring blocks and state row
        where there are such."""
        row = self.alloc.row(slot, nb)
        tables = (row,)
        if self.ring is not None:
            tables += (self.ring.blocks(slot),)
        if self.state is not None:
            tables += (np.int32(self.state.row(slot)),)
        return tables if len(tables) > 1 else row

    def holds(self, slot: int, covered: int) -> bool:
        """Whether `slot`'s cache can seed a request that continues at
        `covered`: always for paged rows (they stay where they were
        written); a ring only while it has not been written past that
        window; a state only when it holds exactly `covered` tokens."""
        return all(a.holds(slot, covered) for a in self._accounts)

    def rewritten(self, slot: int, length: int) -> None:
        """A prefill (or nothing at all, `length` 0) has just written the
        slot's ring and state from scratch: they hold `length` tokens."""
        for account in self._accounts:
            account.reset(slot, length)

    def written(self, active: np.ndarray, lengths: np.ndarray) -> None:
        """A dispatched chunk writes the `active` slots' rings and states up
        to their (projected) `lengths`."""
        for account in self._accounts:
            account.note_written(active, lengths[active])

    def fork(self, src: int, dst: int, covered: int) -> list[tuple]:
        """Point `dst` at `src`'s first `covered` tokens (the caller has
        asked `holds`): the donor's full blocks aliased in the table, and
        the device copies that remain, as (program, *operands) for the
        caller to run in order on the pools: the boundary's partial block,
        the donor's ring pages, its state rows. `src == dst` copies nothing.
        Operands are NumPy: no eager device op ahead of a copy. Raises
        PoolDry when the boundary block cannot be allocated."""
        copies = []
        cp = self.alloc.fork(src, dst, covered)
        if cp is not None:
            copies.append((self._copy(fork_block), np.int32(cp[0]), np.int32(cp[1])))
        if src == dst:
            return copies
        if self.ring is not None:
            copies.append((self._copy(fork_ring),
                           self.ring.blocks(src), self.ring.blocks(dst)))
            # what the donor's ring was written up to is what the copy holds
            self.ring.reset(dst, int(self.ring.hi[src]))
        if self.state is not None:
            copies.append((self._copy(fork_state), np.int32(self.state.row(src)),
                           np.int32(self.state.row(dst))))
            self.state.reset(dst, int(self.state.count[src]))
        return copies

    def zero(self, slot: int) -> list[tuple]:
        """The slot starts a request with no prefill: its ring and state hold
        nothing, and the state's rows go back to zero (the copy to run, as
        `fork`'s)."""
        self.rewritten(slot, 0)
        if self.state is None:
            return []
        return [(self._copy(zero), np.int32(self.state.row(slot)))]

    # -- what a chunk walks and reads -------------------------------------------
    def walk(self, lengths: np.ndarray, nb: int, W: int = 1) -> tuple[np.ndarray, int]:
        """(live block columns of each slot, columns a loop iteration scores
        together) for the paged read of a chunk whose slots end at `lengths`
        over a table of `nb` columns, `W` queries a slot:
        `ops/paged_attention.live_block_range` on the host. A slot has the
        columns up to its last query's, less those wholly before a uniform
        stack's window (a ring is not in the table)."""
        bsz = self.alloc.block_size
        last = lengths.astype(np.int64) - 1
        live = np.minimum(last // bsz + 1, nb)
        window = self.cfg.sliding_window
        if window is not None and self.ring is None:
            live -= np.maximum(last - window + 1, 0) // bsz
        # (imported here as the model imports them: a process that never
        # dispatches a chunk never imports Pallas)
        if self._paged == "latent":
            from areal_tpu.ops.paged_attention_latent import PAGES_PER_GROUP

            return live, PAGES_PER_GROUP
        from areal_tpu.ops.paged_attention import group_pages

        return live, group_pages(bsz, self._row_lanes, self._pool_dtype.itemsize, W, nb)

    def rows_read(self, tail: list) -> dict[str, int]:
        """A chunk's counters of cached rows read, by kind, from the tail of
        what a mixed stack's program returns (models/qwen2.py): [full rows,
        window rows], then the linear layers' state updates where it has
        such, then the latent rows where it has those."""
        rest = list(tail[2:])
        state = rest.pop(0) if self.state is not None else 0
        return {"full": tail[0], "window": tail[1], "state": state,
                "latent": rest.pop(0) if self._paged == "latent" else 0}

    # -- what it cannot serve ----------------------------------------------------
    def lacks(self, needs, config=None) -> list[str]:
        """For each of `needs` that a kind present lacks: "needs ...: why"."""
        out = []
        for kind in self.kinds:
            for need in needs:
                why = KINDS[kind].get(need)
                if callable(why):
                    why = why(self, config)
                if why:
                    out.append(f"needs {_NEEDS[need]}: {why}")
        return out

    def _refuse(self, refused: list[str]) -> None:
        if refused:
            what = "; ".join(KINDS[kind]["is"] for kind in self.kinds)
            raise NotImplementedError(
                f"{self.cfg.model_type} ({what}) is not served with: "
                + "; ".join(refused)
            )

    def unserved(self, config, *, vision: bool = False,
                 weight_quant: bool = False) -> None:
        """Raise NotImplementedError, every reason in words, if `config` (or
        a vision tower, or int8 weights) asks for a mechanism that needs of
        the cache what a kind present lacks: at `initialize()`, and not at
        the first request that needs it."""
        refused = []
        if "block" in self.kinds and (
            len(self.kinds) > 1 or self.cfg.sliding_window is not None
        ):
            refused.append(
                "window, linear, latent or leading dense layers: the block step "
                "reads one paged pool under the block-causal mask alone"
            )
        for setting, needs in MECHANISMS.values():
            asked = setting(config, vision, weight_quant)
            if asked:
                refused += [f"{asked} {why}" for why in self.lacks(needs, config)]
        self._refuse(refused)

    def unserved_call(self, what: str, mechanism: str) -> None:
        """The same for a call `what` that is `mechanism` whatever the config
        says: a session's export or import on a unified engine."""
        needs = MECHANISMS[mechanism][1]
        self._refuse([f"{what} ({mechanism}) {why}" for why in self.lacks(needs)])


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
