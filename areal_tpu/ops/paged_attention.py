"""Paged-attention decode kernel: attend over the KV pool IN PLACE.

The decode engine's KV lives in a paged pool `[L, n_blocks, bsz, nKV*hd]`
(every layer in one array, each row the kv heads side by side) with
host-side `[R, nb]` block tables (engine/kv_pool.py). The op takes the
WHOLE pool and a layer index: a per-layer slice of a scan carry is a copy
of the slice, and a pool stored `[..., nKV, hd]` has to be relaid into
rows before every call — on the v5e those two cost three quarters of a
decode chunk (PERF.md, PR 24's trace), so neither exists any more.
Decode is HBM-bandwidth-bound on TPU: like SGLang's paged radix cache
(the reference's decode substrate), nothing copies the live KV a chunk.

Two implementations behind one signature, selected like `attn_impl`:

- `"pallas"` (TPU): a split-KV flash-decode kernel whose work is the live
  (slot, block column) pairs and nothing else. The grid is the R slots; a
  grid step loops over the block columns its slot HAS, the live range
  `[lo, hi)` (`live_block_range`: the first and one past the last column
  whose mask holds a valid row; `lo == hi == 0` for a slot that is not
  active), a GROUP of them an iteration (`group_pages`: as many pages as
  keep an iteration's copies near a MiB, from the call's static shapes;
  eight at a 256-lane row, one at 2,048 lanes and for a verify). The pools
  stay in HBM as they are stored and the kernel copies the pages it scores
  itself: the block table, the layer index, `lo`, `hi` and the walk's chain
  (`slot_schedule`: how many groups lie before each slot, and which slot is
  the next to have one; `work_list` gives all four) are scalar-prefetch
  operands, so an iteration reads `(layer, bt[r, c])` for each column of
  its group and DMAs exactly those pool blocks HBM->VMEM, each by its own
  copy and semaphore, into one of two group buffers while the group before
  is scored; a slot's last group starts the next live slot's first, so the
  copies stay ahead across slots too. Attention reads KV *through the
  table*, in the layout the pool is stored in (tests/test_pool_in_place.py
  holds the traced programs to that). One iteration takes each block's
  whole contiguous `(bsz, nKV*hd)` slab (every kv head) and scores all
  query heads against the group's rows with block-diagonal queries in one
  matmul; online-softmax partials (max, sum, acc) carry across a slot's
  groups in scratch, updated once a group. A column outside the range is
  never named: not fetched, no step taken for it (the `(R, nb)` grid this
  replaced still paid a fifth to a third of a live step for each); a short
  last group's missing pages are masked. A column with no valid row adds
  exactly nothing to the online softmax; at one page a group a live slot's
  output is equal to the bit to a walk over every column, at more it is the
  same sum regrouped (a bf16 rounding apart); a slot with no live column
  writes zeros. The loop's length is read on the device from the slot's own
  range, so no program is keyed by how ragged a batch is.
- `"xla"` (CPU / tests): gathers the slot's `nb` blocks per step and
  runs plain einsums over them (scores and softmax in float32). The
  committed stream goldens (tests/fixtures/) were recorded through this
  arithmetic, so its operation order is kept as it is.

The per-token KV *write* is not this op's job: `decode_step_paged`
(models/qwen2.py) scatters the single (layer, block, offset) row into the
pool it carries — O(1) per token.

Int8 pools (ops/kv_quant.py): `k_pool`/`v_pool` may arrive as
(int8 data, f32 scales) tuples. The Pallas kernel then DMAs the scale
block through the SAME block-table index map as the data block; the
per-(row, kv head) scale factors out of the head_dim contraction, so it
multiplies the scores (K) and the probabilities (V) in f32 — only the
bytes moved from HBM are halved. The XLA impl dequantizes immediately
after its gather, before the einsums, so both impls score the same
effective values.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from areal_tpu.ops.kv_quant import dequantize_kv, scales_rowmajor, split_pool

_NEG_INF = -1e30

IMPLS = ("auto", "pallas", "xla")


def resolve_impl(impl: str) -> str:
    if impl not in IMPLS:
        raise ValueError(f"paged_attn impl={impl!r} not in {IMPLS}")
    if impl == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "xla"
    return impl


def _default_interpret() -> bool:
    return jax.default_backend() != "tpu"


# ---------------------------------------------------------------------------
# XLA impl: gather the slot's blocks, plain einsums over them
# ---------------------------------------------------------------------------


@jax.named_scope("pool_read")
def _gather_dequant(pool, block_table, layer, hd, dtype):
    """Gather one layer's `block_table` blocks into [R, nb*bsz, nKV, hd]
    (the heads are split out of the row AFTER the gather, on the gathered
    rows); int8 pools are dequantized right after the gather (the seam the
    Pallas kernel puts right after its DMA), so both impls score the same
    effective values."""
    data, scales = split_pool(pool)
    R, nb = block_table.shape
    bsz = data.shape[2]
    c = data[layer, block_table].reshape(R, nb * bsz, -1, hd)
    if scales is None:
        return c
    sc = scales_rowmajor(scales[layer, block_table])  # [R, nb*bsz, nKV]
    return dequantize_kv(c, sc, dtype)


def _paged_attention_xla(q, k_pool, v_pool, block_table, valid, layer, sm_scale):
    R, nH, hd = q.shape
    kc = _gather_dequant(k_pool, block_table, layer, hd, q.dtype)
    vc = _gather_dequant(v_pool, block_table, layer, hd, q.dtype)
    nKV = kc.shape[2]
    group = nH // nKV
    qg = q.reshape(R, nKV, group, hd)
    scores = jnp.einsum("rkgd,rskd->rkgs", qg, kc.astype(q.dtype))
    if sm_scale == 1.0 / math.sqrt(hd):
        # a divide, not the mathematically-equal multiply: the committed
        # stream goldens were recorded through this operation
        scores = (scores / np.sqrt(hd)).astype(jnp.float32)
    else:
        scores = (scores * sm_scale).astype(jnp.float32)
    scores = jnp.where(valid[:, None, None, :], scores, _NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("rkgs,rskd->rkgd", probs, vc.astype(q.dtype))
    return out.reshape(R, nH, hd)


# ---------------------------------------------------------------------------
# Pallas TPU kernel: a walk over the live (slot, block column) pairs,
# online-softmax partial reduction
# ---------------------------------------------------------------------------


def live_block_range(valid, block_size: int, active=None):
    """(lo, hi), two [R] int32 vectors: the block columns of each slot that
    hold a row some query of the slot attends. `valid` is the mask the
    kernel is given, [R, nb*bsz] or [R, W, nb*bsz] (the union over a
    verify's W queries); `lo` is the first such column, `hi` one past the
    last, and a slot with none, or one that is not `active`, has
    `lo == hi == 0`. A causal mask gives `[0, position // bsz + 1)`, a
    sliding window a leading `lo > 0`. Compute it once a token step, outside
    the layer loop: every layer of a kind reads under the same mask."""
    R = valid.shape[0]
    cols = valid.reshape(R, -1, valid.shape[-1] // block_size, block_size)
    cols = cols.any(axis=(1, 3))  # [R, nb]
    if active is not None:
        cols = cols & active[:, None]
    nb = cols.shape[1]
    col = jnp.arange(nb, dtype=jnp.int32)
    hi = jnp.max(jnp.where(cols, col + 1, 0), axis=1)
    lo = jnp.minimum(jnp.min(jnp.where(cols, col, nb), axis=1), hi)
    return lo, hi


def slot_schedule(lo, hi, pages: int = 1):
    """(start, nxt), two [R] int32 vectors that chain the slots' live ranges
    into one walk of GROUPS of `pages` columns (`group_pages`; a slot's last
    group may be short): `start[r]` is the number of groups of the slots
    before `r` (so group `g` of slot `r` is the walk's `start[r] + g`-th, and
    its parity picks the group buffer), `nxt[r]` the next slot after `r` that
    has a live column, `R` if none. With `live_block_range`'s (lo, hi) this
    is the kernel's whole work list; taken with it, once a token step
    (`work_list`)."""
    R = lo.shape[0]
    count = (hi - lo).astype(jnp.int32)
    if pages > 1:
        count = (count + (pages - 1)) // pages
    start = jnp.cumsum(count) - count
    slot = jnp.where(count > 0, jnp.arange(R, dtype=jnp.int32), R)
    after = jax.lax.cummin(slot, reverse=True)
    nxt = jnp.concatenate([after[1:], jnp.full((1,), R, jnp.int32)])
    return start.astype(jnp.int32), nxt


# Pool bytes (K and V pages together) a loop iteration of the kernel should
# move before a larger group stops paying. An iteration costs about 0.35 us
# beyond its pages' copies and matmuls on the v5e (the loop's own latencies,
# the softmax's reductions, the accumulator's rescale); a page pair of 128 KiB
# (D 256, bf16) is copied in 0.18 us, one of 1 MiB (D 2,048) in 1.5 us, so the
# first gains 43% from a group of eight and the second nothing from any
# (PR 41's kernel-alone table, PERF.md section 6; bench_artifacts/pr42/
# kernel_groups.py measures it again).
GROUP_BYTES = 1 << 20
MAX_GROUP_PAGES = 8


def group_pages(bsz: int, D: int, itemsize: int, W: int, nb: int) -> int:
    """Live block columns a loop iteration of `_paged_kernel` scores together:
    arithmetic on the call's static shapes, nothing else. The largest power
    of two, at most `MAX_GROUP_PAGES`, whose pages in both pools stay within
    `GROUP_BYTES` (so the two group buffers a pool, 2 MiB at most, and the
    float32 copies of a scored group, `4 / itemsize` times the group's bytes,
    fit VMEM with room), clipped to the table's `nb` columns (a ring of two
    pages, a 256-token bucket: no empty page is scored). One for a verify or
    block step (`W > 1`): its `W` queries share an iteration's overheads
    already, and no cell runs one to measure more. At one page the kernel is
    the walk a column an iteration, instruction for instruction."""
    if W > 1:
        return 1
    pages = GROUP_BYTES // (2 * bsz * D * itemsize)
    pages = 1 << max(pages, 1).bit_length() - 1  # a power of two, at least 1
    return max(min(pages, MAX_GROUP_PAGES, nb), 1)


def pool_group_pages(k_pool, W: int, nb: int) -> int:
    """`group_pages` of a K pool as the kernel is handed it (an int8 pool's
    data array: the scale strips are a sixty-fourth of its bytes)."""
    data, _ = split_pool(k_pool)
    return group_pages(data.shape[2], data.shape[3], data.dtype.itemsize, W, nb)


def work_list(valid, k_pool, active=None):
    """(lo, hi, start, nxt): the kernel's whole work list under `valid`
    ([R, nb*bsz], or [R, W, nb*bsz] for a verify) over `k_pool`'s pages, the
    chain counting the groups the kernel will take there. Once a token step,
    outside the layer loop."""
    bsz = split_pool(k_pool)[0].shape[2]
    W = 1 if valid.ndim == 2 else valid.shape[1]
    live = live_block_range(valid, bsz, active)
    pages = pool_group_pages(k_pool, W, valid.shape[-1] // bsz)
    return (*live, *slot_schedule(*live, pages))


def _paged_kernel(
    bt_ref,  # [R, nb] scalar-prefetch block table
    layer_ref,  # [1] scalar-prefetch layer index
    lo_ref,  # [R] scalar-prefetch: first live block column of each slot
    hi_ref,  # [R] scalar-prefetch: one past the last live column
    start_ref,  # [R] scalar-prefetch: live columns of the slots before
    nxt_ref,  # [R] scalar-prefetch: the next slot with a live column
    mask_ref,  # (1, nb, W, bsz) int32 validity rows of the slot, per query
    q_ref,  # (1, W*nHp, D) block-diagonal queries, D = nKV*hd
    *refs,  # [sel], k, [k scales], v, [v scales] (HBM), out, scratch
    sm_scale: float,
    quant: bool,
    pages: int,
):
    """One grid step = one slot: a loop over the slot's live block columns
    `lo[r] .. hi[r] - 1`, a GROUP of `pages` of them an iteration
    (`group_pages`), each a whole pool block (ALL kv heads) copied HBM->VMEM
    by the kernel itself, by its own copy and semaphore (a loop over the
    pages the group has: the kernel's text is a page's whatever the group,
    so a set-up traces and lowers no more for it), into one of two group
    buffers while the group before it is scored: one mask, one score matmul
    `[rows, pages*bsz]`, one online-softmax update and one weighted sum a
    group, so the loop's own latencies, the reductions and the accumulator's
    rescale are paid a group and not a page. The walk runs on across slots:
    the last group of a slot starts the copies of the next live slot's first,
    so a group's latency hides behind a group's arithmetic everywhere but at
    the walk's first. A slot's last group may be short: its missing pages
    are not copied, and their rows (whatever an earlier group left in the
    buffer: finite, the walk's first group zeroes the buffers) are masked
    out. A slot with no live column copies and scores nothing and writes
    zeros. The block is the contiguous (bsz, nKV*hd) slab the pool stores, so
    every tile is lane-dense whatever the head count. Query row i carries
    head i's query in its kv head's hd lanes and zeros elsewhere, so
    `q @ k.T` is exactly the per-head score; `p @ v` is exact on the head's
    own lanes (the caller reads only those). Int8 pools: the per-(row, kv
    head) scale factors out of the hd contraction, so it multiplies the
    SCORES (K) and the PROBS (V) — the int8 tile feeds the MXU straight
    after the copy. At `pages == 1` this is the walk a column an iteration,
    operation for operation."""
    if quant:
        # sel: (nKV, W*nHp, 1) f32 one-hot, query row -> its kv head
        (sel_ref, k_hbm, ks_hbm, v_hbm, vs_hbm, o_ref, acc_ref, m_ref, l_ref,
         k_buf, v_buf, ks_buf, vs_buf, sems) = refs
        # (pool, its group buffers, whether a page's rows are the lanes there)
        pools = ((k_hbm, k_buf, False), (v_hbm, v_buf, False),
                 (ks_hbm, ks_buf, True), (vs_hbm, vs_buf, True))
    else:
        k_hbm, v_hbm, o_ref, acc_ref, m_ref, l_ref, k_buf, v_buf, sems = refs
        pools = ((k_hbm, k_buf, False), (v_hbm, v_buf, False))
    r = pl.program_id(0)
    R = pl.num_programs(0)
    rows = q_ref.shape[1]
    nb, W, bsz = mask_ref.shape[1:]
    layer = layer_ref[0]
    lo = lo_ref[r]
    n = hi_ref[r] - lo
    if pages > 1:
        n = (n + (pages - 1)) // pages  # the slot's groups
    start = start_ref[r]
    nxt = nxt_ref[r]

    def page_copies(slot, col, buf, i=None):
        # the page walk: block `col` of `slot` comes straight from the pool
        # page (layer, table entry) names, as the pool stores it (scale
        # strips walk the same entry), into page `i` of group buffer `buf`
        blk = bt_ref[slot, col]
        out = []
        for p, (hbm, vmem, lanes) in enumerate(pools):
            if pages == 1:
                dst, sem = vmem.at[buf], sems.at[p, buf]
            else:
                rows_i = pl.ds(pl.multiple_of(i * bsz, bsz), bsz)
                dst = vmem.at[buf, :, rows_i] if lanes else vmem.at[buf, rows_i]
                sem = sems.at[p, buf, i]
            out.append(pltpu.make_async_copy(hbm.at[layer, blk], dst, sem))
        return out

    def group_copies(slot, col, buf, go):
        """Start (`go`) or await the copies of the group of `slot` whose
        first column is `col`: the pages the slot has from there, `pages` at
        most, in a loop of their own, so that the kernel's text (what a
        set-up traces and lowers a chunk program) is a page's whatever the
        group."""

        def page(i, carry):
            for c in page_copies(slot, col + i, buf, i):
                c.start() if go else c.wait()
            return carry

        if pages == 1:
            for c in page_copies(slot, col, buf):
                c.start() if go else c.wait()
        else:
            jax.lax.fori_loop(
                0, jnp.minimum(hi_ref[slot] - col, pages), page, None
            )

    # the walk's first group: no slot before this one started it
    @pl.when((n > 0) & (start == 0))
    def _first_group():
        if pages > 1:
            for _, vmem, _ in pools:
                vmem[...] = jnp.zeros_like(vmem)
        group_copies(r, lo, 0, True)

    m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
    l_ref[:] = jnp.zeros_like(l_ref)
    acc_ref[:] = jnp.zeros_like(acc_ref)

    def head_rows(sc):
        # (nKV, pages*bsz) group scales -> [rows, pages*bsz]: row i gets its
        # kv head's
        return sum(
            sel_ref[h] * sc[h : h + 1, :] for h in range(sc.shape[0])
        )

    def live_group(g, carry):
        buf = (start + g) % 2
        more = g + 1 < n

        def first():  # the group's first column
            return lo + (g if pages == 1 else g * pages)

        # the group after this one, the next live slot's first after the last
        @pl.when(more | (nxt < R))
        def _next_group():
            slot = jnp.where(more, r, jnp.minimum(nxt, R - 1))
            group_copies(
                slot, jnp.where(more, first() + pages, lo_ref[slot]), 1 - buf,
                True,
            )

        group_copies(r, first(), buf, False)
        q = q_ref[0].astype(jnp.float32)  # [rows, D]
        k = k_buf[buf].astype(jnp.float32)  # [pages*bsz, D]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        s = s * sm_scale
        if quant:
            s = s * head_rows(ks_buf[buf])
        # per-query causal horizon: mask row w applies to that query's heads
        col = first()
        held = mask_ref[0, col] if pages == 1 else jnp.concatenate(
            [mask_ref[0, jnp.minimum(col + i, nb - 1)] for i in range(pages)],
            axis=1,
        )  # [W, pages*bsz]
        seen = jnp.broadcast_to(
            held[:, None, :], (W, rows // W, pages * bsz)
        ).reshape(rows, pages * bsz) != 0
        if pages > 1:
            # a short group's missing pages hold no row of this slot
            lane = jax.lax.broadcasted_iota(jnp.int32, seen.shape, 1)
            seen &= lane < (hi_ref[r] - col) * bsz
        s = jnp.where(seen, s, _NEG_INF)

        m_prev = m_ref[:]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        # rows with no valid key yet: every p entry is exp(-inf - -inf) = 1
        p = jnp.where(m_new > _NEG_INF / 2, p, 0.0)
        l_ref[:] = l_ref[:] * alpha + jnp.sum(p, axis=1, keepdims=True)
        m_ref[:] = m_new
        if quant:
            p = p * head_rows(vs_buf[buf])
        v = v_buf[buf].astype(jnp.float32)
        acc_ref[:] = acc_ref[:] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        return carry

    jax.lax.fori_loop(0, n, live_group, None)

    l = l_ref[:]
    safe_l = jnp.where(l > 0.0, l, 1.0)
    o_ref[0] = (acc_ref[:] / safe_l).astype(o_ref.dtype)


def _paged_pallas(q, k_pool, v_pool, block_table, valid, layer, sm_scale,
                  interpret, kernel_name="paged_attention", live=None,
                  pages=None):
    """q [R, W, nH, hd], valid [R, W, nb*bsz] -> [R, W, nH, hd]. Decoding is
    the W == 1 case of the speculative verify. `live` = (lo, hi) as
    `live_block_range` gives them, with or without `slot_schedule`'s
    (start, nxt) over this call's groups after them (`work_list` gives all
    four); read from `valid` here when not given. `pages` is
    `group_pages`'s of the call's shapes unless a test or a measurement
    names another."""
    (k_pool, k_scales), (v_pool, v_scales) = split_pool(k_pool), split_pool(v_pool)
    R, W, nH, hd = q.shape
    _, _, bsz, D = k_pool.shape
    nb = block_table.shape[1]
    nKV = D // hd
    group = nH // nKV
    if not interpret and bsz % 128 != 0:
        raise ValueError(
            f"pallas paged attention needs page_size % 128 == 0 on TPU "
            f"(got {bsz}); use impl='xla' or a 128-multiple page size"
        )
    quant = k_scales is not None
    # heads padded to the f32 sublane tile so every in-kernel reshape and
    # matmul operand is tile-aligned (Qwen2.5-0.5B has 14 heads)
    nHp = -(-nH // 8) * 8
    rows = W * nHp
    kv_of_head = np.minimum(np.arange(nHp) // group, nKV - 1)
    onehot = np.zeros((nHp, nKV), np.float32)
    onehot[np.arange(nH), kv_of_head[:nH]] = 1.0
    qp = jnp.pad(q, ((0, 0), (0, 0), (0, nHp - nH), (0, 0)))
    q_exp = (
        qp[:, :, :, None, :] * jnp.asarray(onehot, q.dtype)[:, :, None]
    ).reshape(R, rows, D)
    mask = valid.astype(jnp.int32).reshape(R, W, nb, bsz).swapaxes(1, 2)
    if pages is None:
        pages = group_pages(bsz, D, k_pool.dtype.itemsize, W, nb)
    if live is None:
        live = live_block_range(valid, bsz)
    if len(live) == 2:
        live = (*live, *slot_schedule(*live, pages))

    # the pools stay where they are: the kernel copies the pages it scores
    pool_spec = pl.BlockSpec(memory_space=pl.ANY)
    in_specs = [
        pl.BlockSpec((1, nb, W, bsz), lambda r, *_: (r, 0, 0, 0)),
        pl.BlockSpec((1, rows, D), lambda r, *_: (r, 0, 0)),
    ]
    scratch = [
        pltpu.VMEM((rows, D), jnp.float32),
        pltpu.VMEM((rows, 1), jnp.float32),
        pltpu.VMEM((rows, 1), jnp.float32),
        # two group buffers a pool: one is scored while the other fills
        pltpu.VMEM((2, pages * bsz, D), k_pool.dtype),
        pltpu.VMEM((2, pages * bsz, D), v_pool.dtype),
    ]
    if quant:
        sel = jnp.asarray(np.tile(onehot, (W, 1)).T[:, :, None])
        in_specs += [
            pl.BlockSpec((nKV, rows, 1), lambda r, *_: (0, 0, 0)),
            pool_spec, pool_spec, pool_spec, pool_spec,
        ]
        operands = (sel, k_pool, k_scales, v_pool, v_scales)
        scratch += [
            pltpu.VMEM((2, nKV, pages * bsz), k_scales.dtype),
            pltpu.VMEM((2, nKV, pages * bsz), v_scales.dtype),
        ]
    else:
        in_specs += [pool_spec, pool_spec]
        operands = (k_pool, v_pool)
    # a semaphore a pool, buffer and page of the group
    scratch.append(pltpu.SemaphoreType.DMA(
        (4 if quant else 2, 2) + ((pages,) if pages > 1 else ())
    ))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=6,
        grid=(R,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, rows, D), lambda r, *_: (r, 0, 0)),
        scratch_shapes=scratch,
    )
    out = pl.pallas_call(
        functools.partial(
            _paged_kernel, sm_scale=sm_scale, quant=quant, pages=pages
        ),
        name=kernel_name,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((R, rows, D), q.dtype),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            # the page copies chain from one slot into the next
            dimension_semantics=("arbitrary",),
        ),
    )(block_table, jnp.asarray(layer, jnp.int32).reshape(1),
      *(x.astype(jnp.int32) for x in live), mask, q_exp, *operands)
    # each head keeps the hd lanes of its own kv head
    out = out.reshape(R, W, nHp, nKV, hd)
    return out[:, :, np.arange(nH), kv_of_head[:nH]]


def paged_attention_qlen(
    q: jax.Array,  # [R, W, nH, hd]: W query positions per slot
    k_pool,  # [L, n_blocks, bsz, nKV*hd] the WHOLE pool, or (int8, scales)
    v_pool,  # [L, n_blocks, bsz, nKV*hd] or (int8 data, f32 scales)
    block_table: jax.Array,  # [R, nb] int32 pool-block ids per slot
    valid: jax.Array,  # [R, W, nb*bsz] bool per-query attendable rows
    layer,  # int or int32 scalar: the layer whose pages are read
    *,
    impl: str = "auto",
    sm_scale: float | None = None,
    interpret: bool | None = None,
    kernel_name: str = "paged_attention",
    live=None,  # (lo, hi[, start, nxt]): the work list; read from `valid` if None
    pages: int | None = None,  # a test's or a measurement's group; else the rule's
) -> jax.Array:
    """q_len>1 decode attention against the block table (speculative
    verify chunks): slot r's W queries (positions base..base+W-1) attend
    the slot's paged rows under per-query causal masks. Returns
    [R, W, nH, hd] in q's dtype.

    The XLA impl gathers the slot's blocks and runs
    `ops/chunked_attention.verify_attention`, the W=1 impl's op sequence
    with one more query axis. The Pallas
    impl extends the split-KV flash-decode kernel with the W query
    positions riding in the q block: one block DMA per grid step serves
    all W queries instead of W re-reads.
    """
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    if interpret is None:
        interpret = _default_interpret()
    impl = resolve_impl(impl)
    if impl == "xla":
        from areal_tpu.ops.chunked_attention import verify_attention

        hd = q.shape[-1]
        kc = _gather_dequant(k_pool, block_table, layer, hd, q.dtype)
        vc = _gather_dequant(v_pool, block_table, layer, hd, q.dtype)
        return verify_attention(q, kc, vc, valid, sm_scale=sm_scale)
    return _paged_pallas(
        q, k_pool, v_pool, block_table, valid, layer, sm_scale, interpret,
        kernel_name, live, pages,
    )


# ---------------------------------------------------------------------------
# public entry
# ---------------------------------------------------------------------------


def paged_attention(
    q: jax.Array,  # [R, nH, hd] query (one decode step per slot)
    k_pool,  # [L, n_blocks, bsz, nKV*hd] the WHOLE pool, or (int8, scales)
    v_pool,  # [L, n_blocks, bsz, nKV*hd] or (int8 data, f32 scales)
    block_table: jax.Array,  # [R, nb] int32 pool-block ids per slot
    valid: jax.Array,  # [R, nb*bsz] bool: logical rows each slot attends
    layer,  # int or int32 scalar: the layer whose pages are read
    *,
    impl: str = "auto",
    sm_scale: float | None = None,
    interpret: bool | None = None,
    kernel_name: str = "paged_attention",
    live=None,  # (lo, hi[, start, nxt]): the work list; read from `valid` if None
    pages: int | None = None,  # a test's or a measurement's group; else the rule's
) -> jax.Array:
    """Decode attention of R single-token queries over paged KV.

    Logical row s of slot r lives at pool position
    `(layer, block_table[r, s // bsz], s % bsz)`; `valid` carries the causal
    (and sliding-window) mask over those logical rows. Returns
    `[R, nH, hd]` in q's dtype. `kernel_name` names the Pallas call in a
    device trace (a mixed stack reads its window layers' ring under a name
    of its own). `live` is the slots' range of block columns that hold a
    valid row (`live_block_range`, and `slot_schedule`'s chain over it when
    the caller has taken that too): the Pallas kernel walks those columns
    and no others (the XLA impl gathers every column and takes no notice of
    it); a slot outside `active` there reads as zeros.
    """
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    if interpret is None:
        interpret = _default_interpret()
    impl = resolve_impl(impl)
    if impl == "xla":
        return _paged_attention_xla(
            q, k_pool, v_pool, block_table, valid, layer, sm_scale
        )
    return _paged_pallas(
        q[:, None], k_pool, v_pool, block_table, valid[:, None], layer,
        sm_scale, interpret, kernel_name, live, pages,
    )[:, 0]
