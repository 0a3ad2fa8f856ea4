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

- `"pallas"` (TPU): a split-KV flash-decode kernel. The block table is a
  scalar-prefetch operand and the layer index a second, so each grid
  step's BlockSpec index map reads `(layer, bt[r, b])` and DMAs exactly
  that pool block HBM→VMEM — attention reads KV *through the table*, in
  the layout the pool is stored in (tests/test_pool_in_place.py holds
  the traced programs to that). One grid step takes the block's whole
  contiguous `(bsz, nKV*hd)` slab (every kv head) and scores all query
  heads against it with block-diagonal queries. Online-softmax partial
  (max, sum, acc) scratch carries across the `nb` block steps of each
  slot. The grid is `(R, nb)`, `nb` set by the deepest slot, but a slot
  works only on the block columns it HAS: its live range `[lo, hi)`
  (`live_block_range`: the first and one past the last column whose mask
  holds a valid row; `lo == hi == 0` for a slot that is not active) rides
  as two more scalar-prefetch vectors. A grid step outside the range runs
  no cast, matmul or softmax update, and its index maps name the block the
  nearest live step names (the null block 0 for an empty slot), so Pallas
  issues no copy for it either. A column with no valid row adds exactly
  nothing to the online softmax, so skipping it changes no bit of a live
  slot's output; an empty slot writes zeros.
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
# Pallas TPU kernel: split-KV grid, online-softmax partial reduction
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


def _paged_kernel(
    bt_ref,  # [R, nb] scalar-prefetch block table
    layer_ref,  # [1] scalar-prefetch layer index (read by the index maps)
    lo_ref,  # [R] scalar-prefetch: first live block column of each slot
    hi_ref,  # [R] scalar-prefetch: one past the last live column
    mask_ref,  # (1, 1, W, bsz) int32 validity rows for this block, per query
    q_ref,  # (1, W*nHp, D) block-diagonal queries, D = nKV*hd
    *refs,  # [sel], k, [k scales], v, [v scales], out, acc, m, l
    sm_scale: float,
    quant: bool,
):
    """One grid step = one pool block of one slot, ALL kv heads, and only
    where `lo[r] <= b < hi[r]`: outside its slot's live range a step does
    nothing (the index maps hold its operands still, so nothing is copied
    for it either). The block is the contiguous (bsz, nKV*hd) slab the pool
    stores, so every tile is lane-dense whatever the head count. Query row
    i carries head i's query in its kv head's hd lanes and zeros elsewhere,
    so `q @ k.T` is exactly the per-head score; `p @ v` is exact on the head's own lanes (the
    caller reads only those). Int8 pools: the per-(row, kv head) scale
    factors out of the hd contraction, so it multiplies the SCORES (K) and
    the PROBS (V) — the int8 tile feeds the MXU straight after the DMA."""
    if quant:
        # sel: (nKV, W*nHp, 1) f32 one-hot, query row -> its kv head
        sel_ref, k_ref, ks_ref, v_ref, vs_ref, o_ref, acc_ref, m_ref, l_ref = refs
    else:
        k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref = refs
    r, b = pl.program_id(0), pl.program_id(1)
    nb = pl.num_programs(1)
    rows = q_ref.shape[1]
    W, bsz = mask_ref.shape[2], mask_ref.shape[3]

    @pl.when(b == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    def head_rows(sc_ref):
        # (nKV, bsz) block scales -> [rows, bsz]: row i gets its kv head's
        sc = sc_ref[...]
        return sum(
            sel_ref[h] * sc[h : h + 1, :] for h in range(sc.shape[0])
        )

    @pl.when((lo_ref[r] <= b) & (b < hi_ref[r]))
    def _live_column():
        q = q_ref[0].astype(jnp.float32)  # [rows, D]
        k = k_ref[...].astype(jnp.float32)  # [bsz, D]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        s = s * sm_scale
        if quant:
            s = s * head_rows(ks_ref)
        # per-query causal horizon: mask row w applies to that query's heads
        m2 = jnp.broadcast_to(
            mask_ref[0, 0][:, None, :], (W, rows // W, bsz)
        ).reshape(rows, bsz)
        s = jnp.where(m2 != 0, s, _NEG_INF)

        m_prev = m_ref[:]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        # rows with no valid key yet: every p entry is exp(-inf - -inf) = 1
        p = jnp.where(m_new > _NEG_INF / 2, p, 0.0)
        l_ref[:] = l_ref[:] * alpha + jnp.sum(p, axis=1, keepdims=True)
        m_ref[:] = m_new
        if quant:
            p = p * head_rows(vs_ref)
        v = v_ref[...].astype(jnp.float32)
        acc_ref[:] = acc_ref[:] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    @pl.when(b == nb - 1)
    def _finalize():
        l = l_ref[:]
        safe_l = jnp.where(l > 0.0, l, 1.0)
        o_ref[0] = (acc_ref[:] / safe_l).astype(o_ref.dtype)


def _paged_pallas(q, k_pool, v_pool, block_table, valid, layer, sm_scale,
                  interpret, kernel_name="paged_attention", live=None):
    """q [R, W, nH, hd], valid [R, W, nb*bsz] -> [R, W, nH, hd]. Decoding is
    the W == 1 case of the speculative verify. `live` = (lo, hi) as
    `live_block_range` gives them; read from `valid` here when not given."""
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
    lo, hi = live_block_range(valid, bsz) if live is None else live

    # a dead step reads the column the nearest live step reads: an index
    # map that holds still issues no copy
    def column(r, b, lo, hi):
        return jnp.clip(b, lo[r], jnp.maximum(hi[r] - 1, lo[r]))

    # the index map IS the page walk: block b of slot r comes straight
    # from the pool page (layer, table entry) names, as the pool stores it
    # (scale strips walk the same map); an empty slot names the null block
    # whatever its table still holds
    def page(r, b, bt, layer, lo, hi):
        blk = jnp.where(hi[r] > lo[r], bt[r, column(r, b, lo, hi)], 0)
        return layer[0], blk, 0, 0

    kv_spec = pl.BlockSpec((None, None, bsz, D), page)
    sc_spec = pl.BlockSpec((None, None, nKV, bsz), page)
    in_specs = [
        pl.BlockSpec(
            (1, 1, W, bsz),
            lambda r, b, bt, layer, lo, hi: (r, column(r, b, lo, hi), 0, 0),
        ),
        pl.BlockSpec((1, rows, D), lambda r, b, *_: (r, 0, 0)),
    ]
    if quant:
        sel = jnp.asarray(np.tile(onehot, (W, 1)).T[:, :, None])
        in_specs += [
            pl.BlockSpec((nKV, rows, 1), lambda r, b, *_: (0, 0, 0)),
            kv_spec, sc_spec, kv_spec, sc_spec,
        ]
        operands = (sel, k_pool, k_scales, v_pool, v_scales)
    else:
        in_specs += [kv_spec, kv_spec]
        operands = (k_pool, v_pool)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(R, nb),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, rows, D), lambda r, b, *_: (r, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((rows, D), jnp.float32),
            pltpu.VMEM((rows, 1), jnp.float32),
            pltpu.VMEM((rows, 1), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_paged_kernel, sm_scale=sm_scale, quant=quant),
        name=kernel_name,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((R, rows, D), q.dtype),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
    )(block_table, jnp.asarray(layer, jnp.int32).reshape(1),
      lo.astype(jnp.int32), hi.astype(jnp.int32), mask, q_exp, *operands)
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
    live=None,  # (lo, hi) of `live_block_range`; read from `valid` if None
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
        kernel_name, live,
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
    live=None,  # (lo, hi) of `live_block_range`; read from `valid` if None
) -> jax.Array:
    """Decode attention of R single-token queries over paged KV.

    Logical row s of slot r lives at pool position
    `(layer, block_table[r, s // bsz], s % bsz)`; `valid` carries the causal
    (and sliding-window) mask over those logical rows. Returns
    `[R, nH, hd]` in q's dtype. `kernel_name` names the Pallas call in a
    device trace (a mixed stack reads its window layers' ring under a name
    of its own). `live` is the slots' range of block columns that hold a
    valid row, which the Pallas kernel neither fetches nor scores beyond
    (the XLA impl gathers every column and takes no notice of it); a slot
    outside `active` there reads as zeros.
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
        sm_scale, interpret, kernel_name, live,
    )[:, 0]
