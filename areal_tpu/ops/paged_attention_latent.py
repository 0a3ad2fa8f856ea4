"""Paged decode attention over a LATENT pool: every head scores one shared row.

A latent-attention model (DeepSeek-V2's MLA) caches one row a token and
layer, `[c_kv (kv_lora_rank) | k_pe (rotary lanes)]`, and no V side: the
pool is `[latent layers, n_blocks, bsz, D]`, addressed by the same block
tables as a paged KV pool (engine/kv_pool.py). With the attention absorbed
into the projections (models/qwen2.py:`_latent_decode_attention`) a head's
query is carried into the row's own space, `q = [q~ (kv_lora_rank) | q_pe]`,
so that

    score_h(s) = sm_scale * q_h . row(s)        all `nH` heads against ONE row
    u_h        = sum_s softmax_s(score_h) row(s)[:dv]      dv = kv_lora_rank

and the page that gave the scores gives the weighted sum too: it is read
once. `ops/paged_attention.py` scores block-diagonal queries against a
`(bsz, nKV*hd)` slab and reads a second pool for V; neither fits here.

Two implementations behind one signature, as there:

- `"pallas"` (TPU): PR 33's frame. The grid is the R slots; a grid step
  loops over its slot's live block columns `[lo, hi)` (`live_block_range`),
  `PAGES_PER_GROUP` of them an iteration, and copies each page HBM->VMEM
  itself into one of two group buffers while the group before is scored;
  the chain runs on across slots (`slot_schedule`, over groups).
  The matmuls take the pool's dtype as operands (bf16 on the chip) with
  float32 accumulation; max, sum and accumulator of the online softmax are
  float32. A slot with no live column (not active) writes zeros.
- `"xla"` (CPU / tests): gathers the slot's blocks and runs plain einsums,
  scores and softmax in float32.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from areal_tpu.ops.paged_attention import (
    _NEG_INF,
    _default_interpret,
    live_block_range,
    resolve_impl,
    slot_schedule,
)

KERNEL_NAME = "paged_attention_latent"


@jax.named_scope("pool_read")
def _gather_rows(pool, block_table, layer):
    """One layer's `block_table` blocks as [R, nb*bsz, D]."""
    R, nb = block_table.shape
    return pool[layer, block_table].reshape(R, nb * pool.shape[2], pool.shape[3])


def _latent_xla(q, pool, block_table, valid, layer, dv, sm_scale):
    rows = _gather_rows(pool, block_table, layer).astype(q.dtype)
    scores = (jnp.einsum("rnd,rsd->rns", q, rows) * sm_scale).astype(jnp.float32)
    scores = jnp.where(valid[:, None, :], scores, _NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("rns,rsd->rnd", probs, rows[..., :dv])


def _latent_kernel(
    bt_ref,  # [R, nb] scalar-prefetch block table
    layer_ref,  # [1] scalar-prefetch layer index
    lo_ref,  # [R] scalar-prefetch: first live block column of each slot
    hi_ref,  # [R] scalar-prefetch: one past the last live column
    start_ref,  # [R] scalar-prefetch: page groups of the slots before
    nxt_ref,  # [R] scalar-prefetch: the next slot with a live column
    mask_ref,  # (1, nb, 1, bsz) int32 validity rows of the slot
    q_ref,  # (1, nHp, D) the heads' queries in the row's space
    pool_hbm,  # [Ll, n_blocks, bsz, D] the whole pool, in HBM
    o_ref,  # (1, nHp, dv)
    acc_ref,  # (nHp, dv) float32
    m_ref,  # (nHp, 1) float32
    l_ref,  # (nHp, 1) float32
    page_buf,  # (2, pages * bsz, D)
    sems,  # DMA (2, pages)
    *,
    sm_scale: float,
    dv: int,
    pages: int,
):
    """One grid step = one slot: a loop over the slot's live block columns,
    `pages` of them an iteration (a GROUP: the softmax's reductions, the
    accumulator's rescale and the loop's own latencies are paid a group, not
    a page), each page `(bsz, D)` copied by the kernel itself while the group
    before it is scored (`ops/paged_attention.py:_paged_kernel`'s walk). A
    group is the K of the scores and, its first `dv` lanes, the V of the
    weighted sum. A slot's last group may be short: its missing pages are
    not copied, and their rows (whatever an earlier group left in the
    buffer: finite, the buffers start as zeros) are masked out."""
    r = pl.program_id(0)
    R = pl.num_programs(0)
    rows = q_ref.shape[1]
    nb, bsz = mask_ref.shape[1], mask_ref.shape[3]
    layer = layer_ref[0]
    lo, hi = lo_ref[r], hi_ref[r]
    n = (hi - lo + pages - 1) // pages
    start = start_ref[r]
    nxt = nxt_ref[r]

    def copies(slot, g, buf, go):
        """Start (`go`) or await the copies of group `g` of `slot`."""
        for i in range(pages):
            col = lo_ref[slot] + g * pages + i

            @pl.when(col < hi_ref[slot])
            def _page():
                cp = pltpu.make_async_copy(
                    pool_hbm.at[layer, bt_ref[slot, col]],
                    page_buf.at[buf, pl.ds(i * bsz, bsz)], sems.at[buf, i],
                )
                cp.start() if go else cp.wait()

    # the walk's first group: no slot before this one started it
    @pl.when((n > 0) & (start == 0))
    def _first_group():
        page_buf[...] = jnp.zeros_like(page_buf)
        copies(r, 0, 0, True)

    m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
    l_ref[:] = jnp.zeros_like(l_ref)
    acc_ref[:] = jnp.zeros_like(acc_ref)

    def live_group(g, carry):
        buf = (start + g) % 2
        more = g + 1 < n

        # the group after this one, the next live slot's first after the last
        @pl.when(more | (nxt < R))
        def _next_group():
            slot = jnp.where(more, r, jnp.minimum(nxt, R - 1))
            copies(slot, jnp.where(more, g + 1, 0), 1 - buf, True)

        copies(r, g, buf, False)
        page = page_buf[buf]  # [pages * bsz, D], the pool's dtype
        s = jax.lax.dot_general(
            q_ref[0].astype(page.dtype), page, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * sm_scale  # [rows, pages * bsz]
        seen = []
        for i in range(pages):
            col = lo + g * pages + i
            there = (mask_ref[0, jnp.minimum(col, nb - 1)] != 0) & (col < hi)
            seen.append(jnp.broadcast_to(there, (rows, bsz)))
        seen = seen[0] if pages == 1 else jnp.concatenate(seen, axis=1)
        s = jnp.where(seen, s, _NEG_INF)

        m_prev = m_ref[:]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        # rows with no valid key yet: every p entry is exp(-inf - -inf) = 1
        p = jnp.where(m_new > _NEG_INF / 2, p, 0.0)
        l_ref[:] = l_ref[:] * alpha + jnp.sum(p, axis=1, keepdims=True)
        m_ref[:] = m_new
        acc_ref[:] = acc_ref[:] * alpha + jax.lax.dot_general(
            p.astype(page.dtype), page[:, :dv], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        return carry

    jax.lax.fori_loop(0, n, live_group, None)

    l = l_ref[:]
    safe_l = jnp.where(l > 0.0, l, 1.0)
    o_ref[0] = (acc_ref[:] / safe_l).astype(o_ref.dtype)


# live block columns a loop iteration of the kernel scores together. At the
# cell's shape on the v5e (64 slots x 128 columns x 128 heads x 640 lanes,
# bench_artifacts/pr38/kernel_groups.py, PR 38) a live page costs 0.67-0.70 us
# at 1, 0.44-0.46 at 2, 0.34-0.36 at 4, 0.28-0.31 at 8: the page's two matmuls
# are 0.19 us of it, the rest is paid an iteration. (A slot's last group
# scores up to `PAGES_PER_GROUP - 1` masked pages; 2.6 MB of page buffers.)
PAGES_PER_GROUP = 8


def _latent_pallas(q, pool, block_table, valid, layer, dv, sm_scale, interpret, live,
                   pages=PAGES_PER_GROUP):
    R, nH, D = q.shape
    bsz = pool.shape[2]
    nb = block_table.shape[1]
    if not interpret and bsz % 128 != 0:
        raise ValueError(
            f"pallas latent attention needs page_size % 128 == 0 on TPU "
            f"(got {bsz}); use impl='xla' or a 128-multiple page size"
        )
    # heads padded to the sublane tile of the matmul operands
    nHp = -(-nH // 16) * 16
    qp = jnp.pad(q, ((0, 0), (0, nHp - nH), (0, 0)))
    mask = valid.astype(jnp.int32).reshape(R, nb, 1, bsz)
    if live is None:
        live = live_block_range(valid, bsz)
    lo, hi = live[:2]
    # the walk is over groups of `pages` columns: the chain counts those
    groups = (hi - lo + pages - 1) // pages
    start, nxt = slot_schedule(jnp.zeros_like(groups), groups)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=6,
        grid=(R,),
        in_specs=[
            pl.BlockSpec((1, nb, 1, bsz), lambda r, *_: (r, 0, 0, 0)),
            pl.BlockSpec((1, nHp, D), lambda r, *_: (r, 0, 0)),
            # the pool stays where it is: the kernel copies the pages it scores
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, nHp, dv), lambda r, *_: (r, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((nHp, dv), jnp.float32),
            pltpu.VMEM((nHp, 1), jnp.float32),
            pltpu.VMEM((nHp, 1), jnp.float32),
            pltpu.VMEM((2, pages * bsz, D), pool.dtype),
            pltpu.SemaphoreType.DMA((2, pages)),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_latent_kernel, sm_scale=sm_scale, dv=dv, pages=pages),
        name=KERNEL_NAME,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((R, nHp, dv), q.dtype),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            # the page copies chain from one slot into the next
            dimension_semantics=("arbitrary",),
        ),
    )(block_table, jnp.asarray(layer, jnp.int32).reshape(1),
      *(x.astype(jnp.int32) for x in (lo, hi, start, nxt)), mask, qp, pool)
    return out[:, :nH]


def paged_attention_latent(
    q: jax.Array,  # [R, nH, D]: each head's query in the cached row's space
    pool: jax.Array,  # [Ll, n_blocks, bsz, D] the WHOLE latent pool
    block_table: jax.Array,  # [R, nb] int32 pool-block ids per slot
    valid: jax.Array,  # [R, nb*bsz] bool: logical rows each slot attends
    layer,  # int or int32 scalar: the latent layer whose pages are read
    *,
    dv: int,  # leading lanes of a row that are summed (kv_lora_rank)
    sm_scale: float,
    impl: str = "auto",
    interpret: bool | None = None,
    live=None,  # (lo, hi[, start, nxt]): the work list; read from `valid` if None
) -> jax.Array:
    """Decode attention of R single-token queries, `nH` heads each, over the
    slots' cached latent rows. Logical row s of slot r lives at
    `(layer, block_table[r, s // bsz], s % bsz)`. Returns `[R, nH, dv]` in
    q's dtype: per head the softmax-weighted sum of the rows' first `dv`
    lanes. The Pallas kernel walks the `live` columns and no others; a slot
    outside `active` there reads as zeros (the XLA form gathers every
    column and takes no notice of `live`)."""
    if interpret is None:
        interpret = _default_interpret()
    if resolve_impl(impl) == "xla":
        return _latent_xla(q, pool, block_table, valid, layer, dv, sm_scale)
    return _latent_pallas(
        q, pool, block_table, valid, layer, dv, sm_scale, interpret, live
    )
