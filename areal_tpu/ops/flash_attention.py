"""Segment-aware flash attention for packed sequences — Pallas TPU kernel.

The trainer's hot op. The reference leans on flash-attn CUDA kernels through
HF/Megatron (SURVEY §2.3 "megatron fused deps": flash-attn, and SGLang's
kernels on the decode side); here the same role is played by a Pallas kernel
designed for our packed layout:

- inputs are a single packed 1-D token stream `[T, heads, head_dim]` with
  `segment_ids[T]` marking sequence membership (PADDING_SEGMENT = -1 for the
  pad tail) — the layout produced by pack_tensor_dict + FFD micro-batching.
  Attention is causal-within-segment, so one kernel serves any mix of
  sequence lengths with static shapes (no recompiles).
- online-softmax tiling (flash attention): O(T) memory instead of the
  O(T^2) score matrix, which is what makes 32k-token generations trainable.
- GQA is expressed in the BlockSpec index maps: query head h reads KV head
  h // (nH // nKV) — no KV replication in HBM.
- the matmuls' operands are what the caller gave: q, k, v and dO blocks go
  to the MXU in their own dtype, `p` and `ds` are rounded to it before the
  products that consume them (as XLA's dense path rounds its softmax), and
  every product accumulates in float32. Float32 whatever the inputs: the
  scores, mask, running max and sum, `exp`, `lse`, `delta`, `dlse`, the
  accumulators and the per-query-head `dk_h` / `dv_h`. On the chip bf16
  operands change no bit and no time: Mosaic's default precision multiplies
  float32 blocks in one bf16 pass, rounding them as `astype` does (PERF.md,
  PR 43); it is interpret mode on the CPU that then computes the chip's
  arithmetic. Float32 inputs keep float32 operands.
- no product contracts dimension 0 of an operand (Mosaic would transpose
  the block for it): `%flash_dkv` scores its block transposed, `[Bk, Bq]`.
- backward is two more Pallas kernels (dq; dk/dv per query head reduced over
  the GQA group outside) wired through jax.custom_vjp, with the standard
  delta = rowsum(dO * O) trick so the backward never materialises probs.

Causality is decided by explicit global token-position arrays (qpos/kpos),
not block indices — that is what lets the SAME kernel serve both the local
case (positions = arange) and the ring-attention case
(areal_tpu/ops/ring_attention.py), where the kv chunk comes from another
shard and carries an arbitrary position offset.

Block liveness. A (query block, key block) pair can hold a valid (query,
key) pair only if (1) the blocks' segment-id intervals, taken over their
non-pad tokens, overlap, and (2) some non-pad query is not before every
non-pad key: max(qpos) >= min(kpos). Both are necessary conditions of
`_mask_for`, whatever the packing, pad tail, ring offset or zig-zag layout
(ids need not be monotone). `block_liveness` is that rule as a [nq, nk]
table, on NumPy or JAX arrays. With positions = arange, test 2 is the causal
above-diagonal skip; a packed row of short sequences keeps only the blocks
near its diagonal; a ring step that brings a later shard, or one with no
sequence in common, has none.

The walk. The kernels' grids are (head, row, outer block): query blocks for
`%flash_fwd` and `%flash_dq`, key blocks for `%flash_dkv`. The inner axis is
a loop inside the kernel over the outer block's run of live partners
`[lo, hi)` (`live_runs` of the table: `walk_runs`, once a call in the
wrappers, XLA on four int32 a block; shapes follow Tq, Tk and the block size
alone). The inner operands stay in HBM, a block an index, and the kernel
copies them itself into two buffers, partner j + 1 while partner j is
scored; the chain runs on across outer blocks, rows and heads
(`_schedule`, four scalar-prefetch vectors), so a call exposes one copy. A
dead pair's contribution would be alpha = 1, p = 0, so leaving it out is
exact to the bit, and so is walking one: a hole inside a run (ids that are
not contiguous, a zig-zag shard's two chunks) is scored and adds nothing. A
pair outside every run costs nothing: no grid step, no copy (the
(nH, Tq/512, Tk/512) grid this replaced paid 0.055 us a dead step in
`%flash_fwd` at `[14, 8192, 64]`, 0.06 in `%flash_dq`, 0.5 in `%flash_dkv`,
whose dead steps still fetched seven query-side blocks; PERF.md, PR 37). An
outer block with no partner writes its zeros (`lse` -1e30) and nothing else.
The leading row axis is 1 from the trainer and the ring; `jax.vmap` (the
decode engine's batched prefill, the pipelined trainer's stages) folds its
rows into it by the kernels' own batching rule (`_rows_under_vmap`), each
row with its own work list, because Pallas's rule would turn a batched
scalar-prefetch operand into a loop over rows.

The kernel also returns the per-row log-sum-exp and differentiates through
it (ds = p * (dp - delta + dlse)) so sharded callers can merge partial
results from multiple kv chunks and still take exact gradients.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.custom_batching import custom_vmap
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

PADDING_SEGMENT = -1
_NEG_INF = -1e30

def _default_interpret() -> bool:
    return jax.default_backend() != "tpu"


def _mask_for(seg_q, seg_k, qpos, kpos, key_major: bool = False):
    """[Bq, Bk] validity: same segment, causal by global position, not pad;
    `key_major`: the same table as [Bk, Bq]."""
    q, k = (slice(None), None), (None, slice(None))  # x[:, None], x[None, :]
    if key_major:
        q, k = k, q
    return (seg_q[q] == seg_k[k]) & (qpos[q] >= kpos[k]) & (seg_q[q] != PADDING_SEGMENT)


# ---------------------------------------------------------------------------
# Block liveness
# ---------------------------------------------------------------------------

_INT_MAX = 2**31 - 1
_INT_MIN = -(2**31)
# rows of a `block_bounds` table
_SEG_LO, _SEG_HI, _POS_LO, _POS_HI = range(4)


def block_bounds(seg, pos, block: int):
    """[4, T // block] int32: each block's (min id, max id, min position,
    max position) over its non-pad tokens. An all-pad block gets the empty
    intervals (INT_MAX, INT_MIN), which overlap nothing. NumPy in, NumPy out;
    JAX in, JAX out."""
    xp = jnp if isinstance(seg, jax.Array) else np
    seg = seg.reshape(-1, block)
    pos = pos.reshape(-1, block)
    real = seg != PADDING_SEGMENT
    lo = lambda x: xp.where(real, x, _INT_MAX).min(axis=1)  # noqa: E731
    hi = lambda x: xp.where(real, x, _INT_MIN).max(axis=1)  # noqa: E731
    return xp.stack([lo(seg), hi(seg), lo(pos), hi(pos)]).astype(xp.int32)


def block_liveness(seg_q, seg_k, qpos, kpos, block_q: int, block_k: int):
    """[Tq // block_q, Tk // block_k] bool: False only where `_mask_for` over
    the block pair is all False. True where the id intervals overlap and
    some query is not before every key: necessary conditions, so a live
    entry may still hold no valid pair when ids are not contiguous."""
    qb = block_bounds(seg_q, qpos, block_q)[:, :, None]
    kb = block_bounds(seg_k, kpos, block_k)[:, None, :]
    return (
        (qb[_SEG_LO] <= kb[_SEG_HI])
        & (kb[_SEG_LO] <= qb[_SEG_HI])
        & (qb[_POS_HI] >= kb[_POS_LO])
    )


def live_runs(live):
    """(lo, hi), int32 of `live`'s shape less its last axis: the run of inner
    blocks `[lo, hi)` an outer block walks, from its first live partner to
    one past its last; `lo == hi == 0` where it has none. A dead block
    inside the run (non-contiguous ids, a zig-zag shard's two chunks) is
    walked and adds exactly nothing. NumPy in, NumPy out; JAX in, JAX out."""
    xp = jnp if isinstance(live, jax.Array) else np
    n = live.shape[-1]
    col = xp.arange(n, dtype=xp.int32)
    hi = xp.where(live, col + 1, 0).max(axis=-1)
    lo = xp.minimum(xp.where(live, col, n).min(axis=-1), hi)
    return lo.astype(xp.int32), hi.astype(xp.int32)


def walk_runs(seg_q, seg_k, qpos, kpos, block_q: int, block_k: int):
    """The three kernels' work list for one row: (lo_q, hi_q) [nq], each
    query block's run of key blocks (`%flash_fwd`, `%flash_dq`), and
    (lo_k, hi_k) [nk], each key block's run of query blocks (`%flash_dkv`).
    Shapes follow Tq, Tk and the block sizes alone."""
    live = block_liveness(seg_q, seg_k, qpos, kpos, block_q, block_k)
    return (*live_runs(live), *live_runs(live.swapaxes(-1, -2)))


def live_block_counts(seg, pos, shard_len: int, block: int = 512):
    """(live, (walk_q, walk_k), all) block pairs of one packed row, counted
    on the host (NumPy) as the wrappers lay the row out: ring shards of
    `shard_len` tokens (the row's own length when it is not sharded), each
    padded to whole blocks of `_fit_block(block, shard_len)`. `walk_q` is
    the inner steps `%flash_fwd` (and `%flash_dq`) take over every (query
    shard, visiting shard) call, `walk_k` those of `%flash_dkv`: the sums of
    `live_runs`' lengths, `live` where no run has a hole."""
    blk = _fit_block(block, shard_len)
    pad = -shard_len % blk
    rows = lambda x, fill: np.pad(  # noqa: E731
        np.asarray(x).reshape(-1, shard_len), ((0, 0), (0, pad)),
        constant_values=fill,
    ).reshape(-1)
    seg, pos = rows(seg, PADDING_SEGMENT), rows(pos, 0)
    live = block_liveness(seg, seg, pos, pos, blk, blk)
    n = len(seg) // (shard_len + pad)
    # [query shard, query block, kv shard, key block]: a run lies in one call
    calls = live.reshape(n, -1, n, live.shape[1] // n)
    walk_q, walk_k = (
        int((hi - lo).sum())
        for lo, hi in (live_runs(calls), live_runs(calls.transpose(2, 3, 0, 1)))
    )
    return int(live.sum()), (walk_q, walk_k), live.size


def _schedule(lo, hi):
    """The walk's chain over a call's whole grid (head, row, outer block),
    four int32 vectors for scalar prefetch from the runs `[B, n]` of its
    rows, flat `[B * n]` in the order a head visits them: `lo`, `hi`, `upto`
    (partners of the outer blocks up to and with this one: its last entry a
    head's total) and `after` (the first outer block from this one on that
    has a partner, `B * n` if none). A partner's place in the walk,
    `head * total + upto - (hi - lo) + j`, picks its buffer by parity."""
    lo, hi = lo.reshape(-1), hi.reshape(-1)
    count = hi - lo
    own = jnp.where(count > 0, jnp.arange(count.size, dtype=jnp.int32), count.size)
    after = jax.lax.cummin(own, reverse=True)
    return tuple(x.astype(jnp.int32) for x in (lo, hi, jnp.cumsum(count), after))


def _walk(sched, copies, score, before, after, empty):
    """One grid step (head h, row b, outer block i) of a kernel: `before()`,
    `score(buf)` for each partner of the block's run in ascending order,
    `after()`; the partner's operands copied HBM->VMEM by
    `copies(b, h, col, buf)` into one of two buffers while the partner
    before it is scored. The walk runs on across outer blocks, rows and
    heads: a block's last partner starts the first copy of the next block
    that has one, so only the call's first copy is exposed. A block with no
    partner copies and scores nothing: `empty()` writes what `before` and
    `after` alone would have."""
    lo_ref, hi_ref, upto_ref, after_ref = sched
    h, b, i = (pl.program_id(a) for a in range(3))
    nH, B, n_outer = (pl.num_programs(a) for a in range(3))
    at, last = b * n_outer + i, B * n_outer - 1
    lo = lo_ref[at]
    n = hi_ref[at] - lo

    @pl.when(n == 0)
    def _no_partner():
        empty()

    @pl.when(n > 0)
    def _partners():
        place = h * upto_ref[last] + upto_ref[at] - n
        # where the walk goes after this block's last partner: on in this
        # head, or to the next head's first block that has a partner
        nxt = after_ref[jnp.minimum(at + 1, last)]
        in_head = (at < last) & (nxt <= last)
        onward = in_head | (h + 1 < nH)
        to = jnp.where(in_head, nxt, after_ref[0])
        th = jnp.where(in_head, h, h + 1)

        # the walk's first copy: no block before this one started it
        @pl.when(place == 0)
        def _first_copy():
            for c in copies(b, h, lo, 0):
                c.start()

        before()

        def partner(j, carry):
            buf = (place + j) % 2
            more = j + 1 < n

            @pl.when(more | onward)
            def _next_copy():
                pick = lambda here, there: jnp.where(more, here, there)  # noqa: E731
                ahead = copies(
                    pick(b, to // n_outer), pick(h, th), pick(lo + j + 1, lo_ref[to]), 1 - buf
                )
                for c in ahead:
                    c.start()

            for c in copies(b, h, lo + j, buf):
                c.wait()
            score(buf)
            return carry

        jax.lax.fori_loop(0, n, partner, None)
        after()


def _copies(srcs, bufs, sems, buf):
    return [
        pltpu.make_async_copy(src, dst.at[buf], sems.at[n, buf])
        for n, (src, dst) in enumerate(zip(srcs, bufs))
    ]


def _key_side_copies(idk_hbm, k_hbm, v_hbm, bufs, sems, group: int):
    """`_walk`'s `copies` for a query block's partners: key block `col`'s
    ids and positions, and the K and V blocks of query head h's kv head."""

    def copies(b, h, col, buf):
        srcs = (idk_hbm.at[b, col], k_hbm.at[b, h // group, col], v_hbm.at[b, h // group, col])
        return _copies(srcs, bufs, sems, buf)

    return copies


def _scores(a, b, mask, sm_scale):
    """Float32 masked scores `a b^T` of two blocks in their own dtype."""
    s = jax.lax.dot_general(
        a, b, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )
    return jnp.where(mask, s * sm_scale, _NEG_INF)


_N_SCHED = 4  # `_schedule`'s vectors, the kernels' scalar-prefetch operands


# ---------------------------------------------------------------------------
# Forward kernel
# ---------------------------------------------------------------------------


def _fwd_kernel(*refs, sm_scale: float, group: int):
    """Grid (head, row, query block). Key-side operands stay in HBM, laid
    out a block an index: ids [B, nk, 2, Bk] (segment ids, positions), k and
    v [B, nKV, nk, Bk, hd]."""
    sched = refs[:_N_SCHED]
    (seg_q_ref, qpos_ref, q_ref, idk_hbm, k_hbm, v_hbm, o_ref, lse_ref,
     acc_ref, m_ref, l_ref, idk_buf, k_buf, v_buf, sems) = refs[_N_SCHED:]

    copies = _key_side_copies(idk_hbm, k_hbm, v_hbm, (idk_buf, k_buf, v_buf), sems, group)
    hd = q_ref.shape[-1]

    def _init():
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    def _compute(buf):
        # operands in the caller's dtype, every product into float32
        q = q_ref[0, 0]  # [Bq, hd]
        k = k_buf[buf, :, :hd]  # [Bk, hd]
        mask = _mask_for(seg_q_ref[0, 0], idk_buf[buf, 0], qpos_ref[0, 0], idk_buf[buf, 1])
        s = _scores(q, k, mask, sm_scale)

        m_prev = m_ref[:]  # [Bq, 1]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        # Fully-masked rows: every entry of p is exp(_NEG_INF - _NEG_INF) = 1;
        # zero them so l stays 0 for pad rows.
        p = jnp.where(m_new > _NEG_INF / 2, p, 0.0)
        l_ref[:] = l_ref[:] * alpha + jnp.sum(p, axis=1, keepdims=True)
        m_ref[:] = m_new
        v = v_buf[buf, :, :hd]
        # l sums the float32 p; the product takes p rounded to v's dtype
        acc_ref[:] = acc_ref[:] * alpha + jax.lax.dot_general(
            p.astype(v.dtype),
            v,
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    def _finalize():
        l = l_ref[:]
        safe_l = jnp.where(l > 0.0, l, 1.0)
        o_ref[0, 0] = (acc_ref[:] / safe_l).astype(o_ref.dtype)
        lse = jnp.where(l > 0.0, m_ref[:] + jnp.log(safe_l), _NEG_INF)
        lse_ref[0, 0, 0] = lse[:, 0]

    def _no_key():
        o_ref[...] = jnp.zeros_like(o_ref)
        lse_ref[...] = jnp.full_like(lse_ref, _NEG_INF)

    _walk(sched, copies, _compute, _init, _finalize, _no_key)


def _lanes(hd: int) -> int:
    return -(-hd // 128) * 128


def _blocked(x, block: int):
    """[B, n, T, hd] -> [B, n, T // block, block, lanes]: an inner block is
    one leading index of the HBM operand, its rows whole 128-lane tiles (a
    head of 64 is zero-padded: Mosaic slices a copy's source by whole tiles;
    the kernels read the first hd lanes of their buffer)."""
    x = _pad_to(x, _lanes(x.shape[-1]), x.ndim - 1)
    return x.reshape(*x.shape[:-2], -1, block, x.shape[-1])


def _ids_blocked(seg, pos, block: int):
    """[B, T] ids and positions -> [B, T // block, 2, block]."""
    B, T = seg.shape
    return jnp.stack([seg, pos], axis=1).reshape(B, 2, T // block, block).swapaxes(1, 2)


_ANY = pl.BlockSpec(memory_space=pl.ANY)
# the copies chain from one grid step into the next
_SEQUENTIAL = pltpu.CompilerParams(
    dimension_semantics=("arbitrary", "arbitrary", "arbitrary")
)


def _fwd_call(
    q4, k4, v4, seg_q, seg_k, qpos, kpos, lo_q, hi_q,
    *, sm_scale, block_q, block_k, interpret,
):
    """q4: [B, nH, Tq, hd]; k4/v4: [B, nKV, Tk, hd]; ids and positions
    [B, T]; runs [B, nq]. Returns (o [B,nH,Tq,hd], lse [B,nH,Tq])."""
    B, nH, Tq, hd = q4.shape
    nKV, Tk = k4.shape[1:3]
    row = lambda h, b, i, *_: (b, 0, i)  # noqa: E731
    tile = lambda h, b, i, *_: (b, h, i, 0)  # noqa: E731

    o, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, sm_scale=sm_scale, group=nH // nKV),
        name="flash_fwd",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=_N_SCHED,
            grid=(nH, B, Tq // block_q),
            in_specs=[
                pl.BlockSpec((1, 1, block_q), row),
                pl.BlockSpec((1, 1, block_q), row),
                pl.BlockSpec((1, 1, block_q, hd), tile),
                _ANY, _ANY, _ANY,
            ],
            out_specs=[
                pl.BlockSpec((1, 1, block_q, hd), tile),
                # LSE rides as [B, nH, 1, Tq]: the trailing block dims
                # (1, block_q) match the trailing array dims (1, Tq) under
                # Mosaic's rule for ANY head count (a (1, block_q) block over
                # [nH, Tq] is illegal whenever nH is not a multiple of 8 —
                # e.g. Qwen2.5-0.5B's 14).
                pl.BlockSpec((1, 1, 1, block_q), lambda h, b, i, *_: (b, h, 0, i)),
            ],
            scratch_shapes=[
                pltpu.VMEM((block_q, hd), jnp.float32),
                pltpu.VMEM((block_q, 1), jnp.float32),
                pltpu.VMEM((block_q, 1), jnp.float32),
                pltpu.VMEM((2, 2, block_k), jnp.int32),
                pltpu.VMEM((2, block_k, _lanes(hd)), k4.dtype),
                pltpu.VMEM((2, block_k, _lanes(hd)), v4.dtype),
                pltpu.SemaphoreType.DMA((3, 2)),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((B, nH, Tq, hd), q4.dtype),
            jax.ShapeDtypeStruct((B, nH, 1, Tq), jnp.float32),
        ],
        interpret=interpret,
        compiler_params=_SEQUENTIAL,
    )(
        *_schedule(lo_q, hi_q),
        seg_q.reshape(B, 1, Tq),
        qpos.reshape(B, 1, Tq),
        q4,
        _ids_blocked(seg_k, kpos, block_k),
        _blocked(k4, block_k),
        _blocked(v4, block_k),
    )
    return o, lse.reshape(B, nH, Tq)


# ---------------------------------------------------------------------------
# Backward kernels
# ---------------------------------------------------------------------------


# rows of the per-query-row float32 operand `[B, nH, nq, 4, Bq]` (the fourth
# is zeros: Mosaic tiles three sublanes by four, and a copy takes whole tiles)
_LSE, _DELTA, _DLSE = range(3)


def _bwd_dq_kernel(*refs, sm_scale: float, group: int):
    """Grid (head, row, query block); the key side in HBM as in `_fwd_kernel`."""
    sched = refs[:_N_SCHED]
    (seg_q_ref, qpos_ref, q_ref, do_ref, rows_ref, idk_hbm, k_hbm, v_hbm,
     dq_ref, dq_acc_ref, idk_buf, k_buf, v_buf, sems) = refs[_N_SCHED:]

    copies = _key_side_copies(idk_hbm, k_hbm, v_hbm, (idk_buf, k_buf, v_buf), sems, group)
    hd = q_ref.shape[-1]

    def _init():
        dq_acc_ref[:] = jnp.zeros_like(dq_acc_ref)

    def _compute(buf):
        q = q_ref[0, 0]
        k = k_buf[buf, :, :hd]
        v = v_buf[buf, :, :hd]
        do = do_ref[0, 0]
        lse = rows_ref[0, 0, 0, _LSE]  # [Bq]
        delta = rows_ref[0, 0, 0, _DELTA]  # [Bq]
        dlse = rows_ref[0, 0, 0, _DLSE]  # [Bq]
        mask = _mask_for(seg_q_ref[0, 0], idk_buf[buf, 0], qpos_ref[0, 0], idk_buf[buf, 1])
        s = _scores(q, k, mask, sm_scale)
        p = jnp.exp(s - lse[:, None])
        p = jnp.where(lse[:, None] > _NEG_INF / 2, p, 0.0)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        # float32: s, p, dp, ds, the accumulator; ds rounded for its product
        ds = p * (dp - delta[:, None] + dlse[:, None])
        dq_acc_ref[:] += sm_scale * jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    def _finalize():
        dq_ref[0, 0] = dq_acc_ref[:].astype(dq_ref.dtype)

    def _no_key():
        dq_ref[...] = jnp.zeros_like(dq_ref)

    _walk(sched, copies, _compute, _init, _finalize, _no_key)


def _bwd_dkv_kernel(*refs, sm_scale: float):
    """Grid (query head, row, key block). Query-side operands stay in HBM, a
    block an index: ids [B, nq, 2, Bq], q and dO [B, nH, nq, Bq, hd], the
    float32 rows [B, nH, nq, 4, Bq]."""
    sched = refs[:_N_SCHED]
    (seg_k_ref, kpos_ref, k_ref, v_ref, idq_hbm, q_hbm, do_hbm, rows_hbm,
     dk_ref, dv_ref, dk_acc_ref, dv_acc_ref, idq_buf, q_buf, do_buf, rows_buf,
     sems) = refs[_N_SCHED:]

    def copies(b, h, col, buf):
        srcs = (idq_hbm.at[b, col], q_hbm.at[b, h, col], do_hbm.at[b, h, col], rows_hbm.at[b, h, col])
        return _copies(srcs, (idq_buf, q_buf, do_buf, rows_buf), sems, buf)

    hd = k_ref.shape[-1]

    def _init():
        dk_acc_ref[:] = jnp.zeros_like(dk_acc_ref)
        dv_acc_ref[:] = jnp.zeros_like(dv_acc_ref)

    def _compute(buf):
        # The block is scored transposed, k q^T as [Bk, Bq]: p^T dO and
        # ds^T q are then plain [Bk, Bq] x [Bq, hd] products (contracting
        # dimension 0 of both operands made Mosaic transpose p and ds, a
        # third of the kernel's time), and the query rows' lse, delta and
        # dlse broadcast down the sublanes as they arrive, lane-major.
        q = q_buf[buf, :, :hd]
        k = k_ref[0, 0]
        v = v_ref[0, 0]
        do = do_buf[buf, :, :hd]
        lse = rows_buf[buf, _LSE][None, :]  # [1, Bq]
        delta = rows_buf[buf, _DELTA][None, :]
        dlse = rows_buf[buf, _DLSE][None, :]
        mask = _mask_for(
            idq_buf[buf, 0], seg_k_ref[0, 0], idq_buf[buf, 1], kpos_ref[0, 0],
            key_major=True,
        )
        s = _scores(k, q, mask, sm_scale)  # [Bk, Bq], float32 as p, dp, ds
        p = jnp.exp(s - lse)
        p = jnp.where(lse > _NEG_INF / 2, p, 0.0)
        # dv += p^T @ do (p^T rounded to dO's dtype)
        dv_acc_ref[:] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dp = jax.lax.dot_general(
            v, do, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = p * (dp - delta + dlse)
        # dk += ds^T @ q (ds^T rounded to q's dtype)
        dk_acc_ref[:] += sm_scale * jax.lax.dot_general(
            ds.astype(q.dtype), q, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    def _finalize():
        dk_ref[0, 0] = dk_acc_ref[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc_ref[:].astype(dv_ref.dtype)

    def _no_query():
        dk_ref[...] = jnp.zeros_like(dk_ref)
        dv_ref[...] = jnp.zeros_like(dv_ref)

    _walk(sched, copies, _compute, _init, _finalize, _no_query)


def _bwd_call(
    q4, k4, v4, seg_q, seg_k, qpos, kpos, lo_q, hi_q, lo_k, hi_k,
    o, lse, do, dlse, *, sm_scale, block_q, block_k, interpret,
):
    B, nH, Tq, hd = q4.shape
    nKV, Tk = k4.shape[1:3]
    group = nH // nKV
    delta = jnp.sum(
        o.astype(jnp.float32) * do.astype(jnp.float32), axis=-1
    )  # [B, nH, Tq]
    # The per-row vectors travel together, a query block an index:
    # [B, nH, nq, 4, Bq], so a (4, Bq) block's trailing dims are the array's
    # for any nH (see _fwd_call out_specs).
    rows = jnp.stack([lse, delta, dlse, jnp.zeros_like(lse)], axis=2)
    rows = rows.reshape(B, nH, 4, Tq // block_q, block_q).swapaxes(2, 3)
    row = lambda h, b, i, *_: (b, 0, i)  # noqa: E731
    tile = lambda h, b, i, *_: (b, h, i, 0)  # noqa: E731

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, sm_scale=sm_scale, group=group),
        name="flash_dq",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=_N_SCHED,
            grid=(nH, B, Tq // block_q),
            in_specs=[
                pl.BlockSpec((1, 1, block_q), row),
                pl.BlockSpec((1, 1, block_q), row),
                pl.BlockSpec((1, 1, block_q, hd), tile),
                pl.BlockSpec((1, 1, block_q, hd), tile),
                pl.BlockSpec(
                    (1, 1, 1, 4, block_q), lambda h, b, i, *_: (b, h, i, 0, 0)
                ),
                _ANY, _ANY, _ANY,
            ],
            out_specs=pl.BlockSpec((1, 1, block_q, hd), tile),
            scratch_shapes=[
                pltpu.VMEM((block_q, hd), jnp.float32),
                pltpu.VMEM((2, 2, block_k), jnp.int32),
                pltpu.VMEM((2, block_k, _lanes(hd)), k4.dtype),
                pltpu.VMEM((2, block_k, _lanes(hd)), v4.dtype),
                pltpu.SemaphoreType.DMA((3, 2)),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, nH, Tq, hd), q4.dtype),
        interpret=interpret,
        compiler_params=_SEQUENTIAL,
    )(
        *_schedule(lo_q, hi_q),
        seg_q.reshape(B, 1, Tq),
        qpos.reshape(B, 1, Tq),
        q4,
        do,
        rows,
        _ids_blocked(seg_k, kpos, block_k),
        _blocked(k4, block_k),
        _blocked(v4, block_k),
    )

    # dk/dv computed per *query* head, then reduced over the GQA group.
    kv_tile = lambda h, b, j, *_: (b, h // group, j, 0)  # noqa: E731
    dk_h, dv_h = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, sm_scale=sm_scale),
        name="flash_dkv",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=_N_SCHED,
            grid=(nH, B, Tk // block_k),
            in_specs=[
                pl.BlockSpec((1, 1, block_k), row),
                pl.BlockSpec((1, 1, block_k), row),
                pl.BlockSpec((1, 1, block_k, hd), kv_tile),
                pl.BlockSpec((1, 1, block_k, hd), kv_tile),
                _ANY, _ANY, _ANY, _ANY,
            ],
            out_specs=[
                pl.BlockSpec((1, 1, block_k, hd), tile),
                pl.BlockSpec((1, 1, block_k, hd), tile),
            ],
            scratch_shapes=[
                pltpu.VMEM((block_k, hd), jnp.float32),
                pltpu.VMEM((block_k, hd), jnp.float32),
                pltpu.VMEM((2, 2, block_q), jnp.int32),
                pltpu.VMEM((2, block_q, _lanes(hd)), q4.dtype),
                pltpu.VMEM((2, block_q, _lanes(hd)), do.dtype),
                pltpu.VMEM((2, 4, block_q), jnp.float32),
                pltpu.SemaphoreType.DMA((4, 2)),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((B, nH, Tk, hd), jnp.float32),
            jax.ShapeDtypeStruct((B, nH, Tk, hd), jnp.float32),
        ],
        interpret=interpret,
        compiler_params=_SEQUENTIAL,
    )(
        *_schedule(lo_k, hi_k),
        seg_k.reshape(B, 1, Tk),
        kpos.reshape(B, 1, Tk),
        k4,
        v4,
        _ids_blocked(seg_q, qpos, block_q),
        _blocked(q4, block_q),
        _blocked(do, block_q),
        rows,
    )

    dk = dk_h.reshape(B, nKV, group, Tk, hd).sum(axis=2).astype(k4.dtype)
    dv = dv_h.reshape(B, nKV, group, Tk, hd).sum(axis=2).astype(v4.dtype)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# custom-VJP core (row-major batch, heads-major, block-aligned shapes)
# ---------------------------------------------------------------------------


def _rows_under_vmap(call):
    """`call` (every operand and result `[B, ...]`, a row of the batch an
    independent problem with its own work list) with `jax.vmap` mapped onto
    that batch axis: the vmapped rows become more rows of ONE kernel. Left
    to Pallas's own batching rule a scalar-prefetch operand that differs by
    row turns the call into a sequential loop over rows."""
    folded = custom_vmap(call)

    @folded.def_vmap
    def _fold(axis_size, in_batched, *args):
        args = [
            a if batched else jnp.broadcast_to(a, (axis_size, *a.shape))
            for a, batched in zip(args, in_batched)
        ]
        out = folded(*(a.reshape(-1, *a.shape[2:]) for a in args))
        unfold = lambda y: y.reshape(axis_size, -1, *y.shape[1:])  # noqa: E731
        return jax.tree.map(unfold, out), jax.tree.map(lambda _: True, out)

    return folded


@functools.lru_cache(maxsize=None)
def _flash(sm_scale: float, block_q: int, block_k: int, interpret: bool):
    """The differentiable core for those static parameters:
    `flash(q4, k4, v4, seg_q, seg_k, qpos, kpos, runs) -> (o, lse)` with a
    leading batch axis on every operand (1 from the trainer and the ring;
    `jax.vmap` folds into it) and `runs` = `walk_runs` of each row. `runs`
    only says which block pairs to leave out: longer runs give the same
    result. `custom_vmap` has no reverse mode, so the `custom_vjp` is the
    outer of the two, as in `models/qwen2.py:_expert_mixture`."""
    static = dict(
        sm_scale=sm_scale, block_q=block_q, block_k=block_k, interpret=interpret
    )
    # jitted: a ring's n steps (and remat's second forward) trace and lower
    # the kernels once, not n times (XLA inlines the calls; a trainer's
    # set-up is mostly tracing once its programs are in the compile cache)
    fwd_call = _rows_under_vmap(jax.jit(functools.partial(_fwd_call, **static)))
    bwd_call = _rows_under_vmap(jax.jit(functools.partial(_bwd_call, **static)))

    @jax.custom_vjp
    def flash(q4, k4, v4, seg_q, seg_k, qpos, kpos, runs):
        return fwd_call(q4, k4, v4, seg_q, seg_k, qpos, kpos, *runs[:2])

    def flash_fwd(q4, k4, v4, seg_q, seg_k, qpos, kpos, runs):
        o, lse = fwd_call(q4, k4, v4, seg_q, seg_k, qpos, kpos, *runs[:2])
        # named for a `jax.checkpoint` region around the caller: one whose
        # policy keeps these two (`utils/hbm.py:KEEP_ATTENTION`) runs no
        # second `%flash_fwd` in its backward; `o` is also what the caller
        # goes on with, and a ring names every step's partials here
        o, lse = checkpoint_name(o, "attn_out"), checkpoint_name(lse, "attn_lse")
        return (o, lse), (q4, k4, v4, seg_q, seg_k, qpos, kpos, runs, o, lse)

    def flash_bwd(res, cts):
        *operands, runs, o, lse = res
        do, dlse = cts
        if dlse is None or isinstance(dlse, jax.custom_derivatives.SymbolicZero):
            dlse = jnp.zeros_like(lse)
        grads = bwd_call(*operands, *runs, o, lse, do, dlse.astype(jnp.float32))
        return (*grads, None, None, None, None, None)

    flash.defvjp(flash_fwd, flash_bwd)
    return flash


def _fit_block(requested: int, t: int) -> int:
    """Largest usable block ≤ `requested` for a length-`t` axis.

    Always a multiple of 128: Mosaic requires lane dims divisible by 128 and
    sublane dims divisible by 8, so a block equal to a ragged T (e.g. 130)
    would fail to lower — we round T *up* to 128 instead and rely on padding.
    """
    requested = max(128, (requested // 128) * 128)
    return min(requested, ((max(t, 1) + 127) // 128) * 128)


def _pad_to(x, n, axis, value=0):
    pad = n - x.shape[axis]
    if pad <= 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


def flash_attention_chunk(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    seg_q: jax.Array,
    seg_k: jax.Array,
    q_positions: jax.Array,
    kv_positions: jax.Array,
    *,
    sm_scale: float | None = None,
    block_q: int = 512,
    block_k: int = 512,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Attention of local queries against ONE kv chunk (ring building block).

    q: [Tq, nH, hd]; k/v: [Tk, nKV, hd]; positions are *global* token indices
    deciding causality. Returns (out [Tq, nH, hd], lse [Tq, nH]) where `out`
    is normalised within this chunk and `lse` is the chunk's log-sum-exp —
    merge across chunks with logsumexp weights (see ring_attention.merge).
    """
    Tq, nH, hd = q.shape
    Tk = k.shape[0]
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(hd)
    if interpret is None:
        interpret = _default_interpret()
    block_q = _fit_block(block_q, Tq)
    block_k = _fit_block(block_k, Tk)
    Tqp = ((Tq + block_q - 1) // block_q) * block_q
    Tkp = ((Tk + block_k - 1) // block_k) * block_k

    q3 = jnp.swapaxes(_pad_to(q, Tqp, 0), 0, 1)
    k3 = jnp.swapaxes(_pad_to(k, Tkp, 0), 0, 1)
    v3 = jnp.swapaxes(_pad_to(v, Tkp, 0), 0, 1)
    seg_q = _pad_to(seg_q.astype(jnp.int32), Tqp, 0, PADDING_SEGMENT)
    seg_k = _pad_to(seg_k.astype(jnp.int32), Tkp, 0, PADDING_SEGMENT)
    qpos = _pad_to(q_positions.astype(jnp.int32), Tqp, 0)
    kpos = _pad_to(kv_positions.astype(jnp.int32), Tkp, 0)

    runs = walk_runs(seg_q, seg_k, qpos, kpos, block_q, block_k)
    o4, lse = _flash(float(sm_scale), block_q, block_k, bool(interpret))(
        *(x[None] for x in (q3, k3, v3, seg_q, seg_k, qpos, kpos)),
        tuple(r[None] for r in runs),
    )
    return jnp.swapaxes(o4[0], 0, 1)[:Tq], jnp.swapaxes(lse[0], 0, 1)[:Tq]


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    segment_ids: jax.Array,
    *,
    sm_scale: float | None = None,
    block_q: int = 512,
    block_k: int = 512,
    interpret: bool | None = None,
) -> jax.Array:
    """Packed-layout flash attention (single device / replicated tokens).

    Args:
      q: [T, nH, hd]; k, v: [T, nKV, hd] (GQA: nH % nKV == 0).
      segment_ids: [T] int32; PADDING_SEGMENT (-1) marks pad tokens.
    Returns: [T, nH, hd] in q.dtype. T is padded internally to the block size.
    """
    T, nH, hd = q.shape
    nKV = k.shape[1]
    assert nH % nKV == 0, (nH, nKV)
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(hd)
    if interpret is None:
        interpret = _default_interpret()

    block_q = _fit_block(block_q, T)
    block_k = _fit_block(block_k, T)
    blk = math.lcm(block_q, block_k)
    Tp = ((T + blk - 1) // blk) * blk

    q3 = jnp.swapaxes(_pad_to(q, Tp, 0), 0, 1)  # [nH, Tp, hd]
    k3 = jnp.swapaxes(_pad_to(k, Tp, 0), 0, 1)
    v3 = jnp.swapaxes(_pad_to(v, Tp, 0), 0, 1)
    seg = _pad_to(segment_ids.astype(jnp.int32), Tp, 0, PADDING_SEGMENT)
    pos = jnp.arange(Tp, dtype=jnp.int32)

    runs = walk_runs(seg, seg, pos, pos, block_q, block_k)
    o4, _ = _flash(float(sm_scale), block_q, block_k, bool(interpret))(
        *(x[None] for x in (q3, k3, v3, seg, seg, pos, pos)),
        tuple(r[None] for r in runs),
    )
    return jnp.swapaxes(o4[0], 0, 1)[:T]
