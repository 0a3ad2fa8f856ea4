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
- every kernel casts its q/k/v/dO blocks to float32 before the matmuls, so
  scores, softmax, the output accumulation and all gradients are float32
  arithmetic (bf16 operands for the MXU are not used here).
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
table, on NumPy or JAX arrays. The wrappers compute it once a call (XLA, on
four int32 a block) and pack it 32 key blocks a word (`live_table`); the
three kernels read their pair's bit from SMEM and run `_compute` only where
it is set. A dead pair's contribution would be alpha = 1, p = 0, so skipping
it is exact to the bit. With positions = arange, test 2 is the causal
above-diagonal skip; a packed row of short sequences keeps only the blocks
near its diagonal; a ring step that brings a later shard, or one with no
sequence in common, is skipped whole. A skipped pair keeps its place in the
grid (the table is a blocked SMEM operand, which batches under `vmap`; only
a scalar-prefetch operand could shorten the index maps' walk, and `vmap`
turns that into a loop over rows): about 0.18 us a step, fetches or none
(PERF.md, PR 29).

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
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

PADDING_SEGMENT = -1
_NEG_INF = -1e30

def _default_interpret() -> bool:
    return jax.default_backend() != "tpu"


def _mask_for(seg_q, seg_k, qpos, kpos):
    """[Bq, Bk] validity: same segment, causal by global position, not pad."""
    return (
        (seg_q[:, None] == seg_k[None, :])
        & (qpos[:, None] >= kpos[None, :])
        & (seg_q[:, None] != PADDING_SEGMENT)
    )


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


def live_block_counts(seg, pos, shard_len: int, block: int = 512):
    """(live, all) block pairs the three kernels visit for one packed row,
    counted on the host (NumPy) as the wrappers lay the row out: ring shards
    of `shard_len` tokens (the row's own length when it is not sharded),
    each padded to whole blocks of `_fit_block(block, shard_len)`."""
    blk = _fit_block(block, shard_len)
    pad = -shard_len % blk
    rows = lambda x, fill: np.pad(  # noqa: E731
        np.asarray(x).reshape(-1, shard_len), ((0, 0), (0, pad)),
        constant_values=fill,
    ).reshape(-1)
    seg, pos = rows(seg, PADDING_SEGMENT), rows(pos, 0)
    live = block_liveness(seg, seg, pos, pos, blk, blk)
    return int(live.sum()), live.size


def _words(nk: int) -> int:
    """int32 words a query block's row of `live_table` takes."""
    return -(-nk // 32)


def live_table(seg_q, seg_k, qpos, kpos, block_q: int, block_k: int):
    """`block_liveness` as the kernels read it, [1, nq * words] int32: pair
    (i, j) is bit j % 32 of word i * words + j // 32."""
    live = block_liveness(seg_q, seg_k, qpos, kpos, block_q, block_k)
    nq, nk = live.shape
    words = _words(nk)
    live = jnp.pad(live, ((0, 0), (0, words * 32 - nk))).reshape(nq, words, 32)
    bits = live.astype(jnp.uint32) << jnp.arange(32, dtype=jnp.uint32)
    packed = bits.sum(axis=-1, dtype=jnp.uint32)
    return jax.lax.bitcast_convert_type(packed, jnp.int32).reshape(1, -1)


def _table_spec(nq: int, nk: int) -> pl.BlockSpec:
    # The whole table in SMEM, fetched once: its block index never changes
    # (under vmap the batching rule gives each row its own). Two-dimensional
    # so that the batched block's last two dims equal the array's.
    return pl.BlockSpec(
        (1, nq * _words(nk)), lambda *_: (0, 0), memory_space=pltpu.SMEM
    )


def _when_live(live_ref, i, j, nk):
    """`pl.when` on the bit of (query block i, key block j)."""
    word = live_ref[0, i * _words(nk) + (j >> 5)]
    return pl.when(((word >> (j & 31)) & 1) == 1)


# ---------------------------------------------------------------------------
# Forward kernel
# ---------------------------------------------------------------------------


def _fwd_kernel(
    live_ref,
    seg_q_ref,
    seg_k_ref,
    qpos_ref,
    kpos_ref,
    q_ref,
    k_ref,
    v_ref,
    o_ref,
    lse_ref,
    acc_ref,
    m_ref,
    l_ref,
    *,
    sm_scale: float,
):
    i = pl.program_id(1)
    j = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    @_when_live(live_ref, i, j, pl.num_programs(2))
    def _compute():
        q = q_ref[0].astype(jnp.float32)  # [Bq, hd]
        k = k_ref[0].astype(jnp.float32)  # [Bk, hd]
        s = jax.lax.dot_general(
            q,
            k,
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        s = s * sm_scale
        mask = _mask_for(seg_q_ref[0], seg_k_ref[0], qpos_ref[0], kpos_ref[0])
        s = jnp.where(mask, s, _NEG_INF)

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
        v = v_ref[0].astype(jnp.float32)
        acc_ref[:] = acc_ref[:] * alpha + jax.lax.dot_general(
            p,
            v,
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(j == nk - 1)
    def _finalize():
        l = l_ref[:]
        safe_l = jnp.where(l > 0.0, l, 1.0)
        o_ref[0] = (acc_ref[:] / safe_l).astype(o_ref.dtype)
        lse = jnp.where(l > 0.0, m_ref[:] + jnp.log(safe_l), _NEG_INF)
        lse_ref[0, 0] = lse[:, 0]


def _fwd_call(
    q3, k3, v3, seg_q, seg_k, qpos, kpos, live, sm_scale, block_q, block_k,
    interpret,
):
    """q3: [nH, Tq, hd]; k3/v3: [nKV, Tk, hd]. Returns (o [nH,Tq,hd], lse [nH,Tq])."""
    nH, Tq, hd = q3.shape
    nKV, Tk, _ = k3.shape
    group = nH // nKV
    grid = (nH, Tq // block_q, Tk // block_k)

    o, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, sm_scale=sm_scale),
        name="flash_fwd",
        grid=grid,
        in_specs=[
            _table_spec(Tq // block_q, Tk // block_k),
            pl.BlockSpec((1, block_q), lambda h, i, j: (0, i)),
            pl.BlockSpec((1, block_k), lambda h, i, j: (0, j)),
            pl.BlockSpec((1, block_q), lambda h, i, j: (0, i)),
            pl.BlockSpec((1, block_k), lambda h, i, j: (0, j)),
            pl.BlockSpec((1, block_q, hd), lambda h, i, j: (h, i, 0)),
            pl.BlockSpec(
                (1, block_k, hd), lambda h, i, j, g=group: (h // g, j, 0)
            ),
            pl.BlockSpec(
                (1, block_k, hd), lambda h, i, j, g=group: (h // g, j, 0)
            ),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, hd), lambda h, i, j: (h, i, 0)),
            # LSE rides as [nH, 1, Tq]: the trailing block dims (1, block_q)
            # match the trailing array dims (1, Tq) under Mosaic's rule for
            # ANY head count (a (1, block_q) block over [nH, Tq] is illegal
            # whenever nH is not a multiple of 8 — e.g. Qwen2.5-0.5B's 14).
            pl.BlockSpec((1, 1, block_q), lambda h, i, j: (h, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((nH, Tq, hd), q3.dtype),
            jax.ShapeDtypeStruct((nH, 1, Tq), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, hd), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
        ],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
    )(
        live,
        seg_q.reshape(1, Tq),
        seg_k.reshape(1, Tk),
        qpos.reshape(1, Tq),
        kpos.reshape(1, Tk),
        q3,
        k3,
        v3,
    )
    return o, lse.reshape(nH, Tq)


# ---------------------------------------------------------------------------
# Backward kernels
# ---------------------------------------------------------------------------


def _scores(q, k, seg_q, seg_k, qpos, kpos, sm_scale):
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )
    s = s * sm_scale
    return jnp.where(_mask_for(seg_q, seg_k, qpos, kpos), s, _NEG_INF)


def _bwd_dq_kernel(
    live_ref,
    seg_q_ref,
    seg_k_ref,
    qpos_ref,
    kpos_ref,
    q_ref,
    k_ref,
    v_ref,
    do_ref,
    lse_ref,
    delta_ref,
    dlse_ref,
    dq_ref,
    dq_acc_ref,
    *,
    sm_scale: float,
):
    i = pl.program_id(1)
    j = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        dq_acc_ref[:] = jnp.zeros_like(dq_acc_ref)

    @_when_live(live_ref, i, j, pl.num_programs(2))
    def _compute():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0, 0]  # [Bq]
        delta = delta_ref[0, 0]  # [Bq]
        dlse = dlse_ref[0, 0]  # [Bq]
        s = _scores(
            q, k, seg_q_ref[0], seg_k_ref[0], qpos_ref[0], kpos_ref[0], sm_scale
        )
        p = jnp.exp(s - lse[:, None])
        p = jnp.where(lse[:, None] > _NEG_INF / 2, p, 0.0)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = p * (dp - delta[:, None] + dlse[:, None])
        dq_acc_ref[:] += sm_scale * jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    @pl.when(j == nk - 1)
    def _finalize():
        dq_ref[0] = dq_acc_ref[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(
    live_ref,
    seg_q_ref,
    seg_k_ref,
    qpos_ref,
    kpos_ref,
    q_ref,
    k_ref,
    v_ref,
    do_ref,
    lse_ref,
    delta_ref,
    dlse_ref,
    dk_ref,
    dv_ref,
    dk_acc_ref,
    dv_acc_ref,
    *,
    sm_scale: float,
):
    jk = pl.program_id(1)
    iq = pl.program_id(2)
    nq = pl.num_programs(2)

    @pl.when(iq == 0)
    def _init():
        dk_acc_ref[:] = jnp.zeros_like(dk_acc_ref)
        dv_acc_ref[:] = jnp.zeros_like(dv_acc_ref)

    @_when_live(live_ref, iq, jk, pl.num_programs(1))
    def _compute():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0, 0]
        delta = delta_ref[0, 0]
        dlse = dlse_ref[0, 0]
        s = _scores(
            q, k, seg_q_ref[0], seg_k_ref[0], qpos_ref[0], kpos_ref[0], sm_scale
        )
        p = jnp.exp(s - lse[:, None])
        p = jnp.where(lse[:, None] > _NEG_INF / 2, p, 0.0)
        # dv += p^T @ do
        dv_acc_ref[:] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = p * (dp - delta[:, None] + dlse[:, None])
        # dk += ds^T @ q
        dk_acc_ref[:] += sm_scale * jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    @pl.when(iq == nq - 1)
    def _finalize():
        dk_ref[0] = dk_acc_ref[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc_ref[:].astype(dv_ref.dtype)


def _bwd_call(
    q3, k3, v3, seg_q, seg_k, qpos, kpos, live, o, lse, do, dlse,
    sm_scale, block_q, block_k, interpret,
):
    nH, Tq, hd = q3.shape
    nKV, Tk, _ = k3.shape
    group = nH // nKV
    seg_q2 = seg_q.reshape(1, Tq)
    seg_k2 = seg_k.reshape(1, Tk)
    qpos2 = qpos.reshape(1, Tq)
    kpos2 = kpos.reshape(1, Tk)
    delta = jnp.sum(
        o.astype(jnp.float32) * do.astype(jnp.float32), axis=-1
    )  # [nH, Tq]
    # Per-row vectors travel as [nH, 1, Tq] so their (1, 1, block_q) blocks
    # satisfy Mosaic's trailing-dims rule for any nH (see _fwd_call out_specs).
    lse3 = lse.reshape(nH, 1, Tq)
    delta3 = delta.reshape(nH, 1, Tq)
    dlse3 = dlse.reshape(nH, 1, Tq)

    operands = (
        live, seg_q2, seg_k2, qpos2, kpos2, q3, k3, v3, do, lse3, delta3, dlse3
    )
    table_spec = [_table_spec(Tq // block_q, Tk // block_k)]
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, sm_scale=sm_scale),
        name="flash_dq",
        grid=(nH, Tq // block_q, Tk // block_k),
        in_specs=table_spec + [
            pl.BlockSpec((1, block_q), lambda h, i, j: (0, i)),
            pl.BlockSpec((1, block_k), lambda h, i, j: (0, j)),
            pl.BlockSpec((1, block_q), lambda h, i, j: (0, i)),
            pl.BlockSpec((1, block_k), lambda h, i, j: (0, j)),
            pl.BlockSpec((1, block_q, hd), lambda h, i, j: (h, i, 0)),
            pl.BlockSpec(
                (1, block_k, hd), lambda h, i, j, g=group: (h // g, j, 0)
            ),
            pl.BlockSpec(
                (1, block_k, hd), lambda h, i, j, g=group: (h // g, j, 0)
            ),
            pl.BlockSpec((1, block_q, hd), lambda h, i, j: (h, i, 0)),
            pl.BlockSpec((1, 1, block_q), lambda h, i, j: (h, 0, i)),
            pl.BlockSpec((1, 1, block_q), lambda h, i, j: (h, 0, i)),
            pl.BlockSpec((1, 1, block_q), lambda h, i, j: (h, 0, i)),
        ],
        out_specs=pl.BlockSpec((1, block_q, hd), lambda h, i, j: (h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((nH, Tq, hd), q3.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, hd), jnp.float32)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
    )(*operands)

    # dk/dv computed per *query* head, then reduced over the GQA group.
    dk_h, dv_h = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, sm_scale=sm_scale),
        name="flash_dkv",
        grid=(nH, Tk // block_k, Tq // block_q),
        in_specs=table_spec + [
            pl.BlockSpec((1, block_q), lambda h, jk, iq: (0, iq)),
            pl.BlockSpec((1, block_k), lambda h, jk, iq: (0, jk)),
            pl.BlockSpec((1, block_q), lambda h, jk, iq: (0, iq)),
            pl.BlockSpec((1, block_k), lambda h, jk, iq: (0, jk)),
            pl.BlockSpec((1, block_q, hd), lambda h, jk, iq: (h, iq, 0)),
            pl.BlockSpec(
                (1, block_k, hd), lambda h, jk, iq, g=group: (h // g, jk, 0)
            ),
            pl.BlockSpec(
                (1, block_k, hd), lambda h, jk, iq, g=group: (h // g, jk, 0)
            ),
            pl.BlockSpec((1, block_q, hd), lambda h, jk, iq: (h, iq, 0)),
            pl.BlockSpec((1, 1, block_q), lambda h, jk, iq: (h, 0, iq)),
            pl.BlockSpec((1, 1, block_q), lambda h, jk, iq: (h, 0, iq)),
            pl.BlockSpec((1, 1, block_q), lambda h, jk, iq: (h, 0, iq)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, hd), lambda h, jk, iq: (h, jk, 0)),
            pl.BlockSpec((1, block_k, hd), lambda h, jk, iq: (h, jk, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((nH, Tk, hd), jnp.float32),
            jax.ShapeDtypeStruct((nH, Tk, hd), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, hd), jnp.float32),
            pltpu.VMEM((block_k, hd), jnp.float32),
        ],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
    )(*operands)

    dk = dk_h.reshape(nKV, group, Tk, hd).sum(axis=1).astype(k3.dtype)
    dv = dv_h.reshape(nKV, group, Tk, hd).sum(axis=1).astype(v3.dtype)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# custom-VJP core (heads-major, block-aligned shapes)
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(8, 9, 10, 11))
def _flash(
    q3, k3, v3, seg_q, seg_k, qpos, kpos, live,
    sm_scale, block_q, block_k, interpret,
):
    """live: `live_table` of the four id and position vectors. It only says
    which block pairs to skip: more bits set give the same result."""
    return _fwd_call(
        q3, k3, v3, seg_q, seg_k, qpos, kpos, live,
        sm_scale, block_q, block_k, interpret,
    )


def _flash_fwd(
    q3, k3, v3, seg_q, seg_k, qpos, kpos, live,
    sm_scale, block_q, block_k, interpret,
):
    o, lse = _fwd_call(
        q3, k3, v3, seg_q, seg_k, qpos, kpos, live,
        sm_scale, block_q, block_k, interpret,
    )
    return (o, lse), (q3, k3, v3, seg_q, seg_k, qpos, kpos, live, o, lse)


def _flash_bwd(sm_scale, block_q, block_k, interpret, res, cts):
    q3, k3, v3, seg_q, seg_k, qpos, kpos, live, o, lse = res
    do, dlse = cts
    if dlse is None or isinstance(dlse, jax.custom_derivatives.SymbolicZero):
        dlse = jnp.zeros_like(lse)
    dq, dk, dv = _bwd_call(
        q3, k3, v3, seg_q, seg_k, qpos, kpos, live, o, lse, do,
        dlse.astype(jnp.float32),
        sm_scale, block_q, block_k, interpret,
    )
    return (dq, dk, dv) + (None,) * 5


_flash.defvjp(_flash_fwd, _flash_bwd)


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

    o3, lse = _flash(
        q3, k3, v3, seg_q, seg_k, qpos, kpos,
        live_table(seg_q, seg_k, qpos, kpos, block_q, block_k),
        sm_scale, block_q, block_k, interpret,
    )
    return jnp.swapaxes(o3, 0, 1)[:Tq], jnp.swapaxes(lse, 0, 1)[:Tq]


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

    o3, _ = _flash(
        q3, k3, v3, seg, seg, pos, pos,
        live_table(seg, seg, pos, pos, block_q, block_k),
        sm_scale, block_q, block_k, interpret,
    )
    return jnp.swapaxes(o3, 0, 1)[:T]
