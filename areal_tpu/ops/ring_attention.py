"""Ring attention: context-parallel flash attention over the token axis.

The reference's long-context story is Megatron context parallelism — 2·cp
zig-zag chunk sharding delegated to TransformerEngine CUDA kernels
(areal/utils/mcore/packed_context_parallel.py:9, megatron_engine.py:815-882)
— plus Ulysses all-to-all SP on the FSDP path (areal/utils/ulysses.py). On
TPU both collapse into ONE mechanism: the packed token stream is sharded
over mesh axes ("dp","sp"), and attention runs as a shard_map ring —

    each shard holds a [T/n] chunk of Q, K, V; K/V chunks rotate around the
    ring via jax.lax.ppermute (XLA lowers to ICI neighbour exchange), each
    step computing a partial flash-attention (areal_tpu/ops/flash_attention
    .flash_attention_chunk) of local Q against the visiting K/V chunk;
    partials merge exactly via log-sum-exp weights.

Causality is decided by *global* token positions, so packing and segment
isolation behave exactly as in the single-shard kernel. Gradients flow
through ppermute and the kernel's custom VJP — no custom ring backward.

Two shard layouts, selected by the `zigzag` flag:

- contiguous: shard i holds tokens [i·T/n, (i+1)·T/n). Simple, but causal
  masking makes the work triangular — shard 0 attends to almost nothing,
  shard n-1 to everything, and the ring runs at the slowest shard's pace.
- zig-zag: the token axis is permuted (utils/data.zigzag_indices — applied
  by the model at forward entry and inverted on its outputs) so shard i
  holds the chunk PAIR (i, 2n-1-i) of 2n chunks. Every shard then owns one
  early and one late chunk and does equal causal work. The kernel is
  unchanged — only the global position maps differ (the per-shard layout
  is encoded in qpos/kpos, which `flash_attention_chunk` already takes
  explicitly), so the result is exact, not an approximation.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from areal_tpu.ops.flash_attention import (
    _NEG_INF,
    flash_attention,
    flash_attention_chunk,
)
from areal_tpu.parallel import mesh as mesh_lib


def _cp_axis_names(mesh: Mesh) -> tuple[str, ...]:
    return tuple(
        a
        for a in (mesh_lib.AXIS_DP, mesh_lib.AXIS_SP)
        if a in mesh.axis_names and mesh.shape[a] > 1
    )


def cp_ring_shards(
    T: int,
    mesh: Mesh | None = None,
    axis_names: tuple[str, ...] | None = None,
) -> int:
    """Number of shards the ring path will split a [T] token axis over, or
    0 when `ring_flash_attention` would fall back to the single-shard
    kernel. This is THE predicate both the model (deciding whether to
    zig-zag-permute its inputs) and the ring (deciding its layout) consult
    — they must never disagree, or plain flash would silently misread
    permuted data."""
    if mesh is None:
        mesh = mesh_lib.current_mesh()
    if mesh is None:
        return 0
    if axis_names is None:
        axis_names = _cp_axis_names(mesh)
    n = math.prod(mesh.shape[a] for a in axis_names) if axis_names else 1
    if n <= 1 or T % n != 0 or (T // n) < 128:
        return 0
    return n


def zigzag_eligible(
    T: int,
    mesh: Mesh | None = None,
    axis_names: tuple[str, ...] | None = None,
) -> bool:
    """True when the zig-zag layout applies: the ring path engages AND the
    token axis splits into 2n equal chunks."""
    n = cp_ring_shards(T, mesh, axis_names)
    return n >= 2 and T % (2 * n) == 0


def _shard_positions(
    idx: jax.Array, Tl: int, n: int, zigzag: bool
) -> jax.Array:
    """Global token positions held by ring shard `idx` ([Tl] int32)."""
    if not zigzag:
        return idx.astype(jnp.int32) * Tl + jnp.arange(Tl, dtype=jnp.int32)
    c = Tl // 2
    ar = jnp.arange(c, dtype=jnp.int32)
    lo = idx.astype(jnp.int32) * c + ar
    hi = (2 * n - 1 - idx).astype(jnp.int32) * c + ar
    return jnp.concatenate([lo, hi])


def _ring_body(
    q_l: jax.Array,  # [Tl, nH(_l), hd]
    k_l: jax.Array,
    v_l: jax.Array,
    seg_l: jax.Array,  # [Tl]
    *,
    axis_names: tuple[str, ...],
    n: int,
    zigzag: bool,
    sm_scale: float | None,
    interpret: bool | None,
) -> jax.Array:
    Tl = q_l.shape[0]
    idx = jax.lax.axis_index(axis_names)
    qpos = _shard_positions(idx, Tl, n, zigzag)

    k_c, v_c, seg_c = k_l, v_l, seg_l
    perm = [(i, (i + 1) % n) for i in range(n)]
    # Online merge: keep ONE running (out, lse) pair — O(T/n) memory per
    # shard — rescaled by log-sum-exp weights each ring step. Rows with no
    # valid keys anywhere keep lse at _NEG_INF and out at 0.
    o_run = None
    lse_run = None
    for s in range(n):
        src = (idx - s) % n
        kpos = _shard_positions(src, Tl, n, zigzag)
        o_s, lse_s = flash_attention_chunk(
            q_l, k_c, v_c, seg_l, seg_c, qpos, kpos,
            sm_scale=sm_scale, interpret=interpret,
        )
        o_s = o_s.astype(jnp.float32)
        if o_run is None:
            o_run, lse_run = o_s, lse_s
        else:
            m = jnp.maximum(lse_run, lse_s)
            m0 = jnp.where(m > _NEG_INF / 2, m, 0.0)
            wa = jnp.exp(lse_run - m0)
            wb = jnp.exp(lse_s - m0)
            denom = wa + wb
            safe = jnp.where(denom > 0.0, denom, 1.0)
            o_run = (wa[..., None] * o_run + wb[..., None] * o_s) / safe[..., None]
            lse_run = jnp.where(denom > 0.0, m0 + jnp.log(safe), _NEG_INF)
        if s < n - 1:
            k_c = jax.lax.ppermute(k_c, axis_names, perm)
            v_c = jax.lax.ppermute(v_c, axis_names, perm)
            seg_c = jax.lax.ppermute(seg_c, axis_names, perm)

    return o_run.astype(q_l.dtype)


def ring_flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    segment_ids: jax.Array,
    *,
    mesh: Mesh | None = None,
    axis_names: tuple[str, ...] | None = None,
    zigzag: bool = False,
    sm_scale: float | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """Sequence-sharded attention. Same contract as flash_attention, but the
    [T] token axis may be sharded over mesh axes ("dp","sp"); falls back to
    the single-shard kernel when there is nothing to ring over.

    `zigzag=True` declares that the caller laid the token axis out in the
    balanced zig-zag chunk order (utils/data.zigzag_indices): shard i holds
    chunks (i, 2n-1-i), and q/k/v/segment_ids are all in that permuted
    order. Causality then runs on the zig-zag global position maps. The
    caller must have checked `zigzag_eligible` with the same (T, mesh) —
    a zig-zag stream falling back to plain flash would be silently wrong,
    so that case raises instead.
    """
    if mesh is None:
        mesh = mesh_lib.current_mesh()
    if mesh is None:
        if zigzag:
            raise ValueError(
                "zigzag layout requires the ring path (no mesh bound); the "
                "caller permuted a stream plain flash would misread"
            )
        return flash_attention(
            q, k, v, segment_ids, sm_scale=sm_scale, interpret=interpret
        )
    if axis_names is None:
        axis_names = _cp_axis_names(mesh)
    T, nH, _ = q.shape
    nKV = k.shape[1]
    n = cp_ring_shards(T, mesh, axis_names)
    if n == 0:
        # Nothing to shard over / too small to tile: single-shard kernel
        # (XLA will all-gather the token axis if it was sharded).
        if zigzag:
            raise ValueError(
                f"zigzag layout requested but the ring path falls back at "
                f"T={T} on mesh axes {axis_names} — caller/ring predicate "
                "mismatch (use zigzag_eligible)"
            )
        return flash_attention(
            q, k, v, segment_ids, sm_scale=sm_scale, interpret=interpret
        )
    if zigzag and T % (2 * n) != 0:
        raise ValueError(
            f"zigzag layout needs T % 2n == 0 (T={T}, n={n}); "
            "use zigzag_eligible before permuting"
        )

    # Keep TP sharding of the head axis through the shard_map when it divides.
    tp = mesh.shape.get(mesh_lib.AXIS_TP, 1)
    head_axis = (
        mesh_lib.AXIS_TP if tp > 1 and nH % tp == 0 and nKV % tp == 0 else None
    )
    body = functools.partial(
        _ring_body,
        axis_names=axis_names,
        n=n,
        zigzag=zigzag,
        sm_scale=sm_scale,
        interpret=interpret,
    )
    tok = P(axis_names)
    qkv_spec = P(axis_names, head_axis, None)
    return jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(qkv_spec, qkv_spec, qkv_spec, tok),
        out_specs=qkv_spec,
        check_vma=False,
    )(q, k, v, segment_ids.astype(jnp.int32))
