"""Chunked (online-softmax) attention in pure XLA — no Pallas.

The flash-attention trick expressed as a `lax.scan` over KV chunks:
running max / normalizer / weighted accumulator per query, O(T · chunk)
live memory instead of the dense path's O(T²) score matrix. XLA fuses the
per-chunk einsums onto the MXU; no custom lowering, so it runs on any
backend and composes with GSPMD sharding like any jnp program.

Role in the impl lineup (models/qwen2.py::resolve_attn_impl):
- "flash" (Pallas) — fastest on TPU, no sliding-window support;
- "chunked" (this) — long-context path for SLIDING-WINDOW models
  (Mistral-class) and a hardware-independent O(T) fallback;
- "dense" — [T, T] mask, short packs / tiny tests.

Causality, segment isolation and the sliding-window band are applied per
chunk; the backward comes from autodiff through the scan with the chunk
body checkpointed (logits recomputed per chunk, as in ops/fused_xent.py).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

PADDING_SEGMENT = -1


def verify_attention(
    q: jax.Array,  # [R, W, nH, hd] — W query positions per slot
    k_cache: jax.Array,  # [R, S, nKV, hd] per-slot contiguous KV
    v_cache: jax.Array,  # [R, S, nKV, hd]
    valid: jax.Array,  # [R, W, S] bool: rows query position w may attend
    sm_scale: float | None = None,
) -> jax.Array:
    """q_len>1 decode attention over per-slot KV (speculative verify).

    The multi-query twin of the single-token decode attention
    (`ops/paged_attention._paged_attention_xla`): the verify chunk of
    draft-free speculative decoding scores all `W` draft positions of a
    slot in ONE forward, so
    each of the W queries needs its own causal horizon (`valid[r, w, s]`,
    typically `s <= base_position + w`) over the same cache rows.

    Deliberately the op/cast sequence of that single-token attention
    with one extra query axis: the engine's contract is that a verify
    chunk's logits at position j equal the chunked decode loop's logits
    for the same context (to float32's last digits: two programs), and
    the XLA `paged_attention_qlen` gathers the slot's blocks and calls
    THIS function. W is small (spec_k + 1), so the dense [R, W, S] score
    tensor is the same order of memory the single-step path already pays.
    """
    R, W, nH, hd = q.shape
    nKV = k_cache.shape[2]
    group = nH // nKV
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(hd)
    qg = q.reshape(R, W, nKV, group, hd)
    scores = jnp.einsum("rwkgd,rskd->rwkgs", qg, k_cache.astype(q.dtype))
    if scale == 1.0 / math.sqrt(hd):
        # the single-token attention divides by sqrt(hd): the same op, not
        # the mathematically-equal multiply
        scores = (scores / np.sqrt(hd)).astype(jnp.float32)
    else:
        scores = (scores * scale).astype(jnp.float32)
    scores = jnp.where(valid[:, :, None, None, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("rwkgs,rskd->rwkgd", probs, v_cache.astype(q.dtype))
    return out.reshape(R, W, nH, hd)


def chunked_attention(
    q: jax.Array,  # [T, nH, hd]
    k: jax.Array,  # [T, nKV, hd]
    v: jax.Array,  # [T, nKV, hd]
    segment_ids: jax.Array,  # [T]
    sm_scale: float | None = None,
    sliding_window: int | None = None,
    kv_chunk: int = 512,
    q_horizon: jax.Array | None = None,  # [T] last visible index a query
) -> jax.Array:
    """Packed causal-within-segment attention, O(T·kv_chunk) memory.
    `q_horizon`: each query's last visible index of the stream in place of
    its own (a block-causal mask: models/qwen2.block_horizon)."""
    T, nH, hd = q.shape
    nKV = k.shape[1]
    group = nH // nKV
    scale = sm_scale if sm_scale is not None else hd**-0.5

    cs = int(min(kv_chunk, T))
    n_pad = (-T) % cs
    if n_pad:
        k = jnp.pad(k, ((0, n_pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, n_pad), (0, 0), (0, 0)))
        seg_k_full = jnp.pad(
            segment_ids, (0, n_pad), constant_values=PADDING_SEGMENT
        )
    else:
        seg_k_full = segment_ids
    n_chunks = (T + n_pad) // cs

    qg = (q * scale).reshape(T, nKV, group, hd)
    q_idx = jnp.arange(T)
    q_last = q_idx if q_horizon is None else q_horizon

    k_chunks = k.reshape(n_chunks, cs, nKV, hd)
    v_chunks = v.reshape(n_chunks, cs, nKV, v.shape[-1])
    seg_chunks = seg_k_full.reshape(n_chunks, cs)
    off_chunks = jnp.arange(n_chunks, dtype=jnp.int32) * cs

    def body(carry, chunk):
        m, denom, acc = carry
        kc, vc, seg_c, off = chunk
        # [nKV, group, T, cs] scores in f32
        s = jnp.einsum(
            "tkgd,skd->kgts", qg, kc, preferred_element_type=jnp.float32
        )
        k_idx = off + jnp.arange(cs)
        mask = (
            (segment_ids[:, None] == seg_c[None, :])
            & (q_last[:, None] >= k_idx[None, :])
            & (segment_ids[:, None] != PADDING_SEGMENT)
        )
        if sliding_window is not None:
            mask = mask & (q_idx[:, None] - k_idx[None, :] < sliding_window)
        s = jnp.where(mask[None, None], s, -jnp.inf)
        m_new = jnp.maximum(m, s.max(axis=-1))
        # fully-masked rows keep m == -inf; exp(-inf - -inf) would be NaN
        safe_m = jnp.where(jnp.isneginf(m_new), 0.0, m_new)
        p = jnp.exp(s - safe_m[..., None])
        p = jnp.where(mask[None, None], p, 0.0)
        rescale = jnp.where(
            jnp.isneginf(m), 0.0, jnp.exp(m - safe_m)
        )
        denom = denom * rescale + p.sum(axis=-1)
        acc = acc * rescale[..., None] + jnp.einsum(
            "kgts,skd->kgtd", p, vc, preferred_element_type=jnp.float32
        )
        return (m_new, denom, acc), None

    init = (
        jnp.full((nKV, group, T), -jnp.inf, jnp.float32),
        jnp.zeros((nKV, group, T), jnp.float32),
        # (v may be narrower than q and k: a latent model's heads)
        jnp.zeros((nKV, group, T, v.shape[-1]), jnp.float32),
    )
    (m, denom, acc), _ = jax.lax.scan(
        jax.checkpoint(body, prevent_cse=False),
        init,
        (k_chunks, v_chunks, seg_chunks, off_chunks),
    )
    out = acc / jnp.maximum(denom, 1e-30)[..., None]
    # [nKV, group, T, hd] -> [T, nH, hd]
    return out.transpose(2, 0, 1, 3).reshape(T, nH, v.shape[-1]).astype(q.dtype)


def causal_blocked_attention(
    q: jax.Array,  # [T, nH, dq]
    k: jax.Array,  # [T, nH, dq]
    v: jax.Array,  # [T, nH, dv]
    sm_scale: float,
    q_block: int = 512,
    kv_chunk: int = 1024,
) -> jax.Array:
    """Causal attention over ONE sequence with as many key heads as query
    heads, queries AND keys a block at a time: a forward-only long prefill
    at many heads (128 heads x 16,384 queries against one 512-key chunk are
    4.3 GB of float32 scores in `chunked_attention`; a block here is
    `nH * q_block * kv_chunk`). A block of queries walks the key chunks up
    to its own last row and no further (a `fori_loop` of that length), so
    the triangle above the diagonal costs nothing. Rows past the real
    tokens (bucket padding) lie after every real query. Returns
    [T, nH, dv] in q's dtype."""
    T, nH, _ = q.shape
    dv = v.shape[-1]
    qb = int(min(q_block, T))
    kc = int(min(kv_chunk, T))
    pad_q, pad_k = (-T) % qb, (-T) % kc
    if pad_q:
        q = jnp.pad(q, ((0, pad_q), (0, 0), (0, 0)))
    if pad_k:
        k = jnp.pad(k, ((0, pad_k), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, pad_k), (0, 0), (0, 0)))
    n_q = (T + pad_q) // qb

    def q_block_out(_, i):
        q0 = i * qb
        qi = jax.lax.dynamic_slice_in_dim(q, q0, qb, axis=0) * sm_scale
        q_idx = q0 + jnp.arange(qb)

        def key_chunk(j, carry):
            m, denom, acc = carry
            kj = jax.lax.dynamic_slice_in_dim(k, j * kc, kc, axis=0)
            vj = jax.lax.dynamic_slice_in_dim(v, j * kc, kc, axis=0)
            s = jnp.einsum("tnd,snd->nts", qi, kj, preferred_element_type=jnp.float32)
            seen = q_idx[:, None] >= (j * kc + jnp.arange(kc))[None, :]
            s = jnp.where(seen[None], s, -1e30)
            m_new = jnp.maximum(m, s.max(axis=-1))
            p = jnp.where(seen[None], jnp.exp(s - m_new[..., None]), 0.0)
            rescale = jnp.exp(m - m_new)
            denom = denom * rescale + p.sum(axis=-1)
            acc = acc * rescale[..., None] + jnp.einsum(
                "nts,snd->ntd", p.astype(v.dtype), vj,
                preferred_element_type=jnp.float32,
            )
            return m_new, denom, acc

        init = (
            jnp.full((nH, qb), -1e30, jnp.float32),
            jnp.zeros((nH, qb), jnp.float32),
            jnp.zeros((nH, qb, dv), jnp.float32),
        )
        # key chunks 0 .. the one holding this block's last row
        _, denom, acc = jax.lax.fori_loop(
            0, (q0 + qb + kc - 1) // kc, key_chunk, init
        )
        out = acc / jnp.maximum(denom, 1e-30)[..., None]
        return None, out.transpose(1, 0, 2).astype(q.dtype)  # [qb, nH, dv]

    _, out = jax.lax.scan(q_block_out, None, jnp.arange(n_q))
    return out.reshape(n_q * qb, nH, dv)[:T]
