"""Fused LM-head + cross-entropy: label logprobs without [T, V] logits.

The reference computes full-vocab logits and feeds them to
`gather_logprobs[_entropy]` (areal/utils/functional.py:43,:84) — fine on
GPU where the trainer shards the vocab dim (Megatron vocab-parallel xent),
but on a single TPU chip the f32 [tokens, vocab] tensor and its gradient
are what cap the micro-batch size: at 4096 tokens x 151936 vocab they are
2.5 GiB each, and the measured HBM ceiling (bf16 0.5B + AdamW) sits right
at mb=4096 — mb=8192 and remat-off both OOM.

TPU-first replacement: an online-logsumexp scan over VOCAB CHUNKS (the
same trick flash attention applies over keys). Each chunk materializes
only [T, chunk] logits, immediately folds them into running (max, sumexp,
label-logit, entropy-numerator) carries, and `jax.checkpoint` on the chunk
body makes autodiff recompute the chunk's logits in the backward — so the
peak logits footprint is [T, chunk] in both passes and the gradient w.r.t.
the head weight accumulates chunk by chunk. The lm_head matmul itself
stays MXU-shaped ([T, H] @ [H, chunk]).

Exact math (not an approximation): results match the dense
gather_logprobs/gather_logprobs_entropy to float32 roundoff; the chunk
matmuls force f32 accumulation (`preferred_element_type`), which on bf16
weights is slightly MORE accurate than the dense path's bf16 einsum.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp


@jax.named_scope("xent")
def chunked_label_logprobs(
    hidden: jax.Array,
    head_w: jax.Array,
    labels: jax.Array,
    *,
    head_is_vh: bool = False,
    temperature: float = 1.0,
    with_entropy: bool = False,
    vocab_chunk: int = 16384,
):
    """log p(labels) (f32 [T]) — and entropy [T] when `with_entropy` —
    from post-final-norm hidden states and the LM head weight.

    hidden: [T, H]; head_w: [H, V] (untied lm_head) or [V, H] with
    `head_is_vh=True` (tied embedding table — avoids transposing it);
    labels: int [T]. `temperature` divides logits before the softmax,
    matching gather_logprobs' convention.

    Label-range contract: labels outside [0, V) fall in no vocab chunk,
    so their picked-logit term is 0 and the returned logp degrades to
    -logsumexp. This mirrors the dense path's take_along_axis clamp —
    out-of-range labels are the CALLER's bug (padding rows must be masked
    by loss_mask, not given sentinel label ids) and are deliberately not
    asserted here, since a device-side check would sync every step.
    """
    T = hidden.shape[0]
    V = head_w.shape[0] if head_is_vh else head_w.shape[1]
    cs = int(min(vocab_chunk, V))
    n_full = V // cs
    rem = V - n_full * cs
    inv_t = jnp.float32(1.0 / max(temperature, 1e-6))
    labels = labels.astype(jnp.int32)

    def chunk_logits(offset, width):
        if head_is_vh:
            w_c = jax.lax.dynamic_slice(
                head_w, (offset, 0), (width, head_w.shape[1])
            )
            lg = jnp.einsum(
                "th,vh->tv", hidden, w_c,
                preferred_element_type=jnp.float32,
            )
        else:
            w_c = jax.lax.dynamic_slice(
                head_w, (0, offset), (head_w.shape[0], width)
            )
            lg = jnp.einsum(
                "th,hv->tv", hidden, w_c,
                preferred_element_type=jnp.float32,
            )
        return lg * inv_t

    def fold(carry, offset, width):
        m, s, e, lab = carry
        logits = chunk_logits(offset, width)  # [T, width] f32
        m_new = jnp.maximum(m, logits.max(axis=-1))
        scale = jnp.exp(m - m_new)
        p = jnp.exp(logits - m_new[:, None])
        s = s * scale + p.sum(axis=-1)
        if with_entropy:
            e = e * scale + (p * logits).sum(axis=-1)
        idx = labels - offset
        ok = (idx >= 0) & (idx < width)
        picked = jnp.take_along_axis(
            logits, jnp.clip(idx, 0, width - 1)[:, None], axis=-1
        )[:, 0]
        lab = lab + jnp.where(ok, picked, 0.0)
        return (m_new, s, e, lab)

    init = (
        jnp.full((T,), -jnp.inf, jnp.float32),
        jnp.zeros((T,), jnp.float32),
        jnp.zeros((T,), jnp.float32),
        jnp.zeros((T,), jnp.float32),
    )

    if n_full:
        body = jax.checkpoint(
            lambda carry, off: (fold(carry, off, cs), None),
            prevent_cse=False,
        )
        carry, _ = jax.lax.scan(
            body, init, jnp.arange(n_full, dtype=jnp.int32) * cs
        )
    else:
        carry = init
    if rem:
        rem_body = jax.checkpoint(
            partial(fold, width=rem), prevent_cse=False, static_argnums=()
        )
        carry = rem_body(carry, jnp.int32(n_full * cs))

    m, s, e, lab = carry
    lse = m + jnp.log(s)
    logp = lab - lse
    if with_entropy:
        entropy = lse - e / s
        return logp, entropy
    return logp


@jax.named_scope("xent")
def chunked_clamped_entropy(
    hidden: jax.Array,
    head_w: jax.Array,
    *,
    head_is_vh: bool = False,
    entropy_clamp: float = 0.2,
    temperature: float = 1.0,
    token_chunk: int = 128,
):
    """Clamped softmax entropy (AEnt) for the fused-head engine mode.

    The clamp threshold is a global order statistic over the vocab, so it
    cannot fold into chunked_label_logprobs' online vocab scan. Instead:
    iterate over TOKEN chunks, materialize each chunk's [token_chunk, V]
    logits (78 MB f32 at 128x151936 — bounded, vs 2.5 GiB for the full
    [T, V]), and run the dense clamped entropy on it.  `jax.checkpoint`
    on the chunk body makes the backward recompute the chunk logits, so
    peak memory stays one chunk in both passes.

    Exact — matches clamped_softmax_entropy(dense logits) to f32 roundoff.
    """
    from areal_tpu.utils.functional import clamped_softmax_entropy

    T, H = hidden.shape
    pad = (-T) % token_chunk
    h = jnp.pad(hidden, ((0, pad), (0, 0))) if pad else hidden
    hc = h.reshape(-1, token_chunk, H)

    @jax.checkpoint
    def one(h_chunk):
        if head_is_vh:
            logits = jnp.einsum(
                "th,vh->tv", h_chunk, head_w,
                preferred_element_type=jnp.float32,
            )
        else:
            logits = jnp.einsum(
                "th,hv->tv", h_chunk, head_w,
                preferred_element_type=jnp.float32,
            )
        return clamped_softmax_entropy(logits, entropy_clamp, temperature)

    ent = jax.lax.map(one, hc).reshape(-1)
    return ent[:T] if pad else ent
