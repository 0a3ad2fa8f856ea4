"""Plain float32 reference of the Qwen2 decoder: the yardstick `correct` is
decided against.

Straightforward `jax.numpy`, one sequence at a time, no kernels, no cache, no
packing: token embedding, then per layer RMSNorm -> q/k/v projections WITH
bias -> rotary embedding (HF rotate-half) -> grouped-query causal softmax
attention -> output projection -> residual -> RMSNorm -> SwiGLU -> residual,
then the final RMSNorm and the (tied) head. It follows the published model
(Qwen2 technical report; `transformers` `modeling_qwen2.py`). Departures:
none in the mathematics; everything is float32 with
`jax.default_matmul_precision("highest")`, because a TPU otherwise runs a
float32 matmul in bf16 passes.

It reads the program's parameter tree (names and axis order of
`areal_tpu/models/qwen2.py:param_shapes`, layers stacked on axis 0) but none
of its code. Layers are visited one at a time and cast to float32 as they
are used, so no second copy of the weights is ever alive.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

# Tolerances of the comparison, with their reason
# (benchmark/lib/harness.py:compare_with_reference applies them, to each
# compared sequence). The program computes in bf16 (8 bits of mantissa)
# through 24-28 layers; the reference in float32. On the v5e at the published
# widths the mean |delta logprob| of a sequence measured 0.019-0.028 and the
# largest single delta 0.105 (PERF.md, Findings PR 23), so the mean bound has
# a factor of two and the largest a factor of three. A wrong causal mask, a
# dropped q/k/v bias or a wrong rotary convention moves the mean by more than
# 1 nat with the seeded weights, and an int8 KV pool moves the largest delta
# to 1.6-3.9 and the mean to 0.06-0.34 (tests/benchmark/test_bench_reference.py
# shows each at a tiny width).
MEAN_ABS_TOL = 0.06
MAX_ABS_TOL = 0.3


def _rms_norm(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w


def _rope(x, positions, theta):
    """x: [T, n, hd]; pairs (i, i + hd/2) rotate by position * theta^(-2i/hd)."""
    hd = x.shape[-1]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


@functools.partial(jax.jit, static_argnames=("eps", "theta"))
def _layer(lp, x, *, eps: float, theta: float):
    """One decoder layer on one sequence. x: [T, H] float32."""
    lp = jax.tree.map(lambda a: a.astype(jnp.float32), lp)
    T = x.shape[0]
    pos = jnp.arange(T)
    a = lp["attn"]
    h = _rms_norm(x, lp["input_norm"], eps)
    q = jnp.einsum("th,hnd->tnd", h, a["q_kernel"])
    k = jnp.einsum("th,hnd->tnd", h, a["k_kernel"])
    v = jnp.einsum("th,hnd->tnd", h, a["v_kernel"])
    if "q_bias" in a:
        q, k, v = q + a["q_bias"], k + a["k_bias"], v + a["v_bias"]
    q, k = _rope(q, pos, theta), _rope(k, pos, theta)
    n_q, n_kv, hd = q.shape[1], k.shape[1], q.shape[2]
    rep = n_q // n_kv  # query head i reads kv head i // rep
    k = jnp.repeat(k, rep, axis=1)
    v = jnp.repeat(v, rep, axis=1)
    s = jnp.einsum("tnd,snd->nts", q, k) / np.sqrt(hd)
    causal = pos[:, None] >= pos[None, :]
    s = jnp.where(causal[None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("nts,snd->tnd", p, v)
    x = x + jnp.einsum("tnd,ndh->th", o, a["o_kernel"])
    m = lp["mlp"]
    h = _rms_norm(x, lp["post_attn_norm"], eps)
    g = jax.nn.silu(h @ m["gate_kernel"]) * (h @ m["up_kernel"])
    return x + g @ m["down_kernel"]


@functools.partial(jax.jit, static_argnames=("eps",))
def _head_logprobs(final_norm, head, x, labels, temperature, *, eps: float):
    """log softmax(logits / temperature)[label] per position. head: [V, H]."""
    x = _rms_norm(x, final_norm.astype(jnp.float32), eps)
    logits = (x @ head.astype(jnp.float32).T) / temperature
    logz = jax.nn.logsumexp(logits, axis=-1)
    return jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0] - logz


def token_logprobs(
    params, model_config, token_ids, temperature: float = 1.0, pad_to: int = 0
):
    """log p(token[t+1] | token[:t+1]) for t in [0, T-1): float32 [T-1].

    `params` is the program's tree (any dtype, any placement); `token_ids`
    one sequence of length T. `pad_to` right-pads the sequence so that
    sequences of several lengths share one compiled shape; attention is
    causal, so the padding cannot reach the positions that are returned."""
    n = len(token_ids)
    ids = np.zeros(max(n, pad_to), dtype=np.int32)
    ids[:n] = np.asarray(token_ids, dtype=np.int32)
    ids = jnp.asarray(ids)
    eps, theta = float(model_config.rms_norm_eps), float(model_config.rope_theta)
    embed = params["embed"]["embedding"]
    with jax.default_matmul_precision("highest"):
        x = jnp.take(embed, ids, axis=0).astype(jnp.float32)
        n_layers = jax.tree.leaves(params["layers"])[0].shape[0]
        for i in range(n_layers):
            lp = jax.tree.map(lambda a: a[i], params["layers"])
            x = _layer(lp, x, eps=eps, theta=theta)
        if model_config.tie_word_embeddings:
            head = embed
        else:
            head = params["lm_head"]["kernel"].T
        lp = _head_logprobs(
            params["final_norm"], head, x[:-1], ids[1:],
            jnp.float32(temperature), eps=eps,
        )
    return np.asarray(lp)[: n - 1]
