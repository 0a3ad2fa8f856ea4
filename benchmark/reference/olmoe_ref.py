"""Plain float32 reference of the OLMoE decoder (allenai/OLMoE-1B-7B): the
yardstick `correct` is decided against in the `olmoe-1b-7b` cells.

Straightforward `jax.numpy`, one sequence at a time, no kernels, no cache, no
packing, no sorting, no capacity. It follows `transformers`'
`modeling_olmoe.py`, layer by layer:

    h   = x + Attn(RMSNorm_in(x));   y = h + MoE(RMSNorm_post(h))     eps from the config, no biases
    Attn: q = RMSNorm_q(x Wq) over ALL nH*hd outputs; k = RMSNorm_k(x Wk) over all nKV*hd; v = x Wv
          split into heads; rotate-half RoPE; causal softmax(q k^T / sqrt(hd)) v; Wo
    MoE:  p = softmax(x Wr) over all E experts; (w, idx) = top_k(p);
          w renormalised only if `norm_topk_prob` (false as published)
          out = sum_j w_j * Wdown[idx_j]( silu(x Wgate[idx_j]) * (x Wup[idx_j]) )
    logits = RMSNorm_f(y_L) Whead   (untied)

The mixture is computed as a DENSE product over all experts masked by the
top-k: every expert runs on every token, one expert at a time, and a token's
row is weighted by w_j where the expert is among its k and by exactly 0
elsewhere. That is the published sum, term for term, with no dispatch to get
wrong. Departures from `modeling_olmoe.py`: none in the mathematics
(`clip_qkv` is null in the published config and is not implemented);
everything is float32 under `jax.default_matmul_precision("highest")`, because
a TPU otherwise runs a float32 matmul in bf16 passes.

It reads the program's parameter tree (names and axis order of
`areal_tpu/models/qwen2.py:param_shapes`, layers stacked on axis 0, experts on
the next) but none of its code. Layers are visited one at a time and experts
one at a time inside a layer, each picked out of the stacked tree and cast to
float32 as it is used, so no second copy of the weights is ever alive (one
layer's experts are 0.8 GB in bf16 and 1.6 GB in float32, beside a full chip).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

# Tolerances of the comparison, per compared sequence, with their reason
# (benchmark/lib/kind_rollout_moe.py applies them). The program computes in
# bf16 through 8 layers, the reference in float32. Measured on the v5e at the
# published widths (PERF.md, Findings PR 26): over 63 sequences (60 requests
# of the cell at nine seeds, prefill then the paged cache; two packed
# forwards; one request through a small engine) the mean |delta logprob| of a
# sequence was 0.019-0.034 and the largest single delta 0.063-0.191. So the
# mean bound has a factor of 1.8 and the largest a factor of 2.6. A near-tie
# at the k-th expert can fall differently in bf16 and float32: that token
# swaps its last expert (a weight of about 0.03) for the next in rank. Through
# 8 layers this stays inside these figures (the largest deltas above ARE such
# tokens); at depth 1, where one layer's experts are most of the hidden state,
# the same flip moved a token by up to 1.3 nat (tools/olmoe_chip_check.py,
# which therefore holds the largest delta only where `with_margins` says the
# routing is not a near-tie). What the bounds fail: an int8 KV pool (mean
# 0.85, largest 3.0 at the published widths), the weights rounded to float8's
# 3 mantissa bits (PERF.md), and at a tiny width (tests/test_olmoe.py) a
# dropped token-expert pair, a renormalised top-k and a per-head instead of
# full-width q/k norm. What they do NOT fail: a router softmax in bf16, which
# measured 0.0219 and 0.160 at the published widths against 0.0213 and 0.138
# without it; float32 router arithmetic is held by the CPU test, where program
# and reference agree to 1e-5.
MEAN_ABS_TOL = 0.06
MAX_ABS_TOL = 0.5


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


def _f32(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


def _attention(a, h, eps, theta):
    """h: [T, H] float32, already normed. `a`: the layer's attention leaves."""
    a = _f32(a)
    T = h.shape[0]
    pos = jnp.arange(T)
    n_q, hd = a["q_kernel"].shape[-2:]
    n_kv = a["k_kernel"].shape[-2]
    # the projections as [T, n*hd] vectors: the q/k norm is over ALL of them
    q = h @ a["q_kernel"].reshape(h.shape[1], n_q * hd)
    k = h @ a["k_kernel"].reshape(h.shape[1], n_kv * hd)
    v = h @ a["v_kernel"].reshape(h.shape[1], n_kv * hd)
    q = _rms_norm(q, a["q_norm"], eps).reshape(T, n_q, hd)
    k = _rms_norm(k, a["k_norm"], eps).reshape(T, n_kv, hd)
    v = v.reshape(T, n_kv, hd)
    q, k = _rope(q, pos, theta), _rope(k, pos, theta)
    rep = n_q // n_kv  # query head i reads kv head i // rep (1 as published)
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    s = jnp.einsum("tnd,snd->nts", q, k) / np.sqrt(hd)
    s = jnp.where((pos[:, None] >= pos[None, :])[None], s, -jnp.inf)
    o = jnp.einsum("nts,snd->tnd", jax.nn.softmax(s, axis=-1), v)
    return jnp.einsum("tnd,ndh->th", o, a["o_kernel"])


def _moe(m, i, h, top_k: int, norm_topk: bool):
    """h: [T, H] float32, already normed. `m`: the MLP leaves of ALL layers,
    `i` this layer's index: one expert's kernels are picked out at a time.
    Dense over all experts, masked by the top-k (see the module's docstring).
    Returns (out [T, H], margin [T]): the relative gap between the k-th and
    the (k+1)-th router probability, which says whose routing is a near-tie
    that bf16 and float32 can settle differently."""
    p = jax.nn.softmax(h @ m["router_kernel"][i].astype(jnp.float32), axis=-1)  # [T, E]
    ranked, idx = jax.lax.top_k(p, top_k + 1)
    w, idx = ranked[:, :top_k], idx[:, :top_k]
    margin = (ranked[:, top_k - 1] - ranked[:, top_k]) / ranked[:, top_k - 1]
    if norm_topk:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    n_experts = p.shape[-1]
    # [T, E]: w_j at the token's chosen experts, exactly 0 elsewhere
    dense_w = jnp.sum(jax.nn.one_hot(idx, n_experts, dtype=jnp.float32) * w[..., None], axis=1)

    def one_expert(acc, e):
        gate, up, down = (m[k][i, e].astype(jnp.float32)
                          for k in ("gate_kernel", "up_kernel", "down_kernel"))
        y = (jax.nn.silu(h @ gate) * (h @ up)) @ down
        return acc + dense_w[:, e, None] * y, None

    out, _ = jax.lax.scan(one_expert, jnp.zeros_like(h), jnp.arange(n_experts))
    return out, margin


@functools.partial(jax.jit, static_argnames=("eps", "theta", "top_k", "norm_topk"))
def _layer(layers, i, x, *, eps: float, theta: float, top_k: int, norm_topk: bool):
    """Decoder layer `i` on one sequence. x: [T, H] float32. `layers` is the
    whole stacked tree: only what is used is ever cast to float32."""
    small = {k: v for k, v in layers.items() if k != "mlp"}
    lp = jax.tree.map(lambda a: a[i], small)
    h = _rms_norm(x, lp["input_norm"].astype(jnp.float32), eps)
    x = x + _attention(lp["attn"], h, eps, theta)
    h = _rms_norm(x, lp["post_attn_norm"].astype(jnp.float32), eps)
    y, margin = _moe(layers["mlp"], i, h, top_k, norm_topk)
    return x + y, margin


@functools.partial(jax.jit, static_argnames=("eps",))
def _head_logprobs(final_norm, head, x, labels, temperature, *, eps: float):
    """log softmax(logits / temperature)[label] per position. head: [H, V]."""
    x = _rms_norm(x, final_norm.astype(jnp.float32), eps)
    logits = (x @ head.astype(jnp.float32)) / temperature
    logz = jax.nn.logsumexp(logits, axis=-1)
    return jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0] - logz


def _logprobs(params, model_config, ids, temperature):
    """(log p(ids[t+1] | ids[:t+1]) for every t: float32 [len(ids) - 1], and
    per position the smallest router margin of any layer: [len(ids)])."""
    statics = dict(eps=float(model_config.rms_norm_eps), theta=float(model_config.rope_theta),
                   top_k=int(model_config.num_experts_per_tok),
                   norm_topk=bool(model_config.norm_topk_prob))
    x = jnp.take(params["embed"]["embedding"], ids, axis=0).astype(jnp.float32)
    n_layers = jax.tree.leaves(params["layers"])[0].shape[0]
    margin = jnp.full(ids.shape, jnp.inf, jnp.float32)
    for i in range(n_layers):
        x, m = _layer(params["layers"], i, x, **statics)
        margin = jnp.minimum(margin, m)
    lp = _head_logprobs(params["final_norm"], params["lm_head"]["kernel"], x[:-1], ids[1:],
                        jnp.float32(temperature), eps=statics["eps"])
    return lp, margin


def token_logprobs(
    params, model_config, token_ids, temperature: float = 1.0, pad_to: int = 0,
    with_margins: bool = False,
):
    """log p(token[t+1] | token[:t+1]) for t in [0, T-1): float32 [T-1].
    With `with_margins` also, for the position that predicts each of them,
    the smallest relative gap between its k-th and (k+1)-th expert in any
    layer (`_moe`): float32 [T-1].

    `params` is the program's tree (any dtype, any placement); `token_ids`
    one sequence of length T. `pad_to` right-pads the sequence so that
    sequences of several lengths share one compiled shape; attention is
    causal and a token's experts depend on its own row alone, so the padding
    cannot reach the positions that are returned."""
    n = len(token_ids)
    ids = np.zeros(max(n, pad_to), dtype=np.int32)
    ids[:n] = np.asarray(token_ids, dtype=np.int32)
    with jax.default_matmul_precision("highest"):
        lp, margin = _logprobs(params, model_config, jnp.asarray(ids), temperature)
    if with_margins:
        return np.asarray(lp)[: n - 1], np.asarray(margin)[: n - 1]
    return np.asarray(lp)[: n - 1]


def loss_and_grads(params, model_config, token_ids, temperature: float = 1.0):
    """Mean negative log-likelihood of one sequence's next tokens and its
    gradient with respect to every leaf of `params` (for the CPU tests: the
    trainer's loss and gradients are compared with these)."""
    ids = jnp.asarray(np.asarray(token_ids, dtype=np.int32))

    def nll(p):
        return -jnp.mean(_logprobs(p, model_config, ids, temperature)[0])

    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(nll)(params)
