"""Plain float32 reference of the K-EXAONE decoder (`exaone_moe`,
LGAI-EXAONE/K-EXAONE-236B-A23B): the yardstick `correct` is decided against
in the `k-exaone-236b-a23b` cells.

Straightforward `jax.numpy`, one sequence at a time, no kernels, no cache, no
packing, no sorting, no scan over layers. Layer l of the stack:

    h = x + Attn_l(RMSNorm(x));   y = h + MLP_l(RMSNorm(h))        eps from the config, no biases
    Attn_l: q = x Wq (nH heads of hd), k, v = x Wk, x Wv (nKV heads); RMSNorm over each head's hd
            lanes of q and of k; if layer_types[l] is "sliding_attention": rotate-half RoPE on q and
            k, and query t sees key s iff 0 <= t - s < sliding_window; if "full_attention": no rotary
            embedding at all, causal; softmax(q k^T / sqrt(hd)) v in float32; Wo
    MLP_l, l < first_k_dense:  Wdown(silu(x Wgate) * (x Wup)) at intermediate_size
    MLP_l otherwise:  s = sigmoid(x Wr) over ALL published experts; S = top_k(s + b);
            w_i = routed_scaling_factor * s_i / (sum_{j in S} s_j + 1e-20)     (norm_topk_prob)
            out = sum_{i in S, i held here} w_i E_i(x) + E_shared(x),  E a SwiGLU of moe_intermediate_size
    logits = RMSNorm_f(y_L) Whead   (untied; over the rows of the vocabulary held here)

Departures from the published description, each at its line below:
- pre-norm placement, per-head q/k norm, NoPE on the full layers and the
  selection bias `b` are the EXAONE 4.0 / DeepSeek-V3 family's, taken from the
  model card as ISSUE 30's author knew it (no network here): `assumed` in
  benchmark/configs/k-exaone-236b-a23b.json lists them.
- the multi-token prediction layer takes no part in these logits and is absent.
- `held = (first, count)`: the experts this chip holds of the published
  `num_experts_published`. Routing is over all of them; only the held experts'
  terms are summed, the shared expert once, and nothing stands in for the
  rest (the model-configs guide, section 4). With every expert held it is the
  whole layer. The vocabulary slice is whatever rows the embedding and head
  have: ids, logits and the log-softmax are over the slice.

Attention is computed a block of queries and a group of heads at a time
(`Q_BLOCK` queries of the heads that share one kv head), so 8,192 tokens at
64 heads never build more than a [8, 512, T] score block. Experts are visited
one at a time, each cast to float32 as it is used.

It reads the program's parameter tree (names and axis order of
`areal_tpu/models/qwen2.py:param_shapes`, layers unstacked as `layers_{i}`)
but none of its code.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

# Tolerances of the comparison, per compared sequence (kind_rollout_hybrid.py
# applies them), each from two readings on the v5e at the published widths
# (PERF.md, Findings PR 30): the largest the program gave over its seeds, and
# the reference itself one precision lower (the weights rounded to float8's 3
# mantissa bits), which has to fail. The program computes in bf16 through 5
# layers, the reference in float32.
#
# A near-tie at the k-th expert falls differently in bf16 and float32: the
# token swaps its last expert for the next in rank. Here a chosen expert
# weighs about 2.5/8 = 0.31 (sigmoid scores near each other, normalised and
# scaled; OLMoE's eighth weighs 0.03), and where one of the swapped pair is
# held on this chip its whole term appears or vanishes. Such a token moves by
# up to 3 nat, and drags the tokens that attend to it inside a window of 128:
# through the engine and through the trainer's plain bf16 `forward` alike, so
# it is the precision and not a path. A bound on the LARGEST delta therefore
# cannot stand between the two readings (program 0.35-3.14 a sequence over 108
# requests, float8 3.21-3.75; over the tokens the reference marks as no
# near-tie, `with_margins`, still up to 1.78 against 2.61-3.75). What such
# tokens leave alone is the body of the distribution:
# - MEAN_ABS_TOL: the program read 0.037-0.088 (the engine, 108 requests of
#   eighteen runs) and 0.058-0.062 (`forward`, 4,096 tokens, two seeds),
#   float8 0.691 and 0.694.
# - P90_ABS_TOL, the 90th percentile of a sequence's |delta|: the program
#   read 0.090-0.098 (`forward`; PERF.md has the engine's), float8 1.42 and
#   1.44. Nine tokens in ten sit under it whatever the flips do, at 32 tokens
#   as at 2,048.
# What the bounds fail besides float8: at a tiny width (tests/benchmark/
# test_bench_kexaone.py) rotary embedding on the full layers, a dropped
# scaling factor, a window on every layer.
MEAN_ABS_TOL = 0.2
P90_ABS_TOL = 0.5
# a margin under which the reference counts a token's routing a near-tie
# (reported with every comparison, decides nothing)
NEAR_TIE_MARGIN = 0.05

Q_BLOCK = 512


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


def _attention(a, h, eps, theta, window):
    """h: [T, H] float32, already normed. `window` None: a full layer."""
    a = _f32(a)
    T = h.shape[0]
    pos = jnp.arange(T)
    q = jnp.einsum("th,hnd->tnd", h, a["q_kernel"])
    k = jnp.einsum("th,hnd->tnd", h, a["k_kernel"])
    v = jnp.einsum("th,hnd->tnd", h, a["v_kernel"])
    # [family] RMSNorm over each head's lanes of q and k (EXAONE 4.0's QK norm)
    q, k = _rms_norm(q, a["q_norm"], eps), _rms_norm(k, a["k_norm"], eps)
    if window is not None:
        q, k = _rope(q, pos, theta), _rope(k, pos, theta)
    # else: [family] a full layer takes no rotary embedding at all (NoPE)
    n_q, hd = q.shape[1:]
    n_kv = k.shape[1]
    rep = n_q // n_kv  # query heads g*rep .. g*rep + rep - 1 read kv head g
    qb = min(Q_BLOCK, T)
    pad = (-T) % qb
    qp = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(-1, qb, n_kv, rep, hd)
    starts = jnp.arange(qp.shape[0]) * qb

    def block(_, inp):
        qblk, t0 = inp  # [qb, n_kv, rep, hd]
        t = t0 + jnp.arange(qb)
        seen = t[:, None] >= pos[None, :]
        if window is not None:
            seen = seen & (t[:, None] - pos[None, :] < window)

        def group(_, g):  # the rep query heads of kv head g
            s = jnp.einsum("trd,sd->rts", qblk[:, g], k[:, g]) / np.sqrt(hd)
            s = jnp.where(seen[None], s, -jnp.inf)
            return None, jnp.einsum("rts,sd->trd", jax.nn.softmax(s, axis=-1), v[:, g])

        _, o = jax.lax.scan(group, None, jnp.arange(n_kv))  # [n_kv, qb, rep, hd]
        return None, o.transpose(1, 0, 2, 3)

    _, o = jax.lax.scan(block, None, (qp, starts))
    o = o.reshape(-1, n_q, hd)[:T]
    return jnp.einsum("tnd,ndh->th", o, a["o_kernel"])


def _swiglu(h, gate, up, down):
    return (jax.nn.silu(h @ gate) * (h @ up)) @ down


def _moe(m, h, top_k: int, norm_topk: bool, scaling: float, first: int):
    """h: [T, H] float32, already normed. `m`: one layer's MLP leaves, whose
    stacked kernels hold experts first .. first + count - 1 of the router's
    width. Dense over the held experts, masked by the top-k. Returns
    (out [T, H], margin [T]): the relative gap between the k-th and the
    (k+1)-th score the choice is made on where one of the two is held here
    (infinite otherwise), which says whose routing is a near-tie that bf16
    and float32 can settle differently."""
    s = jax.nn.sigmoid(h @ m["router_kernel"].astype(jnp.float32))  # [T, E_published]
    # [family] DeepSeek-V3's e_score_correction_bias: it enters the choice,
    # not the weight; n_group = topk_group = 1 makes the grouping the identity
    ranked, idx = jax.lax.top_k(s + m["router_bias"].astype(jnp.float32), top_k + 1)
    margin = (ranked[:, top_k - 1] - ranked[:, top_k]) / ranked[:, top_k - 1]
    # a swap of the k-th for the (k+1)-th moves this chip's part only if one
    # of the two is held here
    count = m["gate_kernel"].shape[0]
    here = (idx[:, top_k - 1:] >= first) & (idx[:, top_k - 1:] < first + count)
    margin = jnp.where(jnp.any(here, axis=-1), margin, jnp.inf)
    idx = idx[:, :top_k]
    w = jnp.take_along_axis(s, idx, axis=-1)
    if norm_topk:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    w = scaling * w
    n_pub = s.shape[-1]
    # [T, E_published]: w_i at the token's chosen experts, exactly 0 elsewhere
    dense_w = jnp.sum(jax.nn.one_hot(idx, n_pub, dtype=jnp.float32) * w[..., None], axis=1)

    def one_expert(acc, e):
        y = _swiglu(h, *(m[k][e].astype(jnp.float32)
                         for k in ("gate_kernel", "up_kernel", "down_kernel")))
        return acc + dense_w[:, first + e, None] * y, None

    out, _ = jax.lax.scan(one_expert, jnp.zeros_like(h), jnp.arange(m["gate_kernel"].shape[0]))
    # the shared expert, ungated, once
    shared = _swiglu(h, *(m[k].astype(jnp.float32) for k in
                          ("shared_gate_kernel", "shared_up_kernel", "shared_down_kernel")))
    return out + shared, margin


@functools.partial(jax.jit, static_argnames=(
    "eps", "theta", "window", "sparse", "top_k", "norm_topk", "scaling", "first"))
def _layer(lp, x, *, eps, theta, window, sparse, top_k, norm_topk, scaling, first):
    """One decoder layer on one sequence. x: [T, H] float32. Returns
    (x, the router's margin per token: infinite in a dense layer)."""
    # [family] pre-norm placement
    h = _rms_norm(x, lp["input_norm"].astype(jnp.float32), eps)
    x = x + _attention(lp["attn"], h, eps, theta, window)
    h = _rms_norm(x, lp["post_attn_norm"].astype(jnp.float32), eps)
    if sparse:
        y, margin = _moe(lp["mlp"], h, top_k, norm_topk, scaling, first)
        return x + y, margin
    y = _swiglu(h, *(lp["mlp"][k].astype(jnp.float32)
                     for k in ("gate_kernel", "up_kernel", "down_kernel")))
    return x + y, jnp.full(x.shape[:1], jnp.inf, jnp.float32)


@functools.partial(jax.jit, static_argnames=("eps",))
def _head_logprobs(final_norm, head, x, labels, temperature, *, eps: float):
    """log softmax(logits / temperature)[label] per position. head: [H, V]."""
    x = _rms_norm(x, final_norm.astype(jnp.float32), eps)
    logits = (x @ head.astype(jnp.float32)) / temperature
    logz = jax.nn.logsumexp(logits, axis=-1)
    return jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0] - logz


def layer_statics(model_config, i: int, held: tuple[int, int] | None = None) -> dict:
    """The numbers of layer i, read from the configuration (and nothing of
    the program's code): window, whether it is sparse, the router's rule."""
    first = model_config.expert_first if held is None else held[0]
    return dict(
        eps=float(model_config.rms_norm_eps), theta=float(model_config.rope_theta),
        window=(int(model_config.sliding_window)
                if model_config.layer_types[i] == "sliding_attention" else None),
        sparse=i >= model_config.first_k_dense,
        top_k=int(model_config.num_experts_per_tok),
        norm_topk=bool(model_config.norm_topk_prob),
        scaling=float(model_config.routed_scaling_factor), first=int(first))


def hidden_states(params, model_config, ids, held=None):
    """(x after the last layer: [T, H] float32, and per position the
    smallest router margin of any layer: [T])."""
    x = jnp.take(params["embed"]["embedding"], ids, axis=0).astype(jnp.float32)
    margin = jnp.full(ids.shape, jnp.inf, jnp.float32)
    for i in range(model_config.num_hidden_layers):
        x, m = _layer(params[f"layers_{i}"], x, **layer_statics(model_config, i, held))
        margin = jnp.minimum(margin, m)
    return x, margin


def _logprobs(params, model_config, ids, temperature, held=None):
    x, margin = hidden_states(params, model_config, ids, held)
    lp = _head_logprobs(params["final_norm"], params["lm_head"]["kernel"], x[:-1], ids[1:],
                        jnp.float32(temperature), eps=float(model_config.rms_norm_eps))
    return lp, margin


def token_logprobs(params, model_config, token_ids, temperature: float = 1.0,
                   pad_to: int = 0, held: tuple[int, int] | None = None,
                   with_margins: bool = False):
    """log p(token[t+1] | token[:t+1]) for t in [0, T-1): float32 [T-1].
    With `with_margins` also, for the position that predicts each of them,
    the smallest relative gap between its k-th and (k+1)-th expert's score
    in any sparse layer (`_moe`): float32 [T-1].

    `params` is the program's tree (any dtype, any placement); `token_ids`
    one sequence of length T. `held` = (first, count) overrides the
    configuration's held range (the tree's expert kernels must then hold
    `count` experts). `pad_to` right-pads the sequence so that sequences of
    several lengths share one compiled shape; attention is causal and a
    token's experts depend on its own row alone, so the padding cannot reach
    the positions that are returned."""
    n = len(token_ids)
    ids = np.zeros(max(n, pad_to), dtype=np.int32)
    ids[:n] = np.asarray(token_ids, dtype=np.int32)
    with jax.default_matmul_precision("highest"):
        lp, margin = _logprobs(params, model_config, jnp.asarray(ids), temperature, held)
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
