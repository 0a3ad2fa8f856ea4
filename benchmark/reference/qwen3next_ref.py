"""Plain float32 reference of the Qwen3-Next decoder (`qwen3_next`,
Qwen/Qwen3-Next-80B-A3B-Instruct): the yardstick `correct` is decided against
in the `qwen3-next-80b-a3b` cell.

Straightforward `jax.numpy`, one sequence at a time, no kernels, no cache, no
packing, no sorting, no chunks. Layer l of the stack (pre-norm):

    h = x + Mixer_l(N(x));   y = h + MoE(N(h))
    N: RMSNorm, eps from the config, float32, scale as the tree holds it (the
       effective scale 1 + w of a zero-centred checkpoint weight w)
    Mixer_l, layer_types[l] == "full_attention" (gated attention):
        q_proj gives each head 2 hd lanes: the first hd are q, the last hd the gate gq;
        k, v = x Wk, x Wv; per-head RMSNorm of q and of k; rotate-half RoPE on lanes
        [0, partial_rotary_factor * hd) of q and k, the rest pass through;
        causal softmax(q k^T / sqrt(hd)) v;  out = Wo(concat_heads(attn) * sigmoid(gq))
    Mixer_l, "linear_attention" (Gated DeltaNet): [q | k | v | z] = x W_qkvz (Hk heads of dk for
        q and k, Hv heads of dv for v and z), [b | a] = x W_ba (Hv each);
        u = [q | k | v];  u_t <- silu(sum_j c[:, j] u_{t-(K-1)+j}) (depthwise, causal, zeros
        before the start, no bias);  beta = sigmoid(b);  g = -exp(A_log) softplus(a + dt_bias);
        q, k L2-normalised over dk (eps 1e-6), q scaled by dk^-0.5, both repeated Hv/Hk times
        (repeat_interleave);  per value head, S in R^{dk x dv} from zero, TOKEN BY TOKEN:
            S <- exp(g_t) S;  d = beta_t (v_t - S^T k_t);  S <- S + k_t d^T;  o_t = S^T q_t
        o = w_n * rmsnorm(o) * silu(z) per head (eps from the config);  out = W_out(concat_heads(o))
    MoE: p = softmax(x W_r) over ALL published experts; S = top_k(p); w_i = p_i / sum_{j in S} p_j
        (norm_topk_prob); out = sum_{i in S, i held here} w_i E_i(x) + sigmoid(x . w_s) E_shared(x),
        E a SwiGLU of moe_intermediate_size, E_shared one of shared_expert_intermediate_size
    logits = N_f(y_L) W_head   (untied; over the rows of the vocabulary held here)

Departures from the published module, each at its line below:
- the multi-token prediction layer the model card names takes no part in
  these logits and is absent.
- norm scales are read as the tree holds them (effective); `hf_io` adds the 1.
- `W_qkvz` / `W_ba` are read in the tree's column order ([q | k | v | z],
  [b | a], heads in order); a checkpoint groups them by key head and `hf_io`
  permutes (`assumed` in benchmark/configs/qwen3-next-80b-a3b.json).
- `held = (first, count)`: the experts this chip holds of the published
  `num_experts_published`. Routing is over all of them; only the held experts'
  terms are summed, the shared expert once, and nothing stands in for the
  rest (the model-configs guide, section 4). The vocabulary slice is whatever
  rows the embedding and head have.

The delta rule runs as its recurrence, a `lax.scan` over tokens (the program
runs it in chunks of 64 with a triangular solve, and as a kernel a token in
decode: neither is used here). Attention is computed a block of queries and a
group of heads at a time, so 8,192 tokens never build more than a
[8, 512, T] score block. Experts are visited one at a time.

It reads the program's parameter tree (names and axis order of
`areal_tpu/models/qwen2.py:param_shapes`, layers unstacked as `layers_{i}`)
but none of its code.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

# Tolerances of the comparison, per compared sequence (kind_rollout_linear.py
# applies them), each set between two readings on the v5e at the published
# widths (PERF.md, section 2 and Findings PR 32): the largest the program gave
# over its seeds, and the reference itself one precision lower (every matrix
# rounded to float8's 3 mantissa bits), which has to fail. The program
# computes in bf16 through 8 layers, with float32 state; the reference in
# float32.
#
# The router is 512 wide and a token's tenth and eleventh probabilities lie
# within 5% of each other in some layer for three tokens in four
# (`clear_share` 0.21-0.28): bf16 and float32 settle such a near-tie
# differently, and where one of the swapped pair is held here a term of
# weight about 0.1 appears or vanishes. So bf16 against float32 reads a mean
# near 0.16 whatever the path, and single tokens move by up to 2.5 nat: as
# for K-EXAONE (`kexaone_ref.py`) no bound on the largest delta stands
# between the readings, and the second bound is on the 90th percentile.
# - MEAN_ABS_TOL: the program read 0.133-0.226 (the engine: prefill, then the
#   state kernel and the paged cache a token at a time; 60 requests of ten
#   runs, 84 to 2,048 tokens each) and 0.160, 0.174 (the prefill path alone
#   over 4,096 tokens, two seeds); float8 1.246 and 1.323.
# - P90_ABS_TOL: the program read 0.260-0.611 (the two largest on sequences
#   of 88 and 91 tokens; 0.457 the next) and 0.350, 0.378; float8 2.607 and
#   2.720.
# Each bound sits near the geometric mean of its two readings, with the more
# room above the program's, since fresh seeds and short sequences read higher.
# What these two bounds do NOT see: the recurrent state one precision lower.
# With A ~ U(0, 16) most heads forget within a token or two. At the published
# widths on the chip (call qa), this file with its state rounded to bf16 after
# every token (`jax.lax.reduce_precision`, which XLA cannot drop as it drops an
# `astype` round trip) moved by a mean of 0.014 and a p90 of 0.017 over 4,096
# tokens, and the engine with `ops/gdn_step.py`'s state so rounded read
# 0.146-0.160 / 0.298-0.343 on the four requests on which it read 0.129-0.147
# / 0.291-0.309 as it is. So the state's float32 is held by two bounds of its
# own, on the state itself (`check_state` in kind_rollout_linear.py):
# - STATE_F32_SHARE_MIN: of the non-zero entries of the pool's `S` as the
#   window left it, the share that bf16 cannot represent. float32 arithmetic
#   leaves all but one entry in 65,536 there (the engine read 0.99998 of 50
#   million); a pool of bf16, or a kernel that rounds what it writes, leaves
#   none (the engine with the rounded state read 0.0).
# - STATE_STEP_REL_TOL: 32 token steps of the program's own state update
#   (`ops/gdn_step.py`, the op the decode chunk calls) on the pool's own rows
#   with seeded inputs, against `delta_rule_step` below on the same inputs:
#   the largest |delta| of the state, and of the steps' outputs, over the
#   largest magnitude. The kernel read 0.0 on the chip (four seeds: the same
#   float32 operations in the same order; another order of the sums reads
#   about 1e-6); the state rounded to bf16 after every step 5.0e-3 to 5.2e-3,
#   the outputs 2.1e-3 to 3.1e-3 (2^-9 of an entry, a few steps deep). The
#   bound sits forty times under the smallest of these.
MEAN_ABS_TOL = 0.45
P90_ABS_TOL = 1.1
STATE_F32_SHARE_MIN = 0.5
STATE_STEP_REL_TOL = 5e-5
# a margin under which the reference counts a token's routing a near-tie
# (reported with every comparison, decides nothing)
NEAR_TIE_MARGIN = 0.05

Q_BLOCK = 512


def _rms_norm(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w


def _rope(x, positions, theta, rot):
    """x: [T, n, hd]; of the first `rot` lanes, pairs (i, i + rot/2) rotate by
    position * theta^(-2i/rot); lanes [rot, hd) pass through."""
    inv_freq = 1.0 / (theta ** (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot))
    ang = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2, rest = x[..., : rot // 2], x[..., rot // 2: rot], x[..., rot:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], axis=-1)


def _f32(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


def _gated_attention(a, h, eps, theta, rot):
    """h: [T, H] float32, already normed."""
    a = _f32(a)
    T = h.shape[0]
    pos = jnp.arange(T)
    qg = jnp.einsum("th,hnd->tnd", h, a["q_kernel"])  # [T, nH, 2 hd]: q lanes, then the gate's
    hd = qg.shape[-1] // 2
    q, gate = qg[..., :hd], qg[..., hd:]
    k = jnp.einsum("th,hnd->tnd", h, a["k_kernel"])
    v = jnp.einsum("th,hnd->tnd", h, a["v_kernel"])
    q, k = _rms_norm(q, a["q_norm"], eps), _rms_norm(k, a["k_norm"], eps)
    q, k = _rope(q, pos, theta, rot), _rope(k, pos, theta, rot)
    n_q, n_kv = q.shape[1], k.shape[1]
    rep = n_q // n_kv  # query heads g*rep .. g*rep + rep - 1 read kv head g
    qb = min(Q_BLOCK, T)
    pad = (-T) % qb
    qp = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(-1, qb, n_kv, rep, hd)
    starts = jnp.arange(qp.shape[0]) * qb

    def block(_, inp):
        qblk, t0 = inp  # [qb, n_kv, rep, hd]
        seen = (t0 + jnp.arange(qb))[:, None] >= pos[None, :]

        def group(_, g):  # the rep query heads of kv head g
            s = jnp.einsum("trd,sd->rts", qblk[:, g], k[:, g]) / np.sqrt(hd)
            s = jnp.where(seen[None], s, -jnp.inf)
            return None, jnp.einsum("rts,sd->trd", jax.nn.softmax(s, axis=-1), v[:, g])

        _, o = jax.lax.scan(group, None, jnp.arange(n_kv))  # [n_kv, qb, rep, hd]
        return None, o.transpose(1, 0, 2, 3)

    _, o = jax.lax.scan(block, None, (qp, starts))
    o = o.reshape(-1, n_q, hd)[:T] * jax.nn.sigmoid(gate)
    return jnp.einsum("tnd,ndh->th", o, a["o_kernel"])


def delta_rule_step(S, inp):
    """One token of the gated delta rule for one sequence. S: [n_v, d_k, d_v]
    float32; inp = (q_t, k_t [n_v, d_k], v_t [n_v, d_v], g_t, beta_t [n_v]).
    Returns (S, o_t [n_v, d_v])."""
    q_t, k_t, v_t, g_t, b_t = inp
    S = S * jnp.exp(g_t)[:, None, None]
    m = jnp.einsum("hkv,hk->hv", S, k_t)
    d = b_t[:, None] * (v_t - m)
    S = S + k_t[:, :, None] * d[:, None, :]
    return S, jnp.einsum("hkv,hk->hv", S, q_t)


def _gated_delta_net(a, h, eps, n_k: int, n_v: int):
    """h: [T, H] float32, already normed. `n_k` key heads, `n_v` value heads;
    their sizes follow from the tree's shapes."""
    a = _f32(a)
    T = h.shape[0]
    conv = a["conv_kernel"]  # [C, K], C = 2 n_k dk + n_v dv
    d_v = a["out_kernel"].shape[0] // n_v
    d_k = (conv.shape[0] - n_v * d_v) // (2 * n_k)
    C, K = conv.shape
    qkvz = h @ a["qkvz_kernel"]
    u, z = qkvz[:, :C], qkvz[:, C:].reshape(T, n_v, d_v)
    ba = h @ a["ba_kernel"]
    beta = jax.nn.sigmoid(ba[:, :n_v])
    g = -jnp.exp(a["A_log"]) * jax.nn.softplus(ba[:, n_v:] + a["dt_bias"])  # [T, n_v], <= 0
    # depthwise causal convolution: zeros before the sequence's start, no bias
    up = jnp.pad(u, ((K - 1, 0), (0, 0)))
    u = jax.nn.silu(sum(up[j: j + T] * conv[:, j] for j in range(K)))
    q = u[:, : n_k * d_k].reshape(T, n_k, d_k)
    k = u[:, n_k * d_k: 2 * n_k * d_k].reshape(T, n_k, d_k)
    v = u[:, 2 * n_k * d_k:].reshape(T, n_v, d_v)

    def l2(t):
        return t * jax.lax.rsqrt(jnp.sum(t * t, axis=-1, keepdims=True) + 1e-6)

    q = jnp.repeat(l2(q) * d_k ** -0.5, n_v // n_k, axis=1)
    k = jnp.repeat(l2(k), n_v // n_k, axis=1)

    _, o = jax.lax.scan(delta_rule_step, jnp.zeros((n_v, d_k, d_v), jnp.float32),
                        (q, k, v, g, beta))
    o = _rms_norm(o, a["norm"], eps) * jax.nn.silu(z)
    return o.reshape(T, n_v * d_v) @ a["out_kernel"]


def _swiglu(h, gate, up, down):
    return (jax.nn.silu(h @ gate) * (h @ up)) @ down


def _moe(m, h, top_k: int, norm_topk: bool, first: int):
    """h: [T, H] float32, already normed. `m`: one layer's MLP leaves, whose
    stacked kernels hold experts first .. first + count - 1 of the router's
    width. Dense over the held experts, masked by the top-k. Returns
    (out [T, H], margin [T]): the relative gap between the k-th and the
    (k+1)-th probability where one of the two is held here (infinite
    otherwise): whose routing bf16 and float32 can settle differently."""
    p = jax.nn.softmax(h @ m["router_kernel"].astype(jnp.float32), axis=-1)  # [T, E_published]
    ranked, idx = jax.lax.top_k(p, top_k + 1)
    margin = (ranked[:, top_k - 1] - ranked[:, top_k]) / ranked[:, top_k - 1]
    count = m["gate_kernel"].shape[0]
    here = (idx[:, top_k - 1:] >= first) & (idx[:, top_k - 1:] < first + count)
    margin = jnp.where(jnp.any(here, axis=-1), margin, jnp.inf)
    idx, w = idx[:, :top_k], ranked[:, :top_k]
    if norm_topk:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    # [T, E_published]: w_i at the token's chosen experts, exactly 0 elsewhere
    dense_w = jnp.sum(
        jax.nn.one_hot(idx, p.shape[-1], dtype=jnp.float32) * w[..., None], axis=1)

    def one_expert(acc, e):
        y = _swiglu(h, *(m[k][e].astype(jnp.float32)
                         for k in ("gate_kernel", "up_kernel", "down_kernel")))
        return acc + dense_w[:, first + e, None] * y, None

    out, _ = jax.lax.scan(one_expert, jnp.zeros_like(h), jnp.arange(count))
    # the shared expert, once, behind its sigmoid gate
    shared = _swiglu(h, *(m[k].astype(jnp.float32) for k in
                          ("shared_gate_kernel", "shared_up_kernel", "shared_down_kernel")))
    gate = jax.nn.sigmoid(h @ m["shared_router_kernel"].astype(jnp.float32))  # [T, 1]
    return out + gate * shared, margin


@functools.partial(jax.jit, static_argnames=(
    "eps", "theta", "rot", "linear", "n_k", "n_v", "top_k", "norm_topk", "first"))
def _layer(lp, x, *, eps, theta, rot, linear, n_k, n_v, top_k, norm_topk, first):
    """One decoder layer on one sequence. x: [T, H] float32. Returns
    (x, the router's margin per token)."""
    h = _rms_norm(x, lp["input_norm"].astype(jnp.float32), eps)
    if linear:
        x = x + _gated_delta_net(lp["attn"], h, eps, n_k, n_v)
    else:
        x = x + _gated_attention(lp["attn"], h, eps, theta, rot)
    h = _rms_norm(x, lp["post_attn_norm"].astype(jnp.float32), eps)
    y, margin = _moe(lp["mlp"], h, top_k, norm_topk, first)
    return x + y, margin


@functools.partial(jax.jit, static_argnames=("eps",))
def _head_logprobs(final_norm, head, x, labels, temperature, *, eps: float):
    """log softmax(logits / temperature)[label] per position. head: [H, V]."""
    x = _rms_norm(x, final_norm.astype(jnp.float32), eps)
    logits = (x @ head.astype(jnp.float32)) / temperature
    logz = jax.nn.logsumexp(logits, axis=-1)
    return jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0] - logz


def layer_statics(model_config, i: int, held: tuple[int, int] | None = None) -> dict:
    """The numbers of layer i, read from the configuration (and nothing of
    the program's code)."""
    first = model_config.expert_first if held is None else held[0]
    hd = model_config.head_dim or model_config.hidden_size // model_config.num_attention_heads
    return dict(
        eps=float(model_config.rms_norm_eps), theta=float(model_config.rope_theta),
        rot=int(hd * model_config.partial_rotary_factor),
        linear=model_config.layer_types[i] == "linear_attention",
        n_k=int(model_config.linear_num_key_heads), n_v=int(model_config.linear_num_value_heads),
        top_k=int(model_config.num_experts_per_tok),
        norm_topk=bool(model_config.norm_topk_prob), first=int(first))


def hidden_states(params, model_config, ids, held=None):
    """(x after the last layer: [T, H] float32, and per position the
    smallest router margin of any layer: [T])."""
    x = jnp.take(params["embed"]["embedding"], ids, axis=0).astype(jnp.float32)
    margin = jnp.full(ids.shape, jnp.inf, jnp.float32)
    for i in range(model_config.num_hidden_layers):
        x, m = _layer(params[f"layers_{i}"], x, **layer_statics(model_config, i, held))
        margin = jnp.minimum(margin, m)
    return x, margin


def logits(params, model_config, token_ids, held=None):
    """Float32 logits [T, V] of one sequence (for the CPU tests)."""
    ids = jnp.asarray(np.asarray(token_ids, dtype=np.int32))
    with jax.default_matmul_precision("highest"):
        x, _ = hidden_states(params, model_config, ids, held)
        x = _rms_norm(x, params["final_norm"].astype(jnp.float32),
                      float(model_config.rms_norm_eps))
        return x @ params["lm_head"]["kernel"].astype(jnp.float32)


def moe_layer(mlp_leaves, h, model_config, held: tuple[int, int] | None = None):
    """One layer's MoE output on already-normed rows h [T, H] (for the share
    test: shares of the experts against the uncut layer)."""
    s = layer_statics(model_config, 0, held)
    with jax.default_matmul_precision("highest"):
        return _moe(mlp_leaves, jnp.asarray(h, jnp.float32), s["top_k"], s["norm_topk"],
                    s["first"])[0]


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
    the smallest relative gap between its k-th and (k+1)-th expert's
    probability in any layer (`_moe`): float32 [T-1].

    `params` is the program's tree (any dtype, any placement); `token_ids`
    one sequence of length T. `pad_to` right-pads the sequence so that
    sequences of several lengths share one compiled shape; attention, the
    convolution and the recurrence are causal and a token's experts depend on
    its own row alone, so the padding cannot reach the positions returned."""
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
    gradient with respect to every leaf of `params` (for the CPU tests)."""
    ids = jnp.asarray(np.asarray(token_ids, dtype=np.int32))

    def nll(p):
        return -jnp.mean(_logprobs(p, model_config, ids, temperature)[0])

    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(nll)(params)
