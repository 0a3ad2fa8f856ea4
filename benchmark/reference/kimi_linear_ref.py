"""Plain float32 reference of the Kimi-Linear decoder (`kimi_linear`,
moonshotai/Kimi-Linear-48B-A3B-Instruct): the yardstick `correct` is decided
against in the `kimi-linear-48b-a3b` cell.

Straightforward `jax.numpy`, one sequence at a time, no kernels, no cache, no
packing, no sorting, no chunks, no scan over layers; the delta rule as its
TOKEN-BY-TOKEN recurrence and the latent attention in its EXPANDED form only,
so that the program's chunked prefill, its state kernel and its absorbed
decode are each held to arithmetic they do not share. Layer l (numbered from
1 in `linear_attn_config`), x a token's normed hidden state, h a head:

    y = x + Mixer_l(RMSNorm(x));   z = y + MLP_l(RMSNorm(y))        eps 1e-5, no biases
    Mixer_l, l in kda_layers (Kimi Delta Attention, n heads of dk = dv = head_dim):
        q~ = silu(conv4(x W_q));  k~ = silu(conv4(x W_k));  v = silu(conv4(x W_v))
            (three depthwise causal convolutions of short_conv_kernel_size, zeros before the
            start, no bias)
        q_h = l2norm(q~_h) dk^-1/2;  k_h = l2norm(k~_h)                        (eps 1e-6)
        g_h = -exp(A_log_h) softplus((x W_fa) W_fb + dt_bias)_h   a VECTOR of dk a head, <= 0
        beta_h = sigmoid(x W_b)_h                                  one a head
        S_h in R^{dk x dv} from zero, token by token, float32:
            S <- Diag(exp(g_t)) S;  d = beta_t (v_t - S^T k_t);  S <- S + k_t d^T;  o_t = S^T q_t
        out = W_o concat_h( w_n * rmsnorm(o_h) * sigmoid((x W_ga) W_gb)_h )
    Mixer_l, l in full_attn_layers (latent attention, NO positional encoding):
        [q_nope_h | q_pe_h] = x W_q,h     (full rank: no bottleneck, no query norm)
        [c_kv | k_pe] = x W_kva;  c = RMSNorm(c_kv);  [k_nope_h | v_h] = c W_kvb,h
        k_h = [k_nope_h | k_pe]: k_pe one head shared by all, NOT rotated, nor is q_pe
        causal softmax((nope + pe)^-1/2 q_h . k_h(s)) in float32;  out = W_o concat_h(o_h)
    MLP_l, l <= first_k_dense_replace:  Wdown(silu(x Wgate) * (x Wup)) at intermediate_size
    MLP_l otherwise:  s = sigmoid(x W_r) over ALL published experts in float32; S = the k
        largest of s + b (b the selection bias; one expert group: the grouping is vacuous);
        w_e = routed_scaling_factor s_e / sum_{j in S} s_j   (moe_renormalize)
        out = sum_{e in S, e held here} w_e FFN_e(x) + FFN_shared(x),  FFN a SwiGLU
    logits = RMSNorm_f(z_L) W_head   (untied; over the rows of the vocabulary held here)

What the catalog's keys do not state is from the Kimi Linear report and the
published modelling code as ISSUE 45's author knew them (no network here),
each marked [family] at its line and listed under `assumed` in
benchmark/configs/kimi-linear-48b-a3b.json.

`held = (first, count)`: the experts this chip holds of the published ones.
Routing is over all of them; only the held experts' terms are summed, the
shared expert once, and nothing stands in for the rest (the model-configs
guide, section 4). The vocabulary slice is whatever rows the embedding and
head have.

Attention is computed `HEAD_GROUP` heads and `Q_BLOCK` queries at a time, so
8,192 tokens never hold more than a [8, 256, T] score block. Experts are
visited one at a time. Every weight is cast to float32 as it is used;
`weight_bits` rounds it to that many mantissa bits on the way (3: float8
e4m3's, the must-fail reading; no second tree fits beside the engine's), and
`state_bits` rounds the recurrent state after every token (7: bf16's).

It reads the program's parameter tree (names and axis order of
`areal_tpu/models/qwen2.py:param_shapes`, layers unstacked as `layers_{i}`)
but none of its code.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

# Tolerances of the comparison (kind_rollout_kda.py applies them), each from
# two readings on the v5e at the published widths (PERF.md, section 2 and
# Findings PR 45): the largest the program gave over its seeds, and one
# precision lower, which has to fail: (a) this reference with its weights at
# float8's 3 mantissa bits (`weight_bits=3`), (b) the engine with the latent
# pool's rows rounded to float8 (e4m3) as they are written, (c) the engine
# with the state rounded to bf16 after every step
# (`bench_artifacts/pr45/lower_precision.py weights | pool | state`). The
# program computes in bf16 through 8 layers with a float32 state, the
# reference in float32.
#
# The router is 256 wide and sigmoid-scored; a token's eighth and ninth
# `s + b` lie within 5% of each other in some layer for five tokens in six
# (`clear_share` 0.11-0.20): bf16 and float32 settle such a near-tie
# differently, and where one of the swapped pair is held here a term of
# weight about 0.3 (2.446 / 8) appears or vanishes: single tokens move by up
# to 4.3 nat and a sequence's mean reads 0.15 whatever the path. As for
# K-EXAONE and Qwen3-Next no bound on the largest delta stands between the
# readings; the bounds are on the mean and on the 90th percentile of |delta|
# over a compared sequence.
# - MEAN_ABS_TOL: the program read 0.116-0.191 over 42 requests of seven runs
#   (calls k2, k3, k6: 89 to 2,048 tokens each); float8 weights 1.149-1.229 over
#   the six of one run (k3).
# - P90_ABS_TOL: the program read 0.303-0.575 (0.574 on 94 tokens, 0.513 the
#   next); float8 weights 2.417-2.685.
# Each bound sits near the geometric mean of its two readings (0.47, 1.18),
# with the more room above the program's, since fresh seeds and short
# sequences read higher: Qwen3-Next's two numbers, as it happens.
# What these two bounds do NOT see, each held by bounds of its own on the
# cache itself (`check_state`, `check_latent_rows` in kind_rollout_kda.py):
# - the latent rows one precision lower: two layers of eight are latent, and
#   with their rows at float8 as written the engine read 0.121-0.219 /
#   0.305-0.558 (k3), inside the program's own readings (DeepSeek-V2's cell,
#   every layer latent, sees it: 0.23-0.32 against 0.04-0.16). So
#   LATENT_BEYOND_F8_SHARE_MIN: of the non-zero entries of the pool's rows as
#   the window left them, the share float8 (e4m3) cannot hold: a bf16 pool
#   read 0.9374 on the chip (k6: fifteen of sixteen mantissas), rows written
#   at float8 0.0 (k6). (Read with `reduce_precision`: XLA:TPU drops an
#   `astype` round trip through float8, and the share then reads 0.0 for any
#   pool: k5.)
# - the recurrent state one precision lower: with the state rounded to bf16
#   after every step the engine read 0.141-0.159 / 0.338-0.435 (k3: unseen,
#   as Qwen3-Next found). STATE_F32_SHARE_MIN: of the non-zero entries of the
#   pool's `S`, the share bf16 cannot hold: the engine read 0.99998 of 403
#   million in every run; the rounded state 0.0. STATE_STEP_REL_TOL: 32 steps
#   of `ops/gdn_step.py` under a vector decay on the pool's own rows with
#   seeded inputs against `delta_rule_step` below: the kernel read 0.0 in
#   every run (the same float32 operations in the same order); rounded to
#   bf16 after every step 5.0e-3 (state), 1.4e-3 (outputs) (k3). The bound
#   sits thirty times under the smaller.
MEAN_ABS_TOL = 0.45
P90_ABS_TOL = 1.1
LATENT_BEYOND_F8_SHARE_MIN = 0.5
STATE_F32_SHARE_MIN = 0.5
STATE_STEP_REL_TOL = 5e-5
# a margin under which the reference counts a token's routing a near-tie
# (reported with every comparison, decides nothing)
NEAR_TIE_MARGIN = 0.05

Q_BLOCK = 256
HEAD_GROUP = 8


def _round(x, bits: int):
    """float32 `x` rounded to `bits` mantissa bits (3: float8 e4m3's, 7: bf16's)."""
    m, e = jnp.frexp(x)
    scale = float(1 << (bits + 1))
    return jnp.ldexp(jnp.round(m * scale) / scale, e)


def _w(a, bits=None):
    """A weight in float32 as it is used; `bits`: rounded on the way."""
    a = a.astype(jnp.float32)
    return a if bits is None else _round(a, bits)


def round_mantissa(params, bits: int):
    """The tree with every leaf rounded to `bits` mantissa bits, in its own
    dtype (for the CPU tests, where a second tree fits)."""
    return jax.tree.map(lambda a: _round(a.astype(jnp.float32), bits).astype(a.dtype), params)


def _rms_norm(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w


def delta_rule_step(S, inp, state_bits=None):
    """One token of the delta rule under a vector decay, one sequence.
    S: [n, dk, dv] float32; inp = (q_t, k_t [n, dk], v_t [n, dv], g_t [n, dk]
    (a log decay a key lane) or [n] (one a head), beta_t [n]).
    Returns (S, o_t [n, dv])."""
    q_t, k_t, v_t, g_t, b_t = inp
    decay = jnp.exp(g_t)
    S = S * (decay[:, :, None] if decay.ndim == 2 else decay[:, None, None])
    m = jnp.einsum("hkv,hk->hv", S, k_t)
    d = b_t[:, None] * (v_t - m)
    S = S + k_t[:, :, None] * d[:, None, :]
    if state_bits is not None:
        S = _round(S, state_bits)
    return S, jnp.einsum("hkv,hk->hv", S, q_t)


def _conv_silu(u, kernel):
    """Depthwise causal convolution over [T, C] by `kernel` [C, K]: zeros
    before the sequence's start, no bias [family], then silu."""
    T, K = u.shape[0], kernel.shape[1]
    up = jnp.pad(u, ((K - 1, 0), (0, 0)))
    return jax.nn.silu(sum(up[j: j + T] * kernel[:, j] for j in range(K)))


def _kda(a, h, st, bits=None, state_bits=None):
    """h: [T, H] float32, already normed. `a`: the layer's KDA leaves."""
    T = h.shape[0]
    eps = st["eps"]
    n, dk = a["q_kernel"].shape[1:]
    dv = a["v_kernel"].shape[2]
    # [family] three projections, each behind a convolution of its own
    q, k, v = (
        _conv_silu(jnp.einsum("th,hnd->tnd", h, _w(a[f"{x}_kernel"], bits)).reshape(T, -1),
                   _w(a[f"{x}_conv_kernel"], bits)).reshape(T, n, -1)
        for x in ("q", "k", "v"))

    def l2(t):  # [family] eps 1e-6 inside the root
        return t * jax.lax.rsqrt(jnp.sum(t * t, axis=-1, keepdims=True) + 1e-6)

    q, k = l2(q) * dk ** -0.5, l2(k)  # [family] q scaled, k not
    # [family] the decay: a low-rank gate to a head's dk lanes, A_log a head, dt_bias a lane
    f = (h @ _w(a["f_a_kernel"], bits)) @ _w(a["f_b_kernel"], bits)
    g = -jnp.exp(_w(a["A_log"], bits))[None, :, None] * jax.nn.softplus(
        f + _w(a["dt_bias"], bits)).reshape(T, n, dk)
    beta = jax.nn.sigmoid(h @ _w(a["b_kernel"], bits))  # [T, n]
    _, o = jax.lax.scan(functools.partial(delta_rule_step, state_bits=state_bits),
                        jnp.zeros((n, dk, dv), jnp.float32), (q, k, v, g, beta))
    # [family] a SIGMOID output gate, low-rank, on the head-normed output
    gate = ((h @ _w(a["g_a_kernel"], bits)) @ _w(a["g_b_kernel"], bits)).reshape(T, n, dv)
    o = _rms_norm(o, _w(a["o_norm"], bits), eps) * jax.nn.sigmoid(gate)
    return jnp.einsum("tnd,ndh->th", o, _w(a["o_kernel"], bits))


def _attention(a, h, st, bits=None):
    """h: [T, H] float32, already normed. `a`: the layer's latent-attention
    leaves. The expanded form, no rotation anywhere (`mla_use_nope`)."""
    T = h.shape[0]
    pos = jnp.arange(T)
    nH, nope, rope, dv, C = st["heads"], st["nope"], st["rope"], st["dv"], st["latent"]
    kv = h @ _w(a["kv_a_kernel"], bits)
    # [family] the norm is over c_kv alone; k_pe is one head shared by all, as it is
    c_kv = _rms_norm(kv[:, :C], _w(a["kv_a_norm"], bits), st["eps"])
    k_pe = kv[:, C:]
    scale = (nope + rope) ** -0.5

    G = HEAD_GROUP if nH % HEAD_GROUP == 0 else 1
    w_q = a["q_kernel"].reshape(-1, nH // G, G, nope + rope)
    w_kvb = a["kv_b_kernel"].reshape(C, nH // G, G, nope + dv)
    w_o = a["o_kernel"].reshape(nH // G, G, dv, -1)
    qb = min(Q_BLOCK, T)
    pad = (-T) % qb
    starts = jnp.arange((T + pad) // qb) * qb

    def head_group(out, g):
        q = jnp.einsum("th,hgd->tgd", h, _w(w_q[:, g], bits))  # [T, G, nope + rope]
        kvb = jnp.einsum("tc,cgd->tgd", c_kv, _w(w_kvb[:, g], bits))
        k = jnp.concatenate(
            [kvb[..., :nope], jnp.broadcast_to(k_pe[:, None, :], (T, G, rope))], axis=-1)
        v = kvb[..., nope:]
        qp = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(-1, qb, G, nope + rope)

        def block(_, inp):
            qblk, t0 = inp
            seen = (t0 + jnp.arange(qb))[:, None] >= pos[None, :]
            s = jnp.einsum("tgd,sgd->gts", qblk, k) * scale
            s = jnp.where(seen[None], s, -jnp.inf)
            return None, jnp.einsum("gts,sgd->tgd", jax.nn.softmax(s, axis=-1), v)

        _, o = jax.lax.scan(block, None, (qp, starts))
        o = o.reshape(-1, G, dv)[:T]
        return out + jnp.einsum("tgd,gdh->th", o, _w(w_o[g], bits)), None

    out, _ = jax.lax.scan(head_group, jnp.zeros_like(h), jnp.arange(nH // G))
    return out


def _swiglu(h, gate, up, down):
    return (jax.nn.silu(h @ gate) * (h @ up)) @ down


def route(s, bias, top_k: int):
    """s: [T, E_published] sigmoid scores, bias [E_published] -> (idx [T, k + 1]:
    the k chosen experts and the runner-up by s + bias, best first; ranked
    [T, k + 1]: their s + bias)."""
    return tuple(reversed(jax.lax.top_k(s + bias, top_k + 1)))


def _moe(m, h, st, bits=None):
    """h: [T, H] float32, already normed. `m`: one layer's MLP leaves, whose
    stacked kernels hold experts first .. first + count - 1 of the router's
    width. Returns (out [T, H], margin [T]): the relative gap between the
    k-th and the (k+1)-th `s + b` where one of the two is held here."""
    top_k, first = st["top_k"], st["first"]
    s = jax.nn.sigmoid(h @ _w(m["router_kernel"], bits))  # [T, E_published] float32
    # the bias enters the CHOICE alone, never the weight
    idx, ranked = route(s, _w(m["router_bias"], bits), top_k)
    margin = (ranked[:, top_k - 1] - ranked[:, top_k]) / jnp.abs(ranked[:, top_k - 1])
    count = m["gate_kernel"].shape[0]
    here = (idx[:, top_k - 1:] >= first) & (idx[:, top_k - 1:] < first + count)
    margin = jnp.where(jnp.any(here, axis=-1), margin, jnp.inf)
    idx = idx[:, :top_k]
    w = jnp.take_along_axis(s, idx, axis=-1)
    if st["norm_topk"]:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    w = st["scaling"] * w
    dense_w = jnp.sum(jax.nn.one_hot(idx, s.shape[-1], dtype=jnp.float32) * w[..., None], axis=1)

    def one_expert(acc, e):
        y = _swiglu(h, *(_w(m[k][e], bits) for k in ("gate_kernel", "up_kernel", "down_kernel")))
        return acc + dense_w[:, first + e, None] * y, None

    out, _ = jax.lax.scan(one_expert, jnp.zeros_like(h), jnp.arange(count))
    # [family] the shared expert is one ungated SwiGLU of num_shared_experts x the expert width
    shared = _swiglu(h, *(_w(m[k], bits) for k in
                          ("shared_gate_kernel", "shared_up_kernel", "shared_down_kernel")))
    return out + shared, margin


@functools.partial(jax.jit, static_argnames=("st", "bits", "state_bits"))
def _layer(lp, x, *, st, bits=None, state_bits=None):
    """One decoder layer on one sequence. x: [T, H] float32. Returns (x, the
    router's margin per token: infinite in a dense layer). `st`: a tuple of
    (name, value) pairs, hashable."""
    s = dict(st)
    # [family] pre-norm placement
    h = _rms_norm(x, _w(lp["input_norm"], bits), s["eps"])
    if s["linear"]:
        x = x + _kda(lp["attn"], h, s, bits, state_bits)
    else:
        x = x + _attention(lp["attn"], h, s, bits)
    h = _rms_norm(x, _w(lp["post_attn_norm"], bits), s["eps"])
    if s["sparse"]:
        y, margin = _moe(lp["mlp"], h, s, bits)
        return x + y, margin
    y = _swiglu(h, *(_w(lp["mlp"][k], bits) for k in ("gate_kernel", "up_kernel", "down_kernel")))
    return x + y, jnp.full(x.shape[:1], jnp.inf, jnp.float32)


@functools.partial(jax.jit, static_argnames=("eps", "bits"))
def _head_logprobs(final_norm, head, x, labels, temperature, *, eps: float, bits=None):
    """log softmax(logits / temperature)[label] per position. head: [H, V]."""
    x = _rms_norm(x, _w(final_norm, bits), eps)
    logits = (x @ _w(head, bits)) / temperature
    logz = jax.nn.logsumexp(logits, axis=-1)
    return jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0] - logz


def layer_statics(model_config, i: int, held: tuple[int, int] | None = None) -> tuple:
    """The numbers of layer i, read from the configuration (and nothing of
    the program's code), as a hashable tuple of pairs."""
    mc = model_config
    first = mc.expert_first if held is None else held[0]
    return tuple(dict(
        eps=float(mc.rms_norm_eps), linear=mc.layer_types[i] == "linear_attention",
        heads=int(mc.num_attention_heads), nope=int(mc.qk_nope_head_dim),
        rope=int(mc.qk_rope_head_dim), dv=int(mc.v_head_dim), latent=int(mc.kv_lora_rank),
        sparse=i >= mc.first_k_dense, top_k=int(mc.num_experts_per_tok),
        norm_topk=bool(mc.norm_topk_prob), scaling=float(mc.routed_scaling_factor),
        first=int(first)).items())


def hidden_states(params, model_config, ids, held=None, bits=None, state_bits=None):
    """(x after the last layer: [T, H] float32, and per position the
    smallest router margin of any layer: [T])."""
    x = jnp.take(params["embed"]["embedding"], ids, axis=0).astype(jnp.float32)
    if bits is not None:
        x = _round(x, bits)
    margin = jnp.full(ids.shape, jnp.inf, jnp.float32)
    for i in range(model_config.num_hidden_layers):
        x, m = _layer(params[f"layers_{i}"], x, st=layer_statics(model_config, i, held),
                      bits=bits, state_bits=state_bits)
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
    """One sparse layer's MoE output on already-normed rows h [T, H] (for the
    share test: shares of the experts against the uncut layer)."""
    st = dict(layer_statics(model_config, model_config.num_hidden_layers - 1, held))
    with jax.default_matmul_precision("highest"):
        return _moe(mlp_leaves, jnp.asarray(h, jnp.float32), st)[0]


def _logprobs(params, model_config, ids, temperature, held=None, bits=None, state_bits=None):
    x, margin = hidden_states(params, model_config, ids, held, bits, state_bits)
    lp = _head_logprobs(params["final_norm"], params["lm_head"]["kernel"], x[:-1], ids[1:],
                        jnp.float32(temperature), eps=float(model_config.rms_norm_eps), bits=bits)
    return lp, margin


def token_logprobs(params, model_config, token_ids, temperature: float = 1.0,
                   pad_to: int = 0, held: tuple[int, int] | None = None,
                   with_margins: bool = False, weight_bits: int | None = None,
                   state_bits: int | None = None):
    """log p(token[t+1] | token[:t+1]) for t in [0, T-1): float32 [T-1].
    With `with_margins` also, for the position that predicts each of them,
    the smallest relative gap between its k-th and (k+1)-th expert's `s + b`
    in any sparse layer: float32 [T-1].

    `params` is the program's tree (any dtype, any placement); `token_ids`
    one sequence of length T. `held` = (first, count) overrides the
    configuration's held range. `pad_to` right-pads the sequence so that
    sequences of several lengths share one compiled shape; attention, the
    convolutions and the recurrence are causal and a token's experts depend
    on its own row alone, so the padding cannot reach the positions that are
    returned. `weight_bits`: every weight rounded to that many mantissa bits
    as it is used; `state_bits`: the recurrent state after every token."""
    n = len(token_ids)
    ids = np.zeros(max(n, pad_to), dtype=np.int32)
    ids[:n] = np.asarray(token_ids, dtype=np.int32)
    with jax.default_matmul_precision("highest"):
        lp, margin = _logprobs(params, model_config, jnp.asarray(ids), temperature, held,
                               weight_bits, state_bits)
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
