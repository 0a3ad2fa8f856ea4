"""Plain float32 reference of the DeepSeek-V2 decoder (`deepseek_v2`,
deepseek-ai/DeepSeek-V2): the yardstick `correct` is decided against in the
`deepseek-v2` cell.

Straightforward `jax.numpy`, one sequence at a time, no kernels, no cache, no
packing, no sorting, no scan over layers, and the EXPANDED form of the latent
attention only: the program's decode step runs the absorbed form over its
cached rows, so it is held to arithmetic it does not share. Layer l, with x a
token's normed hidden state and h one of nH heads:

    y = x + Attn_l(RMSNorm(x));   z = y + MLP_l(RMSNorm(y))          eps 1e-6, no biases
    Attn_l: c_q = RMSNorm(x W_qa);  [q_nope_h | q_pe_h] = c_q W_qb;  q_pe_h = RoPE(q_pe_h)
            [c_kv | k_pe] = x W_kva;  c_kv = RMSNorm(c_kv);  k_pe = RoPE(k_pe)   (one head for all)
            [k_nope_h | v_h] = c_kv W_kvb,h;  k_h = [k_nope_h | k_pe]
            score_h(t, s) = scale q_h(t) . k_h(s), causal;  scale = (nope + rope)^-1/2 m^2,
            m = 0.1 mscale_all_dim ln(factor) + 1;  softmax in float32;  o_h = sum_s p v_h(s)
            out = concat_h(o_h) W_o
    RoPE: YaRN over the rope/2 frequencies theta_i = base^(-2i/rope): with
            c(n) = rope ln(orig / (2 pi n)) / (2 ln base), low = floor(c(beta_fast)),
            high = ceil(c(beta_slow)), r_i = clip((i - low) / (high - low), 0, 1),
            inv_freq_i = theta_i (1 - r_i) + theta_i / factor r_i; cos and sin times
            mscale(factor, mscale) / mscale(factor, mscale_all_dim) (1 in the published config)
    MLP_l, l < first_k_dense:  Wdown(silu(x Wgate) * (x Wup)) at intermediate_size
    MLP_l otherwise:  s = softmax(x W_g) over ALL published experts in float32; a group's score
            is the largest s among its consecutive experts; the topk_group best of n_group groups
            are kept; S = the k best s among their experts; w_e = routed_scaling_factor s_e
            (norm_topk_prob false: no renormalisation; no selection bias)
            out = sum_{e in S, e held here} w_e FFN_e(x) + FFN_shared(x),  FFN a SwiGLU
    logits = RMSNorm_f(z_L) W_head   (untied; over the rows of the vocabulary held here)

What the catalog's keys do not state is from the DeepSeek-V2 report and
modelling code as ISSUE 38's author knew them (no network here), each marked
[family] at its line and listed under `assumed` in
benchmark/configs/deepseek-v2.json.

`held = (first, count)`: the experts this chip holds of the published ones
(whole routing groups). Routing is over all of them; only the held experts'
terms are summed, the shared experts once, and nothing stands in for the rest
(the model-configs guide, section 4). The vocabulary slice is whatever rows
the embedding and head have.

Attention is computed `HEAD_GROUP` heads and `Q_BLOCK` queries at a time, the
heads' q, k and v built from c_q and the latent rows as they are needed, so
16,384 tokens at 128 heads never hold more than a [8, 256, T] score block and
one head group's keys. Experts are visited one at a time. Every weight is
cast to float32 as it is used; `weight_bits` rounds it to that many mantissa
bits on the way (3: float8 e4m3's, the must-fail reading; no second tree fits
beside the engine's).

It reads the program's parameter tree (names and axis order of
`areal_tpu/models/qwen2.py:param_shapes`, layers unstacked as `layers_{i}`)
but none of its code.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

# Tolerances of the comparison (kind_rollout_latent.py applies them), each
# from two readings on the v5e at the published widths (PERF.md, section 2
# and Findings PR 38): the largest the program gave over its seeds, and one
# precision lower, which has to fail at least one of them: (a) this
# reference with its weights at float8's 3 mantissa bits (`weight_bits=3`),
# (b) the engine with the latent pool's rows rounded to float8 (e4m3) as
# they are written, by the prefill and by every decode step
# (`bench_artifacts/pr38/lower_precision.py`), since the pool is what is
# new. The program computes in bf16 through 5 layers, the reference in
# float32.
#
# A near-tie at the third group or the sixth expert falls differently in
# bf16 and float32; a chosen expert weighs s_e * 16, about 0.1 here, and
# where one of the swapped pair is held on this chip its whole term appears
# or vanishes. Such a token moves by 1 to 5 nat (the largest read 5.6),
# through the engine and through a plain bf16 `forward` alike. So the
# largest cannot be bounded and, as `kexaone_ref.py` does, the bounds are
# on the body of |delta|: its mean and its 90th percentile. The requests
# compared are as short as 50 tokens (the traffic's outputs start at 32),
# whose own 90th percentile is their sixth largest delta, a count of flips:
# one of 62 tokens read 0.448 where the sequences of over a hundred read
# 0.100-0.148. So the mean is bounded a sequence and the 90th percentile
# over the run's compared tokens together (3,100-3,900 of them).
# - MEAN_ABS_TOL, a compared sequence: the program read 0.039-0.158 over 126
#   requests of twenty-one runs (calls c2-c4, c6; 0.158 on 62 tokens, 0.142
#   the next); float8 pool rows 0.228-0.320 over the six of one run (c5: five
#   of six over the bound, the one under it 61 tokens long); float8 weights
#   0.765-1.054 over 12 of two (c2, c3).
# - P90_ABS_TOL, the compared tokens of a run together: the program read
#   0.110-0.129 over nineteen runs, float8 pool rows 0.686 (c5), float8
#   weights 2.104 (c3).
MEAN_ABS_TOL = 0.25
P90_ABS_TOL = 0.35
# a margin under which the reference counts a token's routing a near-tie
# (reported with every comparison, decides nothing)
NEAR_TIE_MARGIN = 0.05

Q_BLOCK = 256
HEAD_GROUP = 8


def _round(x, bits: int):
    """float32 `x` rounded to `bits` mantissa bits (3: float8 e4m3's)."""
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


def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1.0 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(rope: int, base: float, factor: float, orig: int, beta_fast: float,
                  beta_slow: float):
    """The rope/2 rotary frequencies under YaRN, and (low, high), the ends of
    the ramp among the frequency indices."""
    def index_turning(n):  # the (fractional) index of the frequency that turns n times
        return rope * math.log(orig / (n * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(index_turning(beta_fast)), 0)
    high = min(math.ceil(index_turning(beta_slow)), rope - 1)
    theta = 1.0 / (base ** (jnp.arange(0, rope, 2, dtype=jnp.float32) / rope))
    ramp = jnp.clip((jnp.arange(rope // 2, dtype=jnp.float32) - low) / max(high - low, 1e-3),
                    0.0, 1.0)
    return theta * (1.0 - ramp) + theta / factor * ramp, (low, high)


def _rope(x, positions, inv_freq, table_scale):
    """x: [T, ..., rope]; pairs (i, i + rope/2) rotate by position * inv_freq_i.
    [family] the program's tree holds the rotary lanes as `rotate_half` pairs
    them; a checkpoint's are interleaved (2i, 2i + 1) and `hf_io` permutes them."""
    half = x.shape[-1] // 2
    ang = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (half,)
    cos = (jnp.cos(ang) * table_scale).reshape(shape)
    sin = (jnp.sin(ang) * table_scale).reshape(shape)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _attention(a, h, st, bits=None):
    """h: [T, H] float32, already normed. `a`: the layer's attention leaves;
    `st`: the statics of `layer_statics`."""
    T = h.shape[0]
    pos = jnp.arange(T)
    nH, nope, rope, dv, C = st["heads"], st["nope"], st["rope"], st["dv"], st["latent"]
    inv_freq, _ = yarn_inv_freq(rope, st["theta"], *st["yarn"][:4])
    # cos and sin carry mscale(factor, mscale) / mscale(factor, mscale_all_dim): 1 as published
    table_scale = yarn_mscale(st["yarn"][0], st["yarn"][4]) / yarn_mscale(
        st["yarn"][0], st["yarn"][5])
    # [family] the low-rank query has an RMSNorm of its own
    c_q = _rms_norm(h @ _w(a["q_a_kernel"], bits), _w(a["q_a_norm"], bits), st["eps"])
    kv = h @ _w(a["kv_a_kernel"], bits)
    # [family] the norm is over c_kv alone; k_pe is one head shared by all, turned as it is
    c_kv = _rms_norm(kv[:, :C], _w(a["kv_a_norm"], bits), st["eps"])
    k_pe = _rope(kv[:, C:], pos, inv_freq, table_scale)  # [T, rope]
    # [family] mscale enters the softmax scale squared (mscale_all_dim), not the tables
    m = yarn_mscale(st["yarn"][0], st["yarn"][5]) if st["yarn"][5] else 1.0
    scale = (nope + rope) ** -0.5 * m * m

    G = HEAD_GROUP if nH % HEAD_GROUP == 0 else 1
    w_qb = a["q_b_kernel"].reshape(-1, nH // G, G, nope + rope)
    w_kvb = a["kv_b_kernel"].reshape(C, nH // G, G, nope + dv)
    w_o = a["o_kernel"].reshape(nH // G, G, dv, -1)
    qb = min(Q_BLOCK, T)
    pad = (-T) % qb
    starts = jnp.arange((T + pad) // qb) * qb

    def head_group(out, g):
        q = jnp.einsum("tr,rgd->tgd", c_q, _w(w_qb[:, g], bits))  # [T, G, nope + rope]
        q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], pos, inv_freq, table_scale)],
                            axis=-1)
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


def route(s, top_k: int, n_group: int, topk_group: int):
    """s: [T, E_published] softmax scores -> (idx [T, k + 1]: the k chosen
    experts and the runner-up, best first; ranked [T, k + 1]: their scores)."""
    T, E = s.shape
    # [family] a group's score is its best expert's (`group_limited_greedy`)
    group_scores = s.reshape(T, n_group, E // n_group).max(axis=-1)
    _, kept = jax.lax.top_k(group_scores, topk_group)
    in_kept = jnp.zeros((T, n_group), bool).at[jnp.arange(T)[:, None], kept].set(True)
    masked = jnp.where(jnp.repeat(in_kept, E // n_group, axis=1), s, 0.0)
    ranked, idx = jax.lax.top_k(masked, top_k + 1)
    return idx, ranked


def _moe(m, h, st, bits=None):
    """h: [T, H] float32, already normed. `m`: one layer's MLP leaves, whose
    stacked kernels hold experts first .. first + count - 1 of the router's
    width. Returns (out [T, H], margin [T]): the relative gap between the
    k-th and the (k+1)-th score where one of the two is held here."""
    top_k, first = st["top_k"], st["first"]
    s = jax.nn.softmax(h @ _w(m["router_kernel"], bits), axis=-1)  # [T, E_published] float32
    idx, ranked = route(s, top_k, st["n_group"], st["topk_group"])
    margin = (ranked[:, top_k - 1] - ranked[:, top_k]) / ranked[:, top_k - 1]
    count = m["gate_kernel"].shape[0]
    here = (idx[:, top_k - 1:] >= first) & (idx[:, top_k - 1:] < first + count)
    margin = jnp.where(jnp.any(here, axis=-1), margin, jnp.inf)
    idx = idx[:, :top_k]
    # norm_topk_prob false: the softmax score itself, times the scaling factor
    w = jnp.take_along_axis(s, idx, axis=-1)
    if st["norm_topk"]:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    w = st["scaling"] * w
    n_pub = s.shape[-1]
    dense_w = jnp.sum(jax.nn.one_hot(idx, n_pub, dtype=jnp.float32) * w[..., None], axis=1)

    def one_expert(acc, e):
        y = _swiglu(h, *(_w(m[k][e], bits) for k in ("gate_kernel", "up_kernel", "down_kernel")))
        return acc + dense_w[:, first + e, None] * y, None

    out, _ = jax.lax.scan(one_expert, jnp.zeros_like(h), jnp.arange(count))
    # [family] the shared experts are one ungated SwiGLU of n_shared x the expert width
    shared = _swiglu(h, *(_w(m[k], bits) for k in
                          ("shared_gate_kernel", "shared_up_kernel", "shared_down_kernel")))
    return out + shared, margin


@functools.partial(jax.jit, static_argnames=("st", "bits"))
def _layer(lp, x, *, st, bits=None):
    """One decoder layer on one sequence. x: [T, H] float32. Returns (x, the
    router's margin per token: infinite in a dense layer). `st`: a tuple of
    (name, value) pairs, hashable."""
    s = dict(st)
    # [family] pre-norm placement
    h = _rms_norm(x, _w(lp["input_norm"], bits), s["eps"])
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
    beta_fast, beta_slow, mscale, mscale_all = mc.rope_yarn or (32.0, 1.0, 1.0, 0.0)
    factor = float(mc.rope_scaling_factor) if mc.rope_scaling_type == "yarn" else 1.0
    return tuple(dict(
        eps=float(mc.rms_norm_eps), theta=float(mc.rope_theta),
        heads=int(mc.num_attention_heads), nope=int(mc.qk_nope_head_dim),
        rope=int(mc.qk_rope_head_dim), dv=int(mc.v_head_dim), latent=int(mc.kv_lora_rank),
        yarn=(factor, int(mc.rope_original_max_position), float(beta_fast), float(beta_slow),
              float(mscale), float(mscale_all)),
        sparse=i >= mc.first_k_dense, top_k=int(mc.num_experts_per_tok),
        n_group=int(mc.moe_n_group), topk_group=int(mc.moe_topk_group),
        norm_topk=bool(mc.norm_topk_prob), scaling=float(mc.routed_scaling_factor),
        first=int(first)).items())


def hidden_states(params, model_config, ids, held=None, bits=None):
    """(x after the last layer: [T, H] float32, and per position the
    smallest router margin of any layer: [T])."""
    x = jnp.take(params["embed"]["embedding"], ids, axis=0).astype(jnp.float32)
    if bits is not None:
        x = _round(x, bits)
    margin = jnp.full(ids.shape, jnp.inf, jnp.float32)
    for i in range(model_config.num_hidden_layers):
        x, m = _layer(params[f"layers_{i}"], x, st=layer_statics(model_config, i, held), bits=bits)
        margin = jnp.minimum(margin, m)
    return x, margin


def _logprobs(params, model_config, ids, temperature, held=None, bits=None):
    x, margin = hidden_states(params, model_config, ids, held, bits)
    lp = _head_logprobs(params["final_norm"], params["lm_head"]["kernel"], x[:-1], ids[1:],
                        jnp.float32(temperature), eps=float(model_config.rms_norm_eps), bits=bits)
    return lp, margin


def token_logprobs(params, model_config, token_ids, temperature: float = 1.0,
                   pad_to: int = 0, held: tuple[int, int] | None = None,
                   with_margins: bool = False, weight_bits: int | None = None):
    """log p(token[t+1] | token[:t+1]) for t in [0, T-1): float32 [T-1].
    With `with_margins` also, for the position that predicts each of them,
    the smallest relative gap between its k-th and (k+1)-th expert's score in
    any sparse layer: float32 [T-1].

    `params` is the program's tree (any dtype, any placement); `token_ids`
    one sequence of length T. `held` = (first, count) overrides the
    configuration's held range. `pad_to` right-pads the sequence so that
    sequences of several lengths share one compiled shape; attention is
    causal and a token's experts depend on its own row alone, so the padding
    cannot reach the positions that are returned. `weight_bits`: every weight
    rounded to that many mantissa bits as it is used."""
    n = len(token_ids)
    ids = np.zeros(max(n, pad_to), dtype=np.int32)
    ids[:n] = np.asarray(token_ids, dtype=np.int32)
    with jax.default_matmul_precision("highest"):
        lp, margin = _logprobs(params, model_config, jnp.asarray(ids), temperature, held,
                               weight_bits)
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
