"""Plain float32 reference of the SDAR-MoE block-diffusion decoder
(JetLM/SDAR-30B-A3B-Chat, `model_type: sdar_moe`): the yardstick `correct` is
decided against in the `sdar-30b-a3b-chat` cell.

Straightforward `jax.numpy`, one sequence at a time, float32 under
`jax.default_matmul_precision("highest")`, no cache, no kernels, no batching,
no sorting, no capacity. The backbone is the Qwen3-MoE decoder layer; for a row
`x` at position `i`:

    h  = RMSNorm(x; w_in)
    q  = RoPE(RMSNorm_head(h Wq -> [nH, hd]; q_norm), i)
    k  = RoPE(RMSNorm_head(h Wk -> [nKV, hd]; k_norm), i)      v = h Wv -> [nKV, hd]
    a  = softmax(q k_j^T / sqrt(hd) over visible j) v_j        (GQA: query head n reads KV head n // (nH / nKV))
    x  = x + a Wo
    h2 = RMSNorm(x; w_post);  p = softmax(h2 Wg) in float32 over all E;  T = top-k(p);  w_e = p_e / sum_T p
    x  = x + sum_{e in T} w_e * Wdown_e( silu(Wgate_e h2) * Wup_e h2 )

then the final RMSNorm and the untied head. No bias anywhere, `rope_theta` on
the whole head (rotate-half pairs (i, i + hd/2)).

**Visibility (block-causal, block length B):** `j` is visible to `i` iff
`j // B <= i // B`: causal across blocks, both directions inside a block;
blocks are aligned to absolute positions from 0.

**Generation** (the family's published block-diffusion sampler; `generate`),
prompt of P tokens, S denoise steps a block:

1. Positions `[0, (P // B) * B)` are context. The `P % B` prompt tokens left
   over are the first positions of the first generated block, already revealed.
2. A block starts as its revealed prefix (first block only) followed by
   `mask_token_id`. Repeat: one forward over the whole sequence up to the
   block's end. Logits at a position are of that position's OWN token (no
   shift). At every still-masked position take `x0` (greedy here: the argmax)
   and `conf = p(x0)` under the full-vocabulary softmax. Reveal:
   `low_confidence_static` reveals the `n_s` most confident masked positions,
   `n_s = B // S` plus one for the first `B % S` steps (ties go to the lower
   position); `low_confidence_dynamic` reveals every masked position with
   `conf > threshold`, and at least the static `n_s`. The log-probability
   of a token is `log p(x0)` at the step it was revealed.
3. When no mask is left the block is committed and the next starts all masked.
   (The program then runs one more forward, the commit pass, to write the
   clean block's rows to its cache. The reference has no cache: every forward
   of a later block recomputes every earlier row from the clean tokens.)
4. `max_new_tokens` ends the request mid-block: what the block held beyond is
   discarded.

Departures from the published code, each an `assumed` of the configuration's
file: block length 4, 4 denoise steps, the mask token's id, no logit shift,
block alignment to absolute position 0. The mixture is a DENSE product over
all experts masked by the top-k (every expert runs on every token, one expert
at a time; a token's row is weighted by w_e where the expert is among its k
and by exactly 0 elsewhere): the published sum, term for term, with no
dispatch to get wrong.

It reads the program's parameter tree (names and axis order of
`areal_tpu/models/qwen2.py:param_shapes`, layers stacked on axis 0, experts on
the next) but none of its code. Layers are visited one at a time and experts
one at a time inside a layer, each picked out of the stacked tree and cast to
float32 as it is used, so no second copy of the weights is ever alive.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

# Tolerance of the comparison, per compared request (every token revealed at
# every rebuilt denoise state of its checked blocks: 44-48 tokens), with its
# reason (benchmark/lib/kind_rollout_diffusion.py applies it). The program
# computes in bf16 through 5 layers, the reference in float32. ONE limit, on
# the mean |delta log-probability|, from two readings on the v5e at the
# published widths (PERF.md, Findings PR 36): the program's largest over its
# seeds, 0.155 (0.059-0.155 over 72 requests of twelve runs), and the
# reference with its weights rounded to float8's 3 mantissa bits as they are
# used (`weight_bits=3`), the nearest precision below bf16's, which has to
# fail: 0.391-0.519 over six requests. (The engine with its commit pass
# switched off reads 0.71-1.72.) The program's level is this model's bf16
# arithmetic and not the cache path: the trainer's plain `forward` in bf16
# reads 0.08-0.12 against this reference on the CPU (no cache, no kernel),
# four times OLMoE's, because every masked position of every slot enters the
# stack as the same embedding row, a near-tie at the k-th of 128 experts falls
# differently in bf16 and float32 and swaps an eighth of a layer's
# renormalised mixture, and five layers average less of it out than eight.
# The largest single delta and the 90th percentile are reported and decide
# nothing: the largest overlaps (0.26-1.09 against 1.21-1.45 with one request
# at 1.09: it IS such a flip), and of 44-48 tokens the 90th percentile is the
# fifth largest (0.16-0.33 against 0.79-0.95: a second limit would add a way
# to fail a run and nothing the mean does not see).
MEAN_ABS_TOL = 0.2

STRATEGIES = ("low_confidence_static", "low_confidence_dynamic")


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


def _round(x, bits: int):
    """float32 `x` rounded to `bits` mantissa bits (3: float8 e4m3's)."""
    m, e = jnp.frexp(x)
    scale = float(1 << (bits + 1))
    return jnp.ldexp(jnp.round(m * scale) / scale, e)


def _f32(tree, bits=None):
    """Every leaf in float32 as it is used; `bits`: rounded to that many
    mantissa bits on the way (the must-fail reading: one precision lower)."""
    cast = (lambda a: a.astype(jnp.float32)) if bits is None else (
        lambda a: _round(a.astype(jnp.float32), bits))
    return jax.tree.map(cast, tree)


def visible(n: int, block_length: int):
    """[n, n] bool: position j (column) is visible to position i (row)."""
    blk = jnp.arange(n) // block_length
    return blk[None, :] <= blk[:, None]


def _attention(a, h, eps, theta, block_length, bits=None):
    """h: [T, H] float32, already normed. `a`: the layer's attention leaves."""
    a = _f32(a, bits)
    T = h.shape[0]
    pos = jnp.arange(T)
    n_q, hd = a["q_kernel"].shape[-2:]
    n_kv = a["k_kernel"].shape[-2]
    q = jnp.einsum("th,hnd->tnd", h, a["q_kernel"])
    k = jnp.einsum("th,hnd->tnd", h, a["k_kernel"])
    v = jnp.einsum("th,hnd->tnd", h, a["v_kernel"])
    # per head, over the head's hd lanes
    q = _rms_norm(q, a["q_norm"], eps)
    k = _rms_norm(k, a["k_norm"], eps)
    q, k = _rope(q, pos, theta), _rope(k, pos, theta)
    rep = n_q // n_kv  # query head n reads kv head n // rep
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    s = jnp.einsum("tnd,snd->nts", q, k) / np.sqrt(hd)
    s = jnp.where(visible(T, block_length)[None], s, -jnp.inf)
    o = jnp.einsum("nts,snd->tnd", jax.nn.softmax(s, axis=-1), v)
    return jnp.einsum("tnd,ndh->th", o, a["o_kernel"])


def _moe(m, i, h, top_k: int, norm_topk: bool, bits=None):
    """h: [T, H] float32, already normed. `m`: the MLP leaves of ALL layers,
    `i` this layer's index: one expert's kernels are picked out at a time.
    Dense over all experts, masked by the top-k (see the module's docstring)."""
    p = jax.nn.softmax(h @ _f32(m["router_kernel"][i], bits), axis=-1)  # [T, E]
    w, idx = jax.lax.top_k(p, top_k)
    if norm_topk:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    n_experts = p.shape[-1]
    # [T, E]: w_e at the token's chosen experts, exactly 0 elsewhere
    dense_w = jnp.sum(jax.nn.one_hot(idx, n_experts, dtype=jnp.float32) * w[..., None], axis=1)

    def one_expert(acc, e):
        gate, up, down = (_f32(m[k][i, e], bits)
                          for k in ("gate_kernel", "up_kernel", "down_kernel"))
        y = (jax.nn.silu(h @ gate) * (h @ up)) @ down
        return acc + dense_w[:, e, None] * y, None

    out, _ = jax.lax.scan(one_expert, jnp.zeros_like(h), jnp.arange(n_experts))
    return out


@functools.partial(jax.jit, static_argnames=("eps", "theta", "top_k", "norm_topk",
                                             "block_length", "bits"))
def _layer(layers, i, x, *, eps: float, theta: float, top_k: int, norm_topk: bool,
           block_length: int, bits=None):
    """Decoder layer `i` on one sequence. x: [T, H] float32. `layers` is the
    whole stacked tree: only what is used is ever cast to float32."""
    small = {k: v for k, v in layers.items() if k != "mlp"}
    lp = jax.tree.map(lambda a: a[i], small)
    h = _rms_norm(x, _f32(lp["input_norm"], bits), eps)
    x = x + _attention(lp["attn"], h, eps, theta, block_length, bits)
    h = _rms_norm(x, _f32(lp["post_attn_norm"], bits), eps)
    return x + _moe(layers["mlp"], i, h, top_k, norm_topk, bits)


@functools.partial(jax.jit, static_argnames=("eps", "bits"))
def _head_logprobs(final_norm, head, x, *, eps: float, bits=None):
    """log softmax(logits) per row. head: [H, V]."""
    x = _rms_norm(x, _f32(final_norm, bits), eps)
    return jax.nn.log_softmax(x @ _f32(head, bits), axis=-1)


def _statics(cfg) -> dict:
    if not cfg.block_length or cfg.block_length < 1:
        raise ValueError("sdar_ref: the configuration has no block_length")
    return dict(eps=float(cfg.rms_norm_eps), theta=float(cfg.rope_theta),
                top_k=int(cfg.num_experts_per_tok), norm_topk=bool(cfg.norm_topk_prob),
                block_length=int(cfg.block_length))


def _hidden(params, cfg, ids, bits=None):
    """Every layer over one sequence: [T, H] float32 before the final norm."""
    statics = _statics(cfg)
    x = _f32(jnp.take(params["embed"]["embedding"], ids, axis=0), bits)
    n_layers = jax.tree.leaves(params["layers"])[0].shape[0]
    for i in range(n_layers):
        x = _layer(params["layers"], i, x, bits=bits, **statics)
    return x


def _padded(tokens, pad_to: int, block_length: int):
    """Right-padded to `pad_to` (a whole number of blocks, so that padding
    starts a block of its own and no real position sees it)."""
    n = len(tokens)
    width = max(n, pad_to)
    width += (-width) % block_length
    if n % block_length and width > n:
        raise ValueError(f"a padded sequence must end on a block boundary: {n} % {block_length}")
    ids = np.zeros(width, dtype=np.int32)
    ids[:n] = np.asarray(tokens, dtype=np.int32)
    return jnp.asarray(ids)


def forward_logits(params, cfg, tokens):
    """Log-softmax over the vocabulary at every position of one sequence
    under the block mask: float32 [T, V]; row t is of position t's OWN token.
    (For the CPU tests: at the published widths [T, V] is 0.8 GB.)"""
    ids = jnp.asarray(np.asarray(tokens, dtype=np.int32))
    with jax.default_matmul_precision("highest"):
        x = _hidden(params, cfg, ids)
        return np.asarray(_head_logprobs(params["final_norm"], params["lm_head"]["kernel"], x,
                                         eps=float(cfg.rms_norm_eps)))


def state_logprobs(params, cfg, context, block_state, pad_to: int = 0, weight_bits=None):
    """One denoise state: `context` is the prompt followed by the committed
    tokens of every earlier block (a whole number of blocks), `block_state`
    the B tokens of the block as the forward saw them (`mask_token_id` where
    still masked). A full forward over context + block, and the log-softmax at
    the block's B positions: float32 [B, V]. `pad_to` right-pads so that
    states of several depths share a compiled shape. `weight_bits`: every
    weight rounded to that many mantissa bits as it is used (3: float8's, the
    nearest precision below bf16's, which the comparison has to fail); where
    memory allows a second tree, `round_mantissa` gives the same numbers."""
    B = int(cfg.block_length)
    if len(context) % B or len(block_state) != B:
        raise ValueError(f"context of {len(context)} and a block of {len(block_state)} at B={B}")
    n = len(context) + B
    ids = _padded(list(context) + list(block_state), pad_to, B)
    with jax.default_matmul_precision("highest"):
        x = _hidden(params, cfg, ids, weight_bits)
        return np.asarray(_head_logprobs(params["final_norm"], params["lm_head"]["kernel"],
                                         x[n - B:n], eps=float(cfg.rms_norm_eps),
                                         bits=weight_bits))


def loss_and_grads(params, cfg, tokens):
    """Mean negative log-likelihood of each position's OWN token under the
    block mask and its gradient with respect to every leaf of `params` (for
    the CPU tests: the trainer's `forward` under the mask is compared)."""
    ids = jnp.asarray(np.asarray(tokens, dtype=np.int32))

    def nll(p):
        lp = _head_logprobs(p["final_norm"], p["lm_head"]["kernel"], _hidden(p, cfg, ids),
                            eps=float(cfg.rms_norm_eps))
        return -jnp.mean(jnp.take_along_axis(lp, ids[:, None], axis=-1))

    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(nll)(params)


def reveal_quota(step: int, block_length: int, steps: int) -> int:
    """`n_s`: positions the static schedule reveals at denoise step `step`."""
    return block_length // steps + (1 if step < block_length % steps else 0)


def reveal(conf, masked, step: int, steps: int, strategy: str, threshold: float):
    """Which masked positions are revealed at this step: bool [B]. `conf` [B]
    the confidence of each position's `x0`, `masked` [B] bool."""
    if strategy not in STRATEGIES:
        raise ValueError(f"remasking strategy {strategy!r} not in {STRATEGIES}")
    B = len(conf)
    n_s = reveal_quota(step, B, steps)
    # most confident first, ties to the lower position; revealed ones last
    order = sorted(range(B), key=lambda j: (not masked[j], -float(conf[j]), j))
    out = np.zeros(B, dtype=bool)
    for rank, j in enumerate(order):
        if masked[j] and (rank < n_s or (strategy == "low_confidence_dynamic"
                                         and float(conf[j]) > threshold)):
            out[j] = True
    return out


def generate(params, cfg, prompt, max_new_tokens: int, steps: int = 4,
             strategy: str = "low_confidence_static", threshold: float = 0.9):
    """The whole sampler, greedy, as a Python loop (for the CPU tests).
    Returns (tokens, logprobs, reveal_steps), each `max_new_tokens` long or
    shorter never: a request ends at `max_new_tokens`, mid-block if need be."""
    B, mask_id = int(cfg.block_length), int(cfg.mask_token_id)
    seq = list(prompt)
    P = len(seq)
    out_t, out_l, out_s = [], [], []
    context = seq[: (P // B) * B]
    seed = seq[len(context):]
    while len(out_t) < max_new_tokens:
        tok = seed + [mask_id] * (B - len(seed))
        known = [True] * len(seed) + [False] * (B - len(seed))
        logp = [0.0] * B
        at = [-1] * B
        step = 0
        while not all(known):
            lp = state_logprobs(params, cfg, context, tok)
            x0 = lp.argmax(axis=-1)
            x0_lp = lp[np.arange(B), x0]
            rev = reveal(np.exp(x0_lp), [not k for k in known], step, steps, strategy,
                         threshold)
            for j in np.nonzero(rev)[0]:
                tok[j], known[j], logp[j], at[j] = int(x0[j]), True, float(x0_lp[j]), step
            step += 1
        for j in range(len(seed), B):
            out_t.append(tok[j]); out_l.append(logp[j]); out_s.append(at[j])
        context = context + tok
        seed = []
    return out_t[:max_new_tokens], out_l[:max_new_tokens], out_s[:max_new_tokens]


def round_mantissa(params, bits: int):
    """The tree with every leaf rounded to `bits` mantissa bits (3: float8
    e4m3's), in its own dtype: the nearest precision below bf16's 7, which the
    comparison has to fail."""
    return jax.tree.map(lambda a: _round(a.astype(jnp.float32), bits).astype(a.dtype), params)
