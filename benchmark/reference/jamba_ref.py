"""The plain reference for Jamba (`model_type: jamba`, AI21-Jamba2-3B): the
whole forward pass in `jax.numpy`, float32, matmuls at `highest` precision,
no cache, no kernel, no chunked scan. Independent of `areal_tpu/models/`: it
reads the program's parameter TREE by name (leaf names and layouts of
`areal_tpu/models/qwen2.py:param_shapes`; a run of like layers is held
stacked under `run_{first}_{past_last}`, any other layer as `layers_{i}`,
and which layer lies where is worked out HERE from the names) and the
configuration's numbers, and nothing of the program's code.

The layer, as the configuration file's `assumed` states it ([family] where
the published keys leave it unsaid):

- 28 pre-norm residual layers, `x = x + mixer(rmsnorm(x)); x = x +
  mlp(rmsnorm(x))`, a final norm, the head tied to the embedding. Layer i is
  attention iff `i % attn_layer_period == attn_layer_offset`, else Mamba.
- MLP: `down(silu(gate(x)) * up(x))`, dense, in every layer.
- Attention: `num_attention_heads` query heads over `num_key_value_heads`
  key/value heads, causal, scale head_dim^-0.5, NO positional encoding.
- Mamba mixer: `[u | z] = W_in x`; `u = silu(conv1d(u) + b)`, depthwise,
  causal, zeros before the sequence's start; `[dt_r | B | C] = W_x u`, an
  RMSNorm each; `dt = softplus(W_dt dt_r + b_dt)`; `A = -exp(A_log)`;
  `h_t = exp(dt_t A) h_{t-1} + dt_t B_t u_t`, `y_t = C_t . h_t + D u_t`, the
  recurrence TOKEN BY TOKEN (`lax.scan` over tokens); out `W_out (y silu(z))`.

Departures from the published description, each at its line: the tree keeps
`A_log` as `[state lanes, channels]` (a checkpoint's transposed; `hf_io`
turns it), and attention is computed a block of queries at a time so that
3,072 positions at the published widths fit beside the engine.
"""

from __future__ import annotations

import functools
import re

import jax
import jax.numpy as jnp
import numpy as np

# The comparison's limits, a sequence's mean and its largest |difference| in
# nat, each between two readings on the chip (PERF.md section 2 has them with
# their calls): the bf16 program's largest over its seeds (mean 0.074-0.084,
# largest 0.26-0.42: a dense model, no router to flip, the level is the
# residual stream's bf16 rounding through 28 layers) and this reference with
# its weights at float8's 3 mantissa bits, which must fail each.
MEAN_ABS_TOL = 0.16
MAX_ABS_TOL = 0.9
# On the caches themselves, what log-probabilities cannot see (a state
# rounded to bf16 moves them by less than the bf16 activations do):
STATE_F32_SHARE_MIN = 0.5  # of the pool's non-zero entries, those beyond bf16
# the largest |difference| of STATE_STEPS token steps of the program's state
# update against `ssm_step` below on the same inputs, over the largest
# entry: float32 arithmetic in another order reads 1e-6 or less, a state
# rounded to bf16 each step 2e-3 and more
STATE_STEP_REL_TOL = 5e-5
# of the attention pool's non-zero entries, the share float8 (e4m3) cannot hold
ROWS_BEYOND_F8_SHARE_MIN = 0.5

Q_BLOCK = 256


def _round(x, bits):
    """x rounded to `bits` mantissa bits (None: as it is)."""
    if bits is None:
        return x
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=bits)


def _w(a, bits=None):
    """A weight as the arithmetic takes it: float32, rounded to `bits`
    mantissa bits as it is used (no second tree beside the engine)."""
    return _round(a.astype(jnp.float32), bits)


def _rms_norm(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w


def ssm_step(h, inp, state_bits=None):
    """One token of the recurrence for one sequence: h [N, Di]; inp = (dt, u
    [Di], B, C [N], A [N, Di], D [Di]). Returns (h, y [Di])."""
    dt, u, B, C, A, D = inp
    h = jnp.exp(dt[None, :] * A) * h + (dt * u)[None, :] * B[:, None]
    h = _round(h, state_bits)
    return h, jnp.sum(h * C[:, None], axis=0) + D * u


def _mamba(a, x, st, state_bits=None, bits=None):
    f32 = jnp.float32
    a = {k: _w(v, bits) for k, v in a.items()}
    T = x.shape[0]
    N, Rk = st["d_state"], st["dt_rank"]
    uz = x @ a["in_kernel"].astype(f32)
    Di = uz.shape[-1] // 2
    u, z = uz[:, :Di], uz[:, Di:]
    kernel = a["conv_kernel"].astype(f32)  # [Di, K]
    K = kernel.shape[1]
    padded = jnp.pad(u, ((K - 1, 0), (0, 0)))
    u = sum(padded[j : j + T] * kernel[:, j] for j in range(K))
    if "conv_bias" in a:
        u = u + a["conv_bias"].astype(f32)
    u = jax.nn.silu(u)
    proj = u @ a["x_kernel"].astype(f32)
    dt_r = _rms_norm(proj[:, :Rk], a["dt_norm"].astype(f32), st["eps"])  # [family]
    B = _rms_norm(proj[:, Rk : Rk + N], a["b_norm"].astype(f32), st["eps"])
    C = _rms_norm(proj[:, Rk + N :], a["c_norm"].astype(f32), st["eps"])
    dt = jax.nn.softplus(dt_r @ a["dt_kernel"].astype(f32) + a["dt_bias"].astype(f32))
    # (departure: the tree holds A_log as [state lanes, channels])
    A = -jnp.exp(a["ssm_A_log"].astype(f32))
    D = a["D"].astype(f32)

    def one(h, xs):
        return ssm_step(h, (*xs, A, D), state_bits)

    _, y = jax.lax.scan(one, jnp.zeros((N, Di), f32), (dt, u, B, C))
    return (y * jax.nn.silu(z)) @ a["out_kernel"].astype(f32)


def _attention(a, x, st, bits=None):
    f32 = jnp.float32
    a = {k: _w(v, bits) for k, v in a.items()}
    T = x.shape[0]
    q = jnp.einsum("th,hnd->tnd", x, a["q_kernel"].astype(f32))
    k = jnp.einsum("th,hnd->tnd", x, a["k_kernel"].astype(f32))
    v = jnp.einsum("th,hnd->tnd", x, a["v_kernel"].astype(f32))
    nH, nKV, hd = q.shape[1], k.shape[1], q.shape[2]
    k, v = (jnp.repeat(t, nH // nKV, axis=1) for t in (k, v))
    # (departure: a block of queries at a time; no positional encoding [family])
    blocks = []
    for lo in range(0, T, Q_BLOCK):
        qb = q[lo : lo + Q_BLOCK]
        s = jnp.einsum("tnd,snd->nts", qb, k) * hd ** -0.5
        seen = jnp.arange(T)[None, :] <= (lo + jnp.arange(qb.shape[0]))[:, None]
        p = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1)
        blocks.append(jnp.einsum("nts,snd->tnd", p, v))
    o = jnp.concatenate(blocks, axis=0)
    return jnp.einsum("tnd,ndh->th", o, a["o_kernel"].astype(f32))


@functools.partial(jax.jit, static_argnames=("st", "state_bits", "bits"))
def _layer(lp, x, *, st, state_bits=None, bits=None):
    s = dict(st)
    h = _rms_norm(x, _w(lp["input_norm"], bits), s["eps"])
    if s["mamba"]:
        x = x + _mamba(lp["attn"], h, s, state_bits, bits)
    else:
        x = x + _attention(lp["attn"], h, s, bits)
    h = _rms_norm(x, _w(lp["post_attn_norm"], bits), s["eps"])
    m = {k: _w(v, bits) for k, v in lp["mlp"].items()}
    gate, up = h @ m["gate_kernel"], h @ m["up_kernel"]
    return x + (jax.nn.silu(gate) * up) @ m["down_kernel"]


@functools.partial(jax.jit, static_argnames=("eps", "bits"))
def _head_logprobs(final_norm, embedding, x, labels, temperature, *, eps: float, bits=None):
    """log softmax(logits / temperature)[label] per position; the head is
    the embedding, tied."""
    x = _rms_norm(x, _w(final_norm, bits), eps)
    logits = (x @ _w(embedding, bits).T) / temperature
    logz = jax.nn.logsumexp(logits, axis=-1)
    return jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0] - logz


def layer_statics(model_config, i: int) -> tuple:
    """The numbers of layer i, read from the configuration (and nothing of
    the program's code), as a hashable tuple of pairs."""
    mc = model_config
    return tuple(dict(
        eps=float(mc.rms_norm_eps), mamba=mc.layer_types[i] == "mamba",
        d_state=int(mc.ssm_state_size), dt_rank=int(mc.ssm_dt_rank)).items())


def layer_params(params, i: int):
    """Layer i's leaves from the program's tree, by NAME: `layers_{i}`, or
    row i - first of the stacked run `run_{first}_{past_last}` that holds it."""
    if f"layers_{i}" in params:
        return params[f"layers_{i}"]
    for key in params:
        m = re.fullmatch(r"run_(\d+)_(\d+)", key)
        if m and int(m[1]) <= i < int(m[2]):
            return jax.tree.map(lambda a: a[i - int(m[1])], params[key])
    raise KeyError(f"layer {i} is in no entry of the tree ({sorted(params)})")


def hidden_states(params, model_config, ids, state_bits=None, bits=None):
    """x after the last layer: [T, H] float32."""
    x = _w(jnp.take(params["embed"]["embedding"], ids, axis=0), bits)
    for i in range(model_config.num_hidden_layers):
        x = _layer(layer_params(params, i), x, st=layer_statics(model_config, i),
                   state_bits=state_bits, bits=bits)
    return x


def logits(params, model_config, token_ids):
    """Float32 logits [T, V] of one sequence (for the CPU tests)."""
    ids = jnp.asarray(np.asarray(token_ids, dtype=np.int32))
    with jax.default_matmul_precision("highest"):
        x = hidden_states(params, model_config, ids)
        x = _rms_norm(x, params["final_norm"].astype(jnp.float32),
                      float(model_config.rms_norm_eps))
        return x @ params["embed"]["embedding"].astype(jnp.float32).T


def _logprobs(params, model_config, ids, temperature, state_bits=None, bits=None):
    x = hidden_states(params, model_config, ids, state_bits, bits)
    return _head_logprobs(params["final_norm"], params["embed"]["embedding"], x[:-1], ids[1:],
                          jnp.float32(temperature), eps=float(model_config.rms_norm_eps),
                          bits=bits)


def token_logprobs(params, model_config, token_ids, temperature: float = 1.0,
                   pad_to: int = 0, state_bits: int | None = None,
                   weight_bits: int | None = None):
    """log p(token[t+1] | token[:t+1]) for t in [0, T-1): float32 [T-1].

    `params` is the program's tree (any dtype, any placement); `token_ids`
    one sequence of length T. `pad_to` right-pads the sequence so that
    sequences of several lengths share one compiled shape; attention, the
    convolution and the recurrence are causal, so the padding cannot reach
    the positions that are returned. `state_bits`: the recurrent state
    rounded to that many mantissa bits after every token; `weight_bits`: every
    weight rounded to that many as it is used."""
    n = len(token_ids)
    ids = np.zeros(max(n, pad_to), dtype=np.int32)
    ids[:n] = np.asarray(token_ids, dtype=np.int32)
    with jax.default_matmul_precision("highest"):
        lp = _logprobs(params, model_config, jnp.asarray(ids), temperature, state_bits,
                       weight_bits)
    return np.asarray(lp)[: n - 1]


def loss_and_grads(params, model_config, token_ids, temperature: float = 1.0):
    """Mean negative log-likelihood of one sequence's next tokens and its
    gradient with respect to every leaf of `params` (for the CPU tests)."""
    ids = jnp.asarray(np.asarray(token_ids, dtype=np.int32))

    def nll(p):
        return -jnp.mean(_logprobs(p, model_config, ids, temperature))

    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(nll)(params)
