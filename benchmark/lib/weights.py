"""Weights from the seed, made on the device in one jitted call, in the
dtype and placement the engine under test holds them in.

Only the tree's structure, shapes and dtypes are taken from the program
(`init_params`, abstractly); every value is drawn here, as a pure function of
the seed:

- projection kernels ~ N(0, 1/fan_in) with the true fan-in (hidden for q, k,
  v, gate and up; heads x head_dim for o; intermediate for down), so that
  activations keep unit scale and attention scores have a standard deviation
  near 1. (The program's own initialiser takes the fan-in of a stacked
  [layers, hidden, heads, head_dim] kernel to be the number of layers: scores
  come out with a standard deviation in the tens, attention is a hard argmax,
  and bf16 and float32 runs of the same weights decorrelate: PERF.md,
  Findings PR 23.)
- q/k/v biases ~ N(0, 0.5^2), so that a dropped bias changes q, k and v;
- norm weights ~ 1 + N(0, 0.1^2);
- the embedding (also the head when tied) ~ N(0, (LOGIT_STD/sqrt(hidden))^2),
  so that logits over a unit-RMS hidden state have a standard deviation of
  about LOGIT_STD and log-probabilities differ from token to token.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

LOGIT_STD = 2.5


def _std(name: str, path: str, shape: tuple, cfg) -> tuple[float, float]:
    """(mean, std) of a leaf, by its name in the program's tree."""
    hidden = cfg.hidden_size
    if name.endswith("bias"):
        return 0.0, 0.5
    if name.endswith("norm"):
        return 1.0, 0.1
    if name == "embedding" or "lm_head" in path:
        return 0.0, LOGIT_STD / math.sqrt(hidden)
    if name == "o_kernel":
        return 0.0, 1.0 / math.sqrt(shape[-3] * shape[-2])
    if name in ("q_kernel", "k_kernel", "v_kernel"):
        return 0.0, 1.0 / math.sqrt(shape[-3])
    # gate/up/down and anything else matrix-shaped: [..., in, out]
    return 0.0, 1.0 / math.sqrt(shape[-2] if len(shape) >= 2 else 1)


def seeded_params(model_config, seed: int, out_shardings=None):
    """The whole tree in one jitted call; `seed` may exceed 32 signed bits."""
    from areal_tpu.models.qwen2 import init_params

    abstract = jax.eval_shape(lambda: init_params(model_config, jax.random.PRNGKey(0)))
    leaves, treedef = jax.tree_util.tree_flatten_with_path(abstract)
    key = jax.random.fold_in(
        jax.random.PRNGKey(seed & 0x7FFFFFFF), (seed >> 31) & 0x7FFFFFFF)

    def make(key):
        out = []
        for (path, leaf), k in zip(leaves, jax.random.split(key, len(leaves))):
            name = str(getattr(path[-1], "key", ""))
            mean, std = _std(name, jax.tree_util.keystr(path), leaf.shape, model_config)
            x = mean + std * jax.random.normal(k, leaf.shape, jnp.float32)
            out.append(x.astype(leaf.dtype))
        return jax.tree.unflatten(treedef, out)

    return jax.jit(make, out_shardings=out_shardings)(key)
