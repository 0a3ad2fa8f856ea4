"""MoE decoder: routing correctness, EP sharding, HF roundtrip, training."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from areal_tpu.api.alloc_mode import ParallelStrategy
from areal_tpu.models.qwen2 import (
    ModelConfig,
    PADDING_SEGMENT,
    forward,
    init_params,
    moe_mlp,
    param_logical_axes,
    param_shapes,
)
from areal_tpu.parallel import mesh as mesh_lib

MOE_CFG = ModelConfig(
    vocab_size=64,
    hidden_size=32,
    intermediate_size=64,
    num_hidden_layers=2,
    num_attention_heads=4,
    num_key_value_heads=2,
    dtype="float32",
    param_dtype="float32",
    num_experts=4,
    num_experts_per_tok=2,
    moe_intermediate_size=16,
    attn_impl="dense",
)


def test_moe_param_shapes_and_axes_align():
    shapes = param_shapes(MOE_CFG)
    axes = param_logical_axes(MOE_CFG)
    mlp_s = shapes["layers"]["mlp"]
    mlp_a = axes["layers"]["mlp"]
    assert mlp_s["gate_kernel"] == (2, 4, 32, 16)  # [L, E, H, Mm]
    assert mlp_a["gate_kernel"] == ("layers", "experts", "embed", "mlp")
    assert mlp_s["router_kernel"] == (2, 32, 4)


def test_moe_mlp_matches_explicit_topk_reference():
    """Sorted grouped matmuls == naive per-token top-k mixture."""
    rng = np.random.RandomState(0)
    T, H, E, K, Mm = 64, 16, 4, 2, 8
    cfg = ModelConfig(
        hidden_size=H,
        num_experts=E,
        num_experts_per_tok=K,
        moe_intermediate_size=Mm,
        norm_topk_prob=True,
    )
    p = {
        "router_kernel": jnp.asarray(rng.randn(H, E), jnp.float32),
        "gate_kernel": jnp.asarray(rng.randn(E, H, Mm) * 0.3, jnp.float32),
        "up_kernel": jnp.asarray(rng.randn(E, H, Mm) * 0.3, jnp.float32),
        "down_kernel": jnp.asarray(rng.randn(E, Mm, H) * 0.3, jnp.float32),
    }
    x = jnp.asarray(rng.randn(T, H), jnp.float32)
    y, aux = moe_mlp(p, x, cfg)

    # naive reference
    probs = jax.nn.softmax(x @ p["router_kernel"], axis=-1)
    vals, idx = jax.lax.top_k(probs, K)
    vals = vals / vals.sum(-1, keepdims=True)
    y_ref = np.zeros((T, H), np.float32)
    for t in range(T):
        for k in range(K):
            e = int(idx[t, k])
            h = np.asarray(x[t]) @ np.asarray(p["gate_kernel"][e])
            u = np.asarray(x[t]) @ np.asarray(p["up_kernel"][e])
            act = (h / (1 + np.exp(-h))) * u
            y_ref[t] += float(vals[t, k]) * (act @ np.asarray(p["down_kernel"][e]))
    np.testing.assert_allclose(np.asarray(y), y_ref, atol=2e-4, rtol=2e-4)
    assert np.isfinite(float(aux)) and float(aux) >= 1.0 - 1e-3  # >= 1 by Cauchy-Schwarz


def test_moe_skewed_router_loses_no_pair():
    """A router that sends most tokens to one expert loses no pair: there is
    no capacity, so every token's output is its own expert's, exactly (this
    test asserted the drop in the days of `capacity_factor`)."""
    rng = np.random.RandomState(1)
    T, H, E = 32, 8, 4
    cfg = ModelConfig(
        hidden_size=H, num_experts=E, num_experts_per_tok=1,
        moe_intermediate_size=4, norm_topk_prob=False,
    )
    router = rng.randn(H, E) * 0.01
    x = rng.randn(T, H)
    # bias the router along the tokens' mean direction towards expert 2
    x[:, 0] = np.abs(x[:, 0]) + 1.0
    router[0, 2] = 6.0
    p = {
        "router_kernel": jnp.asarray(router, jnp.float32),
        "gate_kernel": jnp.asarray(rng.randn(E, H, 4), jnp.float32),
        "up_kernel": jnp.asarray(rng.randn(E, H, 4), jnp.float32),
        "down_kernel": jnp.asarray(rng.randn(E, 4, H), jnp.float32),
    }
    x = jnp.asarray(x, jnp.float32)
    y, _, load = moe_mlp(p, x, cfg, with_load=True)
    probs = jax.nn.softmax(x @ p["router_kernel"], axis=-1)
    idx = np.asarray(jnp.argmax(probs, axis=-1))
    assert (idx == 2).sum() >= T - 4  # the skew is real: one expert, nearly all
    assert load.tolist() == [T, int(np.bincount(idx, minlength=E).max())]
    y_ref = np.zeros((T, H), np.float32)
    for t in range(T):
        e = int(idx[t])
        xt = np.asarray(x[t])
        g, u = xt @ np.asarray(p["gate_kernel"][e]), xt @ np.asarray(p["up_kernel"][e])
        y_ref[t] = float(probs[t, e]) * ((g / (1 + np.exp(-g))) * u) @ np.asarray(
            p["down_kernel"][e])
    np.testing.assert_allclose(np.asarray(y), y_ref, atol=2e-4, rtol=2e-4)
    assert (np.abs(np.asarray(y)).sum(-1) > 1e-6).all()  # no zero (dropped) row


@pytest.mark.slow
def test_moe_forward_and_grad_finite():
    params = init_params(MOE_CFG, jax.random.PRNGKey(0))
    T = 32
    ids = jnp.asarray(np.arange(T) % 64, jnp.int32)
    pos = jnp.arange(T, dtype=jnp.int32)
    seg = jnp.zeros(T, jnp.int32)
    logits, aux = forward(params, ids, pos, seg, MOE_CFG, with_aux=True)
    assert logits.shape == (T, 64)
    assert np.isfinite(np.asarray(logits)).all()
    assert float(aux) > 0

    def loss(p):
        lg, aux = forward(p, ids, pos, seg, MOE_CFG, with_aux=True)
        return jnp.mean(lg**2) + 0.01 * aux

    grads = jax.grad(loss)(params)
    flat = jax.tree.leaves(grads)
    assert all(np.isfinite(np.asarray(g)).all() for g in flat)
    # router gets gradient signal (through combine weights and aux)
    gnorm_router = float(
        jnp.linalg.norm(grads["layers"]["mlp"]["router_kernel"])
    )
    assert gnorm_router > 0


def test_moe_ep_sharding_compiles_on_mesh(cpu_devices):
    mesh = mesh_lib.build_mesh(
        ParallelStrategy(data_parallel_size=4, tensor_parallel_size=2)
    )
    rules = mesh_lib.default_rules()
    axes = param_logical_axes(MOE_CFG)
    shardings = jax.tree.map(
        lambda a: mesh_lib.named_sharding(mesh, a, rules),
        axes,
        is_leaf=lambda x: isinstance(x, tuple),
    )
    params = init_params(MOE_CFG, jax.random.PRNGKey(0))
    params = jax.tree.map(jax.device_put, params, shardings)
    # expert dim sharded over dp=4
    spec = shardings["layers"]["mlp"]["gate_kernel"].spec
    assert "dp" in str(spec)

    T = 128
    ids = jnp.asarray(np.arange(T) % 64, jnp.int32)
    pos = jnp.arange(T, dtype=jnp.int32)
    seg = jnp.zeros(T, jnp.int32)

    @jax.jit
    def f(p):
        return forward(p, ids, pos, seg, MOE_CFG)

    out = f(params)
    # matches unsharded run
    ref = forward(init_params(MOE_CFG, jax.random.PRNGKey(0)), ids, pos, seg, MOE_CFG)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-4, rtol=2e-4)


def test_moe_hf_roundtrip(tmp_path):
    from areal_tpu.models.hf_io import load_hf_params, save_hf_params

    params = init_params(MOE_CFG, jax.random.PRNGKey(3))
    out_dir = str(tmp_path / "ckpt")
    save_hf_params(params, MOE_CFG, out_dir)
    # config.json for from_hf_config-style consumers
    import json

    with open(f"{out_dir}/config.json", "w") as f:
        json.dump({"model_type": "qwen3_moe"}, f)
    loaded = load_hf_params(out_dir, MOE_CFG, dtype="float32")

    def cmp(a, b):
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32), atol=1e-6
        )

    jax.tree.map(cmp, params, loaded)
