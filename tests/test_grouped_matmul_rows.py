"""The row layout of the grouped expert matmuls (`grouped_matmul_rows`).

XLA:TPU tiles `jax.lax.ragged_dot`'s rows by the largest power of two, at
most 512, that divides their count (`tests/test_trace_names.py` pins that on
a described v5e), so `_expert_mixture_plain` lays few rows a group out with
one tile of zero rows more, past the last group. Held here on the CPU: the
rule at the benchmark's shapes; the layout changes no bit of any result,
against the same call with the rule made the identity, through every way
`moe_mlp` is reached; and the engine's two counters of it.
"""

from dataclasses import replace

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from areal_tpu.api.cli_args import (
    GenerationHyperparameters,
    InferenceEngineConfig,
    JaxDecodeConfig,
)
from areal_tpu.api.io_struct import ModelRequest
from areal_tpu.models import qwen2
from areal_tpu.models.qwen2 import ModelConfig, grouped_matmul_rows, init_params

from test_pool_in_place import _walk

# 16 experts, 8 a token: 64 tokens are 512 pair rows at 32 a group (laid out
# at 640), 128 tokens 1,024 at 64 (1,152); 1, 7 and 65 tokens (8, 56, 520
# rows) already tile finely and stay
E, K, H, M, L = 16, 8, 16, 32, 3
ACT = jax.nn.silu


@pytest.mark.parametrize("rows,groups,want", [
    (512, 64, 640),      # rollout-olmoe-gsm8k: 64 slots x top-8 over 64 experts
    (512, 16, 640),      # rollout-kexaone-mixedlen: the same rows over the 16 held
    (640, 64, 640),      # rollout-qwen3next-mixedlen: 64 x top-10 tile at 128 as they are
    (16384, 64, 16384),  # a 2,048-token prefill bucket at OLMoE: 256 rows a group
    (8192, 16, 8192),    # K-EXAONE's shortest prompt: 512 rows a held group
    (10240, 64, 10240),  # Qwen3-Next's
    (2048, 64, 2176),    # a 256-token prefill bucket at OLMoE: 32 rows a group
    (8, 64, 8), (56, 64, 56), (520, 64, 520), (384, 64, 384),  # a finer tile already
    (1024, 16, 1152), (256, 4, 384),
    (512, 4, 512), (1024, 8, 1024),  # a tile's rows a group: the 512 tile is right
])
def test_rule_at_the_cells_shapes(rows, groups, want):
    got = grouped_matmul_rows(rows, groups)
    assert got == want
    if got != rows:
        # the largest power of two that divides it is the tile
        assert got % qwen2.GROUPED_MATMUL_ROW_TILE == 0
        assert got % (2 * qwen2.GROUPED_MATMUL_ROW_TILE) != 0
        assert got - rows <= qwen2.GROUPED_MATMUL_ROW_TILE


def _inputs(T, seed=11, groups=E):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(ks[0], (T, H))
    # ids in [0, E]: E routes a pair nowhere; expert 5 gets none
    expert = jax.random.randint(ks[1], (T, K), 0, E + 1)
    expert = jnp.where(expert == 5, 6, expert)
    gates = jnp.where(expert < E, jax.random.uniform(ks[2], (T, K)), 0.0)
    kernels = [jax.random.normal(k, s) / 4.0 for k, s in
               zip(ks[3:], ((groups, H, M), (groups, H, M), (groups, M, H)))]
    return x, expert, gates, kernels


MOE = ModelConfig(
    vocab_size=64, hidden_size=H, intermediate_size=M, num_hidden_layers=2,
    num_attention_heads=4, num_key_value_heads=4, dtype="float32", param_dtype="float32",
    model_type="olmoe", qkv_bias=False, qk_norm=True, qk_norm_full=True, num_experts=E,
    num_experts_per_tok=K, moe_intermediate_size=M, norm_topk_prob=False, attn_impl="dense",
)
HELD = replace(MOE, num_experts_published=4 * E, expert_first=E, moe_scoring="sigmoid",
               moe_router_bias=True, norm_topk_prob=True, routed_scaling_factor=2.5)


def _layer_params(cfg, seed=3):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    p = dict(router_kernel=jax.random.normal(ks[0], (H, cfg.num_experts_published_)),
             gate_kernel=jax.random.normal(ks[1], (E, H, M)) / 4.0,
             up_kernel=jax.random.normal(ks[2], (E, H, M)) / 4.0,
             down_kernel=jax.random.normal(ks[3], (E, M, H)) / 4.0)
    if cfg.moe_router_bias:
        p["router_bias"] = 0.01 * jax.random.normal(ks[4], (cfg.num_experts_published_,))
    return p


def _plain(T):
    x, expert, gates, kernels = _inputs(T)
    return (lambda *a: qwen2._expert_mixture_plain(ACT, E, *a)), (x, expert, gates, *kernels)


def _stacked(T):
    x, expert, gates, stack = _inputs(T, groups=L * E)
    return (lambda *a: qwen2._expert_mixture_plain(ACT, E, *a)), (
        x, expert, gates, *stack, jnp.int32(E))


def _moe_mlp(cfg, T, masked):
    x = jax.random.normal(jax.random.PRNGKey(5), (T, H))
    valid = (jnp.arange(T) % 5 != 3) if masked else None

    def fn(p, x):
        return qwen2.moe_mlp(p, x, cfg, valid=valid, with_load=True)

    return fn, (_layer_params(cfg), x)


def _vmapped(B, T):
    """As the engine's batched prefill reaches it: `moe_mlp` under `vmap`,
    the parameters closed over, folded into one call on B*T tokens."""
    x = jax.random.normal(jax.random.PRNGKey(6), (B, T, H))
    n = jnp.arange(B) * 3 + T - 9

    def fn(p, x, n):
        return jax.vmap(
            lambda xb, nb: qwen2.moe_mlp(p, xb, MOE, valid=jnp.arange(T) < nb)[0])(x, n)

    return fn, (_layer_params(MOE), x, n)


def _loss_and_grads(T):
    """The trainer's way in: loss and every leaf's gradient through the
    `custom_vjp` around the plain function."""
    x = jax.random.normal(jax.random.PRNGKey(8), (T, H))

    def fn(p, x):
        def loss(p, x):
            y, aux = qwen2.moe_mlp(p, x, MOE, valid=jnp.arange(T) % 7 != 2)
            return jnp.sum(jnp.tanh(y) ** 2) + aux
        return jax.value_and_grad(loss, argnums=(0, 1))(p, x)

    return fn, (_layer_params(MOE), x)


# name: (the call, pair rows as handed over, as laid out)
CASES = {
    **{f"plain_T{T}": (lambda T=T: _plain(T), T * K, rows) for T, rows in
       ((1, 8), (7, 56), (64, 640), (65, 520), (128, 1152))},
    "valid_mask_T64": (lambda: _moe_mlp(MOE, 64, True), 512, 640),
    "held_share_T64": (lambda: _moe_mlp(HELD, 64, False), 512, 640),
    "held_share_valid_mask_T128": (lambda: _moe_mlp(HELD, 128, True), 1024, 1152),
    "stacked_leaf_first_group_T64": (lambda: _stacked(64), 512, 640),
    "stacked_leaf_first_group_T7": (lambda: _stacked(7), 56, 56),
    "engine_vmap_4x16": (lambda: _vmapped(4, 16), 512, 640),
    "custom_vjp_loss_and_grads_T64": (lambda: _loss_and_grads(64), 512, 640),
    "custom_vjp_loss_and_grads_T24": (lambda: _loss_and_grads(24), 192, 192),
}


def _grouped_rows(fn, args):
    jaxpr = jax.make_jaxpr(fn)(*args).jaxpr
    return {e.invars[0].aval.shape[0] for e in _walk(jaxpr)
            if e.primitive.name == "ragged_dot_general" and e.invars[0].aval.ndim == 2
            and e.invars[1].aval.ndim == 3}


@pytest.mark.parametrize("case", sorted(CASES))
def test_layout_changes_no_bit(case, monkeypatch):
    """With the rule and with the rule made the identity (no argument or flag
    of the program chooses: the function is patched here), every output equal
    to the bit; the grouped matmuls see the rows the rule gives."""
    build, handed, laid_out = CASES[case]
    fn, args = build()
    qwen2._expert_mixture.cache_clear()
    assert laid_out in _grouped_rows(fn, args)
    got = jax.jit(lambda *a: fn(*a))(*args)
    with monkeypatch.context() as mp:
        mp.setattr(qwen2, "grouped_matmul_rows", lambda rows, groups: rows)
        qwen2._expert_mixture.cache_clear()
        fn, args = build()  # the same numbers; a function JAX has traced nothing of
        # the forward rows; a gradient's transposed calls contract over them
        assert handed in _grouped_rows(fn, args)
        assert laid_out == handed or laid_out not in _grouped_rows(fn, args)
        want = jax.jit(lambda *a: fn(*a))(*args)
    qwen2._expert_mixture.cache_clear()
    paths = [jax.tree_util.keystr(p) for p, _ in jax.tree_util.tree_flatten_with_path(got)[0]]
    got, want = jax.tree.leaves(got), jax.tree.leaves(want)
    assert len(got) == len(want) >= 1
    assert max(float(jnp.abs(w).max()) for w in want) > 0.05
    for path, g, w in zip(paths, got, want):
        assert g.shape == w.shape and g.dtype == w.dtype
        assert bool(jnp.all(jnp.isfinite(g)))
        if any(leaf in path for leaf in ("gate_kernel", "up_kernel", "down_kernel")):
            # an expert kernel's gradient is a sum over its group's pair rows,
            # taken by a matmul that contracts all the rows laid out: more
            # zero rows move where its float32 partial sums are cut, never
            # what is summed (here 3 of a float32's last bits; every other
            # leaf, the loss and the activations' gradient are sums a row)
            np.testing.assert_allclose(
                np.asarray(g), np.asarray(w), rtol=0, atol=4e-7 * float(jnp.abs(w).max()))
        else:
            assert np.array_equal(np.asarray(g), np.asarray(w)), (case, path)


# -- the engine's counters ------------------------------------------------------

DENSE = ModelConfig(
    vocab_size=64, hidden_size=32, intermediate_size=64, num_hidden_layers=2,
    num_attention_heads=4, num_key_value_heads=2, dtype="float32", param_dtype="float32",
)


@pytest.mark.parametrize("what,cfg,slots,small", [
    ("moe_32_slots", MOE, 32, True),   # 256 pair rows at 16 a group: laid out at 384
    ("moe_4_slots", MOE, 4, False),    # 32 rows: a 32-row tile as they are
    ("dense", DENSE, 4, False),
])
def test_engine_counts_the_steps_laid_out_for_a_small_tile(cpu_devices, what, cfg, slots, small):
    """`moe_grouped_matmul_steps_total` (token steps x sparse layers of the
    chunks dispatched) and `moe_grouped_matmul_small_tile_steps_total` (those
    whose rows the rule changed), from the chunk program's static shapes; a
    dense model moves neither."""
    from areal_tpu.engine.jax_decode import JaxDecodeEngine

    n_chunk = 4
    eng = JaxDecodeEngine(
        JaxDecodeConfig(context_length=256, max_running_requests=slots,
                        new_tokens_per_chunk=n_chunk, page_size=128, dtype="float32",
                        kv_cache_dtype="float32"),
        InferenceEngineConfig())
    eng.set_model(init_params(cfg, jax.random.PRNGKey(0)), cfg)
    eng.initialize()
    try:
        eng.generate(ModelRequest(
            input_ids=[1, 5, 9, 13, 2],
            gconfig=GenerationHyperparameters(greedy=True, max_new_tokens=6)), timeout=300)
        m = eng.get_metrics()
    finally:
        eng.destroy()
    chunks = m["chunks_dispatched_total"]
    assert chunks >= 2
    sparse = cfg.num_hidden_layers if cfg.num_experts else 0
    assert m["moe_grouped_matmul_steps_total"] == chunks * n_chunk * sparse
    assert m["moe_grouped_matmul_small_tile_steps_total"] == (
        m["moe_grouped_matmul_steps_total"] if small else 0)
