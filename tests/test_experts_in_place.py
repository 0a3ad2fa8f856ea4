"""The decode engine reads an MoE model's expert kernels in place.

Under `cfg.scan_layers` the three expert kernels are stacked leaves
`[L, E, H, M]`. As a layer scan's `xs` each layer's `[E, H, M]` kernels reach
the grouped matmul (`jax.lax.ragged_dot`) as a slice of the leaf, and XLA:TPU,
which runs that matmul as a custom call, copies the slice: 46% of an OLMoE
decode chunk on the v5e (PERF.md, PRs 26 and 27). The forward-only programs
therefore hand the matmul the whole leaf `[L*E, H, M]` and the layer selects
its groups (`models/qwen2.py:_scan_stacked`, `_expert_mixture_plain`). Held
here on the CPU: the engine's own programs never slice, stack, copy or cast
anything as large as one layer's kernel; and the numbers are those of the
unstacked tree, whose layers are their own buffers and call the matmul with
their own E groups, as the trainer does.
"""

from dataclasses import replace

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from areal_tpu.api.cli_args import InferenceEngineConfig, JaxDecodeConfig
from areal_tpu.engine.jax_decode import JaxDecodeEngine
from areal_tpu.models import qwen2
from areal_tpu.models.qwen2 import ModelConfig, init_params

from test_pool_in_place import _programs as _pool_programs
from test_pool_in_place import _sub_jaxprs, _walk

# OLMoE's shape of layer; the expert kernel (E*H*M = 32,768 elements) is
# larger than every other array of these programs (the whole pool: 26,112)
TINY = ModelConfig(
    vocab_size=64, hidden_size=32, intermediate_size=64, num_hidden_layers=3,
    num_attention_heads=4, num_key_value_heads=4, dtype="float32", param_dtype="float32",
    model_type="olmoe", qkv_bias=False, qk_norm=True, qk_norm_full=True, num_experts=8,
    num_experts_per_tok=2, moe_intermediate_size=128, norm_topk_prob=False, attn_impl="dense",
)
L, E, K, H, M = 3, 8, 2, 32, 128
KERNEL = E * H * M
R, CONTEXT, PAGE, CHUNK = 4, 64, 16, 4


def _unstacked(params):
    """The same weights as `scan_layers=False` stores them."""
    out = {k: v for k, v in params.items() if k != "layers"}
    for i in range(L):
        out[f"layers_{i}"] = jax.tree.map(lambda a: a[i], params["layers"])
    return out


@pytest.fixture(scope="module")
def params():
    p = init_params(TINY, jax.random.PRNGKey(7))
    # init_params zeroes nothing here, but its router is even: skew it, so
    # that some experts of a layer get no pair and others several
    r = p["layers"]["mlp"]["router_kernel"]
    p["layers"]["mlp"]["router_kernel"] = r.at[:, :, ::3].multiply(4.0)
    return p


# -- the engine's programs ------------------------------------------------------


@pytest.fixture(scope="module", params=[True, False], ids=["scan", "unrolled"])
def engine(request, cpu_devices, params):
    cfg = replace(TINY, scan_layers=request.param)
    eng = JaxDecodeEngine(
        JaxDecodeConfig(
            context_length=CONTEXT, max_running_requests=R,
            new_tokens_per_chunk=CHUNK, page_size=PAGE, dtype="float32",
            kv_cache_dtype="float32", paged_attn_impl="pallas",
            spec_decode="ngram", spec_k=2,
        ),
        InferenceEngineConfig(),
    )
    eng.set_model(params if request.param else _unstacked(params), cfg)
    eng.initialize()
    eng.pause_generation()
    try:
        yield eng
    finally:
        eng.destroy()


def _kernel_sized(jaxpr):
    """Every equation, other than the grouped matmuls, with an operand or a
    result as large as one layer's expert kernel. Equations that only hold
    other equations (a `pjit`, a loop, `custom_vmap`) pass their operands
    through, except what a scan takes as `xs` and gives as `ys`: those it
    slices and stacks a step. The one equation allowed on the stacked leaf
    is the reshape that folds `[L, E, ...]` into `[L*E, ...]`, a bitcast."""
    found = []
    for eqn in _walk(jaxpr):
        name = eqn.primitive.name
        moved = (*eqn.invars, *eqn.outvars)
        if name == "ragged_dot_general":
            continue
        if name == "scan":
            held = eqn.params["num_consts"] + eqn.params["num_carry"]
            moved = (*eqn.invars[held:], *eqn.outvars[eqn.params["num_carry"]:])
            name = "scan xs/ys"
        elif any(True for _ in _sub_jaxprs(eqn)):
            continue
        shapes = [v.aval.shape for v in moved if hasattr(v.aval, "shape")]
        if name == "reshape" and {int(np.prod(s)) for s in shapes} == {L * KERNEL}:
            assert shapes[0][:2] == (L, E) and shapes[-1][0] == L * E, shapes
            continue
        big = [s for s in shapes if int(np.prod(s)) >= KERNEL]
        if big:
            found.append(f"{name} {big}")
    return found


@pytest.mark.parametrize("program", ["chunk", "verify_chunk", "prefill_batched"])
def test_program_never_moves_an_expert_kernel(engine, program):
    name, fn, args = next(p for p in _pool_programs(engine) if p[0] == program)
    jaxpr = jax.make_jaxpr(fn)(*args).jaxpr
    grouped = [e for e in _walk(jaxpr) if e.primitive.name == "ragged_dot_general"]
    # the walk reached the experts: three grouped matmuls a layer loop (the
    # unrolled tree: a layer), each on a whole leaf
    stacked = engine.model_config.scan_layers
    assert len(grouped) == (3 if stacked else 3 * L), len(grouped)
    for e in grouped:
        assert e.invars[1].aval.shape[0] == (L * E if stacked else E)
        assert e.invars[2].aval.shape == (L * E if stacked else E,)
    moved = _kernel_sized(jaxpr)
    assert not moved, f"{name} moves an expert kernel: {moved}"


def test_the_check_catches_a_kernel_taken_as_xs(params):
    """What the parent did: the stacked leaf as the layer scan's `xs`."""

    def sliced(p, x):
        def layer(x, layer_p):
            return x + qwen2.moe_mlp(layer_p["mlp"], x, TINY)[0], None

        return jax.lax.scan(layer, x, p["layers"])[0]

    jaxpr = jax.make_jaxpr(sliced)(params, jnp.zeros((4, H))).jaxpr
    assert any(m.startswith("scan xs/ys") for m in _kernel_sized(jaxpr))


# -- the same numbers -------------------------------------------------------------

LOOSE = replace(TINY, scan_layers=False)
F32 = dict(rtol=1e-5, atol=1e-5)
NB = CONTEXT // PAGE
N_BLOCKS = R * NB + 1
nKV, hd = TINY.num_key_value_heads, TINY.head_dim_
ACTIVE = jnp.array([True, False, True, True])  # slot 1 is dead
BT = jnp.arange(1, R * NB + 1, dtype=jnp.int32).reshape(R, NB)


def _pools(seed):
    k, v = jax.random.split(jax.random.PRNGKey(seed))
    shape = (L, N_BLOCKS, PAGE, nKV * hd)
    return jax.random.normal(k, shape), jax.random.normal(v, shape)


def _decode_paged(p, cfg):
    kp, vp = _pools(1)
    return qwen2.decode_step_paged(
        p, jnp.array([3, 9, 27, 5]), jnp.array([17, 0, 33, 5]), kp, vp, BT, cfg,
        active=ACTIVE, attn_impl="xla", moe_load=True)


def _verify_paged(p, cfg):
    kp, vp = _pools(2)
    return qwen2.verify_step_paged(
        p, jnp.arange(R * 3, dtype=jnp.int32).reshape(R, 3) + 1,
        jnp.array([17, 0, 33, 5]), kp, vp, BT, cfg, active=ACTIVE, attn_impl="xla")


def _prefill_padded(p, cfg):
    ids = jnp.arange(1, 33, dtype=jnp.int32)
    return qwen2.prefill(p, ids, jnp.arange(32), cfg, valid=jnp.arange(32) < 21)


def _prefill_suffix(p, cfg):
    k, v = jax.random.split(jax.random.PRNGKey(5))
    pk, pv = (jax.random.normal(a, (L, 16, nKV, hd)) for a in (k, v))
    return qwen2.prefill_with_prefix(
        p, jnp.arange(1, 17, dtype=jnp.int32), pk, pv, jnp.int32(11), cfg,
        valid=jnp.arange(16) < 9)


def _prefill_vmapped(p, cfg):
    """The engine's batched prefill: a whole prefill under `vmap`, the
    parameters closed over."""
    ids_b = jnp.arange(1, 3 * 32 + 1, dtype=jnp.int32).reshape(3, 32) % 64

    def core(ids, n):
        return qwen2.prefill(p, ids, jnp.arange(32), cfg, valid=jnp.arange(32) < n)

    return jax.vmap(core)(ids_b, jnp.array([32, 7, 20]))


STEPS = {
    "decode_step_paged": _decode_paged, "verify_step_paged": _verify_paged,
    "prefill": _prefill_padded, "prefill_with_prefix": _prefill_suffix,
    "vmapped_prefill": _prefill_vmapped,
}


@pytest.mark.parametrize("step", sorted(STEPS))
def test_stacked_layers_give_the_unstacked_trees_numbers(params, step):
    """Logits, every row of the cache or pool (the dead slot's and the pad
    rows' too) and the expert load: what the layers give as buffers of
    their own, each calling the grouped matmul with its E groups. The load
    (integers) exactly; floats to float32's last digits, because the CPU's
    `ragged_dot` is one dense product contracted over groups and hidden
    together, whose order of summation moves with the number of groups (on
    the TPU the same rows meet the same weights in the same tiles)."""
    fn = STEPS[step]
    got = jax.jit(lambda p: fn(p, TINY))(params)
    want = jax.jit(lambda p: fn(p, LOOSE))(_unstacked(params))
    assert len(jax.tree.leaves(got)) == len(jax.tree.leaves(want)) >= 2
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert g.shape == w.shape and g.dtype == w.dtype
        if jnp.issubdtype(g.dtype, jnp.integer):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
        else:
            assert bool(jnp.all(jnp.isfinite(g)))
            np.testing.assert_allclose(np.asarray(g), np.asarray(w), **F32)


def test_vmapped_prefill_still_folds_the_batch_into_one_call(params):
    """Under the engine's `vmap` the mixture is one grouped call on B*T
    tokens against the whole stack, not B of them."""
    jaxpr = jax.make_jaxpr(lambda p: _prefill_vmapped(p, TINY))(params).jaxpr
    grouped = [e for e in _walk(jaxpr) if e.primitive.name == "ragged_dot_general"]
    assert len(grouped) == 3
    for e in grouped:
        assert e.invars[0].aval.shape[0] == 3 * 32 * K
        assert e.invars[1].aval.shape[0] == L * E and e.invars[2].aval.shape == (L * E,)


# -- the mixture itself -------------------------------------------------------------


def _mixture_inputs(T):
    ks = jax.random.split(jax.random.PRNGKey(11), 6)
    x = jax.random.normal(ks[0], (T, H))
    # ids in [0, E]: E routes a pair nowhere; expert 5 gets none
    expert = jax.random.randint(ks[1], (T, K), 0, E + 1)
    expert = jnp.where(expert == 5, 6, expert)
    gates = jnp.where(expert < E, jax.random.uniform(ks[2], (T, K)), 0.0)
    stack = [jax.random.normal(k, s) / 6.0 for k, s in
             zip(ks[3:], ((L * E, H, M), (L * E, H, M), (L * E, M, H)))]
    return x, expert, gates, stack


@pytest.mark.parametrize("li", range(L))
def test_mixture_at_an_offset_equals_the_layers_own_call(li):
    """`G = 3E` with the first group at each layer's offset against the
    `G == E` call on that layer's slice of the kernels (float32's last
    digits apart on the CPU, see above)."""
    x, expert, gates, stack = _mixture_inputs(24)
    act = jax.nn.silu
    own = [w[li * E:(li + 1) * E] for w in stack]
    want = jax.jit(lambda *a: qwen2._expert_mixture_plain(act, E, *a))(x, expert, gates, *own)
    got = jax.jit(lambda *a: qwen2._expert_mixture_plain(act, E, *a))(
        x, expert, gates, *stack, jnp.int32(li * E))
    assert float(jnp.abs(want).max()) > 0.1
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), **F32)
    # a token whose pairs all route nowhere gets exactly nothing
    nowhere = jnp.full((24, K), E, jnp.int32).at[1:].set(expert[1:])
    y = qwen2._expert_mixture_plain(
        act, E, x, nowhere, jnp.where(nowhere < E, gates, 0.0), *stack, jnp.int32(li * E))
    assert float(jnp.abs(y[0]).max()) == 0.0 and bool(jnp.all(y[1:] == got[1:]))


def test_group_sizes_are_the_layers_counts_at_its_offset():
    """What the grouped matmul is handed: the E counts of this call at
    `first_group` among `G` zeros, and rows past the last group zeroed."""
    x, expert, gates, stack = _mixture_inputs(24)
    seen = []
    real = jax.lax.ragged_dot

    def spy(lhs, rhs, group_sizes, **kw):
        seen.append((np.asarray(lhs), np.asarray(group_sizes)))
        return real(lhs, rhs, group_sizes, **kw)

    with pytest.MonkeyPatch.context() as mp, jax.disable_jit():
        mp.setattr(jax.lax, "ragged_dot", spy)
        qwen2._expert_mixture_plain(
            jax.nn.silu, E, x, expert, gates, *stack, jnp.int32(2 * E))
    assert len(seen) == 3
    counts = np.bincount(np.asarray(expert).ravel(), minlength=E + 1)
    for lhs, sizes in seen:
        assert sizes.shape == (L * E,)
        assert (sizes[2 * E:] == counts[:E]).all() and not sizes[:2 * E].any()
        assert counts[5] == 0 and counts[E] > 0  # an empty expert, dead pairs
        assert not lhs[counts[:E].sum():].any()  # rows past the last group
