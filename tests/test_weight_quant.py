"""Int8 weight serving end-to-end (ISSUE 16).

Coverage layers:

1. Scheme unit contracts (ops/quant.py, the axis-generic absmax module
   hoisted out of ops/kv_quant.py): per-output-channel symmetric round
   trip bounded by amax/254 for every contraction-axes shape the weight
   path uses, zero channels exact, and the KV path still delegates to
   the SAME functions (one scheme, two consumers).
2. Weight-tree helpers (models/qwen2.py): quantize_weights hits exactly
   the transformer matmul kernels — embeddings, lm_head, norms and
   biases stay fp, MoE expert mlps are skipped while their attn still
   quantizes — idempotently, with scale shapes = kernel shape minus the
   contraction axes; dequantize_weights round-trips within the scheme
   bound.
3. Kernel agreement: the Pallas fused dequant-matmul (interpret mode)
   and the XLA dequant-then-einsum fallback agree on the SAME
   dequantized values within float-reassociation tolerance, for 2D and
   kernel-shaped (4D-weight) contractions; misaligned shapes fall back
   instead of mis-tiling.
4. weight_dtype="fp" is the numerics ORACLE: greedy + sampled streams
   pinned bit-for-bit against a committed golden
   (regenerate with AREAL_WRITE_GOLDEN=1 after an INTENTIONAL numerics
   change) — the int8 fast path must not perturb the default path.
5. Serving + push invariants: unknown weight_dtype rejected; the
   producer-quantized full-tree push installs int8 payloads VERBATIM
   (no recast); fp-named pushes into an int8 engine fail with the
   dtype-mismatch diagnosis, not a bare KeyError; torn int8 frames are
   rejected before a byte stages; drift vs the fp oracle is measured,
   bounded and deterministic.
6. LoRA on a quantized base: fold-then-requantize — the served kernel
   is EXACTLY quantize(dequant(pristine int8 base) + scale * A @ B)
   (pinned bitwise against that oracle, and re-pushing the same delta
   is a no-op because the fold starts from the pristine snapshot), and
   stays within the scheme bound of the quantize-after-fold fp oracle
   (one absmax round of the true merged weights, never a round-trip of
   a round-trip).
"""

import json
import os
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
from areal_tpu.core.weight_transfer import (
    WeightStaging,
    flatten_named,
    pack_buckets,
    raw_wire_nbytes,
)
from areal_tpu.engine.jax_decode import JaxDecodeEngine
from areal_tpu.models.qwen2 import (
    ModelConfig,
    dequantize_weights,
    init_lora_params,
    init_params,
    is_weight_quantized,
    merge_lora,
    quantize_weights,
    wq_contraction_axes,
)
from areal_tpu.ops.quant import dequantize_absmax, quantize_absmax

TINY = ModelConfig(
    vocab_size=64,
    hidden_size=32,
    intermediate_size=64,
    num_hidden_layers=2,
    num_attention_heads=4,
    num_key_value_heads=2,
    dtype="float32",
    param_dtype="float32",
)

MOE = replace(
    TINY,
    num_experts=4,
    num_experts_per_tok=2,
    moe_intermediate_size=16,
    attn_impl="dense",
)

_PARAMS = None


def _params():
    global _PARAMS
    if _PARAMS is None:
        _PARAMS = init_params(TINY, jax.random.PRNGKey(0))
    return _PARAMS


# -- 1. scheme unit contracts ------------------------------------------


@pytest.mark.parametrize("axes", [(0,), (0, 1), (1,)])
def test_absmax_roundtrip_error_bound_per_channel(axes):
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(6, 10, 16).astype(np.float32) * 2.5)
    q, s = quantize_absmax(x, axis=axes)
    assert q.dtype == jnp.int8 and s.dtype == jnp.float32
    assert q.shape == x.shape
    assert s.shape == tuple(
        d for i, d in enumerate(x.shape) if i not in axes
    )
    back = np.asarray(dequantize_absmax(q, s, jnp.float32, axis=axes))
    amax = np.abs(np.asarray(x)).max(axis=axes, keepdims=True)
    err = np.abs(back - np.asarray(x))
    # symmetric round-to-nearest on a 127-step grid: error <= amax/254
    assert (err <= amax / 254 + 1e-7).all(), err.max()


def test_absmax_zero_channels_exact():
    x = jnp.zeros((4, 8), jnp.float32)
    q, s = quantize_absmax(x, axis=(0,))
    assert np.array_equal(np.asarray(q), np.zeros_like(q))
    # scale 1.0 on all-zero channels: dequant is exact zero, never 0/0
    assert np.array_equal(np.asarray(s), np.ones((8,), np.float32))


def test_kv_path_delegates_to_shared_scheme():
    from areal_tpu.ops import kv_quant, quant

    # ops/kv_quant re-exports the hoisted functions, not copies of them
    assert kv_quant.quantize_absmax is quant.quantize_absmax
    assert kv_quant.dequantize_absmax is quant.dequantize_absmax
    assert kv_quant.INT8_QMAX is quant.INT8_QMAX
    rng = np.random.RandomState(1)
    x = jnp.asarray(rng.randn(3, 2, 8).astype(np.float32))
    qk, sk = kv_quant.quantize_kv(x)
    qa, sa = quant.quantize_absmax(x, axis=-1)
    assert np.array_equal(np.asarray(qk), np.asarray(qa))
    assert np.array_equal(np.asarray(sk), np.asarray(sa))


# -- 2. weight-tree helpers ---------------------------------------------

# stacked [L, ...] scan layout: leaf -> (contraction axes, scale shape)
_EXPECT = {
    ("attn", "q_kernel"): ((1,), (2, 4, 8)),
    ("attn", "k_kernel"): ((1,), (2, 2, 8)),
    ("attn", "v_kernel"): ((1,), (2, 2, 8)),
    ("attn", "o_kernel"): ((1, 2), (2, 32)),
    ("mlp", "gate_kernel"): ((1,), (2, 64)),
    ("mlp", "up_kernel"): ((1,), (2, 64)),
    ("mlp", "down_kernel"): ((1,), (2, 32)),
}


def test_quantize_weights_targets_exact_kernel_set():
    p = _params()
    qt = quantize_weights(p)
    assert is_weight_quantized(qt) and not is_weight_quantized(p)
    for (sub, leaf), (axes, sshape) in _EXPECT.items():
        node = qt["layers"][sub][leaf]
        assert isinstance(node, dict) and set(node) == {"q", "scale"}
        assert node["q"].dtype == jnp.int8
        assert node["q"].shape == p["layers"][sub][leaf].shape
        assert node["scale"].dtype == jnp.float32
        assert node["scale"].shape == sshape, (sub, leaf)
        # the quantization is THE shared scheme, bit for bit
        eq, es = quantize_absmax(p["layers"][sub][leaf], axis=axes)
        assert np.array_equal(np.asarray(node["q"]), np.asarray(eq))
        assert np.array_equal(np.asarray(node["scale"]), np.asarray(es))
    # everything vocab/norm/bias-shaped stays fp, bit-identical
    for name in (
        "embed/embedding", "lm_head/kernel", "final_norm",
        "layers/input_norm", "layers/post_attn_norm",
        "layers/attn/q_bias", "layers/attn/k_bias", "layers/attn/v_bias",
    ):
        a, b = flatten_named(p)[name], flatten_named(qt)[name]
        assert np.array_equal(a, b), name
    # idempotent: quantizing a quantized tree changes nothing
    qt2 = quantize_weights(qt)
    fa, fb = flatten_named(qt), flatten_named(qt2)
    assert set(fa) == set(fb)
    for k in fa:
        assert np.array_equal(fa[k], fb[k]), k


def test_dequantize_roundtrip_bounded():
    p = _params()
    back = dequantize_weights(quantize_weights(p), jnp.float32)
    for (sub, leaf), (axes, _) in _EXPECT.items():
        w = np.asarray(p["layers"][sub][leaf])
        r = np.asarray(back["layers"][sub][leaf])
        assert r.dtype == w.dtype
        amax = np.abs(w).max(axis=axes, keepdims=True)
        assert (np.abs(r - w) <= amax / 254 + 1e-7).all(), (sub, leaf)


def test_wq_contraction_axes_table():
    assert wq_contraction_axes("q_kernel", stacked=False) == (0,)
    assert wq_contraction_axes("q_kernel", stacked=True) == (1,)
    assert wq_contraction_axes("o_kernel", stacked=False) == (0, 1)
    assert wq_contraction_axes("o_kernel", stacked=True) == (1, 2)
    assert wq_contraction_axes("down_kernel", stacked=True) == (1,)
    assert wq_contraction_axes("q_bias", stacked=True) is None
    assert wq_contraction_axes("router_kernel", stacked=True) is None


def test_moe_mlp_skipped_attn_still_quantized():
    p = init_params(MOE, jax.random.PRNGKey(2))
    qt = quantize_weights(p)
    mlp = qt["layers"]["mlp"]
    # routed-expert kernels ship fp (router numerics are drift-sensitive
    # and expert kernels are gathered, not plain matmuls)
    for k, v in mlp.items():
        assert not isinstance(v, dict), k
        assert np.array_equal(
            np.asarray(v), np.asarray(p["layers"]["mlp"][k])
        ), k
    # the attention stack still takes the fast path
    assert isinstance(qt["layers"]["attn"]["q_kernel"], dict)


# -- 3. Pallas vs XLA agreement -----------------------------------------


def test_quant_matmul_tile_gate():
    from areal_tpu.ops.quant_matmul import quant_matmul_tiles_ok

    assert quant_matmul_tiles_ok(128, 128)
    assert quant_matmul_tiles_ok(256, 384)
    assert not quant_matmul_tiles_ok(130, 128)
    assert not quant_matmul_tiles_ok(128, 64)


def test_pallas_and_xla_agree_on_quantized_matmul():
    from areal_tpu.ops.quant_matmul import quant_einsum

    rng = np.random.RandomState(3)
    for tshape, wshape, nc in (
        ((5, 128), (128, 256), 1),       # 2D, T not tile-aligned
        ((3, 4, 128), (128, 8, 16), 1),  # q_kernel-like: N = 8*16 = 128
        ((2, 8, 16), (8, 16, 128), 2),   # o_kernel-like: K = 8*16 = 128
    ):
        x = jnp.asarray(rng.randn(*tshape).astype(np.float32))
        w = jnp.asarray(rng.randn(*wshape).astype(np.float32))
        wq, ws = quantize_absmax(w, axis=tuple(range(nc)))
        o_xla = quant_einsum(x, wq, ws, nc, impl="xla")
        o_pl = quant_einsum(x, wq, ws, nc, impl="pallas", interpret=True)
        assert o_xla.shape == o_pl.shape == tshape[:-nc] + wshape[nc:]
        np.testing.assert_allclose(
            np.asarray(o_xla), np.asarray(o_pl), atol=2e-5, rtol=1e-5
        )
        # both implementations score the dequantized values: pin against
        # the plain dequant-then-dot reference
        ref = jnp.einsum(
            "tk,kn->tn",
            x.reshape(-1, int(np.prod(wshape[:nc]))),
            dequantize_absmax(
                wq, ws, jnp.float32, axis=tuple(range(nc))
            ).reshape(int(np.prod(wshape[:nc])), -1),
        ).reshape(o_xla.shape)
        np.testing.assert_allclose(
            np.asarray(o_xla), np.asarray(ref), atol=2e-5, rtol=1e-5
        )


def test_misaligned_shapes_fall_back_not_mistile():
    from areal_tpu.ops.quant_matmul import quant_einsum

    rng = np.random.RandomState(4)
    # K=48, N=40: no legal Pallas tiling — impl="auto" must fall back
    x = jnp.asarray(rng.randn(3, 48).astype(np.float32))
    w = jnp.asarray(rng.randn(48, 40).astype(np.float32))
    wq, ws = quantize_absmax(w, axis=(0,))
    o_auto = quant_einsum(x, wq, ws, 1, impl="auto")
    o_xla = quant_einsum(x, wq, ws, 1, impl="xla")
    assert np.array_equal(np.asarray(o_auto), np.asarray(o_xla))


# -- engine helpers -----------------------------------------------------


def _engine(*, weight_dtype="fp", R=3, chunk=4,
            context=160, params=None, seed=1):
    cfg = JaxDecodeConfig(
        context_length=context,
        max_running_requests=R,
        new_tokens_per_chunk=chunk,
        weight_dtype=weight_dtype,
        dtype="float32",
        kv_cache_dtype="float32",
        random_seed=seed,
    )
    eng = JaxDecodeEngine(cfg, InferenceEngineConfig())
    eng.set_model(params if params is not None else _params(), TINY)
    eng.initialize()
    return eng


def _prompt(n=40, seed=5):
    return np.random.RandomState(seed).randint(1, 64, (n,)).tolist()


_GREEDY = GenerationHyperparameters(max_new_tokens=12, greedy=True)
_SAMPLED = GenerationHyperparameters(
    max_new_tokens=12, temperature=0.8, top_p=0.9
)


def _stream(eng, g, prompt=None):
    r = eng.generate(
        ModelRequest(input_ids=prompt or _prompt(), gconfig=g),
        timeout=120,
    )
    return list(r.output_tokens), [float(x) for x in r.output_logprobs]


# -- 4. weight_dtype="fp" is the numerics oracle ------------------------

GOLDEN = os.path.join(
    os.path.dirname(__file__), "fixtures", "wquant_fp_golden.json"
)


def test_fp_streams_bit_identical_to_golden(cpu_devices):
    """The default path must stay BITWISE what it was before the int8
    fast path landed: weight_dtype="fp" routes every matmul through the
    exact pre-existing jnp.einsum call (no quantize, no dequant, no
    recast), so its streams are pinned token-for-token AND
    logprob-for-logprob against the committed golden. Regenerate with
    AREAL_WRITE_GOLDEN=1 only for an INTENTIONAL numerics change."""
    eng = _engine(weight_dtype="fp")
    try:
        got = {}
        for gname, g in (("greedy", _GREEDY), ("sampled", _SAMPLED)):
            toks, lps = _stream(eng, g)
            got[gname] = {"tokens": toks, "logprobs": lps}
    finally:
        eng.destroy()

    golden = {}
    if os.path.exists(GOLDEN):
        with open(GOLDEN) as f:
            golden = json.load(f)
    if os.environ.get("AREAL_WRITE_GOLDEN"):
        import jaxlib

        golden = dict(got)
        # the streams follow the installed JAX's random stream and XLA:CPU
        # codegen: the file says which installation it pins
        golden["written_by"] = {
            "jax": jax.__version__,
            "jaxlib": jaxlib.__version__,
            "numpy": np.__version__,
        }
        with open(GOLDEN, "w") as f:
            json.dump(golden, f, indent=1, sort_keys=True)
        pytest.skip("golden regenerated")
    assert "greedy" in golden, f"golden missing; regen {GOLDEN}"
    who = f"golden written by {golden.get('written_by')}"
    for gname in ("greedy", "sampled"):
        assert got[gname]["tokens"] == golden[gname]["tokens"], who
        assert got[gname]["logprobs"] == golden[gname]["logprobs"], (gname, who)


# -- 5. serving + push invariants ---------------------------------------


def test_unknown_weight_dtype_rejected(cpu_devices):
    cfg = JaxDecodeConfig(
        weight_dtype="int4", dtype="float32", kv_cache_dtype="float32"
    )
    eng = JaxDecodeEngine(cfg, InferenceEngineConfig())
    eng.set_model(_params(), TINY)
    with pytest.raises(ValueError, match="weight_dtype"):
        eng.initialize()


def _wire(params, dtype="int8"):
    """The producer's exact payload: bf16 push cast, then quantize —
    jax_engine._dcn_payload's order."""
    bf16 = jax.tree.map(
        lambda x: x.astype(jnp.bfloat16)
        if jnp.issubdtype(x.dtype, jnp.floating)
        else x,
        params,
    )
    return flatten_named(
        quantize_weights(bf16) if dtype == "int8" else bf16
    )


def test_quantized_push_installs_verbatim_and_decodes(cpu_devices):
    """Producer-quantized full tree -> framed wire -> int8 engine: the
    int8 payloads and f32 scales install byte-for-byte (the consumer
    cast targets the RESIDENT dtype, so no recast corrupts them), the
    version stamps, and the engine decodes from the pushed weights."""
    fresh = init_params(TINY, jax.random.PRNGKey(7))
    wire = _wire(fresh)
    eng = _engine(weight_dtype="int8")
    try:
        assert eng.get_metrics()["weight_dtype"] == "int8"
        eng.update_weights_from_tensor(dict(wire), version=3)
        assert eng.get_version() == 3
        node = eng.params["layers"]["attn"]["q_kernel"]
        assert node["q"].dtype == jnp.int8
        assert np.array_equal(
            np.asarray(node["q"]), wire["layers/attn/q_kernel/q"]
        )
        assert node["scale"].dtype == jnp.float32
        assert np.array_equal(
            np.asarray(node["scale"]), wire["layers/attn/q_kernel/scale"]
        )
        toks, _ = _stream(eng, _GREEDY)
        assert len(toks) == _GREEDY.max_new_tokens
    finally:
        eng.destroy()


def test_fp_named_push_into_int8_engine_diagnosed(cpu_devices):
    """An fp producer pushing whole-kernel names at an int8 consumer is
    a config mismatch, and the error must SAY so — every kernel name
    shifts by the /q + /scale suffix, so a bare KeyError would read as
    tree corruption."""
    eng = _engine(weight_dtype="int8")
    try:
        with pytest.raises(KeyError, match="weight_dtype"):
            eng.update_weights_from_tensor(
                _wire(_params(), dtype="fp"), version=2
            )
        # and nothing committed
        assert eng.get_version() == 0
    finally:
        eng.destroy()


def test_torn_int8_frame_rejected_before_staging():
    wire = _wire(_params())
    frames = list(pack_buckets(wire, chunk_mb=0.002))
    assert len(frames) > 1
    st = WeightStaging()
    with pytest.raises(ValueError, match="torn"):
        st.add_bucket(frames[0][:-3])
    # the torn attempt staged nothing; intact frames still land with
    # int8 + f32 dtypes preserved through the framing
    for f in frames:
        st.add_bucket(f)
    staged = st.finalize()
    assert set(staged) == set(wire)
    assert staged["layers/attn/q_kernel/q"].dtype == np.int8
    assert staged["layers/attn/q_kernel/scale"].dtype == np.float32
    assert np.array_equal(
        staged["layers/attn/q_kernel/q"], wire["layers/attn/q_kernel/q"]
    )


def test_raw_wire_accounting_bf16_equivalent():
    """wire_bytes_raw prices the int8 push at what the fp wire WOULD
    have shipped: /q counts twice its int8 bytes (bf16 equivalent),
    /scale counts zero (pure overhead of the scheme), everything else
    at face value — so raw/sent is the honest compression ratio."""
    assert raw_wire_nbytes("layers/attn/q_kernel/q", 100, "int8") == 200
    assert raw_wire_nbytes("layers/attn/q_kernel/scale", 64, "float32") == 0
    assert raw_wire_nbytes("embed/embedding", 100, "bfloat16") == 100
    # a leaf literally NAMED q/scale but not int8/f32 is not the scheme
    assert raw_wire_nbytes("x/q", 100, "bfloat16") == 100
    wire_q = _wire(_params())
    wire_f = _wire(_params(), dtype="fp")
    raw = sum(
        raw_wire_nbytes(n, a.nbytes, str(a.dtype))
        for n, a in wire_q.items()
    )
    # the bf16-equivalent of the quantized KERNELS is exactly the bytes
    # the fp wire ships for them
    fp_kernels = sum(
        wire_f[n[: -len("/q")]].nbytes
        for n in wire_q
        if n.endswith("/q")
    )
    unquantized = sum(
        a.nbytes for n, a in wire_q.items()
        if not n.endswith(("/q", "/scale"))
    )
    assert raw == fp_kernels + unquantized


@pytest.mark.parametrize("gname", ["greedy", "sampled"])
def test_int8_drift_vs_fp_oracle_bounded_and_deterministic(
    cpu_devices, gname
):
    """Int8 weights change the numerics — the contract is the drift is
    SMALL and DETERMINISTIC, not zero: over the token-matched prefix
    the per-token |logprob delta| stays bounded, and two independent
    int8 engines reproduce the identical stream (the drift is a fixed
    property of the scheme, not noise). Random weights are the worst
    case for argmax flips, so the bound is on logprobs, not tokens."""
    g = _GREEDY if gname == "greedy" else _SAMPLED
    # several prompts: with random weights the very first sampled token
    # can already differ between fp and int8, which leaves one prompt
    # nothing to measure the drift on
    prompts = [_prompt(40, seed=s) for s in (19, 20, 21)]

    def run(dt):
        e = _engine(weight_dtype=dt)
        try:
            return [_stream(e, g, p) for p in prompts]
        finally:
            e.destroy()

    fp, i8, i8_again = run("fp"), run("int8"), run("int8")
    assert i8 == i8_again

    deltas = []
    for (fp_t, fp_l), (i8_t, i8_l) in zip(fp, i8):
        matched = 0
        for a, b in zip(fp_t, i8_t):
            if a != b:
                break
            matched += 1
        deltas += [abs(a - b) for a, b in zip(fp_l[:matched], i8_l[:matched])]
    # measured drift, pinned: per-channel int8 weights on this tiny f32
    # model stay well under 0.25 logprob on the matched prefix (seen
    # ~0.03 typical); a scheme regression (wrong contraction axis,
    # double quantization, scale downcast) blows far past this
    assert deltas, "no token-matched prefix on any prompt"
    assert max(deltas) < 0.25, deltas


# -- 6. LoRA on a quantized base ----------------------------------------

LORA_CFG = replace(
    TINY, lora_rank=4, lora_alpha=8.0, lora_targets=("q_proj", "v_proj")
)


def _rand_lora(seed):
    lora = init_lora_params(LORA_CFG, jax.random.PRNGKey(seed))
    leaves, td = jax.tree.flatten(lora)
    rng = np.random.RandomState(seed)
    leaves = [
        np.asarray(l) + rng.randn(*np.shape(l)).astype(np.float32) * 0.05
        for l in leaves
    ]
    return jax.tree.unflatten(td, leaves)


def test_lora_fold_then_requantize_matches_oracle(cpu_devices):
    scale = LORA_CFG.lora_alpha / LORA_CFG.lora_rank
    lora = _rand_lora(11)
    eng = _engine(weight_dtype="int8")
    try:
        # pristine int8 base BEFORE any delta lands
        snap = {
            leaf: (
                np.asarray(eng.params["layers"]["attn"][leaf]["q"]),
                np.asarray(eng.params["layers"]["attn"][leaf]["scale"]),
            )
            for leaf in ("q_kernel", "v_kernel")
        }
        eng.update_weights_from_tensor(
            flatten_named({"lora": lora}), version=2, lora_scale=scale
        )
        for leaf in ("q_kernel", "v_kernel"):
            # the oracle replays the engine's exact op sequence (jnp
            # einsum + dequant + requant) so the pin can be BITWISE
            a = jnp.asarray(lora["attn"][f"{leaf}_lora_a"], jnp.float32)
            b = jnp.asarray(lora["attn"][f"{leaf}_lora_b"], jnp.float32)
            delta = jnp.einsum("lhr,lrnd->lhnd", a, b)
            axes = wq_contraction_axes(leaf, stacked=True)
            merged = (
                dequantize_absmax(
                    jnp.asarray(snap[leaf][0]),
                    jnp.asarray(snap[leaf][1]),
                    jnp.float32,
                    axis=axes,
                )
                + scale * delta
            )
            q_exp, s_exp = quantize_absmax(merged, axis=axes)
            node = eng.params["layers"]["attn"][leaf]
            # fold-then-requantize, EXACTLY: one absmax round of the
            # true merged weights
            assert np.array_equal(np.asarray(node["q"]), np.asarray(q_exp))
            assert np.array_equal(
                np.asarray(node["scale"]), np.asarray(s_exp)
            )
            # and within the scheme bound of the quantize-after-fold fp
            # oracle (differs only by the base's own round trip)
            fp_merged = np.asarray(
                merge_lora(
                    {**_params(), "lora": lora}, LORA_CFG
                )["layers"]["attn"][leaf]
            )
            got = np.asarray(
                dequantize_absmax(
                    node["q"], node["scale"], jnp.float32, axis=axes
                )
            )
            amax = np.abs(fp_merged).max(axis=axes, keepdims=True)
            assert (np.abs(got - fp_merged) <= 3 * amax / 254 + 1e-6).all()

        # untouched kernels keep the pristine int8 payload bit-for-bit
        assert np.array_equal(
            np.asarray(eng.params["layers"]["attn"]["k_kernel"]["q"]),
            np.asarray(
                quantize_weights(_params())["layers"]["attn"]["k_kernel"]["q"]
            ),
        )

        # re-pushing the SAME delta refolds from the pristine snapshot:
        # the served tree is unchanged (not base + 2x delta)
        before = {
            leaf: np.asarray(eng.params["layers"]["attn"][leaf]["q"])
            for leaf in ("q_kernel", "v_kernel")
        }
        eng.update_weights_from_tensor(
            flatten_named({"lora": lora}), version=3, lora_scale=scale
        )
        for leaf in ("q_kernel", "v_kernel"):
            assert np.array_equal(
                np.asarray(eng.params["layers"]["attn"][leaf]["q"]),
                before[leaf],
            )
        assert eng.get_version() == 3
    finally:
        eng.destroy()
