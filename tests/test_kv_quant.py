"""Int8 paged KV pool end-to-end (ISSUE 11).

Coverage layers:

1. Scheme unit contracts (ops/kv_quant.py): symmetric per-row/per-head
   absmax round-trip error bounded by amax/254, zero rows exact, layout
   helpers invertible.
2. Kernel agreement: the Pallas split-KV kernels (interpret mode) and the
   XLA gather fallback score the SAME dequantized values for int8 pools —
   decode (W=1) and multi-query verify — so `paged_attn_impl` cannot
   change a quantized stream's numerics beyond float reassociation.
3. Engine invariants:
   - config gate: unknown dtypes rejected.
   - quantized-to-quantized bit-identity: park -> LRU-evict -> host
     offload -> promote, and export -> wire (pack/unpack with scale
     blocks) -> import on a second replica, both reproduce the
     uninterrupted int8 stream exactly (tokens AND logprobs, greedy and
     sampled, spec_decode="ngram" on) — the pool bytes + scales travel
     AS-IS on every hop, no requantization.
   - mixed-dtype fleets: an fp session imported into an int8 engine (and
     vice versa) is rejected as "kv_dtype_mismatch", tombstoned, and the
     resume pays an honest re-prefill (counted as a host-tier miss) —
     the same rule as a weight-version race.
   - byte accounting is PHYSICAL: kv_block_nbytes, swap totals and
     migration totals reflect int8 element size + scale overhead, not
     the fp element size.
4. Drift vs the fp oracle is MEASURED, not assumed zero: greedy + sampled
   with spec on, max |logprob delta| over the token-matched prefix pinned
   under a bound, and the int8 stream pinned deterministic (two fresh
   engines agree bit for bit).
"""

import asyncio
import threading
import time
import uuid
from dataclasses import replace

import numpy as np
import pytest

from _numerics import assert_logprobs_close

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
    pack_kv_session,
    unpack_kv_sessions,
)
from areal_tpu.engine.jax_decode import JaxDecodeEngine
from areal_tpu.models.qwen2 import ModelConfig, init_params
from areal_tpu.ops.kv_quant import (
    dequantize_kv,
    quantize_kv,
    scales_blocked,
    scales_rowmajor,
)

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

_PARAMS = None


def _params():
    global _PARAMS
    if _PARAMS is None:
        _PARAMS = init_params(TINY, jax.random.PRNGKey(0))
    return _PARAMS


# -- 1. scheme unit contracts ------------------------------------------


def test_int8_roundtrip_error_bound():
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(5, 7, 3, 16).astype(np.float32) * 3.0)
    q, s = quantize_kv(x)
    assert q.dtype == jnp.int8 and s.dtype == jnp.float32
    assert q.shape == x.shape and s.shape == x.shape[:-1]
    back = dequantize_kv(q, s, jnp.float32)
    # symmetric round-to-nearest on a 127-step grid: error <= amax/254
    amax = np.abs(np.asarray(x)).max(axis=-1, keepdims=True)
    err = np.abs(np.asarray(back) - np.asarray(x))
    assert (err <= amax / 254 + 1e-7).all(), err.max()


def test_int8_zero_rows_exact_and_deterministic():
    x = jnp.zeros((3, 2, 8), jnp.float32)
    q, s = quantize_kv(x)
    assert np.array_equal(np.asarray(q), np.zeros_like(q))
    # scale 1.0 on zero rows: dequantization is an exact zero, never 0/0
    assert np.array_equal(np.asarray(s), np.ones_like(s))
    assert np.array_equal(
        np.asarray(dequantize_kv(q, s, jnp.float32)), np.zeros_like(x)
    )
    rng = np.random.RandomState(1)
    y = jnp.asarray(rng.randn(4, 2, 8).astype(np.float32))
    q1, s1 = quantize_kv(y)
    q2, s2 = quantize_kv(y)
    assert np.array_equal(np.asarray(q1), np.asarray(q2))
    assert np.array_equal(np.asarray(s1), np.asarray(s2))


def test_scale_layout_helpers_invert():
    rng = np.random.RandomState(2)
    blocked = jnp.asarray(rng.rand(2, 5, 3, 8).astype(np.float32))
    rows = scales_rowmajor(blocked)  # [2, 40, 3]
    assert rows.shape == (2, 40, 3)
    assert np.array_equal(
        np.asarray(scales_blocked(rows, 5, 8)), np.asarray(blocked)
    )


# -- 2. kernel agreement on quantized pools ----------------------------


def _quantized_pool(rng, nblocks=10, bsz=8, nkv=2, hd=16, layers=2):
    kp = rng.randn(layers, nblocks, bsz, nkv, hd).astype(np.float32)
    q, s = quantize_kv(jnp.asarray(kp))
    # stored layout: data [L, n_blocks, block_size, nKV*hd] (heads side by
    # side in the row), scale pool [L, n_blocks, nKV, block_size]
    return q.reshape(layers, nblocks, bsz, nkv * hd), jnp.swapaxes(s, -1, -2)


def test_pallas_and_xla_agree_on_int8_pools():
    from areal_tpu.ops.paged_attention import (
        paged_attention,
        paged_attention_qlen,
    )

    rng = np.random.RandomState(3)
    R, nH, nKV, hd, bsz, nblocks, nb, W = 3, 4, 2, 16, 8, 10, 3, 4
    qk, sk = _quantized_pool(rng, nblocks, bsz, nKV, hd)
    qv, sv = _quantized_pool(rng, nblocks, bsz, nKV, hd)
    bt = jnp.asarray(rng.randint(1, nblocks, (R, nb)).astype(np.int32))

    q1 = jnp.asarray(rng.randn(R, nH, hd).astype(np.float32))
    valid1 = jnp.asarray(rng.rand(R, nb * bsz) < 0.7).at[:, 0].set(True)
    o_xla = paged_attention(q1, (qk, sk), (qv, sv), bt, valid1, 1, impl="xla")
    o_pl = paged_attention(
        q1, (qk, sk), (qv, sv), bt, valid1, 1, impl="pallas", interpret=True
    )
    np.testing.assert_allclose(
        np.asarray(o_xla), np.asarray(o_pl), atol=2e-5, rtol=1e-5
    )

    qw = jnp.asarray(rng.randn(R, W, nH, hd).astype(np.float32))
    validw = (
        jnp.asarray(rng.rand(R, W, nb * bsz) < 0.7).at[:, :, 0].set(True)
    )
    ow_xla = paged_attention_qlen(
        qw, (qk, sk), (qv, sv), bt, validw, 1, impl="xla"
    )
    ow_pl = paged_attention_qlen(
        qw, (qk, sk), (qv, sv), bt, validw, 1, impl="pallas", interpret=True
    )
    np.testing.assert_allclose(
        np.asarray(ow_xla), np.asarray(ow_pl), atol=2e-5, rtol=1e-5
    )


# -- engine helpers -----------------------------------------------------


def _engine(*, kv_dtype="int8", role="unified", host_mb=0.0, R=3,
            context=256, page=8, chunk=4, spec="off", seed=1):
    cfg = JaxDecodeConfig(
        context_length=context,
        max_running_requests=R,
        new_tokens_per_chunk=chunk,
        page_size=page,
        kv_dtype=kv_dtype,
        paged_attn_impl="xla",
        kv_host_pool_mb=host_mb,
        spec_decode=spec,
        spec_k=3,
        role=role,
        kv_migrate_chunk_mb=0.01,
        dtype="float32",
        kv_cache_dtype="float32",
        random_seed=seed,
    )
    eng = JaxDecodeEngine(cfg, InferenceEngineConfig())
    eng.set_model(_params(), TINY)
    eng.initialize()
    return eng


def _run_async(coro, timeout=180):
    result = {}

    def go():
        try:
            result["v"] = asyncio.run(coro)
        except BaseException as e:  # noqa: BLE001
            result["e"] = e

    t = threading.Thread(target=go, daemon=True)
    t.start()
    t.join(timeout)
    assert not t.is_alive(), "async scenario timed out"
    if "e" in result:
        raise result["e"]
    return result.get("v")


def _prefill(eng, req):
    return _run_async(eng.aprefill(req))


async def _gather_generates(eng, prompts, g):
    return await asyncio.gather(
        *[
            eng.agenerate(ModelRequest(input_ids=p, gconfig=g))
            for p in prompts
        ]
    )


def _prompt(n=44, seed=5):
    return np.random.RandomState(seed).randint(1, 64, (n,)).tolist()


_GREEDY = GenerationHyperparameters(max_new_tokens=10, greedy=True)
_SAMPLED = GenerationHyperparameters(
    max_new_tokens=10, temperature=0.8, top_p=0.9
)


# -- 3a. config gate ----------------------------------------------------


def test_unknown_kv_dtype_rejected(cpu_devices):
    cfg = JaxDecodeConfig(
        kv_dtype="int4", dtype="float32", kv_cache_dtype="float32"
    )
    eng = JaxDecodeEngine(cfg, InferenceEngineConfig())
    eng.set_model(_params(), TINY)
    with pytest.raises(ValueError, match="kv_dtype"):
        eng.initialize()


# -- 3b. quantized-to-quantized bit-identity ----------------------------


@pytest.mark.parametrize("gname", ["greedy", "sampled"])
def test_export_import_int8_stream_bit_identity(cpu_devices, gname):
    """An int8 session migrated prefill-replica -> wire -> decode-replica
    resumes BIT-IDENTICALLY to the uninterrupted int8 stream: the wire
    carries the quantized blocks + scale blocks verbatim (checked byte
    for byte through the framed staging), and the importing engine
    uploads them without requantization."""
    g = _GREEDY if gname == "greedy" else _SAMPLED
    prompt = _prompt(44, seed=5)
    oracle = _engine()
    try:
        ro = oracle.generate(
            ModelRequest(rid="m", input_ids=prompt, gconfig=g), timeout=120
        )
    finally:
        oracle.destroy()

    pre = _engine(role="prefill")
    try:
        _prefill(pre, ModelRequest(rid="m", input_ids=prompt, gconfig=g))
        sess = pre.export_session("m")
        assert sess is not None
        assert sess["meta"]["kv_dtype"] == "int8"
        assert sess["k"].dtype == np.int8 and sess["v"].dtype == np.int8
        assert sess["ks"].dtype == np.float32
        m = pre.get_metrics()
        # migrated bytes are PHYSICAL: data + scales, nothing fp-sized
        expect = sum(sess[x].nbytes for x in ("k", "v", "ks", "vs"))
        assert m["kv_migrated_out_bytes_total"] == expect
    finally:
        pre.destroy()

    # wire round-trip: scale blocks survive the framed staging bit-exactly
    # int8 sessions are ~half the fp bytes: a smaller frame cap still
    # exercises the multi-frame staging path
    frames = list(
        pack_kv_session(
            sess["meta"], sess["k"], sess["v"], sess["ks"], sess["vs"],
            chunk_mb=0.002,
        )
    )
    assert len(frames) > 1
    st = WeightStaging()
    for f in frames:
        st.add_bucket(f)
    (meta, k, v, scales), = unpack_kv_sessions(st.finalize())
    assert scales is not None
    ks, vs = scales
    assert np.array_equal(np.asarray(k), sess["k"])
    assert np.array_equal(np.asarray(ks), sess["ks"])
    assert np.array_equal(np.asarray(vs), sess["vs"])

    dec = _engine(role="decode")
    try:
        assert dec.import_session(meta, k, v, ks, vs) == "ok"
        m0 = dec.get_metrics()
        rd = dec.generate(
            ModelRequest(rid="m", input_ids=prompt, gconfig=g), timeout=120
        )
        m1 = dec.get_metrics()
        assert m1["prefills_total"] == m0["prefills_total"]
        assert m1["kv_host_hits_total"] - m0["kv_host_hits_total"] == 1
        assert rd.output_tokens == ro.output_tokens
        assert rd.output_logprobs == ro.output_logprobs
    finally:
        dec.destroy()


def test_int8_wire_requires_scales_iff_int8():
    meta = dict(
        rid="s", covered=4, tokens=[1, 2, 3, 4], rope_delta=0,
        base_key=[1, 2], weight_version=0, nb=1, kv_dtype="int8",
    )
    k = np.zeros((1, 1, 4, 1, 2), np.int8)
    with pytest.raises(ValueError, match="scales"):
        list(pack_kv_session(meta, k, k, chunk_mb=1))
    meta_fp = dict(meta, kv_dtype="fp")
    s = np.ones((1, 1, 1, 4), np.float32)
    with pytest.raises(ValueError, match="scales"):
        list(pack_kv_session(meta_fp, k, k, s, s, chunk_mb=1))
    # an int8 session whose scale tensors were lost in staging is
    # structurally incomplete, not silently fp
    frames = list(pack_kv_session(meta, k, k, s, s, chunk_mb=1))
    st = WeightStaging()
    for f in frames:
        st.add_bucket(f)
    staged = st.finalize()
    staged.pop("kvdata/s/ks")
    staged.pop("kvdata/s/vs")
    with pytest.raises(ValueError, match="scale"):
        unpack_kv_sessions(staged)


@pytest.mark.parametrize("gname", ["greedy", "sampled"])
def test_int8_evicted_resume_bit_identical(cpu_devices, gname):
    """park -> LRU-evict -> host offload -> promote on an int8 pool: the
    resumed stream equals the uninterrupted int8 oracle bit for bit, with
    spec_decode="ngram" live — the offloaded entry carries the int8
    blocks + scales and the promotion uploads them verbatim."""
    g = replace(
        _GREEDY if gname == "greedy" else _SAMPLED, max_new_tokens=24
    )
    g_fill = replace(g, max_new_tokens=8)
    prompt = _prompt(8, seed=11)
    fillers = [_prompt(8, seed=13), _prompt(8, seed=17)]

    oracle = _engine(R=4, spec="ngram")
    try:
        ro = oracle.generate(
            ModelRequest(input_ids=prompt, gconfig=g), timeout=180
        )
    finally:
        oracle.destroy()

    eng = _engine(R=2, host_mb=64.0, spec="ngram")
    try:
        rid = str(uuid.uuid4())
        out = {}

        def _go():
            async def _r():
                return await eng.agenerate(
                    ModelRequest(rid=rid, input_ids=prompt, gconfig=g)
                )

            out["r"] = asyncio.run(_r())

        t = threading.Thread(target=_go, daemon=True)
        t.start()
        deadline = time.monotonic() + 120
        while (
            eng.get_metrics()["generated_tokens_total"] < 1
            and time.monotonic() < deadline
        ):
            time.sleep(0.002)
        eng.pause_generation()
        eng.abort_all()
        eng.continue_generation()
        t.join(120)
        seg1 = out["r"]
        assert seg1.stop_reason == "interrupt"
        # fillers claim BOTH slots concurrently -> the parked int8 KV
        # LRU-evicts to the host tier
        _run_async(
            _gather_generates(eng, fillers, g_fill), timeout=180
        )
        m = eng.get_metrics()
        assert m["kv_swap_out_bytes_total"] > 0, "parked KV never offloaded"
        # swap bytes are physical int8+scales block bytes
        assert m["kv_swap_out_bytes_total"] % m["kv_block_nbytes"] == 0
        seg2 = eng.generate(
            ModelRequest(
                rid=rid,
                input_ids=list(prompt) + list(seg1.output_tokens),
                gconfig=replace(
                    g,
                    max_new_tokens=g.max_new_tokens
                    - len(seg1.output_tokens),
                ),
            ),
            timeout=180,
        )
        m1 = eng.get_metrics()
        assert m1["kv_host_hits_total"] >= 1
    finally:
        eng.destroy()
    tokens = list(seg1.output_tokens) + list(seg2.output_tokens)
    logps = list(seg1.output_logprobs) + list(seg2.output_logprobs)
    assert tokens == list(ro.output_tokens), (tokens, ro.output_tokens)
    assert_logprobs_close(logps, ro.output_logprobs)


# -- 3c. mixed-dtype fleets ---------------------------------------------


def test_mixed_dtype_import_is_tombstoned_honest_miss(cpu_devices):
    prompt = _prompt(36, seed=9)
    pre = _engine(kv_dtype="fp", role="prefill")
    try:
        _prefill(pre, ModelRequest(rid="x", input_ids=prompt,
                                   gconfig=_GREEDY))
        sess_fp = pre.export_session("x")
        assert sess_fp["meta"]["kv_dtype"] == "fp"
        assert "ks" not in sess_fp
    finally:
        pre.destroy()

    dec = _engine(kv_dtype="int8", role="decode")
    try:
        assert dec.import_session(
            sess_fp["meta"], sess_fp["k"], sess_fp["v"]
        ) == "kv_dtype_mismatch"
        m0 = dec.get_metrics()
        assert m0["kv_migrate_dtype_rejects_total"] == 1
        assert m0["kv_migrated_in_sessions_total"] == 0
        # the resume pays an honest re-prefill, counted as a host miss
        rd = dec.generate(
            ModelRequest(rid="x", input_ids=prompt, gconfig=_GREEDY),
            timeout=120,
        )
        m1 = dec.get_metrics()
        assert m1["prefills_total"] - m0["prefills_total"] == 1
        assert m1["kv_host_misses_total"] - m0["kv_host_misses_total"] == 1
        assert len(rd.output_tokens) == 10
    finally:
        dec.destroy()

    # and the reverse direction: int8 session into an fp engine
    prei = _engine(kv_dtype="int8", role="prefill")
    try:
        _prefill(prei, ModelRequest(rid="y", input_ids=prompt,
                                    gconfig=_GREEDY))
        sess_i8 = prei.export_session("y")
    finally:
        prei.destroy()
    decf = _engine(kv_dtype="fp", role="decode")
    try:
        assert decf.import_session(
            sess_i8["meta"], sess_i8["k"], sess_i8["v"],
            sess_i8["ks"], sess_i8["vs"],
        ) == "kv_dtype_mismatch"
        assert decf.get_metrics()["kv_migrate_dtype_rejects_total"] == 1
    finally:
        decf.destroy()


def test_int8_import_missing_scales_rejected(cpu_devices):
    prompt = _prompt(30, seed=21)
    pre = _engine(role="prefill")
    try:
        _prefill(pre, ModelRequest(rid="z", input_ids=prompt,
                                   gconfig=_GREEDY))
        sess = pre.export_session("z")
    finally:
        pre.destroy()
    dec = _engine(role="decode")
    try:
        # int8 meta but no scale arrays: malformed, not an honest miss
        assert dec.import_session(
            sess["meta"], sess["k"], sess["v"]
        ) == "rejected"
        # wrong-dtype data for an int8 session: malformed too
        assert dec.import_session(
            sess["meta"], sess["k"].astype(np.float32),
            sess["v"].astype(np.float32), sess["ks"], sess["vs"],
        ) == "rejected"
        assert dec.get_metrics()["kv_migrated_in_sessions_total"] == 0
    finally:
        dec.destroy()


# -- 3d. physical byte accounting --------------------------------------


def test_block_nbytes_is_physical(cpu_devices):
    efp = _engine(kv_dtype="fp")
    ei8 = _engine(kv_dtype="int8")
    try:
        mf = efp.get_metrics()
        mi = ei8.get_metrics()
        # TINY at page 8, f32: per block-side bs*nkv*hd*4 = 8*2*8*4; int8:
        # bs*nkv*(hd*1 + 4 scale bytes)
        L, bs, nkv, hd = 2, 8, 2, 8
        assert mf["kv_block_nbytes"] == 2 * L * bs * nkv * hd * 4
        assert mi["kv_block_nbytes"] == 2 * L * bs * nkv * (hd + 4)
        assert mf["kv_dtype"] == "fp" and mi["kv_dtype"] == "int8"
        # same block COUNT either way; device bytes shrink with the dtype
        assert mf["kv_blocks_total"] == mi["kv_blocks_total"]
        ratio = mf["kv_pool_device_bytes"] / mi["kv_pool_device_bytes"]
        assert ratio == pytest.approx(
            mf["kv_block_nbytes"] / mi["kv_block_nbytes"]
        )
        assert ratio > 1.5
    finally:
        efp.destroy()
        ei8.destroy()


# -- 3e. prewarm covers the quantized variants --------------------------


def test_prewarm_ghost_compiles_quantized_variants(cpu_devices):
    """Prewarm on an int8 engine must compile the QUANTIZED chunk and
    verify variants (the chunk fns are built from the live kv_dtype, so
    the ghost dispatches trace the int8 scatter + dequant kernels) and
    leave the pool state untouched: a post-prewarm stream equals a fresh
    engine's bit for bit, and the pool is still int8."""
    g = replace(_GREEDY, max_new_tokens=8)
    prompt = _prompt(16, seed=23)

    fresh = _engine(spec="ngram")
    try:
        r0 = fresh.generate(
            ModelRequest(input_ids=prompt, gconfig=g), timeout=180
        )
    finally:
        fresh.destroy()

    eng = _engine(spec="ngram")
    try:
        eng.prewarm(prompt_len=16, gconfig=g, include_fork=False)
        assert eng._chunk_fns, "prewarm compiled no chunk variants"
        assert eng._verify_fns, "prewarm compiled no verify variants"
        assert eng._k_cache.dtype == jnp.int8
        assert eng._k_scale is not None
        r1 = eng.generate(
            ModelRequest(input_ids=prompt, gconfig=g), timeout=180
        )
    finally:
        eng.destroy()
    assert list(r1.output_tokens) == list(r0.output_tokens)
    assert list(r1.output_logprobs) == list(r0.output_logprobs)


# -- 4. drift vs the fp oracle is measured, bounded ---------------------


@pytest.mark.parametrize("gname", ["greedy", "sampled"])
def test_int8_drift_vs_fp_oracle_bounded_and_deterministic(
    cpu_devices, gname
):
    """Int8 changes the numerics — the contract is that the drift is
    SMALL and DETERMINISTIC, not zero: over the token-matched prefix the
    per-token |logprob delta| stays under a bound, and two independent
    int8 engines reproduce the identical stream (so the drift is a fixed
    property of the scheme, not noise). Spec decoding stays ON: accepted
    speculative tokens must remain bit-identical to the int8 non-spec
    path, so speculation cannot ADD drift on top of quantization."""
    g = replace(
        _GREEDY if gname == "greedy" else _SAMPLED, max_new_tokens=16
    )
    # a repetitive prompt so the n-gram drafter actually fires
    prompt = ([7, 8, 9, 10, 11, 12] * 8)[:48]

    def run(kv_dtype, spec):
        e = _engine(kv_dtype=kv_dtype, spec=spec)
        try:
            r = e.generate(
                ModelRequest(input_ids=prompt, gconfig=g), timeout=180
            )
            return list(r.output_tokens), list(r.output_logprobs)
        finally:
            e.destroy()

    fp_t, fp_l = run("fp", "ngram")
    i8_t, i8_l = run("int8", "ngram")
    i8_t2, i8_l2 = run("int8", "ngram")
    i8_t_nospec, i8_l_nospec = run("int8", "off")

    # determinism: the quantized stream is a pure function of the pool
    assert i8_t == i8_t2 and i8_l == i8_l2
    # spec adds NO drift on top of quantization (a different program:
    # same tokens, logprobs to float32 rounding)
    assert i8_t == i8_t_nospec
    assert_logprobs_close(i8_l, i8_l_nospec)

    matched = 0
    for a, b in zip(fp_t, i8_t):
        if a != b:
            break
        matched += 1
    deltas = [abs(a - b) for a, b in zip(fp_l[:matched], i8_l[:matched])]
    # measured drift, pinned: int8 KV on this tiny f32 model stays well
    # under 0.25 logprob on the matched prefix (seen ~0.05 typical); a
    # regression in the scheme (wrong scale axis, double quantization)
    # blows far past this
    assert matched >= 1
    if deltas:
        assert max(deltas) < 0.25, (matched, deltas)
