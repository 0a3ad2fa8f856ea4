"""In-pool paged-attention decode: op-level correctness and engine-level
layout parity.

The decode engine's `kv_layout="paged"` path (the default) must produce
the SAME streams as the retained `kv_layout="workspace"` numerics oracle:
identical greedy tokens, and per-token logprobs that are bitwise equal on
the XLA gather impl (it reproduces the workspace op sequence exactly) /
allclose (fp32, atol 1e-4) on the Pallas split-KV kernel. The engine
sweep covers the full scheduling surface the ISSUE names: prefix forks
(duplicate prompts), suffix prefills (conversation extensions past the
shared-prefix threshold), retire-mid-chunk reconcile under run-ahead,
and frequency-penalty + top-p sampling.
"""

import asyncio
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
from areal_tpu.engine.jax_decode import JaxDecodeEngine
from areal_tpu.models.qwen2 import ModelConfig, decode_step, init_params
from areal_tpu.ops.paged_attention import paged_attention, resolve_impl

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


# ---------------------------------------------------------------------------
# op level
# ---------------------------------------------------------------------------


_LAYERS, _LAYER = 3, 1  # the op reads ONE layer of the whole stacked pool


def _random_pool(rng, n_blocks, bsz, nKV, hd, dtype=np.float32):
    """The stored layout: [L, n_blocks, bsz, nKV*hd], heads side by side."""
    shape = (_LAYERS, n_blocks, bsz, nKV * hd)
    k = rng.standard_normal(shape).astype(np.float32)
    v = rng.standard_normal(shape).astype(np.float32)
    return jnp.asarray(k, dtype), jnp.asarray(v, dtype)


def _dense_reference(q, kp, vp, bt, valid, layer=_LAYER):
    """Gather + plain masked softmax attention in f64-free numpy."""
    R, nH, hd = q.shape
    bsz, nKV = kp.shape[2], kp.shape[3] // hd
    nb = bt.shape[1]
    group = nH // nKV
    kc = np.asarray(kp)[layer][np.asarray(bt).reshape(-1)].reshape(
        R, nb * bsz, nKV, hd
    )
    vc = np.asarray(vp)[layer][np.asarray(bt).reshape(-1)].reshape(
        R, nb * bsz, nKV, hd
    )
    qg = np.asarray(q).reshape(R, nKV, group, hd)
    out = np.zeros((R, nH, hd), np.float32)
    for r in range(R):
        for k_h in range(nKV):
            for g in range(group):
                s = kc[r, :, k_h] @ qg[r, k_h, g] / np.sqrt(hd)
                s = np.where(np.asarray(valid)[r], s, -1e30)
                p = np.exp(s - s.max())
                p /= p.sum()
                out[r, k_h * group + g] = p @ vc[r, :, k_h]
    return out


def test_paged_attention_xla_vs_dense(cpu_devices):
    rng = np.random.default_rng(0)
    R, nH, nKV, hd, bsz, nb, n_blocks = 3, 4, 2, 8, 16, 3, 12
    kp, vp = _random_pool(rng, n_blocks, bsz, nKV, hd)
    q = jnp.asarray(rng.standard_normal((R, nH, hd)).astype(np.float32))
    bt = jnp.asarray(
        rng.choice(np.arange(1, n_blocks), size=(R, nb), replace=False)
        .astype(np.int32)
    )
    lengths = np.array([5, 17, nb * bsz - 1], np.int32)
    valid = jnp.asarray(np.arange(nb * bsz)[None, :] <= lengths[:, None])
    out = paged_attention(q, kp, vp, bt, valid, _LAYER, impl="xla")
    ref = _dense_reference(q, kp, vp, bt, valid)
    np.testing.assert_allclose(np.asarray(out), ref, atol=1e-5, rtol=1e-5)


def test_paged_attention_pallas_vs_xla(cpu_devices):
    """The split-KV online-softmax kernel (interpret mode on CPU) must
    match the gather fallback on every slot, including slots whose valid
    span ends mid-block and a fully-masked (length-0 equivalent) row."""
    rng = np.random.default_rng(1)
    R, nH, nKV, hd, bsz, nb, n_blocks = 4, 8, 2, 16, 16, 4, 20
    kp, vp = _random_pool(rng, n_blocks, bsz, nKV, hd)
    q = jnp.asarray(rng.standard_normal((R, nH, hd)).astype(np.float32))
    bt = jnp.asarray(
        rng.choice(np.arange(1, n_blocks), size=(R, nb), replace=False)
        .astype(np.int32)
    )
    lengths = np.array([0, 9, 30, nb * bsz - 1], np.int32)
    valid = jnp.asarray(np.arange(nb * bsz)[None, :] <= lengths[:, None])
    a = paged_attention(q, kp, vp, bt, valid, _LAYER, impl="xla")
    b = paged_attention(
        q, kp, vp, bt, valid, jnp.int32(_LAYER), impl="pallas", interpret=True
    )
    np.testing.assert_allclose(
        np.asarray(a), np.asarray(b), atol=1e-5, rtol=1e-5
    )


@pytest.mark.parametrize(
    "nH,nKV,hd", [(14, 2, 64), (12, 2, 128)], ids=["0.5b", "1.5b"]
)
def test_paged_attention_pallas_at_published_heads(cpu_devices, nH, nKV, hd):
    """The kernel at the head shapes the chip runs (Qwen2.5-0.5B: 14/2 of
    64; the rollout cell's 1.5B: 12/2 of 128) and its page of 128 rows,
    reading layer `_LAYER` of a bf16 4-D pool through the table, against a
    float32 einsum over the gathered rows."""
    rng = np.random.default_rng(4)
    R, bsz, nb = 3, 128, 2
    n_blocks = 1 + R * nb
    kp, vp = _random_pool(rng, n_blocks, bsz, nKV, hd, jnp.bfloat16)
    q = jnp.asarray(rng.standard_normal((R, nH, hd)), jnp.bfloat16)
    bt = jnp.asarray(
        rng.permutation(np.arange(1, n_blocks)).astype(np.int32).reshape(R, nb)
    )
    lengths = np.array([0, 130, nb * bsz - 1], np.int32)
    valid = jnp.asarray(np.arange(nb * bsz)[None, :] <= lengths[:, None])
    out = paged_attention(
        q, kp, vp, bt, valid, jnp.int32(_LAYER), impl="pallas", interpret=True
    )
    f32 = lambda a: jnp.asarray(a, jnp.float32)  # noqa: E731
    kc = f32(kp)[_LAYER][bt].reshape(R, nb * bsz, nKV, hd)
    vc = f32(vp)[_LAYER][bt].reshape(R, nb * bsz, nKV, hd)
    qg = f32(q).reshape(R, nKV, nH // nKV, hd)
    scores = jnp.einsum("rkgd,rskd->rkgs", qg, kc) / np.sqrt(hd)
    scores = jnp.where(valid[:, None, None, :], scores, -1e30)
    ref = jnp.einsum(
        "rkgs,rskd->rkgd", jax.nn.softmax(scores, axis=-1), vc
    ).reshape(R, nH, hd)
    # the output is rounded to bf16 once; everything before it is f32
    np.testing.assert_allclose(
        np.asarray(f32(out)), np.asarray(ref), atol=2e-2, rtol=2e-2
    )


def test_resolve_impl(cpu_devices):
    assert resolve_impl("xla") == "xla"
    assert resolve_impl("pallas") == "pallas"
    assert resolve_impl("auto") in ("pallas", "xla")
    with pytest.raises(ValueError):
        resolve_impl("cuda")


def test_decode_step_paged_matches_workspace(cpu_devices):
    """One decode step: the paged write (O(1) dynamic scatter) + in-pool
    attention must produce the same logits as decode_step over the
    gathered workspace, and must write the SAME bytes into the written
    row while leaving every other live block untouched."""
    from areal_tpu.models.qwen2 import decode_step_paged

    rng = np.random.default_rng(2)
    params = init_params(TINY, jax.random.PRNGKey(0))
    L, nKV, hd = TINY.num_hidden_layers, TINY.num_key_value_heads, TINY.head_dim_
    R, bsz, nb, n_blocks = 3, 8, 3, 10
    kp = jnp.asarray(
        rng.standard_normal((L, n_blocks, bsz, nKV * hd)).astype(np.float32)
    )
    vp = jnp.asarray(
        rng.standard_normal((L, n_blocks, bsz, nKV * hd)).astype(np.float32)
    )
    bt = jnp.asarray(
        rng.choice(np.arange(1, n_blocks), size=(R, nb), replace=False)
        .astype(np.int32)
    )
    tokens = jnp.asarray([3, 7, 11], jnp.int32)
    positions = jnp.asarray([4, 11, 20], jnp.int32)
    active = jnp.asarray([True, True, False])

    # workspace oracle: gather, step, scatter
    idx = bt.reshape(-1)
    kc = jnp.take(kp, idx, axis=1).reshape(L, R, nb * bsz, nKV, hd)
    vc = jnp.take(vp, idx, axis=1).reshape(L, R, nb * bsz, nKV, hd)
    logits_ws, kc2, vc2 = decode_step(
        params, tokens, positions, kc, vc, TINY, active=active
    )
    kp_ws = kp.at[:, idx].set(kc2.reshape(L, R * nb, bsz, nKV * hd))
    vp_ws = vp.at[:, idx].set(vc2.reshape(L, R * nb, bsz, nKV * hd))

    logits_pg, kp_pg, vp_pg = decode_step_paged(
        params, tokens, positions, kp, vp, bt, TINY, active=active,
        attn_impl="xla",
    )
    np.testing.assert_array_equal(np.asarray(logits_ws), np.asarray(logits_pg))
    # every block except the reserved null block 0 (paged parks inactive
    # writes there; workspace masks them) must match bit for bit
    np.testing.assert_array_equal(
        np.asarray(kp_ws)[:, 1:], np.asarray(kp_pg)[:, 1:]
    )
    np.testing.assert_array_equal(
        np.asarray(vp_ws)[:, 1:], np.asarray(vp_pg)[:, 1:]
    )


# ---------------------------------------------------------------------------
# engine level: full-trace layout parity
# ---------------------------------------------------------------------------

_BASE = [1, 5, 9, 13, 2, 4, 6, 8]  # shared prompt for fork coverage


def _engine(layout: str, impl: str = "auto", **kw):
    cfg = JaxDecodeConfig(
        context_length=kw.pop("context_length", 256),
        max_running_requests=kw.pop("max_running_requests", 4),
        new_tokens_per_chunk=kw.pop("new_tokens_per_chunk", 4),
        page_size=kw.pop("page_size", 16),
        decode_runahead_chunks=kw.pop("decode_runahead_chunks", 1),
        kv_layout=layout,
        paged_attn_impl=impl,
        dtype="float32",
        kv_cache_dtype="float32",
        random_seed=7,
        **kw,
    )
    eng = JaxDecodeEngine(cfg, InferenceEngineConfig())
    eng.set_model(init_params(TINY, jax.random.PRNGKey(0)), TINY)
    eng.initialize()
    return eng


def _run_trace(eng):
    """One request trace hitting forks, suffix prefill, retire-mid-chunk
    and the sampler variants; returns responses in a deterministic order."""

    async def main():
        g = GenerationHyperparameters(greedy=True, max_new_tokens=10)
        # wave of duplicates (same-wave dup fork) + distinct prompts
        wave = await asyncio.gather(
            eng.agenerate(ModelRequest(input_ids=list(_BASE), gconfig=g)),
            eng.agenerate(ModelRequest(input_ids=list(_BASE), gconfig=g)),
            eng.agenerate(ModelRequest(input_ids=[2, 7, 11, 3], gconfig=g)),
            # stop token likely mid-chunk: retire-mid-chunk reconcile under
            # run-ahead (the chunk after the stop is already dispatched)
            eng.agenerate(
                ModelRequest(
                    input_ids=[9, 9, 1, 4],
                    gconfig=replace(g, max_new_tokens=9, stop_token_ids=[1]),
                )
            ),
        )
        # conversation extension PAST the 64-token shared-prefix floor:
        # long donor finishes, then a request re-submits donor prompt +
        # answer + a new suffix -> fork + suffix prefill
        long_prompt = [(i % 60) + 1 for i in range(70)]
        donor = await eng.agenerate(
            ModelRequest(input_ids=list(long_prompt), gconfig=g)
        )
        ext = await eng.agenerate(
            ModelRequest(
                input_ids=list(long_prompt)
                + list(donor.output_tokens)
                + [5, 3],
                gconfig=g,
            )
        )
        # sampled variants: freq penalty and top-p classes share a batch
        sampled = await asyncio.gather(
            eng.agenerate(
                ModelRequest(
                    input_ids=[1, 2, 3],
                    gconfig=GenerationHyperparameters(
                        temperature=1.0,
                        top_p=0.9,
                        max_new_tokens=8,
                        frequency_penalty=0.7,
                    ),
                )
            ),
            eng.agenerate(
                ModelRequest(
                    input_ids=[4, 5, 6],
                    gconfig=GenerationHyperparameters(
                        temperature=0.8, top_p=1.0, max_new_tokens=8
                    ),
                )
            ),
        )
        return list(wave) + [donor, ext] + list(sampled)

    return asyncio.run(main())


def _trace_and_metrics(layout, impl="auto"):
    eng = _engine(layout, impl)
    try:
        out = _run_trace(eng)
        m = eng.get_metrics()
    finally:
        eng.destroy()
    return out, m


def test_engine_layout_parity_xla(cpu_devices):
    """kv_layout='paged' (xla impl) vs 'workspace': bitwise-identical
    tokens AND logprobs across forks, suffix prefill, retire-mid-chunk
    under run-ahead, and freq-penalty/top-p sampling."""
    ws, m_ws = _trace_and_metrics("workspace")
    pg, m_pg = _trace_and_metrics("paged", "xla")
    assert len(ws) == len(pg)
    for i, (a, b) in enumerate(zip(ws, pg)):
        assert a.output_tokens == b.output_tokens, i
        assert a.output_logprobs == b.output_logprobs, i
        assert a.stop_reason == b.stop_reason, i
    # the trace really exercised the sharing paths, on both engines
    for m in (m_ws, m_pg):
        assert m["prefix_forks_total"] >= 1, m
        assert m["suffix_prefills_total"] >= 1, m
        assert m["prefix_cache_hit_rate"] > 0.0, m
    # and the layouts differ where they should: workspace pays gather +
    # scatter per chunk; the paged xla impl keeps only the gather (the
    # scatter-back half of the round trip is eliminated — exactly half
    # the bytes on the same chunk trace)
    assert m_ws["kv_workspace_copy_bytes_total"] > 0
    assert (
        m_pg["kv_workspace_copy_bytes_total"]
        == m_ws["kv_workspace_copy_bytes_total"] // 2
    ), (m_pg["kv_workspace_copy_bytes_total"],
        m_ws["kv_workspace_copy_bytes_total"])
    assert m_pg["kv_layout"] == "paged"


def test_engine_layout_parity_pallas(cpu_devices):
    """The Pallas split-KV kernel (interpret mode on CPU) keeps greedy
    streams identical and logprobs allclose (fp32, atol 1e-4)."""
    ws, _ = _trace_and_metrics("workspace")
    pg, m_pg = _trace_and_metrics("paged", "pallas")
    # the true in-pool path copies NOTHING per chunk
    assert m_pg["kv_workspace_copy_bytes_total"] == 0
    for i, (a, b) in enumerate(zip(ws, pg)):
        assert a.output_tokens == b.output_tokens, i
        np.testing.assert_allclose(
            np.asarray(a.output_logprobs),
            np.asarray(b.output_logprobs),
            atol=1e-4,
            err_msg=str(i),
        )


def test_block_table_upload_dirty_tracking(cpu_devices):
    """Steady-state chunks must NOT re-upload the block table: uploads
    are keyed on (allocator mutation version, nb), so a long generation
    with a stable slot set uploads only when admission/retire/growth
    actually moved the table."""
    eng = _engine("paged", "xla", new_tokens_per_chunk=2)
    try:

        async def main():
            g = GenerationHyperparameters(greedy=True, max_new_tokens=24)
            return await eng.agenerate(
                ModelRequest(input_ids=[3, 1, 4], gconfig=g)
            )

        asyncio.run(main())
        m = eng.get_metrics()
    finally:
        eng.destroy()
    # 24 tokens at 2/chunk = 12 chunks; table mutates only at admission
    # and on block-boundary growth (page_size 16 -> at most a few times)
    assert m["chunks_dispatched_total"] >= 12
    assert m["block_table_uploads_total"] < m["chunks_dispatched_total"], m
    assert m["block_table_uploads_total"] >= 1


def test_prewarm_covers_paged_variants(cpu_devices):
    """Prewarm on a paged engine must ghost-compile the paged chunk
    variants (and the patch fn) so the first overlapped dispatch never
    traces: after prewarm, serving a request compiles nothing new."""
    eng = _engine("paged", "xla")
    try:
        eng.prewarm(prompt_len=8, new_tokens=4, sampler_top_ps=(1.0,))
        compiled = set(eng._chunk_fns)
        assert compiled, "prewarm compiled no chunk variants"
        assert eng._patch_fn is not None

        async def main():
            g = GenerationHyperparameters(greedy=True, max_new_tokens=4)
            return await eng.agenerate(
                ModelRequest(input_ids=[3, 1, 4, 1, 5, 9, 2, 6], gconfig=g)
            )

        asyncio.run(main())
        assert set(eng._chunk_fns) == compiled, (
            "live traffic needed a chunk variant prewarm did not compile"
        )
    finally:
        eng.destroy()


def test_fragmentation_metric(cpu_devices):
    """kv_pool_fragmentation counts the free-block remainder that cannot
    back another max-context admission."""
    eng = _engine(
        "paged", "xla", context_length=64, page_size=16, kv_pool_tokens=112
    )
    try:
        m = eng.get_metrics()
        # 7 usable blocks, max_bps = 4 -> one full-context reservation
        # fits, 3 blocks are structural remainder
        assert m["kv_blocks_free"] == 7
        assert m["kv_pool_fragmentation"] == 3
    finally:
        eng.destroy()
