"""DeepSeek-V2 (`deepseek_v2`) through `JaxDecodeEngine` at a tiny width on
the CPU: prefill (expanded form) then decode (absorbed form) through the
latent pool against the float32 reference's full forward, across bucket
padding, a page and a chunk boundary, a group's forks (blocks aliased), a
reused slot, a prompt above the dense prefill's limit, for all and for a
share of the experts; the interpreted Pallas kernel against `jax.numpy`;
the pool's shape and byte counts; what `initialize()` refuses; the new
scopes in the lowered programs; the other configurations' lowered programs
text-equal to the parent's."""

import hashlib
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark.lib import kind_rollout  # noqa: E402
from benchmark.reference import deepseek_v2_ref  # noqa: E402
from test_deepseek_v2 import FULL, _ids, held_slice, seeded, tiny  # noqa: E402

from areal_tpu.models import qwen2  # noqa: E402
from areal_tpu.ops.paged_attention import live_block_range, slot_schedule  # noqa: E402
from areal_tpu.ops.paged_attention_latent import paged_attention_latent  # noqa: E402

F32_TOL = 1e-4


@pytest.fixture(scope="module")
def params():
    return seeded(FULL)


def _engine(cfg, params, **over):
    from areal_tpu.api.cli_args import JaxDecodeConfig
    from areal_tpu.engine.jax_decode import JaxDecodeEngine

    kw = dict(context_length=256, max_running_requests=4, new_tokens_per_chunk=16, page_size=4,
              dtype="float32", kv_cache_dtype="float32")
    kw.update(over)
    engine = JaxDecodeEngine(JaxDecodeConfig(**kw))
    engine.set_model(params, cfg)
    return engine


def _group(engine, prompt, lens):
    import asyncio

    async def go():
        engine.pause_generation()
        tasks = [asyncio.ensure_future(engine.agenerate(kind_rollout._request(prompt, n, 1.0)))
                 for n in lens]
        await asyncio.sleep(0)
        engine.continue_generation()
        return await asyncio.gather(*tasks)

    return asyncio.run(go())


def _agrees(p, cfg, resps):
    for r in resps:
        seq = list(r.input_tokens) + list(r.output_tokens)
        ref = deepseek_v2_ref.token_logprobs(p, cfg, seq)
        np.testing.assert_allclose(np.asarray(r.output_logprobs), ref[r.input_len - 1:],
                                   atol=10 * F32_TOL)


@pytest.mark.parametrize("held,first", [(16, 0), (4, 8)])
def test_prefill_then_decode_through_the_latent_pool(params, held, first):
    """A group of three: one prefill (70 tokens in a bucket of 128: padding)
    and two forks by aliasing, 40 new tokens over pages of 4 and chunks of 16
    (page and chunk boundaries), then a second group through the slots the
    first one left (a reused slot)."""
    cfg = tiny(held, first)
    p = held_slice(params, first, held)
    engine = _engine(cfg, p).initialize()
    try:
        kq, vq = engine._kv_operands()
        assert set(kq) == {"latent"} and vq == {}
        assert kq["latent"].shape == (cfg.num_hidden_layers, 4 * 64 + 1, 4, cfg.latent_row_lanes)
        first_group = _group(engine, _ids(9, 70).tolist(), (40, 25, 33))
        m = engine.get_metrics()
        second_group = _group(engine, _ids(10, 31).tolist(), (18, 7))
        m2 = engine.get_metrics()
    finally:
        engine.destroy()
    assert (m["prefills_total"], m["prefix_forks_total"]) == (1, 2)
    assert (m2["prefills_total"], m2["prefix_forks_total"]) == (2, 3)
    _agrees(p, cfg, first_group + second_group)
    sparse = cfg.num_hidden_layers - cfg.first_k_dense
    pairs = m["moe_pairs_total"] + m["moe_absent_pairs_total"]
    assert pairs % (cfg.num_experts_per_tok * sparse) == 0
    assert pairs >= (40 + 25 + 33) * cfg.num_experts_per_tok * sparse
    assert (m["moe_absent_pairs_total"] == 0) == (held == 16)
    # a token's kept groups include the held ones: always with every group held
    steps = pairs // (cfg.num_experts_per_tok * sparse)
    assert (m["moe_group_tokens_here_total"] == steps * sparse) == (held == 16)
    assert 0 < m["moe_group_tokens_here_total"] <= steps * sparse
    # held experts with a pair, a sparse layer and token step: at least one where a pair
    # landed, never more than the pairs or than the layer holds
    assert 0 < m["moe_group_experts_touched_total"] <= min(
        m["moe_pairs_total"], m["moe_group_tokens_here_total"] * held)
    # every live row of every latent layer of every live slot step, at least
    # the prompt's rows a step
    assert m["kv_latent_rows_read_total"] >= (40 + 25 + 33) * 69 * cfg.num_hidden_layers
    row = cfg.latent_row_lanes * 4  # float32 here
    assert m["kv_latent_bytes_read_total"] == m["kv_latent_rows_read_total"] * row
    assert m["kv_block_nbytes"] == cfg.num_hidden_layers * 4 * row
    assert m["kv_full_rows_read_total"] == 0 and m["kv_full_bytes_read_total"] == 0


def test_a_prompt_longer_than_the_dense_prefill(params):
    """Above `PREFILL_DENSE_MAX` the expanded attention goes a block of
    queries and a chunk of keys at a time (`causal_blocked_attention`)."""
    prompt = _ids(11, qwen2.PREFILL_DENSE_MAX + 70).tolist()
    engine = _engine(FULL, params, context_length=1280, page_size=16).initialize()
    try:
        (r,) = _group(engine, prompt, (20,))
    finally:
        engine.destroy()
    _agrees(params, FULL, [r])


@pytest.mark.parametrize("over,why", [
    (dict(kv_dtype="int8"), "kv_dtype"),
    (dict(kv_host_pool_mb=1.0), "kv_host_pool_mb"),
    (dict(role="prefill"), "role"),
])
def test_what_initialize_refuses_for_every_dict_of_pools(params, over, why):
    with pytest.raises(NotImplementedError, match=why):
        _engine(FULL, params, **over).initialize()


@pytest.mark.parametrize("over,why", [
    (dict(spec_decode="ngram"), "spec_decode='ngram' needs .*: the absorbed attention scores one query"),
    (dict(weight_dtype="int8"), "weight_dtype='int8' needs .*: the low-rank projections"),
])
def test_what_initialize_refuses_for_a_latent_model(params, over, why):
    with pytest.raises(NotImplementedError, match=why):
        _engine(FULL, params, **over).initialize()


def test_tensor_parallel_decode_is_refused():
    from areal_tpu.api.cli_args import JaxDecodeConfig
    from areal_tpu.engine.kv_pool import SlotCache

    cache = SlotCache(FULL, slots=4, block_size=4, n_blocks=65, max_blocks_per_slot=64,
                      kv_dtype="float32")
    with pytest.raises(NotImplementedError, match="no kv-head axis to shard"):
        cache.unserved(JaxDecodeConfig(tensor_parallel_size=2))


def test_migration_and_the_verify_step_refuse(params):
    engine = _engine(FULL, params).initialize()
    try:
        for call in (lambda: engine.export_session("x"), lambda: engine.import_session({}, None, None),
                     lambda: engine.export_fabric_blocks([])):
            with pytest.raises(NotImplementedError, match="mixed stack"):
                call()
    finally:
        engine.destroy()
    pool = {"latent": jnp.zeros((3, 5, 4, FULL.latent_row_lanes))}
    with pytest.raises(NotImplementedError, match="a verify step over a latent pool"):
        qwen2.verify_step_paged(params, jnp.zeros((1, 2), jnp.int32), jnp.zeros(1, jnp.int32),
                                pool, {}, jnp.ones((1, 4), jnp.int32), FULL)


# -- the kernel ----------------------------------------------------------------


def _kernel_case(seed, R, nH, D, dv, bsz, nb, lengths, active):
    rng = np.random.default_rng(seed)
    n_blocks = 1 + R * nb
    pool = jnp.asarray(rng.standard_normal((2, n_blocks, bsz, D)), jnp.float32)
    q = jnp.asarray(rng.standard_normal((R, nH, D)), jnp.float32)
    table = jnp.asarray(1 + rng.permutation(R * nb).reshape(R, nb), jnp.int32)
    pos = jnp.asarray(lengths, jnp.int32)
    valid = jnp.arange(nb * bsz)[None, :] <= pos[:, None]
    return q, pool, table, valid, jnp.asarray(active)


@pytest.mark.parametrize("R,nH,D,dv,bsz,nb,lengths,active", [
    (4, 8, 128, 64, 8, 6, (0, 13, 47, 30), (True, True, True, True)),    # ragged live ranges
    (4, 16, 128, 96, 8, 4, (31, 5, 17, 9), (True, False, True, False)),  # inactive slots
    (3, 128, 640, 512, 128, 3, (200, 383, 7), (True, True, False)),      # the published widths
    # several groups of pages a slot: whole groups, a short last one, a slot of one page between
    (5, 8, 128, 64, 8, 21, (127, 3, 167, 64, 135), (True, True, True, False, True)),
])
def test_the_interpreted_kernel_against_jax_numpy(R, nH, D, dv, bsz, nb, lengths, active):
    q, pool, table, valid, act = _kernel_case(3, R, nH, D, dv, bsz, nb, lengths, active)
    live = live_block_range(valid, bsz, act)
    kw = dict(dv=dv, sm_scale=0.11472)
    want = paged_attention_latent(q, pool, table, valid, 1, impl="xla", **kw)
    for work in (live, (*live, *slot_schedule(*live))):
        got = paged_attention_latent(q, pool, table, valid, 1, impl="pallas", interpret=True,
                                     live=work, **kw)
        np.testing.assert_allclose(np.asarray(got)[np.asarray(act)],
                                   np.asarray(want)[np.asarray(act)], atol=2e-5, rtol=2e-5)
        assert not np.asarray(got)[~np.asarray(act)].any()  # an inactive slot writes zeros
    # plain arithmetic, a slot at a time
    rows = np.asarray(pool)[1][np.asarray(table)].reshape(R, nb * bsz, D)
    for r in np.nonzero(np.asarray(act))[0]:
        n = lengths[r] + 1
        s = 0.11472 * np.asarray(q)[r] @ rows[r, :n].T
        w = np.exp(s - s.max(-1, keepdims=True))
        ref = (w / w.sum(-1, keepdims=True)) @ rows[r, :n, :dv]
        np.testing.assert_allclose(np.asarray(want)[r], ref, atol=2e-5, rtol=2e-5)


# -- names ---------------------------------------------------------------------


def _lowered(fn, *args):
    return jax.jit(fn).lower(*args).as_text(debug_info=True)


def test_the_new_scopes_are_in_the_lowered_programs(params):
    cfg = FULL
    T = 24
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)  # noqa: E731
    fwd = _lowered(lambda p, i, q, s: qwen2.forward(p, i, q, s, cfg), params, i32(T), i32(T), i32(T))
    pre = _lowered(lambda p, i, q: qwen2.prefill(p, i, q, cfg)[0], params, i32(T), i32(T))
    pool = {"latent": jax.ShapeDtypeStruct((3, 9, 4, cfg.latent_row_lanes), jnp.float32)}
    dec = _lowered(
        lambda p, t, n, kp, bt: qwen2.decode_step_paged(
            p, t, n, kp, {}, bt, cfg, active=jnp.ones(2, bool), attn_impl="xla", moe_load=True),
        params, i32(2), i32(2), pool, i32(2, 4))
    for text in (fwd, pre):
        for scope in ("layer/attn/q_lora", "layer/attn/kv_latent", "layer/attn/latent_attention",
                      "layer/attn/out_proj", "mlp/router/group_route", "dense_layer/layer"):
            assert scope in text, scope
        assert "absorb_q" not in text
    for scope in ("layer/attn/q_lora", "layer/attn/kv_latent", "layer/attn/absorb_q",
                  "layer/attn/latent_attention", "layer/attn/absorb_out", "layer/attn/out_proj",
                  "layer/attn/kv_write/pool_write", "mlp/router/group_route"):
        assert scope in dec, scope


def test_trace_report_reads_the_scopes():
    sys.path.insert(0, os.path.join(REPO, "tools"))
    import trace_report

    for op, scope in [
        ("jit(chunk)/jit(main)/decode_step/layer/attn/latent_attention/paged_attention_latent",
         "latent_attention"),
        ("jit(chunk)/jit(main)/decode_step/layer/attn/absorb_q/dot_general", "absorb_q"),
        ("jit(chunk)/jit(main)/decode_step/layer/mlp/router/group_route/top_k", "group_route"),
    ]:
        assert scope in trace_report.scope_of(op)


# -- the other configurations' programs are the parent's ------------------------
# recorded at 1071463 (PR 37) by this file's `lowered_programs` run there
# (`python tests/test_deepseek_v2_engine.py`)

PARENT_SHA256 = {
    "kexaone.decode_step": "697ceca62893c396",
    "kexaone.forward": "f4fbd84ec725277d",
    "kexaone.prefill": "191fe88441ef71a9",
    "kexaone.verify_step": "16e7cdff10c71eb8",
    "kexaone_part.decode_step": "b75842fbf9044a05",
    "kexaone_part.forward": "4807a6fce7d194fd",
    "kexaone_part.prefill": "8656d66a5fedfc5f",
    "kexaone_part.verify_step": "01c30e1a0f19617c",
}


def _others():
    from test_kexaone import FULL as kexaone
    from test_kexaone import PART as kexaone_part

    return {"kexaone": kexaone, "kexaone_part": kexaone_part}


def lowered_programs() -> dict:
    """{name: lowered text} of the mixed-stack configuration's programs
    (`tests/test_sdar.py` holds the uniform stacks'): `forward`, `prefill`,
    the decode and verify steps over its dict of pools."""
    out = {}
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)  # noqa: E731
    for name, cfg in _others().items():
        p = jax.eval_shape(lambda c=cfg: qwen2.init_params(c, jax.random.PRNGKey(0)))
        T, R, nb, bsz = 24, 2, 4, 4
        D = cfg.num_key_value_heads * cfg.head_dim_
        pages = qwen2.ring_pages(cfg.sliding_window, bsz)
        pool = {"full": jax.ShapeDtypeStruct((len(cfg.cache_layers["full"]), 9, bsz, D), jnp.float32),
                "window": jax.ShapeDtypeStruct(
                    (len(cfg.cache_layers["window"]), 1 + R * pages, bsz, D), jnp.float32)}
        out[f"{name}.forward"] = jax.jit(
            lambda p, i, q, s, c=cfg: qwen2.forward(p, i, q, s, c)).lower(
            p, i32(T), i32(T), i32(T)).as_text()
        out[f"{name}.prefill"] = jax.jit(
            lambda p, i, q, c=cfg: qwen2.prefill(p, i, q, c, with_logits=False)).lower(
            p, i32(T), i32(T)).as_text()
        out[f"{name}.decode_step"] = jax.jit(
            lambda p, t, n, kp, vp, bt, c=cfg: qwen2.decode_step_paged(
                p, t, n, kp, vp, bt, c, active=jnp.ones(R, bool), attn_impl="xla",
                moe_load=True)).lower(p, i32(R), i32(R), pool, pool, i32(R, nb)).as_text()
        out[f"{name}.verify_step"] = jax.jit(
            lambda p, t, n, kp, vp, bt, c=cfg: qwen2.verify_step_paged(
                p, t, n, kp, vp, bt, c, active=jnp.ones(R, bool), attn_impl="xla")).lower(
            p, i32(R, 3), i32(R), pool, pool, i32(R, nb)).as_text()
    return out


def _sha(text: str) -> str:
    return hashlib.sha256(text.encode()).hexdigest()[:16]


@pytest.fixture(scope="module")
def lowered():
    return lowered_programs()


@pytest.mark.parametrize("name", sorted(PARENT_SHA256))
def test_the_mixed_stacks_lowered_programs_are_the_parents(lowered, name):
    assert _sha(lowered[name]) == PARENT_SHA256[name], (
        f"{name}: the lowered program of a model without latent attention changed; if the "
        "change is meant, record `python tests/test_deepseek_v2_engine.py` anew")


if __name__ == "__main__":
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    for k, v in sorted(lowered_programs().items()):
        print(f'    "{k}": "{_sha(v)}",')
