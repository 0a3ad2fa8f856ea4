"""Decode engine: greedy parity with the training forward pass, stop
handling, version stamping across weight swaps, concurrent requests."""

import asyncio

import numpy as np
import pytest

import jax

from areal_tpu.api.cli_args import (
    GenerationHyperparameters,
    InferenceEngineConfig,
    JaxDecodeConfig,
)
from areal_tpu.api.io_struct import ModelRequest
from areal_tpu.engine.jax_decode import JaxDecodeEngine
from areal_tpu.models.qwen2 import ModelConfig, forward, init_params

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


@pytest.fixture(scope="module")
def engine(cpu_devices):
    cfg = JaxDecodeConfig(
        context_length=96,
        max_running_requests=4,
        new_tokens_per_chunk=8,
        dtype="float32",
        kv_cache_dtype="float32",
    )
    eng = JaxDecodeEngine(cfg, InferenceEngineConfig())
    eng.set_model(init_params(TINY, jax.random.PRNGKey(0)), TINY)
    eng.initialize()
    yield eng
    eng.destroy()


def greedy_reference(params, prompt, n_new):
    """Step-by-step greedy continuation using the training forward pass."""
    seq = list(prompt)
    for _ in range(n_new):
        T = len(seq)
        logits = forward(
            params,
            np.array(seq, dtype=np.int32),
            np.arange(T, dtype=np.int32),
            np.zeros(T, dtype=np.int32),
            TINY,
        )
        seq.append(int(np.argmax(np.asarray(logits[-1]))))
    return seq[len(prompt):]


@pytest.mark.slow
def test_engine_declares_what_it_holds_of_a_chip(engine):
    """A live engine is on the account a trainer on the same chips plans its
    step's memory around (`utils/hbm.py:declare_resident`): its weights and
    its pools, as sharded; a destroyed one is off it."""
    from areal_tpu.utils import hbm

    held = sum(
        x.nbytes for x in jax.tree.leaves((engine.params, engine._k_cache, engine._v_cache))
    )
    assert hbm._DECLARED[engine] == held > 0
    assert hbm.declared_resident_bytes(but=engine) == hbm.declared_resident_bytes() - held
    other = JaxDecodeEngine(engine.config, InferenceEngineConfig())
    other.set_model(engine.params, TINY)
    other.initialize()
    try:
        assert hbm._DECLARED[other] == held
    finally:
        other.destroy()
    assert other not in hbm._DECLARED and engine in hbm._DECLARED


def test_greedy_decode_matches_forward(engine):
    prompt = [1, 5, 9, 13, 2]
    n_new = 11
    resp = engine.generate(
        ModelRequest(
            input_ids=prompt,
            gconfig=GenerationHyperparameters(greedy=True, max_new_tokens=n_new),
        ),
        timeout=300,
    )
    assert resp.output_len == n_new
    assert resp.stop_reason == "length"
    expected = greedy_reference(engine.params, prompt, n_new)
    assert resp.output_tokens == expected
    # logprobs are the chosen-token logprobs, finite and <= 0
    assert all(lp <= 1e-6 and np.isfinite(lp) for lp in resp.output_logprobs)


@pytest.mark.slow
def test_stop_token_truncates(engine):
    prompt = [1, 5, 9, 13, 2]
    full = greedy_reference(engine.params, prompt, 12)
    stop_tok = full[4]
    # generation halts at the stop token's FIRST occurrence (inclusive)
    cut = full.index(stop_tok) + 1
    resp = engine.generate(
        ModelRequest(
            input_ids=prompt,
            gconfig=GenerationHyperparameters(
                greedy=True, max_new_tokens=12, stop_token_ids=[stop_tok]
            ),
        ),
        timeout=300,
    )
    assert resp.stop_reason == "stop"
    assert resp.output_tokens == full[:cut]
    assert len(resp.output_logprobs) == cut
    assert len(resp.output_versions) == cut


@pytest.mark.slow
def test_concurrent_requests_isolated(engine):
    async def run_all():
        reqs = [
            ModelRequest(
                input_ids=[2 + i, 7, 11],
                gconfig=GenerationHyperparameters(greedy=True, max_new_tokens=6),
            )
            for i in range(6)  # more than max_running_requests
        ]
        return await asyncio.gather(*[engine.agenerate(r) for r in reqs])

    resps = asyncio.run(run_all())
    for i, resp in enumerate(resps):
        expected = greedy_reference(engine.params, [2 + i, 7, 11], 6)
        assert resp.output_tokens == expected, i


@pytest.mark.slow
def test_version_stamping_across_weight_update(engine):
    engine.set_version(3)
    resp = engine.generate(
        ModelRequest(
            input_ids=[1, 2, 3],
            gconfig=GenerationHyperparameters(greedy=True, max_new_tokens=4),
        ),
        timeout=300,
    )
    assert resp.output_versions == [3, 3, 3, 3]
    # swap weights (same values) and bump version
    engine.update_weights_from_distributed(None, params=engine.params)
    engine.set_version(4)
    resp = engine.generate(
        ModelRequest(
            input_ids=[1, 2, 3],
            gconfig=GenerationHyperparameters(greedy=True, max_new_tokens=4),
        ),
        timeout=300,
    )
    assert resp.output_versions == [4, 4, 4, 4]


@pytest.mark.slow
def test_pause_continue_generation(engine):
    engine.pause_generation()
    assert engine._gen_paused.is_set()
    engine.continue_generation()
    resp = engine.generate(
        ModelRequest(
            input_ids=[4, 4],
            gconfig=GenerationHyperparameters(greedy=True, max_new_tokens=3),
        ),
        timeout=300,
    )
    assert resp.output_len == 3


@pytest.mark.slow
def test_sharded_decode_tp2(cpu_devices):
    """Gen-side tensor parallelism: params + KV cache sharded over a
    [1,1,1,2] decode mesh must reproduce the unsharded greedy output."""
    cfg = JaxDecodeConfig(
        context_length=64,
        max_running_requests=2,
        new_tokens_per_chunk=4,
        dtype="float32",
        kv_cache_dtype="float32",
        tensor_parallel_size=2,
    )
    eng = JaxDecodeEngine(cfg, InferenceEngineConfig())
    eng.set_model(init_params(TINY, jax.random.PRNGKey(0)), TINY)
    eng.initialize()
    try:
        assert eng.mesh is not None
        # every param leaf actually lives on 2 devices
        leaf = jax.tree.leaves(eng.params)[0]
        assert len(leaf.sharding.device_set) == 2
        assert len(eng._k_cache.sharding.device_set) == 2
        prompt = [1, 5, 9, 13, 2]
        # generous timeout: the tp=2 GSPMD compiles run on one CPU core and
        # slow down further when the full suite shares it (observed >900s
        # under a fully loaded suite run)
        resp = eng.generate(
            ModelRequest(
                input_ids=prompt,
                gconfig=GenerationHyperparameters(greedy=True, max_new_tokens=7),
            ),
            timeout=2400,
        )
        expected = greedy_reference(eng.params, prompt, 7)
        assert resp.output_tokens == expected
    finally:
        eng.destroy()


@pytest.mark.slow
def test_interrupt_resume_reuses_parked_kv(cpu_devices):
    """An interrupted request's KV stays parked in its slot; resuming with
    rid affinity (prompt + partial tokens) prefills NOTHING and continues
    the exact greedy continuation."""
    from areal_tpu.engine.jax_decode import _Slot

    cfg = JaxDecodeConfig(
        context_length=64,
        max_running_requests=2,
        new_tokens_per_chunk=4,
        dtype="float32",
        kv_cache_dtype="float32",
    )
    eng = JaxDecodeEngine(cfg, InferenceEngineConfig())
    eng.set_model(init_params(TINY, jax.random.PRNGKey(0)), TINY)
    eng.initialize()
    try:
        eng.pause_generation()  # drive the scheduler by hand
        prompt = [1, 5, 9, 13, 2]
        full = greedy_reference(eng.params, prompt, 12)
        g = GenerationHyperparameters(greedy=True, max_new_tokens=12)
        item = _Slot(rid="r1", prompt=prompt, gconfig=g, future=None, loop=None)
        eng._request_q.put(item)
        with eng._sched_lock:
            eng._admit()
            eng._run_chunk(eng._active_mask())  # 4 tokens
        assert item.tokens == full[:4]
        n = eng.abort_all()
        assert n == 1 and item.stop_reason == "interrupt"
        assert "r1" in eng._parked

        # resume: prompt + partial tokens, same rid; count prefill calls
        calls = []
        orig = eng._get_prefill_fn
        eng._get_prefill_fn = lambda b: calls.append(b) or orig(b)
        g2 = GenerationHyperparameters(greedy=True, max_new_tokens=8)
        item2 = _Slot(
            rid="r1", prompt=prompt + item.tokens, gconfig=g2,
            future=None, loop=None,
        )
        eng._request_q.put(item2)
        with eng._sched_lock:
            eng._admit()
            for _ in range(2):
                if eng._active_mask().any():
                    eng._run_chunk(eng._active_mask())
        assert calls == [], "resume must not prefill anything"
        assert item2.tokens == full[4:12]
        assert "r1" not in eng._parked
    finally:
        eng.destroy()


@pytest.mark.slow
def test_gqa_kv_head_repeat_tp4(cpu_devices):
    """tp=4 > nKV=2: the engine repeats kv heads to tp so the cache shards
    4-ways instead of replicating, and greedy output is unchanged (the
    repeat transformation is semantics-preserving)."""
    cfg = JaxDecodeConfig(
        context_length=64,
        max_running_requests=2,
        new_tokens_per_chunk=4,
        dtype="float32",
        kv_cache_dtype="float32",
        tensor_parallel_size=4,
    )
    eng = JaxDecodeEngine(cfg, InferenceEngineConfig())
    original = init_params(TINY, jax.random.PRNGKey(0))
    eng.set_model(original, TINY)
    eng.initialize()
    try:
        assert eng.model_config.num_key_value_heads == 4  # repeated 2 -> 4
        # cache kv-head dim is sharded over tp, not replicated
        spec = eng._k_cache.sharding.spec
        assert spec[3] == "tp", f"kv cache not sharded: {spec}"
        k = eng.params["layers"]["attn"]["k_kernel"]
        assert k.shape[-2] == 4
        prompt = [1, 5, 9, 13, 2]
        resp = eng.generate(
            ModelRequest(
                input_ids=prompt,
                gconfig=GenerationHyperparameters(greedy=True, max_new_tokens=6),
            ),
            timeout=900,
        )
        # reference computed with the ORIGINAL (unrepeated) params
        expected = greedy_reference(original, prompt, 6)
        assert resp.output_tokens == expected

        # Weight pushes carry UNREPEATED trainer weights; both ingest paths
        # must re-apply the repeat (regression: round-3 review finding).
        trained = init_params(TINY, jax.random.PRNGKey(1))
        eng.update_weights_from_distributed(None, trained, TINY)
        assert eng.model_config.num_key_value_heads == 4
        resp2 = eng.generate(
            ModelRequest(
                input_ids=prompt,
                gconfig=GenerationHyperparameters(greedy=True, max_new_tokens=4),
            ),
            timeout=900,
        )
        assert resp2.output_tokens == greedy_reference(trained, prompt, 4)

        from areal_tpu.core.weight_transfer import flatten_named

        trained2 = init_params(TINY, jax.random.PRNGKey(2))
        eng.update_weights_from_tensor(flatten_named(trained2), version=7)
        resp3 = eng.generate(
            ModelRequest(
                input_ids=prompt,
                gconfig=GenerationHyperparameters(greedy=True, max_new_tokens=4),
            ),
            timeout=900,
        )
        assert resp3.output_tokens == greedy_reference(trained2, prompt, 4)
    finally:
        eng.destroy()


def test_prefill_budget_bounds_admission(cpu_devices):
    """A burst of admissions must not all prefill in one scheduler pass:
    per-pass prefill work is capped at max_prefill_tokens, excess requests
    stay queued (order preserved) and still complete."""
    cfg = JaxDecodeConfig(
        context_length=192,
        max_running_requests=8,
        new_tokens_per_chunk=2,
        max_prefill_tokens=64,  # one 64-token bucket per pass
        dtype="float32",
        kv_cache_dtype="float32",
    )
    eng = JaxDecodeEngine(cfg, InferenceEngineConfig())
    eng.set_model(init_params(TINY, jax.random.PRNGKey(0)), TINY)
    eng.initialize()
    try:
        from areal_tpu.engine.jax_decode import _Slot

        eng.pause_generation()  # drive by hand
        g = GenerationHyperparameters(greedy=True, max_new_tokens=2)
        items = [
            _Slot(
                rid=f"r{i}",
                prompt=[1 + i] * 60,  # 64-token prefill bucket each
                gconfig=g,
                future=None,
                loop=None,
            )
            for i in range(4)
        ]
        for it in items:
            eng._request_q.put(it)
        with eng._sched_lock:
            eng._admit()
            # only the first fits the 64-token budget this pass
            rids = lambda: {s.rid for s in eng._slots if s is not None}
            assert rids() == {"r0"}
            eng._admit()
            assert rids() == {"r0", "r1"}
        eng.continue_generation()
        # the scheduler loop admits the rest across passes; all complete
        deadline = 300
        import time as _time

        t0 = _time.monotonic()
        while any(it.stop_reason is None for it in items):
            assert _time.monotonic() - t0 < deadline, "burst did not drain"
            _time.sleep(0.05)
    finally:
        eng.destroy()


@pytest.mark.slow
def test_stop_strings(cpu_devices):
    """Stop STRINGS (gconfig.stop) truncate generation at the earliest
    token boundary whose decoded prefix contains the string."""

    class DigitTok:
        eos_token_id = None

        def decode(self, ids):
            return "".join(str(i % 10) for i in ids)

    cfg = JaxDecodeConfig(
        context_length=64,
        max_running_requests=2,
        new_tokens_per_chunk=4,
        dtype="float32",
        kv_cache_dtype="float32",
    )
    eng = JaxDecodeEngine(cfg, InferenceEngineConfig(), tokenizer=DigitTok())
    eng.set_model(init_params(TINY, jax.random.PRNGKey(0)), TINY)
    eng.initialize()
    try:
        prompt = [1, 5, 9, 13, 2]
        full = greedy_reference(eng.params, prompt, 8)
        text = "".join(str(t % 10) for t in full)
        stop_s = text[2:4]  # a substring that first completes at token 4
        # precondition: the substring must not occur earlier, or the
        # expected boundary below is wrong (guards against TINY changes)
        assert stop_s not in text[:3]
        resp = eng.generate(
            ModelRequest(
                input_ids=prompt,
                gconfig=GenerationHyperparameters(
                    greedy=True, max_new_tokens=8, stop=[stop_s]
                ),
            ),
            timeout=600,
        )
        assert resp.stop_reason == "stop"
        assert resp.output_tokens == full[:4]
    finally:
        eng.destroy()


def test_frequency_penalty_reduces_repeats(cpu_devices):
    """A strong frequency penalty must strictly reduce token repetition vs
    the unpenalized run (same seed)."""
    def run(freq):
        cfg = JaxDecodeConfig(
            context_length=96,
            max_running_requests=1,
            new_tokens_per_chunk=8,
            dtype="float32",
            kv_cache_dtype="float32",
            random_seed=11,
        )
        eng = JaxDecodeEngine(cfg, InferenceEngineConfig())
        eng.set_model(init_params(TINY, jax.random.PRNGKey(2)), TINY)
        eng.initialize()
        try:
            resp = eng.generate(
                ModelRequest(
                    input_ids=[3, 7, 11],
                    gconfig=GenerationHyperparameters(
                        max_new_tokens=48,
                        temperature=0.3,  # peaked -> repetitive baseline
                        frequency_penalty=freq,
                    ),
                ),
                timeout=600,
            )
            return resp.output_tokens
        finally:
            eng.destroy()

    base = run(0.0)
    pen = run(8.0)  # forceful penalty on a 64-token vocab
    uniq_base = len(set(base)) / len(base)
    uniq_pen = len(set(pen)) / len(pen)
    assert uniq_pen > uniq_base, (uniq_base, uniq_pen)


@pytest.mark.slow
def test_decode_under_foreign_global_mesh(cpu_devices):
    """Regression: a decode engine must trace against ITS OWN mesh even when
    another engine (the COLOCATE train engine) has installed a different
    process-global ambient mesh. Before the thread-local `mesh_scope`
    binding, `constrain` inside the prefill trace resolved the foreign
    8-device mesh while the decode params lived on 2 devices — the
    scheduler thread died on an incompatible-devices compile error and
    every subsequent request hung forever. An UNSHARDED engine (params on
    one device) under a foreign 8-device mesh triggers the same mismatch
    and compiles in seconds, so this guard runs in the default suite."""
    from areal_tpu.api.alloc_mode import ParallelStrategy
    from areal_tpu.parallel import mesh as mesh_lib

    foreign = mesh_lib.build_mesh(
        ParallelStrategy(data_parallel_size=4, tensor_parallel_size=2)
    )
    eng = None
    mesh_lib.set_current_mesh(foreign)
    try:
        cfg = JaxDecodeConfig(
            context_length=64,
            max_running_requests=2,
            new_tokens_per_chunk=4,
            dtype="float32",
            kv_cache_dtype="float32",
        )
        eng = JaxDecodeEngine(cfg, InferenceEngineConfig())
        eng.set_model(init_params(TINY, jax.random.PRNGKey(0)), TINY)
        eng.initialize()
        prompt = [1, 5, 9, 13, 2]
        resp = eng.generate(
            ModelRequest(
                input_ids=prompt,
                gconfig=GenerationHyperparameters(greedy=True, max_new_tokens=5),
            ),
            timeout=2400,
        )
        assert resp.output_len == 5
        # (the reference is this thread's eager `forward` over the engine's
        # one-device params: under no mesh, as the engine's own thread is)
        with mesh_lib.mesh_scope(None):
            expected = greedy_reference(eng.params, prompt, 5)
        assert resp.output_tokens == expected
    finally:
        if eng is not None:
            eng.destroy()
        mesh_lib.set_current_mesh(None)


@pytest.mark.slow
def test_prefix_fork_group_decode(cpu_devices):
    """GRPO-group admission path: group_size same-prompt requests prefill
    ONCE; the rest fork the donor slot's prompt KV (a memcpy), and outputs
    stay exactly equal to the greedy reference. Parity target: the radix
    prefix cache the reference inherits from SGLang
    (areal/engine/sglang_remote.py:22)."""
    import time as _time
    from concurrent.futures import ThreadPoolExecutor

    cfg = JaxDecodeConfig(
        context_length=96,
        max_running_requests=4,
        new_tokens_per_chunk=8,
        dtype="float32",
        kv_cache_dtype="float32",
    )
    eng = JaxDecodeEngine(cfg, InferenceEngineConfig())
    eng.set_model(init_params(TINY, jax.random.PRNGKey(0)), TINY)
    eng.initialize()
    try:
        prompt = [3, 7, 11, 2, 9, 4]
        n_new = 9
        g = GenerationHyperparameters(greedy=True, max_new_tokens=n_new)

        eng.pause_generation()
        with ThreadPoolExecutor(max_workers=4) as pool:
            futs = [
                pool.submit(
                    eng.generate,
                    ModelRequest(input_ids=list(prompt), gconfig=g),
                    600,
                )
                for _ in range(4)
            ]
            deadline = _time.monotonic() + 30
            while eng._request_q.qsize() < 4:
                assert _time.monotonic() < deadline, "requests never enqueued"
                _time.sleep(0.01)
            eng.continue_generation()
            results = [f.result(timeout=600) for f in futs]

        expected = greedy_reference(eng.params, prompt, n_new)
        for r in results:
            assert r.output_tokens == expected
            # latency observability: itl filled, one entry per token
            assert len(r.itl) == r.output_len
            assert all(v > 0 for v in r.itl)
            assert r.ttft != float("inf")
        assert eng._n_prefills == 1
        assert eng._n_prefix_forks == 3
        m = eng.get_metrics()
        assert m["prefix_forks_total"] == 3
        assert m["generated_tokens_total"] >= 4 * n_new

        # Retired slots keep their prompt KV: a later same-prompt request
        # reuses it (fork or in-place) without any new prefill.
        r = eng.generate(ModelRequest(input_ids=list(prompt), gconfig=g), timeout=600)
        assert r.output_tokens == expected
        assert eng._n_prefills == 1

        # A weight install invalidates the registry (old-weight KV must not
        # seed new-weight generation) — the next admission prefills again.
        eng.update_weights_from_tensor({}, version=1)
        r = eng.generate(ModelRequest(input_ids=list(prompt), gconfig=g), timeout=600)
        assert r.output_tokens == expected
        assert eng._n_prefills == 2
    finally:
        eng.destroy()


@pytest.mark.slow
def test_bucketed_chunk_attention_parity(cpu_devices):
    """Length-bucketed decode: with a large context_length the chunk fn
    runs on a sliced KV bucket (256 rows here) instead of the full cache;
    outputs must exactly match the dense greedy reference."""
    cfg = JaxDecodeConfig(
        context_length=2048,
        max_running_requests=2,
        new_tokens_per_chunk=8,
        dtype="float32",
        kv_cache_dtype="float32",
    )
    eng = JaxDecodeEngine(cfg, InferenceEngineConfig())
    eng.set_model(init_params(TINY, jax.random.PRNGKey(0)), TINY)
    eng.initialize()
    try:
        prompt = [1, 5, 9, 13, 2, 7]
        n_new = 10
        resp = eng.generate(
            ModelRequest(
                input_ids=list(prompt),
                gconfig=GenerationHyperparameters(greedy=True, max_new_tokens=n_new),
            ),
            timeout=600,
        )
        assert resp.output_tokens == greedy_reference(eng.params, prompt, n_new)
        # the bucketed variant (2 blocks = 256 rows << the 2048 context)
        # actually compiled and ran
        assert any(k[2] == 2 for k in eng._chunk_fns), eng._chunk_fns.keys()
        # paged accounting: this short request only ever held 2 of the 32
        # context-worth blocks (bucketed gather, not dense reservation)
        m = eng.get_metrics()
        assert m["kv_block_size"] == 128
        assert m["kv_tokens_allocated"] <= 2 * 128, m
    finally:
        eng.destroy()


@pytest.mark.slow
def test_parked_long_sequence_survives_bucketed_chunks(cpu_devices):
    """A parked long sequence must survive other slots' bucketed chunks:
    an inactive slot's write goes to null block 0, so the short request can
    run on a small bucket while the parked slot's KV (partly inside,
    partly beyond the bucket) stays untouched — and the parked
    request then resumes with the exact greedy continuation."""
    from areal_tpu.engine.jax_decode import _Slot

    cfg = JaxDecodeConfig(
        context_length=2048,
        max_running_requests=2,
        new_tokens_per_chunk=8,
        dtype="float32",
        kv_cache_dtype="float32",
    )
    eng = JaxDecodeEngine(cfg, InferenceEngineConfig())
    eng.set_model(init_params(TINY, jax.random.PRNGKey(0)), TINY)
    eng.initialize()
    try:
        eng.pause_generation()  # drive the scheduler by hand
        # long request: run until its KV extends past the 256-row bucket
        long_prompt = [1 + (i % 40) for i in range(300)]
        g_long = GenerationHyperparameters(greedy=True, max_new_tokens=64)
        item = _Slot(rid="long", prompt=list(long_prompt), gconfig=g_long,
                     future=None, loop=None)
        eng._request_q.put(item)
        with eng._sched_lock:
            eng._admit()
            eng._run_chunk(eng._active_mask())  # 8 tokens, len ~307
        partial = list(item.tokens)
        assert len(partial) == 8
        eng.abort_all()
        assert "long" in eng._parked

        # short request decodes alone on the SMALL (256-row) bucket even
        # though the parked slot's KV extends to ~307 rows — safe because
        # inactive slots never write
        g_short = GenerationHyperparameters(greedy=True, max_new_tokens=8)
        short = _Slot(rid="short", prompt=[2, 4, 6], gconfig=g_short,
                      future=None, loop=None)
        eng._request_q.put(short)
        with eng._sched_lock:
            eng._admit()
            eng._run_chunk(eng._active_mask())
        assert any(k[2] == 2 for k in eng._chunk_fns), (
            "short request should use the small 2-block bucket",
            list(eng._chunk_fns),
        )
        eng._slots = [None] * cfg.max_running_requests  # retire short slot

        # resume the long request: continuation must be exact
        resume = _Slot(
            rid="long", prompt=list(long_prompt) + partial,
            gconfig=GenerationHyperparameters(greedy=True, max_new_tokens=8),
            future=None, loop=None,
        )
        eng._request_q.put(resume)
        with eng._sched_lock:
            eng._admit()
            eng._run_chunk(eng._active_mask())
        expected = greedy_reference(eng.params, long_prompt, 16)
        assert partial + resume.tokens == expected
    finally:
        eng.destroy()


@pytest.mark.slow
def test_retired_donor_survives_later_chunks(cpu_devices):
    """Staggered completion: a slot retires (stop_reason stop/length)
    while others keep chunking, then a same-prompt request forks from the
    retired donor's registered prefix. The fork must be exact — i.e.
    later chunks must not have written into the retired slot's rows
    (decode_step_paged redirects inactive-slot writes to null block 0)."""
    from areal_tpu.engine.jax_decode import _Slot

    cfg = JaxDecodeConfig(
        context_length=96,
        max_running_requests=2,
        new_tokens_per_chunk=4,
        dtype="float32",
        kv_cache_dtype="float32",
    )
    eng = JaxDecodeEngine(cfg, InferenceEngineConfig())
    eng.set_model(init_params(TINY, jax.random.PRNGKey(0)), TINY)
    eng.initialize()
    try:
        eng.pause_generation()  # drive the scheduler by hand
        prompt_a = [3, 7, 11, 2, 9]
        prompt_b = [4, 8, 12, 1]
        # A finishes after one chunk; B keeps going for several more
        a = _Slot(rid="a", prompt=list(prompt_a), future=None, loop=None,
                  gconfig=GenerationHyperparameters(greedy=True, max_new_tokens=4))
        b = _Slot(rid="b", prompt=list(prompt_b), future=None, loop=None,
                  gconfig=GenerationHyperparameters(greedy=True, max_new_tokens=20))
        eng._request_q.put(a)
        eng._request_q.put(b)
        with eng._sched_lock:
            eng._admit()
            eng._run_chunk(eng._active_mask())  # A hits max_new_tokens -> retires
            assert a.stop_reason == "length"
            # retirement registers the FULL conversation; the covering-donor
            # lookup serves plain-prompt matches from its head
            pa = tuple(prompt_a[:-1])
            assert any(
                len(k) >= len(pa) and k[: len(pa)] == pa
                for k in eng._prefix_lookup
            ), eng._prefix_lookup
            # B alone keeps chunking — these chunks must not corrupt A's rows
            for _ in range(4):
                if eng._active_mask().any():
                    eng._run_chunk(eng._active_mask())
        assert b.stop_reason == "length"

        # fork a same-prompt request from the retired donor's rows
        forks_before = eng._n_prefix_forks + eng._n_prefix_inplace
        c = _Slot(rid="c", prompt=list(prompt_a), future=None, loop=None,
                  gconfig=GenerationHyperparameters(greedy=True, max_new_tokens=4))
        eng._request_q.put(c)
        with eng._sched_lock:
            eng._admit()
            eng._run_chunk(eng._active_mask())
        assert eng._n_prefix_forks + eng._n_prefix_inplace == forks_before + 1
        assert c.tokens == greedy_reference(eng.params, prompt_a, 4)
        assert c.tokens == a.tokens
    finally:
        eng.destroy()


@pytest.mark.slow
def test_partial_prefix_sharing_multi_turn(cpu_devices):
    """Multi-turn shape: request 2 = request 1's full conversation (prompt
    + generated answer) + a new user turn. The engine forks the shared
    history's KV from the registry and prefills ONLY the suffix
    (prefill_with_prefix), with exactly the dense greedy output."""
    cfg = JaxDecodeConfig(
        context_length=512,
        max_running_requests=2,
        new_tokens_per_chunk=8,
        dtype="float32",
        kv_cache_dtype="float32",
    )
    eng = JaxDecodeEngine(cfg, InferenceEngineConfig())
    eng.set_model(init_params(TINY, jax.random.PRNGKey(0)), TINY)
    eng.initialize()
    try:
        # turn 1: long enough that its covered prefix >= _MIN_SHARED_PREFIX
        turn1 = [1 + (i % 40) for i in range(100)]
        g = GenerationHyperparameters(greedy=True, max_new_tokens=8)
        r1 = eng.generate(
            ModelRequest(input_ids=list(turn1), gconfig=g), timeout=600
        )
        assert r1.output_tokens == greedy_reference(eng.params, turn1, 8)
        assert eng._n_prefills == 1

        # turn 2: history + answer + a fresh user segment, NEW rid
        turn2 = list(turn1) + list(r1.output_tokens) + [5, 17, 3, 29, 11]
        r2 = eng.generate(
            ModelRequest(input_ids=list(turn2), gconfig=g), timeout=600
        )
        assert r2.output_tokens == greedy_reference(eng.params, turn2, 8)
        # the shared history was NOT re-prefilled
        assert eng._n_prefills == 1
        assert eng._n_suffix_prefills == 1
        m = eng.get_metrics()
        assert m["suffix_prefills_total"] == 1

        # turn 3 extends turn 2 — the registry now holds the longer key
        turn3 = list(turn2) + list(r2.output_tokens) + [7, 2]
        r3 = eng.generate(
            ModelRequest(input_ids=list(turn3), gconfig=g), timeout=600
        )
        assert r3.output_tokens == greedy_reference(eng.params, turn3, 8)
        assert eng._n_prefills == 1
        assert eng._n_suffix_prefills == 2
    finally:
        eng.destroy()


@pytest.mark.slow
def test_batched_prefill_wave_unique_prompts(cpu_devices):
    """An admission wave of distinct prompts prefills in ONE batched
    dispatch (vmapped) instead of serial per-request passes; outputs stay
    exactly equal to the greedy reference."""
    import time as _time
    from concurrent.futures import ThreadPoolExecutor

    cfg = JaxDecodeConfig(
        context_length=96,
        max_running_requests=4,
        new_tokens_per_chunk=8,
        dtype="float32",
        kv_cache_dtype="float32",
    )
    eng = JaxDecodeEngine(cfg, InferenceEngineConfig())
    eng.set_model(init_params(TINY, jax.random.PRNGKey(0)), TINY)
    eng.initialize()
    try:
        prompts = [[2 + i, 7, 11, 3 + i] for i in range(4)]
        g = GenerationHyperparameters(greedy=True, max_new_tokens=6)
        eng.pause_generation()
        with ThreadPoolExecutor(max_workers=4) as pool:
            futs = [
                pool.submit(
                    eng.generate,
                    ModelRequest(input_ids=list(p), gconfig=g),
                    600,
                )
                for p in prompts
            ]
            deadline = _time.monotonic() + 30
            while eng._request_q.qsize() < 4:
                assert _time.monotonic() < deadline
                _time.sleep(0.01)
            eng.continue_generation()
            results = [f.result(timeout=600) for f in futs]
        for p, r in zip(prompts, results):
            assert r.output_tokens == greedy_reference(eng.params, p, 6), p
        assert eng._n_prefills == 4
        # the 4-wide batched prefill fn actually compiled and ran
        assert (64, 4) in eng._batched_prefill_fns, list(
            eng._batched_prefill_fns
        )
    finally:
        eng.destroy()


@pytest.mark.slow
def test_prewarm_compiles_all_wave_variants(cpu_devices):
    """prewarm() must deterministically populate every jit-variant cache a
    live load burst could hit — batched prefill at each admissible wave
    size, the decode chunk, and the dup-fork block copy — and must leave
    the engine fully serviceable (greedy parity afterwards)."""
    cfg = JaxDecodeConfig(
        context_length=96,
        max_running_requests=4,
        new_tokens_per_chunk=8,
        dtype="float32",
        kv_cache_dtype="float32",
    )
    eng = JaxDecodeEngine(cfg, InferenceEngineConfig())
    eng.set_model(init_params(TINY, jax.random.PRNGKey(0)), TINY)
    eng.initialize()
    try:
        dt = eng.prewarm(prompt_len=16, new_tokens=4)
        assert dt > 0.0
        # prompt_len 16 -> 64-token prefill bucket; max_running 4 caps the
        # admissible wave sizes at {4, 2, 1}
        assert set(eng._batched_prefill_fns) >= {(64, 4), (64, 2), (64, 1)}
        # both sampler variants (top_p == 1 and top_p < 1) compiled
        assert {k[0] for k in eng._chunk_fns} == {False, True}, eng._chunk_fns
        assert "fork_block" in eng._slot_cache._copies, "dup-fork block copy not compiled"
        # misconfiguration must fail loudly, not silently warm nothing
        with pytest.raises(ValueError, match="length-rejected"):
            eng.prewarm(prompt_len=96, new_tokens=4)
        # engine state must be untouched: fresh greedy request still exact
        prompt = [3, 7, 11, 2, 9]
        resp = eng.generate(
            ModelRequest(
                input_ids=prompt,
                gconfig=GenerationHyperparameters(
                    greedy=True, max_new_tokens=6
                ),
            ),
            timeout=300,
        )
        assert resp.output_tokens == greedy_reference(eng.params, prompt, 6)
    finally:
        eng.destroy()
