"""The names a device trace shows: every jitted program's module name, the
`jax.named_scope` path of what runs inside it, and (compiled for a described
v5e, no chip) the Pallas kernels' custom calls. A reader of PERF.md section 5
and every `device_op_time` / `device_module_time` metric file match on these,
so a refactor that loses one fails here and not in a benchmark run."""

import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from areal_tpu.api.alloc_mode import ParallelStrategy
from areal_tpu.api.cli_args import (
    GenerationHyperparameters,
    InferenceEngineConfig,
    JaxDecodeConfig,
    MicroBatchSpec,
    OptimizerConfig,
    PPOActorConfig,
)
from areal_tpu.api.io_struct import FinetuneSpec, ModelRequest
from areal_tpu.engine.kv_pool import fork_block
from areal_tpu.models.qwen2 import ModelConfig, init_params

TINY = ModelConfig(
    vocab_size=64, hidden_size=32, intermediate_size=64, num_hidden_layers=2,
    num_attention_heads=4, num_key_value_heads=2, dtype="float32",
    param_dtype="float32",
)

# every program of the decode engine and the trainer, as `XLA Modules` shows
# them (`jit_<function name>`); the benchmark's patterns are `^jit_chunk`,
# `^jit_grad_step`, `^jit_fwd_step`, `^jit_apply_update`, `^jit_prefill`
PROGRAMS = (
    "chunk", "verify_chunk", "prefill_batched", "prefill_suffix", "prefill_embed",
    "patch", "fork_block", "fwd_step", "grad_step", "apply_update", "zero_grads",
)


class _JitSpy:
    """Stands in for `jax.jit`: notes each program's name when it is made,
    and its lowered text (with debug info: the scope paths) at its first
    call."""

    def __init__(self):
        self.real = jax.jit
        self.made: list[str] = []
        self.text: dict[str, str] = {}

    def __call__(self, fn, **kw):
        jitted = self.real(fn, **kw)
        name = getattr(fn, "__name__", "<unnamed>")
        self.made.append(name)
        spy = self

        class Program:
            def __call__(self, *a, **k):
                if name not in spy.text:
                    spy.text[name] = jitted.lower(*a, **k).as_text(debug_info=True)
                return jitted(*a, **k)

            def __getattr__(self, attr):
                return getattr(jitted, attr)

        return Program()


@pytest.fixture(scope="module")
def programs(cpu_devices):
    """Runs a tiny decode engine (in-pool paged chunk, as on the chip) and a
    tiny PPO trainer once, with every `jax.jit` of theirs observed."""
    from areal_tpu.engine.jax_decode import JaxDecodeEngine
    from areal_tpu.engine.ppo.actor import JaxPPOActor

    from areal_tpu.utils import perf_tracer

    spy = _JitSpy()
    mp = pytest.MonkeyPatch()
    mp.setattr(jax, "jit", spy)
    recording = perf_tracer.recording()
    rec = recording.__enter__()
    try:
        eng = JaxDecodeEngine(
            JaxDecodeConfig(context_length=256, max_running_requests=4,
                            new_tokens_per_chunk=4, page_size=128, dtype="float32",
                            kv_cache_dtype="float32", paged_attn_impl="pallas"),
            # one episode at a time: the second of two waits at the gate
            InferenceEngineConfig(max_concurrent_rollouts=1, consumer_batch_size=1,
                                  max_head_offpolicyness=4))
        params = init_params(TINY, jax.random.PRNGKey(0))
        eng.set_model(params, TINY)
        eng.initialize()
        try:
            eng.generate(ModelRequest(
                input_ids=[1, 5, 9, 13, 2],
                gconfig=GenerationHyperparameters(greedy=True, max_new_tokens=6)),
                timeout=300)
            _drive_the_loop(eng, params, rec)
            # the programs this request did not need are made, not run
            eng._get_verify_fn(False, 1, 3)
            eng._get_suffix_prefill_fn(64, 64, 1)
            eng._get_embed_prefill_fn(64, 4)
            eng._slot_cache._copy(fork_block)
            eng._get_patch_fn()
        finally:
            eng.destroy()

        actor = JaxPPOActor(PPOActorConfig(
            experiment_name="t", trial_name="t", path="", init_from_scratch=True,
            dtype="float32", mb_spec=MicroBatchSpec(max_tokens_per_mb=512),
            optimizer=OptimizerConfig(lr=5e-3, warmup_steps_proportion=0.0,
                                      lr_scheduler_type="constant"),
            gradient_checkpointing=True, group_size=2, ppo_n_minibatches=1,
            kl_ctl=0.0, use_decoupled_loss=False, recompute_logprob=True))
        actor.model_config = TINY
        actor.create_process_group(ParallelStrategy(data_parallel_size=8))
        actor.initialize(None, FinetuneSpec(1, 64, 8))
        try:
            B, T = 4, 8
            ids = np.tile(np.arange(1, T + 1, dtype=np.int64), (B, 1))
            batch = dict(
                input_ids=ids, attention_mask=np.ones((B, T), np.int64),
                loss_mask=np.pad(np.ones((B, 5), np.int64), ((0, 0), (3, 0))),
                rewards=np.array([1.0, 0.0, 1.0, 0.0], np.float32),
                logprobs=np.zeros((B, T), np.float32))
            batch["prox_logp"] = actor.compute_logp(batch)
            actor.compute_advantages(batch)
            stats = actor.ppo_update(batch)
        finally:
            actor.destroy()
    finally:
        mp.undo()
        recording.__exit__(None, None, None)
    spy.span_names = {s["name"] for s in rec.snapshot()}
    return spy, stats


class _OneRequest:
    """An episode that is one request of the engine."""

    async def arun_episode(self, engine, data):
        resp = await engine.agenerate(ModelRequest(
            input_ids=data["prompt"],
            gconfig=GenerationHyperparameters(greedy=True, max_new_tokens=4)))
        n = resp.input_len + resp.output_len
        return dict(input_ids=np.asarray([resp.input_tokens + resp.output_tokens], np.int32),
                    attention_mask=np.ones((1, n), bool))


class _Loader:
    batch_size = 2

    def __iter__(self):
        while True:
            yield [{"prompt": [3, 7, 11, 4]}, {"prompt": [2, 6, 10, 8]}]


def _drive_the_loop(eng, params, rec):
    """What the loop's spans need: a batch through the gate (one episode at a
    time, so one waits), a pause held through an in-memory push, and a wait
    for traffic."""
    import time

    from areal_tpu.api.io_struct import WeightUpdateMeta

    eng.prepare_batch(_Loader(), workflow=_OneRequest())
    eng.pause()  # (the loop's: it stops admitting episodes, not the engine's generation)
    eng.update_weights_from_distributed(WeightUpdateMeta(type="memory"), params, TINY)
    eng.set_version(1)
    # the push of a tiny tree is over before a scheduler thread on busy cores
    # has looked at the flag: hold a pause of generation until the thread is in it
    eng.pause_generation()
    deadline = time.monotonic() + 30.0
    while time.monotonic() < deadline and not any(
            s["name"] == "decode/paused" for s in rec.snapshot()):
        time.sleep(0.005)
    eng.continue_generation()
    eng.resume()
    # with nothing left to serve the thread goes idle, and closes its spans
    # when it gets a core: wait for the last of them as for the pause above
    deadline = time.monotonic() + 30.0
    while time.monotonic() < deadline and not SPANS["scheduler thread"] <= {
            s["name"] for s in rec.snapshot()}:
        time.sleep(0.005)


# every span the program opens on the paths above, `areal/<name>` in a device
# trace (PERF.md section 3 lists who reads each). The last five of the loop's,
# `decode/pass` and `decode/idle` are ISSUE 34's.
SPANS = {
    "scheduler thread": {
        "decode/pass", "decode/admit", "decode/prefill", "decode/refresh_ctl", "decode/dispatch_chunk",
        "decode/consume_chunk", "decode/wait_device", "decode/paused", "decode/idle",
        "request/queue", "request/prefill", "request/decode"},
    "loop": {"rollout/prepare_batch", "rollout/episode", "rollout/pending",
             "rollout/gate_closed", "rollout/paused", "step/prepare_batch/concat"},
    "push": {"weights/pause", "weights/commit", "weights/resume"},
    "trainer": {
        "train/compute_logp", "train/ppo_update", "train/minibatch", "train/train_batch",
        "train/split_mbs", "train/upload_mb", "train/grad_step", "train/fwd_step",
        "train/read_stats", "train/apply_update", "train/wait_device", "train/step_stats",
        "train/compile"},
}


@pytest.mark.parametrize("where", sorted(SPANS))
def test_every_span_has_its_pinned_name(programs, where):
    spy, _ = programs
    assert SPANS[where] <= spy.span_names, sorted(SPANS[where] - spy.span_names)


def test_every_program_has_its_pinned_name(programs):
    spy, _ = programs
    assert set(PROGRAMS) <= set(spy.made), sorted(set(PROGRAMS) - set(spy.made))
    assert "<lambda>" not in spy.made and "<unnamed>" not in spy.made
    for name in ("chunk", "prefill_batched", "fwd_step", "grad_step", "apply_update"):
        assert f"@jit_{name}" in spy.text[name], name


SCOPES = {
    # the decode chunk: the scan body, the model step inside it, the pool
    # (the lowered text names the layer scan's body relative to itself).
    # No `pool_read` here: in-pool the kernel alone reads the pool, in the
    # layout it is stored in; the scope names the xla impl's gather of a
    # slot's blocks, under `layer/attn/attention` and nowhere else
    # (tests/test_pool_in_place.py holds the default CPU engine to that)
    "chunk": ["decode_step", "decode_step/embed", "layer/attn/qkv", "layer/attn/rope",
              "layer/attn/kv_write/pool_write",
              "layer/attn/attention/paged_attention",
              "layer/attn/o_proj", "layer/mlp", "decode_step/final_norm",
              "decode_step/lm_head", "decode_step/sample"],
    "prefill_batched": ["embed", "layer/attn/qkv", "layer/attn/rope",
                        "layer/attn/attention", "layer/attn/o_proj", "layer/mlp"],
    "fwd_step": ["embed", "layer/attn/qkv", "layer/attn/rope", "layer/attn/attention",
                 "layer/attn/o_proj", "layer/mlp", "final_norm", "xent"],
    "grad_step": ["embed", "layer/attn/qkv", "layer/attn/attention", "layer/attn/o_proj",
                  "layer/mlp", "final_norm", "xent", "loss", "grad_accum"],
    "apply_update": ["grad_norm", "optimizer"],
}


def _has_scope(text: str, path: str) -> bool:
    """Some operation lies under the scope path; autodiff and vmap wrap each
    component (`transpose(jvp(layer))`, `vmap(embed)`)."""
    parts = [r"(?:\w+\()*" + re.escape(c) + r"\)*" for c in path.split("/")]
    return re.search(r'[/"]' + "/".join(parts) + "/", text) is not None


@pytest.mark.parametrize("program", sorted(SCOPES))
def test_program_holds_its_scopes(programs, program):
    spy, _ = programs
    missing = [s for s in SCOPES[program] if not _has_scope(spy.text[program], s)]
    assert not missing, f"{program}: no operation under {missing}"


TINY_MOE = ModelConfig(
    vocab_size=64, hidden_size=32, intermediate_size=16, num_hidden_layers=2,
    num_attention_heads=4, num_key_value_heads=4, dtype="float32", param_dtype="float32",
    model_type="olmoe", qkv_bias=False, qk_norm=True, qk_norm_full=True, num_experts=8,
    num_experts_per_tok=2, moe_intermediate_size=16, norm_topk_prob=False, attn_impl="dense",
)
# what the sparse-expert layer adds under `layer/mlp`, in every program that
# runs it (PERF.md section 3)
MOE_SCOPES = ["layer/mlp/router", "layer/mlp/dispatch", "layer/mlp/experts",
              "layer/mlp/combine"]


@pytest.fixture(scope="module")
def moe_programs(cpu_devices):
    """A tiny OLMoE-shaped decode engine (in-pool paged chunk, batched
    prefill) and the packed training forward with its gradient, observed."""
    from areal_tpu.engine.jax_decode import JaxDecodeEngine
    from areal_tpu.models.qwen2 import forward

    spy = _JitSpy()
    mp = pytest.MonkeyPatch()
    mp.setattr(jax, "jit", spy)
    try:
        params = init_params(TINY_MOE, jax.random.PRNGKey(0))
        eng = JaxDecodeEngine(
            JaxDecodeConfig(context_length=256, max_running_requests=4,
                            new_tokens_per_chunk=4, page_size=128, dtype="float32",
                            kv_cache_dtype="float32", paged_attn_impl="pallas"),
            InferenceEngineConfig())
        eng.set_model(params, TINY_MOE)
        eng.initialize()
        try:
            eng.generate(ModelRequest(
                input_ids=[1, 5, 9, 13, 2],
                gconfig=GenerationHyperparameters(greedy=True, max_new_tokens=6)),
                timeout=300)
            counters = eng.get_metrics()
        finally:
            eng.destroy()

        def grad_step(p, ids):
            T = ids.shape[0]
            return jax.grad(lambda q: forward(
                q, ids, jnp.arange(T), jnp.zeros(T, jnp.int32), TINY_MOE).sum())(p)

        jax.jit(grad_step)(params, jnp.arange(16, dtype=jnp.int32))
    finally:
        mp.undo()
    return spy, counters


@pytest.mark.parametrize("program", ["chunk", "prefill_batched", "grad_step"])
def test_moe_program_holds_the_expert_scopes(moe_programs, program):
    spy, _ = moe_programs
    missing = [s for s in MOE_SCOPES if not _has_scope(spy.text[program], s)]
    assert not missing, f"{program}: no operation under {missing}"
    assert "ragged_dot" in spy.text[program]


def test_moe_engine_counts_expert_load(moe_programs):
    """`moe_pairs_total` and `moe_hot_expert_pairs_total` come back with the
    chunk's tokens: one live slot, 2 experts a token, 2 layers, no expert
    twice in a step."""
    _, m = moe_programs
    assert m["moe_pairs_total"] > 0 and m["moe_pairs_total"] % 4 == 0
    assert m["moe_hot_expert_pairs_total"] * 2 == m["moe_pairs_total"]


def test_a_handed_over_slot_is_counted_and_named_on_its_admit_span(cpu_devices, tmp_path):
    """`slots_handed_over_total` (admissions into a slot whose request's last
    chunk was dispatched and not yet read back; ISSUE 48) and the same count a
    pass as `handed_over` on every `decode/admit` span of a record, which
    `tools/trace_report.py` reads beside the `request/queue` spans: one slot,
    three requests of two chunks, so the second and the third take the slot
    of the one before."""
    import asyncio
    import os
    import sys

    from areal_tpu.engine.jax_decode import JaxDecodeEngine
    from areal_tpu.utils import perf_tracer

    sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools"))
    import trace_report

    with perf_tracer.recording() as rec:
        eng = JaxDecodeEngine(
            JaxDecodeConfig(context_length=64, max_running_requests=1, new_tokens_per_chunk=4,
                            dtype="float32", kv_cache_dtype="float32"),
            InferenceEngineConfig())
        eng.set_model(init_params(TINY, jax.random.PRNGKey(0)), TINY)
        eng.initialize()
        try:
            async def go():
                eng.pause_generation()
                tasks = [asyncio.ensure_future(eng.agenerate(ModelRequest(
                    input_ids=[1 + i, 5, 9, 13, 2],
                    gconfig=GenerationHyperparameters(greedy=True, max_new_tokens=8))))
                    for i in range(3)]
                await asyncio.sleep(0)
                eng.continue_generation()
                return await asyncio.wait_for(asyncio.gather(*tasks), 300)

            assert [len(r.output_tokens) for r in asyncio.run(go())] == [8, 8, 8]
            m = eng.get_metrics()
        finally:
            eng.destroy()
        path = rec.save(str(tmp_path / "record.json"))
    assert m["slots_handed_over_total"] == 2 and m["running_requests"] == 0
    admits = [s for s in rec.snapshot() if s["name"] == "decode/admit"]
    assert admits and all("handed_over" in s["ids"] for s in admits)
    assert sum(s["ids"]["handed_over"] for s in admits) == 2
    assert trace_report.report(path)["admissions"] == {"requests": 3, "handed_over": 2}


def test_a_held_dispatch_has_its_span_its_state_and_its_counters(cpu_devices):
    """ISSUE 51: the wait of a dispatch held until the device is about to need
    it is the span `decode/hold` and the scheduler state `hold`
    (`sched_hold_secs_total`): while it lasts it is the one span the thread has
    open, so no instant of the thread is unmarked; and the counters
    `chunks_held_total`, `held_admissions_total`,
    `chunks_dispatched_late_total` (the estimate overshot) and
    `chunks_late_in_admit_total` (the hold's admission outlasted the deadline)
    beside `chunks_dispatched_total`. The clock
    stands still and every chunk is said to be running, so the thread, once
    in a hold, stays in it until the arrival."""
    import concurrent.futures
    import time

    from areal_tpu.engine import jax_decode
    from areal_tpu.utils import perf_tracer

    assert "hold" in jax_decode.SCHED_STATES and jax_decode.SCHED_STATES[-1] == "other"

    class Now:
        @staticmethod
        def call_soon_threadsafe(fn, *args):
            fn(*args)

    def queue(eng, rid, first, n):
        item = jax_decode._Slot(
            rid=rid, prompt=[first, 5, 9, 13, 2],
            gconfig=GenerationHyperparameters(greedy=True, max_new_tokens=n),
            future=concurrent.futures.Future(), loop=Now())
        eng._request_q.put(item)
        eng._wake.set()
        return item

    def until(what, said):
        deadline = time.monotonic() + 120.0
        while not what():
            assert time.monotonic() < deadline, said
            time.sleep(0.002)

    with perf_tracer.recording() as rec:
        eng = jax_decode.JaxDecodeEngine(
            JaxDecodeConfig(context_length=64, max_running_requests=2, new_tokens_per_chunk=4,
                            dtype="float32", kv_cache_dtype="float32"),
            InferenceEngineConfig())
        eng.set_model(init_params(TINY, jax.random.PRNGKey(0)), TINY)
        eng.initialize()
        eng._clock = lambda: 100.0
        eng._chunk_ready = lambda rec: False
        eng._chunk_estimate = lambda rec: 1.0
        try:
            queue(eng, "a", 1, 40)
            until(lambda: eng.get_metrics()["sched_hold_secs_total"] > 0.0, "never held")
            mine = [s for s in rec.snapshot() if s["name"].startswith("decode/")]
            thread = {s["thread"] for s in mine}
            assert len(thread) == 1
            assert [s["name"] for s in mine if s["open"]] == ["decode/hold"]
            assert eng.get_metrics()["chunks_held_total"] == 0
            b = queue(eng, "b", 2, 4)
            until(lambda: eng.get_metrics()["chunks_held_total"] == 1, "the arrival ended no hold")
            eng._chunk_ready = lambda rec: True  # (no hold from here on: what is left runs out)
            eng._wake.set()
            b.future.result(timeout=120.0)
            m = eng.get_metrics()
        finally:
            eng.destroy()
    assert m["chunks_held_total"] >= 1 and m["held_admissions_total"] == 1
    assert m["chunks_dispatched_late_total"] <= 1  # (the hold the switch above ended)
    assert m["chunks_late_in_admit_total"] == 0  # (the clock stands still: no deadline passes)
    assert m["chunks_dispatched_total"] > m["chunks_held_total"]
    assert set(f"sched_{k}_secs_total" for k in jax_decode.SCHED_STATES) <= set(m)
    holds = [s for s in rec.snapshot() if s["name"] == "decode/hold"]
    assert holds and all(s["parent"] is None and "chunk" in s["ids"] for s in holds)


def test_engine_counts_live_block_columns(cpu_devices):
    """`paged_block_columns_live_total` / `_visited_total` (what the paged
    kernel walks: its live columns, and a step for each slot with none)
    against a NumPy count over the chunks the engine dispatched: two requests, one of them
    past its first page, in four slots under a two-column table."""
    from areal_tpu.engine.jax_decode import JaxDecodeEngine

    R, n_chunk, bsz = 4, 4, 128
    eng = JaxDecodeEngine(
        JaxDecodeConfig(context_length=256, max_running_requests=R,
                        new_tokens_per_chunk=n_chunk, page_size=bsz, dtype="float32",
                        kv_cache_dtype="float32"),
        InferenceEngineConfig())
    eng.set_model(init_params(TINY, jax.random.PRNGKey(0)), TINY)
    eng.initialize()
    chunks = []
    bucket = eng._chunk_bucket

    def noting(active, grow=None):
        s = bucket(active, grow)
        chunks.append((active.copy(), eng._slot_lengths.copy(), -(-s // bsz)))
        return s

    eng._chunk_bucket = noting
    try:
        import asyncio

        async def both():
            return await asyncio.gather(*(
                eng.agenerate(ModelRequest(
                    input_ids=list(range(1, n + 1)),
                    gconfig=GenerationHyperparameters(greedy=True, max_new_tokens=new)))
                for n, new in ((5, 6), (124, 11))))

        asyncio.run(both())
        m = eng.get_metrics()
    finally:
        eng.destroy()
    assert len(chunks) == m["chunks_dispatched_total"] >= 3
    from areal_tpu.ops.paged_attention import group_pages

    live = visited = groups = 0
    for active, lengths, nb in chunks:
        last = lengths[active] + n_chunk - 1  # the chunk's last query
        live += int(np.minimum(last // bsz + 1, nb).sum())
        assert nb == 2 == group_pages(bsz, 2 * TINY.head_dim_, 4, 1, nb)
        groups += int((-(-np.minimum(last // bsz + 1, nb) // 2)).sum())
        # a step a live column, and one a slot that has none
        visited += int(np.minimum(last // bsz + 1, nb).sum()) + R - int(active.sum())
    assert m["paged_block_columns_visited_total"] == visited
    assert m["paged_block_columns_live_total"] == live
    # the groups the kernel's loop takes for them, at the group the pool's
    # shapes give a chunk's table (two of its two columns), and what they score
    assert m["paged_block_groups_walked_total"] == groups
    assert m["paged_block_columns_scored_total"] == 2 * groups >= live
    # one and two live columns were both seen, and empty slots none
    assert len(chunks) < live < visited


def test_dense_engine_reports_no_expert_load(programs):
    spy, _ = programs
    assert "ragged_dot" not in spy.text["chunk"] and "ragged_dot" not in spy.text["grad_step"]


def test_train_batch_counts_padding_and_first_calls(programs):
    _, stats = programs
    s = stats[0]
    # 4 sequences of 8 tokens: 32 real ones in a micro-batch padded to a bucket
    assert s["n_tokens"] == 32.0 and s["padded_tokens"] >= s["n_tokens"]
    assert s["padded_tokens"] % 128 == 0
    # one micro-batch of one 128-token block: the only block pair is live
    assert s["attn_live_block_pct"] == s["attn_walked_block_pct"] == 100.0
    # zero_grads, grad_step and apply_update ran for the first time
    assert s["compiles"] == 3


# ---------------------------------------------------------------------------
# Compiled for a described v5e (no chip): Mosaic accepts the kernels at the
# benchmark's head shapes, and the custom calls carry the kernels' names.
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — whatever keeps the topology from being described
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    return jax.jit(fn).trace(*args).lower(lowering_platforms=("tpu",)).compile().as_text()


def _mosaic_kernels(hlo: str) -> list[str]:
    return [line.split(" = ")[0].split("%")[-1] for line in hlo.splitlines()
            if 'custom_call_target="tpu_custom_call"' in line]


def _one_paged_kernel(hlo: str, slots: int) -> str:
    """The program's only Mosaic call, and no loop around it: ONE kernel over
    a grid of the slots, whose work list (each slot's live range of block
    columns and the chain from slot to slot: four `[slots]` vectors after
    the table and the layer index) rides as scalar-prefetch operands, the
    walk over a slot's columns inside the kernel (a list that varied a grid
    bound would loop in the program, or key it by its length)."""
    calls = [ln for ln in hlo.splitlines() if 'custom_call_target="tpu_custom_call"' in ln]
    kernels = _mosaic_kernels(hlo)
    assert len(kernels) == 1 and " while(" not in hlo, kernels
    operands = calls[0].split("operand_layout_constraints={")[1].split("}}")[0]
    vec = f"s32[{slots}]{{0}}"
    assert operands.split(", ")[1:6] == ["s32[1]{0}"] + [vec] * 4, operands
    return kernels[0]


def test_paged_kernel_is_named_at_the_1p5b_head_shape(one_chip):
    from areal_tpu.ops.paged_attention import paged_attention

    R, nH, nKV, hd, bsz, nb, n_blocks = 128, 12, 2, 128, 128, 10, 1281

    L = 28  # the whole stacked pool, as the engine stores it, and a layer index
    pool = ((L, n_blocks, bsz, nKV * hd), jnp.bfloat16)

    def step(q, kp, vp, bt, valid, li):
        with jax.named_scope("layer"):
            return paged_attention(q, kp, vp, bt, valid, li, impl="pallas",
                                   interpret=False)

    hlo = _compile(
        step, one_chip, ((R, nH, hd), jnp.bfloat16), pool, pool,
        ((R, nb), jnp.int32), ((R, nb * bsz), jnp.bool_), ((), jnp.int32))
    assert "tpu_custom_call" in hlo
    assert "%paged_attention" in hlo and "%layer" not in hlo
    assert _one_paged_kernel(hlo, R).startswith("paged_attention")


@pytest.mark.parametrize("int8", [False, True], ids=["bf16", "int8"])
def test_verify_kernel_is_one_call_at_the_1p5b_head_shape(one_chip, int8):
    """The speculative verify's five query positions a slot through the same
    kernel, over the bf16 pool and over the int8 pool with its scale strips
    (copied by the same chain): still one Mosaic call, no loop around it."""
    from areal_tpu.ops.paged_attention import paged_attention_qlen

    R, W, nH, nKV, hd, bsz, nb, L = 128, 5, 12, 2, 128, 128, 10, 28
    n_blocks = R * nb + 1
    pool = [((L, n_blocks, bsz, nKV * hd), jnp.int8 if int8 else jnp.bfloat16)]
    if int8:
        pool.append(((L, n_blocks, nKV, bsz), jnp.float32))

    def step(q, bt, valid, li, lo, hi, *pools):
        kp, vp = pools[: len(pool)], pools[len(pool):]
        kp, vp = (kp, vp) if int8 else (kp[0], vp[0])
        return paged_attention_qlen(q, kp, vp, bt, valid, li, impl="pallas",
                                    interpret=False, live=(lo, hi))

    hlo = _compile(
        step, one_chip, ((R, W, nH, hd), jnp.bfloat16), ((R, nb), jnp.int32),
        ((R, W, nb * bsz), jnp.bool_), ((), jnp.int32), ((R,), jnp.int32),
        ((R,), jnp.int32), *pool, *pool)
    assert _one_paged_kernel(hlo, R).startswith("paged_attention")


@pytest.mark.parametrize("W", [1, 5], ids=["decode", "verify5"])
def test_model_step_holds_one_paged_kernel_at_the_1p5b_head_shape(one_chip, monkeypatch, W):
    """The decode step and the verify step, two stacked layers at the 1.5B's
    widths, lowered with the kernel through Mosaic: the layer loop holds ONE
    Mosaic call, `%paged_attention`, with the step's work list (taken once,
    outside the layer loop) among its operands."""
    from areal_tpu.models.qwen2 import decode_step_paged, param_shapes, verify_step_paged
    from areal_tpu.ops import paged_attention as pa

    monkeypatch.setattr(pa, "_default_interpret", lambda: False)
    L, R, nb, bsz = 2, 128, 10, 128
    cfg = ModelConfig(
        vocab_size=151936, hidden_size=1536, intermediate_size=8960, num_hidden_layers=L,
        num_attention_heads=12, num_key_value_heads=2, dtype="bfloat16",
        param_dtype="bfloat16")
    bf = jnp.bfloat16
    arg = lambda s, d: jax.ShapeDtypeStruct(s, d, sharding=one_chip)  # noqa: E731
    params = jax.tree.map(lambda s: arg(s, bf), param_shapes(cfg),
                          is_leaf=lambda x: isinstance(x, tuple))
    pool = arg((L, R * nb + 1, bsz, 2 * 128), bf)

    def step(params, kp, vp, bt, tokens, positions, active):
        fn = decode_step_paged if W == 1 else verify_step_paged
        return fn(params, tokens, positions, kp, vp, bt, cfg, active=active,
                  attn_impl="pallas")

    hlo = jax.jit(step, donate_argnums=(1, 2)).trace(
        params, pool, pool, arg((R, nb), jnp.int32),
        arg((R,) if W == 1 else (R, W), jnp.int32), arg((R,), jnp.int32),
        arg((R,), jnp.bool_),
    ).lower(lowering_platforms=("tpu",)).compile().as_text()
    calls = [ln for ln in hlo.splitlines() if 'custom_call_target="tpu_custom_call"' in ln]
    assert len(calls) == 1 and "%paged_attention" in calls[0], _mosaic_kernels(hlo)
    operands = calls[0].split("operand_layout_constraints={")[1].split("}}")[0]
    assert operands.split(", ")[2:6] == [f"s32[{R}]{{0}}"] * 4, operands
    # one loop, the layer scan: the walk over a slot's columns is the kernel's
    assert hlo.count(" while(") == 1


def test_block_step_holds_one_block_kernel_at_sdars_head_shape(one_chip, monkeypatch):
    """A block-diffusion forward, two stacked layers at SDAR-30B-A3B's widths
    (32 query and 4 KV heads of 128, blocks of 4 positions, 128 slots at a
    depth of 10 pages), lowered with the kernel through Mosaic: the layer
    loop holds ONE Mosaic call, named `%paged_attention_block`, with the
    forward's work list among its operands, and three grouped matmuls over
    4,224 rows."""
    from areal_tpu.models.qwen2 import diffusion_step_paged, param_shapes
    from areal_tpu.ops import paged_attention as pa

    monkeypatch.setattr(pa, "_default_interpret", lambda: False)
    L, R, B, nb, bsz = 2, 128, 4, 10, 128
    cfg = ModelConfig(
        vocab_size=151936, hidden_size=2048, intermediate_size=6144, num_hidden_layers=L,
        num_attention_heads=32, num_key_value_heads=4, head_dim=128, model_type="sdar_moe",
        qkv_bias=False, qk_norm=True, num_experts=128, num_experts_per_tok=8,
        moe_intermediate_size=768, norm_topk_prob=True, rope_theta=1e6, block_length=B,
        mask_token_id=151669, dtype="bfloat16", param_dtype="bfloat16")
    bf = jnp.bfloat16
    arg = lambda s, d: jax.ShapeDtypeStruct(s, d, sharding=one_chip)  # noqa: E731
    params = jax.tree.map(lambda s: arg(s, bf), param_shapes(cfg),
                          is_leaf=lambda x: isinstance(x, tuple))
    pool = arg((L, R * nb + 1, bsz, 4 * 128), bf)

    def step(params, kp, vp, bt, tokens, positions, active):
        return diffusion_step_paged(params, tokens, positions, kp, vp, bt, cfg, active=active,
                                    attn_impl="pallas", moe_load=True)

    hlo = jax.jit(step, donate_argnums=(1, 2)).trace(
        params, pool, pool, arg((R, nb), jnp.int32), arg((R, B), jnp.int32),
        arg((R,), jnp.int32), arg((R,), jnp.bool_),
    ).lower(lowering_platforms=("tpu",)).compile().as_text()
    calls = [ln for ln in hlo.splitlines() if 'custom_call_target="tpu_custom_call"' in ln
             and "ragged-dot" not in ln.split(" = ")[0]]
    assert len(calls) == 1 and "%paged_attention_block" in calls[0], _mosaic_kernels(hlo)
    operands = calls[0].split("operand_layout_constraints={")[1].split("}}")[0]
    assert operands.split(", ")[2:6] == [f"s32[{R}]{{0}}"] * 4, operands
    assert hlo.count(" while(") == 1
    names, row_counts, tilings = _grouped_matmul_calls(hlo)
    assert len(names) == 3 and row_counts == {"4224"}, (names, row_counts)
    assert tilings == {"128,256,512", "128,512,256"}, tilings  # a 128-row tile


def test_paged_kernel_is_named_at_olmoes_head_shape(one_chip):
    """16 query and 16 KV heads of 128: a pool row of 4,096 lanes (K and V
    2,048 each), the query block `[slots, 16, 2048]`, the cell's pool."""
    from areal_tpu.ops.paged_attention import paged_attention

    R, nH, nKV, hd, bsz, nb, L = 64, 16, 16, 128, 128, 10, 8
    pool = ((L, R * nb + 1, bsz, nKV * hd), jnp.bfloat16)

    # the range as the model step hands it over, taken outside the layer loop
    def step(q, kp, vp, bt, valid, li, lo, hi):
        return paged_attention(q, kp, vp, bt, valid, li, impl="pallas", interpret=False,
                               live=(lo, hi))

    hlo = _compile(
        step, one_chip, ((R, nH, hd), jnp.bfloat16), pool, pool,
        ((R, nb), jnp.int32), ((R, nb * bsz), jnp.bool_), ((), jnp.int32),
        ((R,), jnp.int32), ((R,), jnp.int32))
    assert "tpu_custom_call" in hlo and "%paged_attention" in hlo
    assert _one_paged_kernel(hlo, R).startswith("paged_attention")


@pytest.mark.parametrize("kind,layers,blocks,nb", [
    ("window", 4, 1 + 64 * 2, 2), ("full", 1, 64 * 64 + 1, 64)])
def test_paged_kernels_are_named_apart_at_kexaones_head_shape(one_chip, kind, layers, blocks, nb):
    """64 query and 8 KV heads of 128 (K-EXAONE): the window layers read their
    ring (two pages a slot, a two-column table) under a kernel name of their
    own, the full layer its pool through 64 block columns under the uniform
    stacks' name: a device trace tells the two apart."""
    from areal_tpu.models.qwen2 import _PAGED_KERNELS
    from areal_tpu.ops.paged_attention import paged_attention

    R, nH, nKV, hd, bsz = 64, 64, 8, 128, 128
    pool = ((layers, blocks, bsz, nKV * hd), jnp.bfloat16)

    def step(q, kp, vp, bt, valid):
        return paged_attention(q, kp, vp, bt, valid, layers - 1, impl="pallas",
                               interpret=False, kernel_name=_PAGED_KERNELS[kind])

    hlo = _compile(
        step, one_chip, ((R, nH, hd), jnp.bfloat16), pool, pool,
        ((R, nb), jnp.int32), ((R, nb * bsz), jnp.bool_))
    assert "tpu_custom_call" in hlo
    assert ("%paged_attention_window" in hlo) == (kind == "window")
    assert ("%paged_attention." in hlo or "%paged_attention " in hlo) == (kind == "full")
    assert _one_paged_kernel(hlo, R).startswith(_PAGED_KERNELS[kind])


def test_latent_kernel_is_named_at_deepseek_v2s_widths(one_chip):
    """128 heads against ONE shared 576-wide row stored at 640 lanes, 512
    summed (DeepSeek-V2): one Mosaic call named `%paged_attention_latent`
    over a grid of the slots, PR 33's work list as its scalar-prefetch
    operands, the cell's pool read in place (no copy beside it)."""
    from areal_tpu.ops.paged_attention_latent import paged_attention_latent

    R, nH, D, dv, bsz, nb, L = 64, 128, 640, 512, 128, 128, 5
    pool = ((L, 786432 // bsz + 1, bsz, D), jnp.bfloat16)

    def step(q, kp, bt, valid, li, lo, hi):
        with jax.named_scope("layer"):
            return paged_attention_latent(q, kp, bt, valid, li, dv=dv, sm_scale=0.11472,
                                          impl="pallas", interpret=False, live=(lo, hi))

    hlo = _compile(
        step, one_chip, ((R, nH, D), jnp.bfloat16), pool, ((R, nb), jnp.int32),
        ((R, nb * bsz), jnp.bool_), ((), jnp.int32), ((R,), jnp.int32), ((R,), jnp.int32))
    assert "tpu_custom_call" in hlo and "%layer" not in hlo
    assert _one_paged_kernel(hlo, R).startswith("paged_attention_latent")
    # the pool is an operand of the call as it is stored: no copy of its 5 GB
    big = [ln for ln in hlo.splitlines() if f"bf16[{L},{786432 // bsz + 1},{bsz},{D}]" in ln
           and " copy(" in ln]
    assert not big, big


def test_a_latent_row_of_576_lanes_is_refused_by_mosaic(one_chip):
    """Why the pool stores 640 lanes: a TPU tiles the minor dimension in 128
    lanes whatever its logical size (the 576-lane pool IS 640 wide in HBM),
    and Mosaic copies whole tiles."""
    from areal_tpu.ops.paged_attention_latent import paged_attention_latent

    R, nH, D, bsz, nb = 8, 128, 576, 128, 4

    def step(q, kp, bt, valid):
        return paged_attention_latent(q, kp, bt, valid, 0, dv=512, sm_scale=0.1,
                                      impl="pallas", interpret=False)

    with pytest.raises(Exception, match="aligned to tiling"):
        _compile(step, one_chip, ((R, nH, D), jnp.bfloat16), ((1, 33, bsz, D), jnp.bfloat16),
                 ((R, nb), jnp.int32), ((R, nb * bsz), jnp.bool_))


def test_gdn_step_kernel_is_named_at_qwen3_nexts_state_shape(one_chip):
    """32 value heads of 128 x 128 float32 a slot, six linear layers in one
    pool (Qwen3-Next): ONE Mosaic call named `%gdn_step`, the whole pool its
    operand and (aliased) its result, no copy of it beside the call."""
    from areal_tpu.ops.gdn_step import gdn_step

    n, R, Hv, dk, dv = 6, 64, 32, 128, 128
    f32 = jnp.float32

    def step(S, q, k, v, g, beta, active):
        with jax.named_scope("layer"):
            return gdn_step(S, q, k, v, g, beta, 4, active, impl="pallas", interpret=False)

    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in (
        ((n, 1 + R, Hv, dk, dv), f32), ((R, Hv, dk), f32), ((R, Hv, dk), f32),
        ((R, Hv, dv), f32), ((R, Hv), f32), ((R, Hv), f32), ((R,), jnp.bool_))]
    hlo = jax.jit(step, donate_argnums=0).trace(*args).lower(
        lowering_platforms=("tpu",)).compile().as_text()
    kernels = _mosaic_kernels(hlo)
    assert len(kernels) == 1 and kernels[0].startswith("gdn_step") and " while(" not in hlo
    assert "%gdn_step" in hlo and "%layer" not in hlo
    state = f"f32[{n},{1 + R},{Hv},{dk},{dv}]"
    copies = [ln for ln in hlo.splitlines() if " copy(" in ln and state in ln]
    assert not copies, copies


def test_kda_step_kernel_is_named_at_kimi_linears_state_shape(one_chip):
    """128 slots x 32 heads of 128 x 128 float32, six KDA layers in one pool
    (Kimi-Linear), the decay a vector over the key lanes: ONE Mosaic call
    named `%kda_step` (the Gated DeltaNet's frame, the decay a column block
    beside q^T and k^T), the whole pool its operand and (aliased) its result,
    no copy of its 1.6 GB beside the call."""
    from areal_tpu.ops.gdn_step import gdn_step

    n, R, Hv, dk, dv = 6, 128, 32, 128, 128
    f32 = jnp.float32

    def step(S, q, k, v, g, beta, active):
        with jax.named_scope("layer"):
            return gdn_step(S, q, k, v, g, beta, 4, active, impl="pallas", interpret=False)

    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in (
        ((n, 1 + R, Hv, dk, dv), f32), ((R, Hv, dk), f32), ((R, Hv, dk), f32),
        ((R, Hv, dv), f32), ((R, Hv, dk), f32), ((R, Hv), f32), ((R,), jnp.bool_))]
    hlo = jax.jit(step, donate_argnums=0).trace(*args).lower(
        lowering_platforms=("tpu",)).compile().as_text()
    kernels = _mosaic_kernels(hlo)
    assert len(kernels) == 1 and kernels[0].startswith("kda_step") and " while(" not in hlo
    assert "%kda_step" in hlo and "%gdn_step" not in hlo and "%layer" not in hlo
    state = f"f32[{n},{1 + R},{Hv},{dk},{dv}]"
    copies = [ln for ln in hlo.splitlines() if " copy(" in ln and state in ln]
    assert not copies, copies


def test_ssm_step_kernel_is_named_at_jamba2s_state_shape_inside_a_scanned_run(one_chip):
    """256 slots x [16 state lanes, 5,120 channels] float32, 26 state-space
    layers in one pool (AI21-Jamba2-3B), the layer's place in the pool the
    counter of a scan over a run of layers: ONE Mosaic call named
    `%ssm_step` inside the loop, the layer index and the live slots' work
    list its scalar-prefetch operands, the whole pool its operand and
    (aliased, through the scan's carry) its result, no copy of its 2.2 GB
    beside the call."""
    from areal_tpu.ops.ssm_step import live_slots, ssm_step

    n, R, N, Di = 26, 256, 16, 5120
    f32 = jnp.float32

    def run(S, dt, u, B, C, A, D, active):
        live = live_slots(active, R)

        def layer(S, i):
            with jax.named_scope("layer"):
                y, S = ssm_step(S, dt, u, B, C, A, D, i, active, impl="pallas", live=live,
                                interpret=False)
            return S, y

        return jax.lax.scan(layer, S, 7 + jnp.arange(13, dtype=jnp.int32))

    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in (
        ((n, 1 + R, N, Di), f32), ((R, Di), f32), ((R, Di), f32), ((R, N), f32), ((R, N), f32),
        ((N, Di), f32), ((Di,), f32), ((R,), jnp.bool_))]
    hlo = jax.jit(run, donate_argnums=0).trace(*args).lower(
        lowering_platforms=("tpu",)).compile().as_text()
    calls = [ln for ln in hlo.splitlines() if 'custom_call_target="tpu_custom_call"' in ln]
    kernels = _mosaic_kernels(hlo)
    assert len(kernels) == 1 and kernels[0].startswith("ssm_step") and hlo.count(" while(") == 1
    assert "%ssm_step" in hlo and "%gdn_step" not in hlo and "%kda_step" not in hlo
    operands = calls[0].split("operand_layout_constraints={")[1].split("}}")[0]
    assert operands.split(", ")[:2] == ["s32[2]{0}", f"s32[{R}]{{0}}"], operands
    state = f"f32[{n},{1 + R},{N},{Di}]"
    assert state in calls[0]
    copies = [ln for ln in hlo.splitlines() if " copy(" in ln and state in ln]
    assert not copies, copies


def test_ssm_scan_kernel_is_named_at_jamba2s_widths_alone_and_under_vmap(one_chip):
    """A prefill's selective scan at AI21-Jamba2-3B's widths, 1,024 tokens of
    5,120 channels x 16 state lanes, one sequence and a wave of eight
    (`jax.vmap`): ONE Mosaic call named `%ssm_scan`, no loop around it."""
    from areal_tpu.ops.ssm_scan import ssm_scan

    T, N, Di = 1024, 16, 5120
    f32 = jnp.float32
    for wave in (0, 8):
        def scan(u, dt, B, C, A):
            one = lambda u, dt, B, C: ssm_scan(  # noqa: E731
                u, dt, B, C, A, scan=None, impl="pallas", interpret=False)
            return jax.vmap(one)(u, dt, B, C) if wave else one(u, dt, B, C)

        lead = (wave,) if wave else ()
        hlo = _compile(scan, one_chip, (lead + (T, Di), f32), (lead + (T, Di), f32),
                       (lead + (T, N), f32), (lead + (T, N), f32), ((N, Di), f32))
        kernels = _mosaic_kernels(hlo)
        assert len(kernels) == 1 and kernels[0].startswith("ssm_scan"), kernels
        assert " while(" not in hlo


def test_jamba2s_decode_step_scans_its_runs_with_the_state_in_place(one_chip, monkeypatch):
    """The whole decode step at AI21-Jamba2-3B's published configuration, 256
    slots: three scanned runs (7, 13 and 6 state-space layers), each ONE
    `%ssm_step` call inside its loop, the two attention layers' `%paged_attention`
    in line (20 query heads against one kv head of 128), no copy of the state
    pool or of the paged pools anywhere, and no `rope` scope in the program."""
    import json as _json
    import os

    from areal_tpu.models.qwen2 import decode_step_paged, param_shapes
    from areal_tpu.ops import paged_attention as pa
    from areal_tpu.ops import ssm_step as ss

    REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

    monkeypatch.setattr(pa, "_default_interpret", lambda: False)
    monkeypatch.setattr(ss, "_default_interpret", lambda: False)
    with open(os.path.join(REPO, "benchmark/configs/ai21-jamba2-3b.json")) as f:
        cfg = ModelConfig.from_hf_config(_json.load(f), dtype="bfloat16", param_dtype="bfloat16")
    R, nb, bsz = 256, 24, 128
    bf, f32 = jnp.bfloat16, jnp.float32
    arg = lambda s, d: jax.ShapeDtypeStruct(s, d, sharding=one_chip)  # noqa: E731
    params = jax.tree.map(lambda s: arg(s, bf), param_shapes(cfg),
                          is_leaf=lambda x: isinstance(x, tuple))
    assert sorted(k for k in params if k.startswith(("run_", "layers_"))) == [
        "layers_21", "layers_7", "run_0_7", "run_22_28", "run_8_21"]
    pool = arg((2, R * nb + 1, bsz, 128), bf)
    state = {"S": arg((26, 1 + R, 16, 5120), f32), "conv": arg((26, 1 + R, 3, 5120), bf)}

    def step(params, kp, vp, bt, tokens, positions, active):
        return decode_step_paged(params, tokens, positions, kp, vp, bt, cfg, active=active,
                                 attn_impl="pallas", moe_load=True)

    lowered = jax.jit(step, donate_argnums=(1, 2)).trace(
        params, {"full": pool, "state": state}, {"full": pool}, arg((R, nb), jnp.int32),
        arg((R,), jnp.int32), arg((R,), jnp.int32), arg((R,), jnp.bool_),
    ).lower(lowering_platforms=("tpu",))
    assert "rope" not in lowered.as_text(debug_info=True).replace(
        "test_jamba2s_decode_step", "")
    hlo = lowered.compile().as_text()
    kernels = sorted(k.split(".")[0] for k in _mosaic_kernels(hlo))
    assert kernels == ["paged_attention"] * 2 + ["ssm_step"] * 3, kernels
    assert hlo.count(" while(") == 3
    for big in ("f32[26,257,16,5120]", f"bf16[2,{R * nb + 1},128,128]"):
        copies = [ln for ln in hlo.splitlines() if " copy(" in ln and big in ln]
        assert not copies, copies
    # the convolution's rows (3 a slot: XLA keeps them in a layout of its own
    # inside the loops, slots on the sublanes) change layout at the program's
    # entry and exit alone, never inside a run's loop
    entry = hlo[hlo.index("\nENTRY "):]
    inside = [ln for ln in hlo[:hlo.index("\nENTRY ")].splitlines()
              if " copy(" in ln and "bf16[26,257,3,5120]" in ln]
    assert not inside and entry.count("bf16[26,257,3,5120]") >= 2, inside


def test_latent_kernel_is_named_at_kimi_linears_widths(one_chip):
    """32 heads against the same 640-lane row at 128 slots, two latent layers
    in the pool (Kimi-Linear: 60 FLOP a byte where DeepSeek-V2's 128 heads
    give 242): the same one Mosaic call named `%paged_attention_latent`, the
    cell's pool read in place."""
    from areal_tpu.ops.paged_attention_latent import paged_attention_latent

    R, nH, D, dv, bsz, nb, L = 128, 32, 640, 512, 128, 64, 2
    blocks = 1048576 // bsz + 1
    pool = ((L, blocks, bsz, D), jnp.bfloat16)

    def step(q, kp, bt, valid, li, lo, hi):
        with jax.named_scope("layer"):
            return paged_attention_latent(q, kp, bt, valid, li, dv=dv, sm_scale=192 ** -0.5,
                                          impl="pallas", interpret=False, live=(lo, hi))

    hlo = _compile(
        step, one_chip, ((R, nH, D), jnp.bfloat16), pool, ((R, nb), jnp.int32),
        ((R, nb * bsz), jnp.bool_), ((), jnp.int32), ((R,), jnp.int32), ((R,), jnp.int32))
    assert "tpu_custom_call" in hlo and "%layer" not in hlo
    assert _one_paged_kernel(hlo, R).startswith("paged_attention_latent")
    big = [ln for ln in hlo.splitlines() if f"bf16[{L},{blocks},{bsz},{D}]" in ln
           and " copy(" in ln]
    assert not big, big


def test_kimi_linears_prefill_has_a_width_the_compiler_takes_at_1536(one_chip):
    """XLA:TPU (libtpu 0.0.34) refused one prompt bucket of this model at
    COMPILE time and killed the scheduler in the warm-up (PR 45, first chip
    call): 1,536 tokens, a fusion of its own around `mlp/dispatch`'s row
    gather out of scoped VMEM. The engine's rule for a refused prefill
    (`_PrefillOrWider`: the same prompt as a pass a bucket or two wider, rows
    cut back) ends on a program the compiler takes, at the published widths'
    first four layers (KDA dense, KDA, KDA, MLA); which width that is belongs
    to the compiler."""
    import json
    import os

    from areal_tpu.engine.jax_decode import _PrefillOrWider
    from areal_tpu.models import qwen2

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(repo, "benchmark/configs/kimi-linear-48b-a3b.json")) as f:
        hf = dict(json.load(f), num_hidden_layers=4)
    cfg = ModelConfig.from_hf_config(hf, dtype="bfloat16", param_dtype="bfloat16")
    T = 1536
    p = jax.eval_shape(lambda: qwen2.init_params(cfg, jax.random.PRNGKey(0)))
    p = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip), p)
    ids = jax.ShapeDtypeStruct((1, T), jnp.int32, sharding=one_chip)
    lens = jax.ShapeDtypeStruct((1,), jnp.int32, sharding=one_chip)

    def program(tokens):
        def prefill_batched(p, ids_b, lens_b):  # one prompt a program, as the engine builds it
            ids_b = jnp.pad(ids_b, ((0, 0), (0, tokens - T)))
            _, k, *rest = jax.vmap(lambda ids, n: qwen2.prefill(
                p, ids, jnp.arange(tokens), cfg, valid=jnp.arange(tokens) < n,
                with_logits=False))(ids_b, lens_b)
            return k[:, :, :T], rest

        return lambda *args: jax.jit(prefill_batched).trace(*args).lower(
            lowering_platforms=("tpu",)).compile()

    taken = _PrefillOrWider(program, T)
    assert "ragged-dot" in taken(p, ids, lens).as_text()
    assert T <= taken.tokens <= T + 128


def test_paged_kernel_is_named_at_qwen3_nexts_head_shape(one_chip):
    """16 query and 2 KV heads of 256 (Qwen3-Next's gated full attention): a
    pool row of 512 lanes for K and for V, two full layers in the pool, 64
    block columns of a context of 8,192."""
    from areal_tpu.ops.paged_attention import paged_attention

    R, nH, nKV, hd, bsz, nb, L = 64, 16, 2, 256, 128, 64, 2
    pool = ((L, R * nb + 1, bsz, nKV * hd), jnp.bfloat16)

    def step(q, kp, vp, bt, valid, lo, hi):
        return paged_attention(q, kp, vp, bt, valid, 1, impl="pallas", interpret=False,
                               live=(lo, hi))

    hlo = _compile(
        step, one_chip, ((R, nH, hd), jnp.bfloat16), pool, pool,
        ((R, nb), jnp.int32), ((R, nb * bsz), jnp.bool_),
        ((R,), jnp.int32), ((R,), jnp.int32))
    assert "tpu_custom_call" in hlo and "%paged_attention" in hlo
    assert _one_paged_kernel(hlo, R).startswith("paged_attention")


@pytest.mark.parametrize("tokens", [64, 2048], ids=["decode_step", "batched_prefill"])
def test_grouped_expert_matmuls_are_named_at_olmoes_widths(one_chip, tokens):
    """XLA:TPU lowers `jax.lax.ragged_dot` to a Mosaic grouped matmul of its
    own: three `%ragged-dot-none` custom calls a layer, which the
    `expert_matmul_*` metrics match on."""
    from areal_tpu.models.qwen2 import moe_mlp

    H, M, E, K = 2048, 1024, 64, 8
    cfg = ModelConfig(hidden_size=H, num_experts=E, num_experts_per_tok=K,
                      moe_intermediate_size=M, norm_topk_prob=False)

    def layer_mlp(x, valid, router, gate, up, down):
        p = dict(router_kernel=router, gate_kernel=gate, up_kernel=up, down_kernel=down)
        return moe_mlp(p, x, cfg, valid=valid, with_load=True)

    bf = jnp.bfloat16
    hlo = _compile(
        layer_mlp, one_chip, ((tokens, H), bf), ((tokens,), jnp.bool_), ((H, E), bf),
        ((E, H, M), bf), ((E, H, M), bf), ((E, M, H), bf))
    names = set(re.findall(r"%(ragged-dot-none(?:\.\d+)?) = ", hlo))
    assert len(names) == 3, names
    assert hlo.count('custom_call_target="tpu_custom_call"') >= 3


@pytest.fixture(scope="module")
def stacked_olmoe_step(one_chip):
    """The compiled decode step over two stacked layers at OLMoE's widths,
    64 slots."""
    from areal_tpu.models.qwen2 import decode_step_paged, param_shapes

    L, R, nb, bsz = 2, 64, 2, 128
    cfg = ModelConfig(
        vocab_size=50304, hidden_size=2048, intermediate_size=1024, num_hidden_layers=L,
        num_attention_heads=16, num_key_value_heads=16, model_type="olmoe", qkv_bias=False,
        qk_norm=True, qk_norm_full=True, num_experts=64, num_experts_per_tok=8,
        moe_intermediate_size=1024, norm_topk_prob=False, tie_word_embeddings=False,
        dtype="bfloat16", param_dtype="bfloat16")
    bf = jnp.bfloat16
    arg = lambda s, d: jax.ShapeDtypeStruct(s, d, sharding=one_chip)  # noqa: E731
    params = jax.tree.map(lambda s: arg(s, bf), param_shapes(cfg),
                          is_leaf=lambda x: isinstance(x, tuple))
    pool = arg((L, R * nb + 1, bsz, 16 * 128), bf)

    def step(params, kp, vp, bt, tokens, positions, active):
        return decode_step_paged(params, tokens, positions, kp, vp, bt, cfg,
                                 active=active, attn_impl="pallas", moe_load=True)

    return jax.jit(step, donate_argnums=(1, 2)).trace(
        params, pool, pool, arg((R, nb), jnp.int32), arg((R,), jnp.int32),
        arg((R,), jnp.int32), arg((R,), jnp.bool_),
    ).lower(lowering_platforms=("tpu",)).compile().as_text()


def test_stacked_decode_step_reads_olmoes_experts_in_place(stacked_olmoe_step):
    """A decode step over two stacked layers at OLMoE's widths: the layer
    loop holds three `%ragged-dot-none` calls whose weight operand is the
    whole stacked leaf, and nothing (the parent had a
    `%dynamic-slice_bitcast_fusion` per kernel, 0.68 ms each on the chip)
    has one layer's kernels as its result."""
    hlo = stacked_olmoe_step
    calls = re.findall(r"%ragged-dot-none(?:\.\d+)? = \S+ custom-call\(([^)]*)\)", hlo)
    assert len(calls) == 3, calls
    # each reads the stacked leaf itself: `[L*E, H, M]`, a bitcast of the argument
    stacked = set(re.findall(
        r"(%\S+) = bf16\[128,(?:2048,1024|1024,2048)\]\S* (?:bitcast|get-tuple-element)\(", hlo))
    assert all(set(c.replace(" ", "").split(",")) & stacked for c in calls), (calls, stacked)
    copied = re.findall(r"%(\S+) = bf16\[64,(?:2048,1024|1024,2048)\]", hlo)
    assert not copied, copied


# (tokens, hidden, expert width, experts held, published, top-k, sigmoid scores): one
# sparse layer's `moe_mlp` at a cell's widths
_OLMOE = (2048, 1024, 64, 64, 8, False)
_GROUPED_LAYERS = {
    "olmoe_64_slots": ((64, *_OLMOE), 640, "128,512,512"),
    "kexaone_64_slots_16_of_128_held": ((64, 6144, 2048, 16, 128, 8, True), 640, "128,512,512"),
    "qwen3next_64_slots_64_of_512_held": ((64, 2048, 512, 64, 512, 10, False), 640, "128,512,512"),
    "olmoe_prefill_bucket_2048": ((2048, *_OLMOE), 16384, "512,512,512"),
    # a block-diffusion forward: 128 slots x 4 positions x top-8 = 4,096 pair
    # rows over 128 groups, 32 a group, laid out at 4,224
    # (experts 768 wide: 512 does not divide it, so that side tiles 256)
    "sdar_128_slots_blocks_of_4": ((512, 2048, 768, 128, 128, 8, False), 4224,
                                   "128,256,512|128,512,256"),
}


def _grouped_matmul_calls(hlo: str) -> tuple[set, set, set]:
    """(names, row counts, `ragged_dot_tiling`s) of a compiled program's
    `%ragged-dot-none` custom calls."""
    calls = re.findall(
        r"%(ragged-dot-none(?:\.\d+)?) = \w+\[(\d+),\d+\]\S* custom-call\(.*"
        r"ragged_dot_tiling\W+([\d,]+)", hlo)
    return tuple(set(c[i] for c in calls) for i in range(3))


@pytest.mark.parametrize("shape", [*_GROUPED_LAYERS, "olmoe_64_slots_two_stacked_layers"])
def test_grouped_matmuls_row_tile_is_pinned(one_chip, shape, request):
    """XLA:TPU tiles `jax.lax.ragged_dot`'s rows by the largest power of
    two, at most 512, that divides their count: that is the rule
    `grouped_matmul_rows` lays a decode step's few rows a group out for (512
    pair rows as 640: a 128-row tile where 512 rows would run a 512-row tile
    for 3-8 rows a group), and leaves a prefill bucket's rows at. Three
    `%ragged-dot-none` calls a layer in each. An XLA whose rule is another
    fails here, not in a ledger line."""
    from areal_tpu.models.qwen2 import grouped_matmul_rows, moe_mlp

    if shape == "olmoe_64_slots_two_stacked_layers":
        hlo, rows, tiling = request.getfixturevalue("stacked_olmoe_step"), 640, "128,512,512"
    else:
        (tokens, H, M, E, E_pub, K, sigmoid), rows, tiling = _GROUPED_LAYERS[shape]
        assert grouped_matmul_rows(tokens * K, E) == rows
        cfg = ModelConfig(
            hidden_size=H, num_experts=E, num_experts_published=E_pub, num_experts_per_tok=K,
            moe_intermediate_size=M, norm_topk_prob=sigmoid,
            moe_scoring="sigmoid" if sigmoid else "softmax")

        def layer_mlp(x, valid, router, gate, up, down):
            p = dict(router_kernel=router, gate_kernel=gate, up_kernel=up, down_kernel=down)
            return moe_mlp(p, x, cfg, valid=valid, with_load=True)

        bf = jnp.bfloat16
        hlo = _compile(
            layer_mlp, one_chip, ((tokens, H), bf), ((tokens,), jnp.bool_), ((H, E_pub), bf),
            ((E, H, M), bf), ((E, H, M), bf), ((E, M, H), bf))
    names, row_counts, tilings = _grouped_matmul_calls(hlo)
    assert len(names) == 3, names
    assert row_counts == {str(rows)} and tilings == set(tiling.split("|")), (row_counts, tilings)


def _flash_work_lists(hlo: str, rows: int, outer: dict[str, int]) -> None:
    """Each flash kernel of the program takes its work list as its first
    four operands, scalar-prefetch vectors whose shapes follow the row count
    and the number of outer blocks alone: `_schedule`'s `[rows * outer
    blocks]` (a block's run, its place in the walk and the next block that
    has a partner); the fifth is the ids of the outer side."""
    calls = [ln for ln in hlo.splitlines() if 'custom_call_target="tpu_custom_call"' in ln]
    assert {c.split(" = ")[0].split("%")[-1].split(".")[0] for c in calls} == set(outer), calls
    for call in calls:
        n = outer[call.split(" = ")[0].split("%")[-1].split(".")[0]]
        operands = call.split("operand_layout_constraints={")[1].split("}}")[0].split(", ")
        assert operands[:4] == [f"s32[{rows * n}]{{0}}"] * 4, operands[:5]
        assert operands[4].startswith(f"s32[{rows},1,"), operands[:5]


def _flash_operands_stay_as_given(hlo: str, T: int, hd: int) -> None:
    """The kernels multiply what they are given: of a compiled bf16 step's
    flash kernels every `[.., hd]` (or, blocked, 128-lane) operand is bf16,
    the only float32 operand the per-row vectors `[.., 4, block]`; and the
    program around them holds no float32 array of a `[T, heads, hd]`
    operand's shape but `%flash_dkv`'s own float32 results, which leave the
    kernel a query head each and are summed over the GQA group outside."""
    calls = [ln for ln in hlo.splitlines() if 'custom_call_target="tpu_custom_call"' in ln]
    assert calls
    for call in calls:
        operands = call.split("operand_layout_constraints={")[1].split("}}")[0].split(", ")
        floats = [o for o in operands if not o.startswith("s32[")]
        assert len(floats) >= 3, operands
        for o in floats:
            assert o.startswith("bf16[") or re.match(r"f32\[[\d,]+,4,\d+\]", o), (o, operands)
    entry = hlo.split("\nENTRY ")[1]
    wide = [ln for ln in entry.splitlines() if re.search(rf" = \(?f32\[[\d,]*{T},{hd}\]", ln)]
    kinds = {re.search(r"\}\)? ([\w-]+)\(", ln).group(1) for ln in wide}
    assert kinds <= {"custom-call", "get-tuple-element", "bitcast"}, wide
    assert all("flash_dkv" in ln for ln in wide if "custom-call(" in ln), wide


def test_flash_kernels_are_named_at_the_0p5b_head_shape(one_chip):
    """`flash_attention` at `train-0.5b-gsm8k`'s call, forward and both
    backward kernels under remat: the names the benchmark's readers match,
    and a grid of (1 row, 14 heads, 16 outer blocks) each, the inner axis a
    loop over the work list inside the kernel."""
    from areal_tpu.ops.flash_attention import flash_attention

    T, nH, nKV, hd = 8192, 14, 2, 64

    def loss(q, k, v, seg):
        return flash_attention(q, k, v, seg, interpret=False).astype(jnp.float32).sum()

    def step(q, k, v, seg):
        return jax.grad(jax.checkpoint(loss), argnums=(0, 1, 2))(q, k, v, seg)

    hlo = _compile(
        step, one_chip, ((T, nH, hd), jnp.bfloat16), ((T, nKV, hd), jnp.bfloat16),
        ((T, nKV, hd), jnp.bfloat16), ((T,), jnp.int32))
    for kernel in ("%flash_fwd", "%flash_dq", "%flash_dkv"):
        assert kernel in hlo, kernel
    # what the transformations around a kernel used to name it
    for wrapper in ("%checkpoint", "%rematted_computation", "%closed_call"):
        assert wrapper not in hlo, wrapper
    _flash_work_lists(hlo, 1, {"flash_fwd": 16, "flash_dq": 16, "flash_dkv": 16})
    _flash_operands_stay_as_given(hlo, T, hd)


@pytest.mark.parametrize("sets", [0, 1, 2], ids=["nothing", "attention", "attention_mlp"])
def test_grad_step_runs_one_flash_forward_once_the_attention_part_is_kept(
        one_chip, monkeypatch, sets):
    """The model's gradient at `train-0.5b-gsm8k`'s layer (896 wide, 14/2
    heads of 64, 8,192 tokens; two layers under the scan), checkpointed, with
    each of `hbm.REMAT_SETS` kept: full recompute holds `%flash_fwd` twice (the
    forward's and the backward's second run of it), the attention part kept
    holds it ONCE; the kernels' names are the ones the benchmark's readers
    match whatever is kept, and no wrapper names one."""
    from areal_tpu.models.qwen2 import forward
    from areal_tpu.ops import flash_attention as fa
    from areal_tpu.utils import hbm

    monkeypatch.setattr(fa, "_default_interpret", lambda: False)
    cfg = ModelConfig(
        vocab_size=512, hidden_size=896, intermediate_size=4864, num_hidden_layers=2,
        num_attention_heads=14, num_key_value_heads=2, dtype="bfloat16",
        param_dtype="bfloat16", remat=True, attn_impl="flash")
    T = 8192
    params = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
        jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0))))
    ints = jax.ShapeDtypeStruct((T,), jnp.int32, sharding=one_chip)

    def step(p, ids, pos, seg):
        return jax.grad(lambda p: forward(
            p, ids, pos, seg, cfg, remat_kept=hbm.REMAT_SETS[sets],
        ).astype(jnp.float32).sum())(p)

    hlo = jax.jit(step).trace(params, ints, ints, ints).lower(
        lowering_platforms=("tpu",)).compile().as_text()
    # (a wrapper that named a kernel, `%checkpoint.N` or
    # `%rematted_computation.N`, would show here in a kernel's place)
    kernels = sorted(k.split(".")[0] for k in _mosaic_kernels(hlo))
    want = ["flash_dkv", "flash_dq", "flash_fwd"] + ["flash_fwd"] * (sets == 0)
    assert kernels == sorted(want), kernels


@pytest.mark.parametrize("nH,nKV", [(12, 2), (16, 16)], ids=["1p5b", "olmoe"])
def test_flash_kernel_batches_under_vmap_at_the_rollout_head_shapes(one_chip, nH, nKV):
    """The decode engine's batched prefill `vmap`s the model forward, each
    row with its own segment ids, so each with its own work list. The work
    list is scalar-prefetch operands, which Pallas's batching rule would turn
    into a sequential loop over the rows: the kernels carry a row axis of
    their own and a batching rule (`_rows_under_vmap`) that folds the
    vmapped rows into it, so the batch is still ONE kernel over a longer
    grid and no loop."""
    from areal_tpu.ops.flash_attention import flash_attention

    B, T, hd = 16, 256, 128
    bf = jnp.bfloat16
    hlo = _compile(
        jax.vmap(lambda q, k, v, seg: flash_attention(q, k, v, seg, interpret=False)),
        one_chip, ((B, T, nH, hd), bf), ((B, T, nKV, hd), bf), ((B, T, nKV, hd), bf),
        ((B, T), jnp.int32))
    kernels = _mosaic_kernels(hlo)
    assert len(kernels) == 1 and "flash_fwd" in kernels[0], kernels
    assert " while(" not in hlo
    _flash_work_lists(hlo, B, {"flash_fwd": 1})
    _flash_operands_stay_as_given(hlo, T, hd)


def test_ring_step_kernels_are_named_at_the_fsdp4_shard_shape(one_chip):
    """`flash_attention_chunk`, forward and both backward kernels, at a ring
    shard of `train-1.5b-fsdp4`: 4,096 tokens, 12/2 heads of 128."""
    from areal_tpu.ops.flash_attention import flash_attention_chunk

    T, nH, nKV, hd = 4096, 12, 2, 128

    def loss(q, k, v, seg_q, seg_k, qpos, kpos):
        o, lse = flash_attention_chunk(q, k, v, seg_q, seg_k, qpos, kpos, interpret=False)
        return o.astype(jnp.float32).sum() + lse.sum()

    bf = jnp.bfloat16
    hlo = _compile(
        jax.grad(jax.checkpoint(loss), argnums=(0, 1, 2)), one_chip, ((T, nH, hd), bf),
        ((T, nKV, hd), bf), ((T, nKV, hd), bf), *[((T,), jnp.int32)] * 4)
    for kernel in ("%flash_fwd", "%flash_dq", "%flash_dkv"):
        assert kernel in hlo, kernel
    _flash_work_lists(hlo, 1, {"flash_fwd": 8, "flash_dq": 8, "flash_dkv": 8})
    _flash_operands_stay_as_given(hlo, T, hd)


# ---------------------------------------------------------------------------
# The paged kernel's group of live columns (`ops/paged_attention.group_pages`)
# at the rollout cells' shapes, compiled through Mosaic for the described v5e;
# the cells whose shapes give a group of one keep the parent's programs; the
# set-up makes the parent's programs and no other.
# ---------------------------------------------------------------------------

# cell: (slots, heads, kv heads, head size, table columns, queries a slot,
# int8 pool, kernel name, the group its shapes give)
_GROUP_CELLS = {
    "rollout-1.5b-gsm8k": (128, 12, 2, 128, 10, 1, False, "paged_attention", 8),
    "rollout-1.5b-gsm8k_256_token_bucket": (128, 12, 2, 128, 2, 1, False, "paged_attention", 2),
    "rollout-1.5b-gsm8k_int8_pool": (128, 12, 2, 128, 10, 1, True, "paged_attention", 8),
    "rollout-qwen3next-mixedlen": (64, 16, 2, 256, 64, 1, False, "paged_attention", 4),
    "rollout-kexaone-mixedlen_full": (64, 64, 8, 128, 64, 1, False, "paged_attention", 2),
    "rollout-kexaone-mixedlen_ring": (64, 64, 8, 128, 2, 1, False, "paged_attention_window", 2),
    "rollout-olmoe-gsm8k": (64, 16, 16, 128, 10, 1, False, "paged_attention", 1),
    "rollout-sdar-gsm8k": (128, 32, 4, 128, 10, 4, False, "paged_attention_block", 1),
}


@pytest.mark.parametrize("cell", list(_GROUP_CELLS))
def test_grouped_kernel_compiles_at_each_cells_shape(one_chip, cell):
    """The kernel with the group its shapes give: ONE Mosaic call over the
    slots with the work list as scalar-prefetch operands, no loop around it
    (the walk over a slot's groups is the kernel's), its two group buffers a
    pool within VMEM (Mosaic refuses what does not fit), and the pools read
    where they are: no operation of the program has a pool as its result."""
    from areal_tpu.ops.paged_attention import paged_attention_qlen, pool_group_pages

    R, nH, nKV, hd, nb, W, int8, name, pages = _GROUP_CELLS[cell]
    L, bsz, n_blocks = 2, 128, R * nb + 1
    pool = ((L, n_blocks, bsz, nKV * hd), jnp.int8 if int8 else jnp.bfloat16)
    scales = ((L, n_blocks, nKV, bsz), jnp.float32)
    assert pool_group_pages(jax.ShapeDtypeStruct(*pool), W, nb) == pages

    def step(q, bt, valid, li, *pools):
        kp, vp = (pools[:2], pools[2:]) if int8 else pools
        return paged_attention_qlen(q, kp, vp, bt, valid, li, impl="pallas",
                                    interpret=False, kernel_name=name)

    hlo = _compile(
        step, one_chip, ((R, W, nH, hd), jnp.bfloat16), ((R, nb), jnp.int32),
        ((R, W, nb * bsz), jnp.bool_), ((), jnp.int32),
        *([pool, scales] * 2 if int8 else [pool] * 2))
    assert _one_paged_kernel(hlo, R) == name or _one_paged_kernel(hlo, R).startswith(name + ".")
    dtype = "s8" if int8 else "bf16"
    made = [ln for ln in hlo.splitlines()
            if re.match(rf"\s*(ROOT )?%\S+ = {dtype}\[{L},{n_blocks},{bsz},{nKV * hd}\]", ln)
            and " parameter(" not in ln]
    assert not made, made


def setup_programs(monkeypatch=None) -> list[str]:
    """The names of the programs a decode engine with the kernel read makes
    in `initialize()` and `_prewarm_chunk_variants` over a request that
    grows through three chunk buckets, in the order they are made."""
    from areal_tpu.engine.jax_decode import JaxDecodeEngine

    spy = _JitSpy()
    mp = monkeypatch or pytest.MonkeyPatch()
    mp.setattr(jax, "jit", spy)
    try:
        eng = JaxDecodeEngine(
            JaxDecodeConfig(context_length=1024, max_running_requests=4,
                            new_tokens_per_chunk=64, page_size=128, dtype="float32",
                            kv_cache_dtype="float32", paged_attn_impl="pallas"),
            InferenceEngineConfig())
        eng.set_model(init_params(TINY, jax.random.PRNGKey(0)), TINY)
        eng.initialize()
        try:
            eng._prewarm_chunk_variants(100, 800, (1.0,))
            return spy.made + [f"chunk nb={nb}" for _, _, nb in sorted(eng._chunk_fns)]
        finally:
            eng.destroy()
    finally:
        if monkeypatch is None:
            mp.undo()


# recorded on the parent commit with `python tests/test_trace_names.py`
PARENT_SETUP_PROGRAMS = ["patch", "chunk", "chunk", "chunk",
                         "chunk nb=2", "chunk nb=4", "chunk nb=8"]


def test_setup_makes_the_parents_programs_and_no_other(cpu_devices, monkeypatch):
    """The group of live columns is chosen where the kernel is traced, from
    the call's shapes: no program is made to choose it, none is keyed by it,
    and the chunk programs are the parent's, a bucket each."""
    assert setup_programs(monkeypatch) == PARENT_SETUP_PROGRAMS


def _location_free(lowered) -> str:
    """A lowered program's text with each Mosaic kernel's serialised module
    (which carries the source lines of the kernel's Python) replaced by the
    module's own text without locations."""
    import base64

    from jax._src.interpreters import mlir as jax_mlir
    from jax._src.lib.mlir import ir

    def asm(match):
        raw = base64.b64decode(match.group(1) + "=" * (-len(match.group(1)) % 4))
        ctx = jax_mlir.make_ir_context()
        ctx.allow_unregistered_dialects = True
        with ctx:
            return "body: " + ir.Module.parse(raw).operation.get_asm(enable_debug_info=False)

    text, n = re.subn(r'body\\22: \\22([A-Za-z0-9+/=]+)\\22', asm, lowered.as_text())
    assert n, "no Mosaic kernel in the program"
    return text


def neighbour_steps(one_chip) -> dict:
    """{name: location-free lowered text} of the model steps whose paged
    kernel takes a group of one: OLMoE's decode step (a row of 2,048 lanes)
    and SDAR's block step (4 queries a slot), two stacked layers each at the
    cells' widths, the kernel lowered for the TPU."""
    from areal_tpu.models.qwen2 import decode_step_paged, diffusion_step_paged, param_shapes

    bf = jnp.bfloat16
    arg = lambda s, d: jax.ShapeDtypeStruct(s, d, sharding=one_chip)  # noqa: E731
    olmoe = ModelConfig(
        vocab_size=50304, hidden_size=2048, intermediate_size=1024, num_hidden_layers=2,
        num_attention_heads=16, num_key_value_heads=16, model_type="olmoe", qkv_bias=False,
        qk_norm=True, qk_norm_full=True, num_experts=64, num_experts_per_tok=8,
        moe_intermediate_size=1024, norm_topk_prob=False, tie_word_embeddings=False,
        dtype="bfloat16", param_dtype="bfloat16")
    sdar = ModelConfig(
        vocab_size=151936, hidden_size=2048, intermediate_size=6144, num_hidden_layers=2,
        num_attention_heads=32, num_key_value_heads=4, head_dim=128, model_type="sdar_moe",
        qkv_bias=False, qk_norm=True, num_experts=128, num_experts_per_tok=8,
        moe_intermediate_size=768, norm_topk_prob=True, rope_theta=1e6, block_length=4,
        mask_token_id=151669, dtype="bfloat16", param_dtype="bfloat16")
    out = {}
    for name, cfg, fn, R, W in (("olmoe.decode_step", olmoe, decode_step_paged, 64, 1),
                                ("sdar.diffusion_step", sdar, diffusion_step_paged, 128, 4)):
        nb, bsz = 10, 128
        params = jax.tree.map(lambda s: arg(s, bf), param_shapes(cfg),
                              is_leaf=lambda x: isinstance(x, tuple))
        pool = arg((2, R * nb + 1, bsz, cfg.num_key_value_heads * cfg.head_dim_), bf)

        def step(params, kp, vp, bt, tokens, positions, active, cfg=cfg, fn=fn):
            return fn(params, tokens, positions, kp, vp, bt, cfg, active=active,
                      attn_impl="pallas", moe_load=True)

        from areal_tpu.ops import paged_attention as pa

        real, pa._default_interpret = pa._default_interpret, lambda: False
        try:
            out[name] = _location_free(jax.jit(step, donate_argnums=(1, 2)).trace(
                params, pool, pool, arg((R, nb), jnp.int32),
                arg((R,) if W == 1 else (R, W), jnp.int32), arg((R,), jnp.int32),
                arg((R,), jnp.bool_)).lower(lowering_platforms=("tpu",)))
        finally:
            pa._default_interpret = real
    return out


def _sha(text: str) -> str:
    import hashlib

    return hashlib.sha256(text.encode()).hexdigest()[:16]


# recorded on the parent commit (PR 38's tree) with `python tests/test_trace_names.py`
PARENT_SHA256 = {
    "olmoe.decode_step": "31294f55415561e8",
    "sdar.diffusion_step": "426539b5a586ca3c",
}


@pytest.fixture(scope="module")
def neighbours(one_chip):
    return neighbour_steps(one_chip)


@pytest.mark.parametrize("name", sorted(PARENT_SHA256))
def test_a_group_of_one_keeps_the_parents_program(neighbours, name):
    """At one page a group the kernel is the parent's, operation for
    operation, and so is the work list around it: the lowered step, Mosaic
    module included (without its source locations), hashes as the parent's."""
    assert _sha(neighbours[name]) == PARENT_SHA256[name], (
        f"{name}: the lowered step of a model whose paged kernel takes one page a group "
        "changed; if the change is meant, record `python tests/test_trace_names.py` anew")


if __name__ == "__main__":
    import os

    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    chip = SingleDeviceSharding(
        topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2").devices[0])
    for k, v in sorted(neighbour_steps(chip).items()):
        print(f'    "{k}": "{_sha(v)}",')
    print("PARENT_SETUP_PROGRAMS =", setup_programs())
