"""Disaggregated prefill/decode: KV-block streaming between replica roles
and cross-replica session migration (ISSUE 10).

Coverage layers:

1. Engine contracts: prefill-only admission parks exactly the prompt's KV
   (the `HostKVEntry` resume shape); `export_session` / `import_session`
   move a session between engines BIT-IDENTICALLY — a decode engine that
   imported a migrated session continues the stream with zero transformer
   prefill and emits the same tokens AND logprobs (greedy and sampled)
   as a never-migrated oracle.
2. Staleness: an import whose KV was computed under a different weight
   version is rejected as an honest miss (tombstoned), and the resume
   re-prefills under the current weights — the cross-replica extension of
   the install-flush rule.
3. Server wire: `/prefill` with a target streams the session server→
   server over the framed KV wire (interval-merged staging); `/kv_commit`
   is idempotent per xid (a replayed migration lands exactly once); a
   torn frame is rejected before staging and the re-sent frame recovers;
   `/drain` migrates every parked session to a survivor that resumes all
   of them with zero re-prefill.
4. Router: a fleet with prefill-role replicas schedules (decode by
   kv-pool headroom, prefill by prefix affinity) and ships both URLs.
"""

import asyncio
import threading
import time

import numpy as np
import pytest

import jax

from areal_tpu.api.cli_args import (
    GenerationHyperparameters,
    InferenceEngineConfig,
    JaxDecodeConfig,
    RouterConfig,
)
from areal_tpu.api.io_struct import ModelRequest
from areal_tpu.core.weight_transfer import (
    WeightStaging,
    pack_kv_session,
    unpack_kv_sessions,
)
from areal_tpu.engine.jax_decode import JaxDecodeEngine
from areal_tpu.launcher.decode_server import DecodeServer
from areal_tpu.launcher.router import DecodeRouter
from areal_tpu.models.qwen2 import ModelConfig, init_params
from areal_tpu.utils.http import arequest_with_retry, close_current_session

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


def _engine(*, role="unified", host_mb=0.0, R=3, context=256, page=8,
            chunk=4, seed=1):
    cfg = JaxDecodeConfig(
        context_length=context,
        max_running_requests=R,
        new_tokens_per_chunk=chunk,
        page_size=page,
        paged_attn_impl="xla",
        kv_host_pool_mb=host_mb,
        role=role,
        kv_migrate_chunk_mb=0.01,  # several frames per session on TINY
        dtype="float32",
        kv_cache_dtype="float32",
        random_seed=seed,
    )
    eng = JaxDecodeEngine(cfg, InferenceEngineConfig())
    eng.set_model(_params(), TINY)
    eng.initialize()
    return eng


def _run_async(coro, timeout=120):
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


_GREEDY = GenerationHyperparameters(max_new_tokens=10, greedy=True)
_SAMPLED = GenerationHyperparameters(
    max_new_tokens=10, temperature=0.8, top_p=0.9
)


def _prompt(n=40, seed=3):
    return np.random.RandomState(seed).randint(1, 64, (n,)).tolist()


# -- 1. engine contracts -----------------------------------------------


def test_prefill_only_parks_exact_coverage_and_resumes_locally():
    eng = _engine()
    try:
        prompt = _prompt()
        r = _prefill(eng, ModelRequest(rid="a", input_ids=prompt,
                                       gconfig=_GREEDY))
        assert r.stop_reason == "prefill"
        assert r.output_tokens == [] and r.output_logprobs == []
        assert eng.list_exportable_sessions() == ["a"]
        # the parked session IS the interrupt shape: a local /generate
        # with the same rid + prompt resumes with zero prefill work
        m0 = eng.get_metrics()
        full = eng.generate(
            ModelRequest(rid="a", input_ids=prompt, gconfig=_GREEDY),
            timeout=120,
        )
        m1 = eng.get_metrics()
        assert len(full.output_tokens) == 10
        assert m1["prefills_total"] == m0["prefills_total"]
        # consumed: the parked entry was an exact match, nothing exportable
        assert eng.list_exportable_sessions() == []
        # oracle: a fresh engine generating directly emits the same stream
        oracle = _engine()
        try:
            ro = oracle.generate(
                ModelRequest(rid="a", input_ids=prompt, gconfig=_GREEDY),
                timeout=120,
            )
        finally:
            oracle.destroy()
        assert full.output_tokens == ro.output_tokens
        assert full.output_logprobs == ro.output_logprobs
    finally:
        eng.destroy()


@pytest.mark.parametrize("gname", ["greedy", "sampled"])
def test_export_import_stream_bit_identity(gname):
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
        m = pre.get_metrics()
        assert m["kv_migrated_out_sessions_total"] == 1
        assert m["kv_migrated_out_bytes_total"] > 0
        # exported sessions leave the exportable set (the move semantics)
        assert pre.list_exportable_sessions() == []
    finally:
        pre.destroy()
    assert sess["meta"]["covered"] == len(prompt) - 1
    assert sess["meta"]["tokens"] == prompt[:-1]

    # wire round-trip through the framed-bucket staging (multiple frames)
    frames = list(
        pack_kv_session(sess["meta"], sess["k"], sess["v"], chunk_mb=0.01)
    )
    assert len(frames) > 1
    st = WeightStaging()
    for f in frames:
        st.add_bucket(f)
    sessions = unpack_kv_sessions(st.finalize())
    assert len(sessions) == 1
    meta, k, v, scales = sessions[0]
    assert scales is None  # fp session: no scale blocks on the wire
    assert np.array_equal(np.asarray(k), sess["k"])
    assert np.array_equal(np.asarray(v), sess["v"])

    dec = _engine(role="decode")
    try:
        assert dec.import_session(meta, k, v) == "ok"
        m0 = dec.get_metrics()
        rd = dec.generate(
            ModelRequest(rid="m", input_ids=prompt, gconfig=g), timeout=120
        )
        m1 = dec.get_metrics()
        # zero transformer prefill: the resume is a host-tier promotion
        assert m1["prefills_total"] == m0["prefills_total"]
        assert m1["kv_host_hits_total"] - m0["kv_host_hits_total"] == 1
        assert (
            m1["reprefill_tokens_avoided_total"]
            - m0["reprefill_tokens_avoided_total"]
            == len(prompt) - 1
        )
        assert m1["kv_migrated_in_sessions_total"] == 1
        # the migrated stream is bit-identical to the never-migrated one
        assert rd.output_tokens == ro.output_tokens
        assert rd.output_logprobs == ro.output_logprobs
    finally:
        dec.destroy()


def test_import_version_mismatch_is_honest_miss():
    prompt = _prompt(36, seed=9)
    pre = _engine(role="prefill")
    try:
        _prefill(pre, ModelRequest(rid="v", input_ids=prompt,
                                   gconfig=_GREEDY))
        sess = pre.export_session("v")
    finally:
        pre.destroy()
    dec = _engine(role="decode")
    try:
        dec.set_version(7)  # a weight commit raced the migration
        assert dec.import_session(sess["meta"], sess["k"], sess["v"]) == (
            "stale_version"
        )
        m0 = dec.get_metrics()
        assert m0["kv_migrate_version_rejects_total"] == 1
        assert m0["kv_migrated_in_sessions_total"] == 0
        # the resume pays an honest re-prefill under the current weights
        # (same params here, so the stream itself still matches a fresh
        # generation) and the lookup counts a host-tier MISS
        rd = dec.generate(
            ModelRequest(rid="v", input_ids=prompt, gconfig=_GREEDY),
            timeout=120,
        )
        m1 = dec.get_metrics()
        assert m1["prefills_total"] - m0["prefills_total"] == 1
        assert m1["kv_host_misses_total"] - m0["kv_host_misses_total"] == 1
        assert len(rd.output_tokens) == 10
    finally:
        dec.destroy()


def test_import_rejects_malformed_sessions():
    prompt = _prompt(30, seed=11)
    pre = _engine(role="prefill")
    try:
        _prefill(pre, ModelRequest(rid="x", input_ids=prompt,
                                   gconfig=_GREEDY))
        sess = pre.export_session("x")
    finally:
        pre.destroy()
    dec = _engine(role="decode")
    try:
        # wrong block geometry
        bad_k = np.zeros((1, 1, 2, 1, 2), np.float32)
        assert dec.import_session(sess["meta"], bad_k, bad_k) == "rejected"
        # coverage/token mismatch
        meta = dict(sess["meta"], covered=sess["meta"]["covered"] + 1)
        assert dec.import_session(meta, sess["k"], sess["v"]) == "rejected"
        assert dec.get_metrics()["kv_migrated_in_sessions_total"] == 0
        # unknown rid exports nothing
        assert dec.export_session("nope") is None
    finally:
        dec.destroy()


def test_export_from_host_tier_after_eviction():
    """A session that was already offloaded to the host tier (pool
    pressure) exports from there — drain covers host-resident sessions,
    not just parked ones."""
    prompt = _prompt(40, seed=13)
    eng = _engine(host_mb=16.0)
    try:
        _prefill(eng, ModelRequest(rid="h", input_ids=prompt,
                                   gconfig=_GREEDY))
        # force the parked slot into the host tier
        eng.pause_generation()
        with eng._sched_lock:
            assert eng._evict_parked_lru() is not None
        eng.continue_generation()
        assert eng.get_metrics()["kv_host_pool_entries"] == 1
        assert eng.list_exportable_sessions() == ["h"]
        sess = eng.export_session("h")
        assert sess is not None
        assert sess["meta"]["covered"] == len(prompt) - 1
        assert eng.list_exportable_sessions() == []
    finally:
        eng.destroy()
    dec = _engine(role="decode")
    try:
        assert dec.import_session(sess["meta"], sess["k"], sess["v"]) == "ok"
        m0 = dec.get_metrics()
        rd = dec.generate(
            ModelRequest(rid="h", input_ids=prompt, gconfig=_GREEDY),
            timeout=120,
        )
        assert dec.get_metrics()["prefills_total"] == m0["prefills_total"]
        assert len(rd.output_tokens) == 10
    finally:
        dec.destroy()


# -- 3. server wire ----------------------------------------------------


async def _start_server(engine, dcfg):
    srv = DecodeServer(dcfg, engine=engine, shutdown_grace=0.2)
    addr = await srv.start(host="127.0.0.1", port=0)
    return srv, addr


def test_prefill_handoff_http_and_kv_commit_idempotency():
    """/prefill with a target streams the session to the decode server;
    the decode server's /generate resumes it with zero prefill; a
    replayed /kv_commit (same xid) dedups instead of double-importing."""
    prompt = _prompt(40, seed=17)
    oracle = _engine()
    try:
        ro = oracle.generate(
            ModelRequest(rid="hh", input_ids=prompt, gconfig=_GREEDY),
            timeout=120,
        )
    finally:
        oracle.destroy()
    pre = _engine(role="prefill")
    dec = _engine(role="decode")

    async def scenario():
        ps, pa = await _start_server(pre, pre.config)
        ds, da = await _start_server(dec, dec.config)
        try:
            out = await arequest_with_retry(
                pa, "/prefill",
                payload=dict(
                    rid="hh",
                    input_ids=prompt,
                    gconfig=dict(max_new_tokens=10, greedy=True),
                    target=da,
                    xid="handoff-1",
                ),
                max_retries=1, timeout=120,
            )
            assert out["stop_reason"] == "prefill"
            assert out["migrated"] is True and out["kv_bytes"] > 0
            # idempotent /prefill replay (lost response): cached result
            out2 = await arequest_with_retry(
                pa, "/prefill",
                payload=dict(
                    rid="hh",
                    input_ids=prompt,
                    gconfig=dict(max_new_tokens=10, greedy=True),
                    target=da,
                    xid="handoff-1",
                ),
                max_retries=1, timeout=120,
            )
            assert out2.get("dedup") == "completed"
            m0 = dec.get_metrics()
            gen = await arequest_with_retry(
                da, "/generate",
                payload=dict(
                    rid="hh",
                    input_ids=prompt,
                    gconfig=dict(max_new_tokens=10, greedy=True),
                ),
                max_retries=1, timeout=120,
            )
            m1 = dec.get_metrics()
            assert gen["output_tokens"] == ro.output_tokens
            assert gen["output_logprobs"] == ro.output_logprobs
            assert m1["prefills_total"] == m0["prefills_total"]
            # exactly one inbound commit landed on the decode server
            srv_m = await arequest_with_retry(
                da, "/metrics", method="GET", max_retries=1, timeout=30
            )
            assert srv_m["kv_migrate"]["in_commits"] == 1
            assert m1["kv_migrated_in_sessions_total"] == 1
        finally:
            await ps.stop()
            await ds.stop()
            await close_current_session()

    try:
        _run_async(scenario())
    finally:
        pre.destroy()
        dec.destroy()


def test_kv_recv_torn_frame_rejected_then_retry_lands_exactly_once():
    """A torn KV frame is a 4xx/5xx BEFORE anything stages; re-sending
    the full frame set (the sender's replay) plus a duplicate commit
    imports the session exactly once."""
    prompt = _prompt(38, seed=19)
    pre = _engine(role="prefill")
    try:
        _prefill(pre, ModelRequest(rid="t", input_ids=prompt,
                                   gconfig=_GREEDY))
        sess = pre.export_session("t")
    finally:
        pre.destroy()
    frames = list(
        pack_kv_session(sess["meta"], sess["k"], sess["v"], chunk_mb=0.01)
    )
    assert len(frames) >= 2
    dec = _engine(role="decode")

    async def scenario():
        ds, da = await _start_server(dec, dec.config)
        try:
            # frame 0 torn in flight: rejected, nothing staged
            with pytest.raises(Exception):
                await arequest_with_retry(
                    da, "/kv_recv?xid=mig1", data=frames[0][: len(frames[0]) // 2],
                    max_retries=1, timeout=30,
                )
            # premature commit: staging incomplete -> 400, staging KEPT
            with pytest.raises(Exception):
                await arequest_with_retry(
                    da, "/kv_commit", payload=dict(xid="mig1"),
                    max_retries=1, timeout=30,
                )
            # full replay (duplicates of any previously-staged bytes are
            # interval-merged) then commit
            for f in frames:
                await arequest_with_retry(
                    da, f"/kv_recv?xid=mig1", data=f, max_retries=1,
                    timeout=30,
                )
            out = await arequest_with_retry(
                da, "/kv_commit", payload=dict(xid="mig1"), max_retries=1,
                timeout=30,
            )
            assert out["imported"] == 1 and out["rids"] == ["t"]
            # replayed commit (lost response): dedup, no second import
            out2 = await arequest_with_retry(
                da, "/kv_commit", payload=dict(xid="mig1"), max_retries=1,
                timeout=30,
            )
            assert out2.get("dedup") is True
            assert dec.get_metrics()["kv_migrated_in_sessions_total"] == 1
        finally:
            await ds.stop()
            await close_current_session()

    try:
        _run_async(scenario())
    finally:
        dec.destroy()


def test_migrate_replay_budget_survives_two_composed_failures():
    """A sender abort and a torn frame are INDEPENDENT failures: when
    both compose on ONE migration (the abort on attempt 0, the tear on
    the replay), the two-replay budget still lands the handoff exactly
    once instead of abandoning the session to a re-prefill."""
    from areal_tpu.core import fault_injection
    from areal_tpu.core.fault_injection import FaultPlan, FaultPoint

    prompt = _prompt(36, seed=29)
    pre = _engine(role="prefill")
    pre.config.kv_migrate_chunk_mb = 0.01  # several frames per session
    dec = _engine(role="decode")

    async def scenario():
        ps, pa = await _start_server(pre, pre.config)
        ds, da = await _start_server(dec, dec.config)
        fault_injection.configure(FaultPlan(
            seed=7,
            points=[
                # attempt 0 dies before its first frame ...
                FaultPoint(site="kv.migrate.send", mode="abort",
                           at=(0,), times=1),
                # ... and attempt 1 (the replay) loses a frame to TWO
                # consecutive tears — enough to defeat the per-frame
                # HTTP retry, so only the outer replay budget saves it
                FaultPoint(site="kv.migrate.recv", mode="torn",
                           at=(1, 2), times=2),
            ],
        ))
        try:
            out = await arequest_with_retry(
                pa, "/prefill",
                payload=dict(
                    rid="rb",
                    input_ids=prompt,
                    gconfig=dict(max_new_tokens=8, greedy=True),
                    target=da,
                    xid="budget-1",
                ),
                max_retries=1, timeout=120,
            )
            # attempt 2 replays the full stream clean: the handoff landed
            assert out["migrated"] is True and out["kv_bytes"] > 0
            fired = fault_injection.snapshot()
            assert any(k.startswith("kv.migrate.send") for k in fired)
            assert any(k.startswith("kv.migrate.recv") for k in fired)
            srv_m = await arequest_with_retry(
                da, "/metrics", method="GET", max_retries=1, timeout=30
            )
            assert srv_m["kv_migrate"]["in_commits"] == 1
            assert dec.get_metrics()["kv_migrated_in_sessions_total"] == 1
        finally:
            fault_injection.deactivate()
            await ps.stop()
            await ds.stop()
            await close_current_session()

    try:
        _run_async(scenario())
    finally:
        pre.destroy()
        dec.destroy()


def test_drain_migrates_parked_sessions_zero_reprefill():
    """/drain parks in-flight generations and streams every session to
    the survivor; all resumes are host-tier promotions (zero prefills)
    and partial+resumed streams match the never-interrupted oracle."""
    prompts = [_prompt(40, seed=23 + i) for i in range(2)]
    # long enough (40 chunks at chunk=4) that the drain reliably lands
    # mid-stream even when a loaded host delays the /drain round-trip —
    # at 48 tokens the streams could finish first and the parts came
    # back "length", a pre-existing flake
    _BUDGET = 160
    g = GenerationHyperparameters(max_new_tokens=_BUDGET, greedy=True)
    oracle = _engine(seed=5)
    try:
        oracles = [
            oracle.generate(
                ModelRequest(rid=f"s{i}", input_ids=prompts[i], gconfig=g),
                timeout=120,
            ).output_tokens
            for i in range(2)
        ]
    finally:
        oracle.destroy()
    a = _engine(seed=5, host_mb=16.0)
    b = _engine(seed=5)

    async def scenario():
        sa, aa = await _start_server(a, a.config)
        sb, ba = await _start_server(b, b.config)
        try:
            loop = asyncio.get_running_loop()

            async def gen(addr, i, ids, budget):
                return await arequest_with_retry(
                    addr, "/generate",
                    payload=dict(
                        rid=f"s{i}",
                        input_ids=ids,
                        gconfig=dict(max_new_tokens=budget, greedy=True),
                    ),
                    max_retries=1, timeout=120,
                )

            tasks = []
            for i in range(2):
                tasks.append(
                    loop.create_task(gen(aa, i, prompts[i], _BUDGET))
                )
                await asyncio.sleep(0.05)  # admission order == oracle's
            # wait until both are mid-stream, then drain
            deadline = time.monotonic() + 60
            while time.monotonic() < deadline:
                m = a.get_metrics()
                if (
                    m["running_requests"] >= 2
                    and m["generated_tokens_total"] >= 2
                ):
                    break
                await asyncio.sleep(0.01)
            drain = await arequest_with_retry(
                aa, "/drain", payload=dict(targets=[ba]), max_retries=1,
                timeout=120,
            )
            parts = [await t for t in tasks]
            assert all(p["stop_reason"] == "interrupt" for p in parts)
            assert drain["drained"] == 2 and drain["failed"] == 0
            m0 = b.get_metrics()
            full = []
            for i, p in enumerate(parts):
                part_toks = [int(t) for t in p["output_tokens"]]
                out = await gen(
                    ba, i, prompts[i] + part_toks, _BUDGET - len(part_toks)
                )
                full.append(part_toks + [int(t) for t in out["output_tokens"]])
            m1 = b.get_metrics()
            assert m1["prefills_total"] == m0["prefills_total"]
            assert m1["kv_host_hits_total"] - m0["kv_host_hits_total"] == 2
            assert full == oracles
        finally:
            await sa.stop()
            await sb.stop()
            await close_current_session()

    try:
        _run_async(scenario(), timeout=240)
    finally:
        a.destroy()
        b.destroy()


# -- 4. router role-awareness ------------------------------------------


def _mk_router(servers, roles, pressure):
    r = DecodeRouter(servers=servers, config=RouterConfig())
    r.servers = list(servers)
    r._roles = dict(roles)
    r._pressure = {s: dict(p) for s, p in pressure.items()}
    r._versions = {s: 0 for s in servers}
    return r


def test_router_disagg_pick_decode_by_headroom_prefill_by_affinity():
    servers = ["p1:1", "p2:1", "d1:1", "d2:1"]
    roles = {"p1:1": "prefill", "p2:1": "prefill",
             "d1:1": "decode", "d2:1": "decode"}
    # d1 nearly full, d2 mostly free: decode must land on d2
    pressure = {
        "d1:1": dict(kv_blocks_total=100, kv_block_size=8,
                     kv_tokens_allocated=760, kv_host_pool_enabled=True),
        "d2:1": dict(kv_blocks_total=100, kv_block_size=8,
                     kv_tokens_allocated=80, kv_host_pool_enabled=True),
        "p1:1": dict(kv_blocks_total=100, kv_block_size=8,
                     kv_tokens_allocated=0, kv_host_pool_enabled=False),
        "p2:1": dict(kv_blocks_total=100, kv_block_size=8,
                     kv_tokens_allocated=0, kv_host_pool_enabled=False),
    }
    r = _mk_router(servers, roles, pressure)
    req = dict(qid="q1", prompt_len=128, new_token_budget=64,
               input_prefix=list(range(128)))
    out = r._try_schedule_locked(req)
    assert out is not None
    assert out["url"] == "d2:1"
    assert out["prefill_url"] in ("p1:1", "p2:1")
    first_prefill = out["prefill_url"]
    assert r._counters["disagg_schedules_total"] == 1
    # decode accounting was charged the DECODE share only (the prompt is
    # discounted on handed-off requests — its KV arrives over the wire)
    assert r._qid_cost["q1"] == pytest.approx(0.4 * 64)
    # same prefix again: prefill affinity sticks to the same replica
    out2 = r._try_schedule_locked(
        dict(qid="q2", prompt_len=128, new_token_budget=64,
             input_prefix=list(range(128)))
    )
    assert out2["prefill_url"] == first_prefill
    # a resume keeps its decode home and skips the handoff
    out3 = r._try_schedule_locked(
        dict(qid="q1", prompt_len=128, new_token_budget=64,
             input_prefix=list(range(128)))
    )
    assert out3["url"] == out["url"]
    assert "prefill_url" not in out3


def test_router_unified_fleet_unchanged_without_prefill_roles():
    servers = ["a:1", "b:1"]
    r = _mk_router(servers, {"a:1": "unified", "b:1": "unified"}, {})
    out = r._try_schedule_locked(
        dict(qid="q", prompt_len=32, new_token_budget=16)
    )
    assert out is not None and "prefill_url" not in out


def test_router_disagg_degrades_when_prefill_replicas_saturated():
    """Every prefill replica inadmissible -> decode URL only (the decode
    replica prefills itself); no handoff, no crash."""
    servers = ["p1:1", "d1:1"]
    roles = {"p1:1": "prefill", "d1:1": "decode"}
    pressure = {
        # prefill replica: zero headroom
        "p1:1": dict(kv_blocks_total=10, kv_block_size=8,
                     kv_tokens_allocated=80, kv_host_pool_enabled=False),
        "d1:1": dict(kv_blocks_total=100, kv_block_size=8,
                     kv_tokens_allocated=0, kv_host_pool_enabled=True),
    }
    r = _mk_router(servers, roles, pressure)
    out = r._try_schedule_locked(
        dict(qid="q", prompt_len=128, new_token_budget=64)
    )
    assert out is not None and out["url"] == "d1:1"
    assert "prefill_url" not in out
