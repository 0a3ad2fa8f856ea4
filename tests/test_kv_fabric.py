"""Fleet-global KV fabric: content-addressed prefix blocks (ISSUE 17).

Coverage layers:

1. Content-key contracts (pure kv_fabric): block-boundary chaining and
   position binding, weight-version / kv-dtype salt distinctness,
   digest round-trip + caps + malformed input, longest-run semantics.
2. Router: the prefix-affinity map hashes with the SAME chained content
   keys (salted — a weight flip retires stale affinity), and the
   scheduler attaches a remote-fetch hint when a sibling advertises a
   longer resident run than the chosen replica.
3. Engine intra-replica dedup: a request whose prompt shares a
   block-aligned head with a DIVERGING resident run forks from it (the
   tuple-prefix donor path cannot see it) — bit-identical to a fresh
   full-prefill oracle, attributed to the fabric counters, never to the
   rid-exact host counters.
4. Fleet fetch over the wire: /kv_fetch streams content-keyed block runs
   between live servers; the receiving engine promotes them (remote
   attribution) and continues the stream bit-identically; /warm_start
   pulls a cold replica's first blocks from its peers.
5. Staleness: a fetched run computed under another weight version is
   rejected as an honest miss — zero stale-block serves.
6. Cheap drain: export_session with a refetchable key set ships a
   meta-only identity frame (no KV bytes); the importer never promotes
   it as if it held blocks.
"""

import asyncio
import threading

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
from areal_tpu.core import kv_fabric
from areal_tpu.core.weight_transfer import (
    WeightStaging,
    pack_kv_session,
    unpack_kv_sessions,
)
from areal_tpu.engine.jax_decode import JaxDecodeEngine
from areal_tpu.launcher.decode_server import DecodeServer
from areal_tpu.launcher.router import DecodeRouter
from areal_tpu.models.qwen2 import ModelConfig, init_params
from areal_tpu.utils.http import arequest_with_retry

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
            chunk=4, seed=1, fabric=True):
    cfg = JaxDecodeConfig(
        context_length=context,
        max_running_requests=R,
        new_tokens_per_chunk=chunk,
        page_size=page,
        paged_attn_impl="xla",
        kv_host_pool_mb=host_mb,
        role=role,
        kv_migrate_chunk_mb=0.01,
        kv_fabric=fabric,
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


def _prompt(n, seed=3):
    return np.random.RandomState(seed).randint(1, 64, (n,)).tolist()


def _chain_of(eng, tokens):
    """The engine's own content chain for `tokens` (its pool block size,
    current weight version, configured kv dtype)."""
    return kv_fabric.chain_keys(
        tokens,
        eng._alloc.block_size,
        int(eng._version),
        str(eng.config.kv_dtype),
    )


# -- 1. content-key contracts -------------------------------------------


def test_chain_keys_block_boundaries_and_position_binding():
    toks = list(range(100, 230))  # 130 tokens
    keys = kv_fabric.chain_keys(toks, 64, 0, "fp")
    # only COMPLETE blocks are keyed: 130 // 64 = 2, the 2-token tail not
    assert len(keys) == 2
    # a flip in block 0 changes EVERY downstream key (chaining)
    toks2 = list(toks)
    toks2[3] += 1
    keys2 = kv_fabric.chain_keys(toks2, 64, 0, "fp")
    assert keys2[0] != keys[0] and keys2[1] != keys[1]
    # a flip in block 1 leaves block 0's key intact (position binding:
    # key equality at i means the whole prefix through i matches)
    toks3 = list(toks)
    toks3[70] += 1
    keys3 = kv_fabric.chain_keys(toks3, 64, 0, "fp")
    assert keys3[0] == keys[0] and keys3[1] != keys[1]
    # a flip in the unkeyed tail changes nothing
    toks4 = list(toks)
    toks4[129] += 1
    assert kv_fabric.chain_keys(toks4, 64, 0, "fp") == keys
    # deterministic across calls (blake2b, not process-salted hash())
    assert kv_fabric.chain_keys(toks, 64, 0, "fp") == keys
    # max_blocks caps the chain without changing the kept keys
    assert kv_fabric.chain_keys(toks, 64, 0, "fp", max_blocks=1) == keys[:1]


def test_chain_keys_salted_by_weight_version_and_kv_dtype():
    toks = _prompt(128, seed=21)
    base = kv_fabric.chain_keys(toks, 64, 3, "fp")
    flipped = kv_fabric.chain_keys(toks, 64, 4, "fp")
    int8 = kv_fabric.chain_keys(toks, 64, 3, "int8")
    # a weight flip or a dtype change retires EVERY key: stale blocks can
    # never be mistaken for current ones (the staleness contract)
    assert not set(base) & set(flipped)
    assert not set(base) & set(int8)
    assert not set(flipped) & set(int8)


def test_digest_round_trip_cap_and_malformed():
    keys = kv_fabric.chain_keys(_prompt(640, seed=22), 64, 0, "fp")
    assert len(keys) == 10
    digest = kv_fabric.encode_digest(keys)
    assert kv_fabric.decode_digest(digest) == keys
    # cap truncates, hard cap bounds any caller value
    assert kv_fabric.decode_digest(
        kv_fabric.encode_digest(keys, cap=4)
    ) == keys[:4]
    assert (
        len(
            kv_fabric.decode_digest(
                kv_fabric.encode_digest(
                    range(kv_fabric.DIGEST_HARD_CAP + 100), cap=10**9
                )
            )
        )
        == kv_fabric.DIGEST_HARD_CAP
    )
    # malformed inputs decode to the empty set, never raise
    assert kv_fabric.decode_digest("") == []
    assert kv_fabric.decode_digest("!!!not-base64!!!") == []
    assert kv_fabric.decode_digest("AAA=") == []  # not a multiple of 8
    assert kv_fabric.decode_digest(None) == []
    assert kv_fabric.encode_digest([]) == ""


def test_longest_run():
    chain = [11, 22, 33, 44]
    assert kv_fabric.longest_run(chain, {11, 22, 33, 44}) == 4
    # chaining lets membership of key n-1 stand for the whole prefix
    assert kv_fabric.longest_run(chain, {33}) == 3
    assert kv_fabric.longest_run(chain, {99}) == 0
    assert kv_fabric.longest_run([], {11}) == 0


# -- 2. router ----------------------------------------------------------


def test_router_prefix_hashes_use_salted_content_keys():
    r = DecodeRouter(servers=["s1"], config=RouterConfig())
    r._versions = {"s1": 0}
    prefix = _prompt(256, seed=23)
    req = {"input_prefix": prefix, "prompt_len": len(prefix)}
    block = max(1, r.config.prefix_block_tokens)
    nb = min(len(prefix) // block, r.config.prefix_max_blocks)
    want = kv_fabric.chain_keys(
        prefix, block, 0, r._fleet_kv_dtype(), max_blocks=nb
    )
    assert r._prefix_hashes(req) == list(reversed(want))
    # the weight-version salt: a fleet-wide flip retires every affinity
    # entry instead of steering new-version requests at stale KV
    r._versions = {"s1": 1}
    h1 = r._prefix_hashes(req)
    assert h1 != list(reversed(want))
    assert not set(h1) & set(want)


def test_router_attaches_remote_fetch_hint_and_prices_it():
    cfg = RouterConfig(schedule_policy="prefix_affinity")
    r = DecodeRouter(servers=["s1", "s2"], config=cfg)
    r._versions = {"s1": 0, "s2": 0}
    prefix = _prompt(256, seed=24)
    block = max(1, cfg.prefix_block_tokens)
    nb = min(len(prefix) // block, cfg.prefix_max_blocks)
    chain = kv_fabric.chain_keys(
        prefix, block, 0, r._fleet_kv_dtype(), max_blocks=nb
    )
    # s2 advertises the whole run resident but is far too hot to route to
    r._fabric_index = {"s2": set(chain)}
    r._measured_tokens["s2"] = 1e9
    req = {
        "qid": "q1",
        "input_prefix": prefix,
        "prompt_len": len(prefix),
        "new_token_budget": 10,
        "group_size": 1,
    }
    out = r._try_schedule_locked(req)
    assert out is not None and out["url"] == "s1"
    hint = out.get("kv_fabric")
    assert hint is not None and hint["peer"] == "s2"
    assert kv_fabric.decode_digest(hint["keys"]) == chain
    assert r._counters["fabric_remote_hints_total"] == 1
    # marginal-cost pricing: the fetched run discounts the charged cost
    # by (1 - fetch_cost_factor) of the covered tokens
    factor = cfg.kv_fabric_fetch_cost_factor
    expected = max(
        r._request_cost(req) - nb * block * (1.0 - factor), 0.0
    )
    assert r._token_usage["s1"] == pytest.approx(expected)


def test_router_routes_to_local_fabric_holder_without_affinity_entry():
    cfg = RouterConfig(schedule_policy="prefix_affinity")
    r = DecodeRouter(servers=["s1", "s2"], config=cfg)
    r._versions = {"s1": 0, "s2": 0}
    prefix = _prompt(256, seed=25)
    block = max(1, cfg.prefix_block_tokens)
    nb = min(len(prefix) // block, cfg.prefix_max_blocks)
    chain = kv_fabric.chain_keys(
        prefix, block, 0, r._fleet_kv_dtype(), max_blocks=nb
    )
    # no _prefix_map entry — but s2 advertises the blocks (content-dedup
    # or an earlier fetch); the scheduler routes there, no wire transfer
    r._fabric_index = {"s2": set(chain)}
    req = {
        "qid": "q2",
        "input_prefix": prefix,
        "prompt_len": len(prefix),
        "new_token_budget": 10,
        "group_size": 1,
    }
    out = r._try_schedule_locked(req)
    assert out is not None and out["url"] == "s2"
    assert "kv_fabric" not in out  # already local: nothing to fetch
    assert r._counters["fabric_local_routes_total"] == 1


# -- 3. engine intra-replica dedup --------------------------------------


@pytest.mark.parametrize("gname", ["greedy", "sampled"])
def test_intra_replica_dedup_diverging_tail_bit_identity(gname):
    """Request 2 shares an 80-token block-aligned head with request 1 but
    DIVERGES afterwards: the tuple-prefix donor paths cannot serve it
    (r1's registered run is not a prefix of r2's prompt), the fabric
    device rung forks the shared blocks, and the stream stays
    bit-identical to a fresh full-prefill oracle."""
    g = _GREEDY if gname == "greedy" else _SAMPLED
    head = _prompt(80, seed=31)
    p1 = head + _prompt(16, seed=32)
    p2 = head + _prompt(16, seed=33)
    # the oracle runs the SAME request sequence with the fabric off: d2's
    # diverging tail defeats the tuple-prefix donor there, so it pays a
    # full re-prefill — and the sampling-key draw order matches
    oracle = _engine(fabric=False)
    try:
        oracle.generate(
            ModelRequest(rid="d1", input_ids=p1, gconfig=g), timeout=120
        )
        ro = oracle.generate(
            ModelRequest(rid="d2", input_ids=p2, gconfig=g), timeout=120
        )
        # the oracle really did pay the second full prefill
        assert oracle.get_metrics()["prefills_total"] == 2
    finally:
        oracle.destroy()
    eng = _engine()
    try:
        eng.generate(
            ModelRequest(rid="d1", input_ids=p1, gconfig=g), timeout=120
        )
        m0 = eng.get_metrics()
        assert m0["kv_fabric_enabled"] is True
        assert m0["kv_fabric_blocks_resident"] > 0
        r2 = eng.generate(
            ModelRequest(rid="d2", input_ids=p2, gconfig=g), timeout=120
        )
        m1 = eng.get_metrics()
        assert r2.output_tokens == ro.output_tokens
        # token-exact; logprobs to float tolerance — the fabric fork runs
        # the SAME suffix-prefill kernel as tuple-prefix sharing, whose
        # fusion differs from a monolithic prefill by ~1 ulp
        assert r2.output_logprobs == pytest.approx(
            ro.output_logprobs, abs=1e-5
        )
        # attributed to the fabric, NOT to the rid-exact host counters
        assert m1["kv_fabric_local_hits_total"] - m0[
            "kv_fabric_local_hits_total"
        ] == 1
        avoided = (
            m1["kv_fabric_local_tokens_avoided_total"]
            - m0["kv_fabric_local_tokens_avoided_total"]
        )
        assert avoided >= 64  # the whole shared block run
        assert m1["kv_host_hits_total"] == m0["kv_host_hits_total"]
        assert (
            m1["reprefill_tokens_avoided_total"]
            - m0["reprefill_tokens_avoided_total"]
            == avoided
        )
    finally:
        eng.destroy()


def test_fabric_registry_stale_on_weight_flip():
    prompt = _prompt(96, seed=34)
    eng = _engine()
    try:
        eng.generate(
            ModelRequest(rid="w", input_ids=prompt, gconfig=_GREEDY),
            timeout=120,
        )
        m0 = eng.get_metrics()
        assert m0["kv_fabric_blocks_resident"] > 0
        assert m0["kv_fabric_digest"]
        # a bare version bump: resident keys carry the OLD salt, so a
        # new-version chain for the very same tokens can never match —
        # honest misses by construction, 0 stale-block serves
        eng.set_version(1)
        old = set(kv_fabric.decode_digest(m0["kv_fabric_digest"]))
        new_chain = _chain_of(eng, prompt[: len(prompt) - 1])
        assert not old & set(new_chain)
        # the weight INSTALL flush drops the registry outright (digest
        # hygiene: stop advertising blocks nobody can ever match)
        eng.pause_generation()
        with eng._sched_lock:
            eng._invalidate_parked()
        eng.continue_generation()
        m1 = eng.get_metrics()
        assert m1["kv_fabric_blocks_resident"] == 0
        assert kv_fabric.decode_digest(m1["kv_fabric_digest"]) == []
    finally:
        eng.destroy()


def test_host_store_indexes_blocks_and_matches_runs():
    prompt = _prompt(96, seed=35)
    eng = _engine(host_mb=16.0)
    try:
        _prefill(eng, ModelRequest(rid="h", input_ids=prompt,
                                   gconfig=_GREEDY))
        eng.pause_generation()
        with eng._sched_lock:
            assert eng._evict_parked_lru() is not None
        eng.continue_generation()
        chain = _chain_of(eng, prompt[:-1])
        assert len(chain) >= 8
        with eng._host_lock:
            store = eng._host_store
            assert set(chain) <= set(store.fabric_keys())
            got = store.match_blocks(chain)
            assert got is not None
            entry, n = got
            assert entry.rid == "h" and n == len(chain)
            # a shorter chain matches its own depth, not the entry's
            got2 = store.match_blocks(chain[:9])
            assert got2 is not None and got2[1] == 9
            # a diverging chain is a clean miss
            assert store.match_blocks([123456789]) is None
        # the host-resident blocks show up in the advertised digest
        m = eng.get_metrics()
        assert set(chain) <= set(
            kv_fabric.decode_digest(m["kv_fabric_digest"])
        )
    finally:
        eng.destroy()


# -- 4. fleet fetch over the wire ---------------------------------------


async def _start_server(engine, dcfg):
    srv = DecodeServer(dcfg, engine=engine, shutdown_grace=0.2)
    addr = await srv.start(host="127.0.0.1", port=0)
    return srv, addr


@pytest.mark.parametrize("fault", [None, "torn", "abort"])
def test_kv_fetch_peer_to_peer_remote_hit_bit_identity(fault):
    """Replica A holds the prompt's blocks; replica B receives the
    /generate carrying the router's fetch hint, pulls the run from A over
    /kv_fetch, and serves the request with a suffix prefill instead of a
    full one — bit-identically, with remote attribution. Under fire
    (the `kv.migrate.*` seams, as a migration's): a TORN first frame is
    re-covered by the frame retry and lands the run exactly once; an
    ABORT of every send attempt, past the replay budget, makes A abandon
    the stream and B degrade to a local full prefill, bit-identically,
    with no fabric session imported."""
    from areal_tpu.core import fault_injection
    from areal_tpu.core.fault_injection import FaultPlan, FaultPoint

    plan = {
        None: None,
        "torn": FaultPlan(seed=3, points=[
            FaultPoint(site="kv.migrate.recv", mode="torn", at=(0,), times=1)]),
        "abort": FaultPlan(seed=4, points=[
            FaultPoint(site="kv.migrate.send", mode="abort", at=(0, 1, 2), times=3)]),
    }[fault]
    prompt = _prompt(96, seed=41)
    oracle = _engine(fabric=False)
    try:
        ro = oracle.generate(
            ModelRequest(rid="f2", input_ids=prompt, gconfig=_GREEDY),
            timeout=120,
        )
    finally:
        oracle.destroy()
    a = _engine()
    b = _engine()
    fired: dict = {}

    async def scenario():
        sa, aa = await _start_server(a, a.config)
        sb, ba = await _start_server(b, b.config)
        try:
            await arequest_with_retry(
                aa, "/generate",
                payload=dict(
                    rid="f1",
                    input_ids=prompt,
                    gconfig=dict(max_new_tokens=10, greedy=True),
                ),
                max_retries=1, timeout=120,
            )
            chain = _chain_of(a, prompt[: len(prompt) - 1])
            assert len(chain) >= 8
            if plan is not None:
                fault_injection.configure(plan)
            try:
                out = await arequest_with_retry(
                    ba, "/generate",
                    payload=dict(
                        rid="f2",
                        input_ids=prompt,
                        gconfig=dict(max_new_tokens=10, greedy=True),
                        kv_fabric=dict(
                            peer=aa, keys=kv_fabric.encode_digest(chain)
                        ),
                    ),
                    max_retries=1, timeout=120,
                )
            finally:
                fired.update(fault_injection.snapshot() if plan else {})
                fault_injection.deactivate()
            ma = await arequest_with_retry(
                aa, "/metrics", method="GET", max_retries=1, timeout=30
            )
            mb = await arequest_with_retry(
                ba, "/metrics", method="GET", max_retries=1, timeout=30
            )
            return out, ma, mb
        finally:
            await sa.stop()
            await sb.stop()

    try:
        out, ma, mb = _run_async(scenario(), timeout=240)
    finally:
        a.destroy()
        b.destroy()
    assert out["output_tokens"] == ro.output_tokens
    # token-exact; logprobs to float tolerance (suffix-prefill numerics,
    # same contract as local tuple-prefix sharing)
    assert out["output_logprobs"] == pytest.approx(
        ro.output_logprobs, abs=1e-5
    )
    if fault == "abort":
        # every send attempt died: A abandoned the stream, B imported no
        # session (no torn half-run ever serves) and paid one honest prefill
        assert sum(v for k, v in fired.items() if k.startswith("kv.migrate.send")) >= 3
        assert ma["kv_migrate"]["out_failures"] >= 1
        assert mb["kv_fabric_sessions_in_total"] == 0
        assert mb["kv_fabric_remote_hits_total"] == 0
        assert mb["kv_fabric_local_hits_total"] == 0
        assert mb["prefills_total"] == 1
        return
    if fault == "torn":
        assert any(k.startswith("kv.migrate.recv") for k in fired), fired
    # server-side accounting: A served the run, B fetched + promoted it
    # (exactly once, a torn frame replayed or not)
    assert ma["kv_fabric"]["serve_sessions"] == 1
    assert ma["kv_fabric"]["serve_bytes"] > 0
    assert mb["kv_fabric"]["fetch_sessions"] == 1
    assert mb["kv_fabric"]["fetch_failures"] == 0
    assert mb["kv_fabric_sessions_in_total"] == 1
    assert mb["kv_fabric_fetch_bytes_total"] > 0
    assert mb["kv_fabric_remote_hits_total"] == 1
    assert mb["kv_fabric_remote_tokens_avoided_total"] >= 64
    # fetched sessions are fabric traffic, not migration traffic
    assert mb["kv_migrated_in_sessions_total"] == 0
    assert mb["reprefill_tokens_avoided_total"] >= 64


def test_warm_start_pulls_top_runs_from_peers():
    prompt = _prompt(96, seed=43)
    a = _engine()
    b = _engine()

    async def scenario():
        sa, aa = await _start_server(a, a.config)
        sb, ba = await _start_server(b, b.config)
        try:
            await arequest_with_retry(
                aa, "/generate",
                payload=dict(
                    rid="w1",
                    input_ids=prompt,
                    gconfig=dict(max_new_tokens=10, greedy=True),
                ),
                max_retries=1, timeout=120,
            )
            out = await arequest_with_retry(
                ba, "/warm_start",
                payload=dict(peers=[aa], max_sessions=2),
                max_retries=1, timeout=120,
            )
            mb = await arequest_with_retry(
                ba, "/metrics", method="GET", max_retries=1, timeout=30
            )
            return out, mb
        finally:
            await sa.stop()
            await sb.stop()

    try:
        out, mb = _run_async(scenario(), timeout=240)
        assert out["status"] == "ok"
        assert out["sessions"] >= 1 and out["bytes"] > 0
        assert out["failures"] == 0
        assert mb["kv_fabric"]["warm_start_sessions"] >= 1
        assert mb["kv_fabric_sessions_in_total"] >= 1
        # the warmed blocks are resident and advertised before any
        # request arrives — the router can route prefixes here on the
        # strength of the digest alone
        assert mb["kv_fabric_blocks_resident"] >= 8
        # and the first matching request promotes instead of prefilling
        m0 = b.get_metrics()
        r = b.generate(
            ModelRequest(rid="w2", input_ids=prompt, gconfig=_GREEDY),
            timeout=120,
        )
        m1 = b.get_metrics()
        assert len(r.output_tokens) == 10
        assert m1["kv_fabric_remote_hits_total"] - m0[
            "kv_fabric_remote_hits_total"
        ] == 1
        assert m1["prefills_total"] == m0["prefills_total"]
    finally:
        a.destroy()
        b.destroy()


# -- 5. staleness -------------------------------------------------------


def test_fetched_run_from_other_weight_version_is_honest_miss():
    prompt = _prompt(96, seed=45)
    a = _engine()
    b = _engine()
    try:
        a.generate(
            ModelRequest(rid="s1", input_ids=prompt, gconfig=_GREEDY),
            timeout=120,
        )
        chain = _chain_of(a, prompt[: len(prompt) - 1])
        sessions = a.export_fabric_blocks(keys=chain)
        assert len(sessions) == 1
        sess = sessions[0]
        assert sess["meta"]["rid"].startswith("fabric-")
        # a weight commit on B raced the fetch: the run's version salt no
        # longer matches — the import is rejected, nothing stale is served
        b.set_version(7)
        assert (
            b.import_session(sess["meta"], sess["k"], sess["v"])
            == "stale_version"
        )
        m0 = b.get_metrics()
        assert m0["kv_fabric_sessions_in_total"] == 0
        r = b.generate(
            ModelRequest(rid="s2", input_ids=prompt, gconfig=_GREEDY),
            timeout=120,
        )
        m1 = b.get_metrics()
        assert len(r.output_tokens) == 10
        assert m1["kv_fabric_remote_hits_total"] == 0
        assert m1["kv_fabric_local_hits_total"] == 0
        assert m1["prefills_total"] - m0["prefills_total"] == 1
    finally:
        a.destroy()
        b.destroy()


def test_export_fabric_blocks_copy_semantics():
    """Serving the fabric never consumes local state: the donor keeps its
    registration and still forks its own siblings afterwards."""
    prompt = _prompt(96, seed=47)
    eng = _engine()
    try:
        eng.generate(
            ModelRequest(rid="c1", input_ids=prompt, gconfig=_GREEDY),
            timeout=120,
        )
        chain = _chain_of(eng, prompt[: len(prompt) - 1])
        before = eng.get_metrics()["kv_fabric_blocks_resident"]
        assert eng.export_fabric_blocks(keys=chain)
        assert eng.export_fabric_blocks(keys=chain)  # repeatable
        m = eng.get_metrics()
        assert m["kv_fabric_blocks_resident"] == before
        # the donor still serves a same-prompt fork locally
        r = eng.generate(
            ModelRequest(rid="c2", input_ids=prompt, gconfig=_GREEDY),
            timeout=120,
        )
        assert len(r.output_tokens) == 10
        assert eng.get_metrics()["prefills_total"] == 1
    finally:
        eng.destroy()


# -- 6. cheap drain (meta-only sessions) --------------------------------


def test_meta_only_export_wire_round_trip_and_honest_import():
    prompt = _prompt(96, seed=49)
    pre = _engine(role="prefill")
    try:
        _prefill(pre, ModelRequest(rid="m", input_ids=prompt,
                                   gconfig=_GREEDY))
        chain = _chain_of(pre, prompt[: len(prompt) - 1])
        # the surviving fleet advertises every block: identity alone ships
        sess = pre.export_session("m", refetchable=set(chain))
        assert sess is not None
        assert sess["meta"].get("meta_only") is True
        assert "k" not in sess
        m = pre.get_metrics()
        assert m["kv_fabric_meta_only_exports_total"] == 1
        assert m["kv_migrated_out_sessions_total"] == 1
    finally:
        pre.destroy()

    # single kvmeta frame on the wire — no kvdata buckets at all
    frames = list(pack_kv_session(sess["meta"], None, None, chunk_mb=0.01))
    assert len(frames) == 1
    st = WeightStaging()
    st.add_bucket(frames[0])
    sessions = unpack_kv_sessions(st.finalize())
    assert len(sessions) == 1
    meta, k, v, scales = sessions[0]
    assert meta.get("meta_only") is True
    assert k is None and v is None and scales is None

    dec = _engine(role="decode")
    try:
        assert dec.import_session(meta, k, v) == "ok"
        m0 = dec.get_metrics()
        # identity landed, but zero KV bytes — and the entry must never
        # promote as if it held blocks
        assert m0["kv_migrated_in_sessions_total"] == 1
        assert m0["kv_migrated_in_bytes_total"] == 0
        r = dec.generate(
            ModelRequest(rid="m", input_ids=prompt, gconfig=_GREEDY),
            timeout=120,
        )
        m1 = dec.get_metrics()
        assert len(r.output_tokens) == 10
        # honest degradation: no sibling held the blocks here, so the
        # resume re-prefilled (no phantom fabric hit, no crash)
        assert m1["prefills_total"] - m0["prefills_total"] == 1
        assert m1["kv_fabric_remote_hits_total"] == 0
    finally:
        dec.destroy()


def test_refetchable_gate_requires_full_coverage():
    """A session whose blocks are NOT all refetchable exports its bytes —
    the meta-only shortcut only fires when the fleet truly covers it."""
    prompt = _prompt(96, seed=51)
    pre = _engine(role="prefill")
    try:
        _prefill(pre, ModelRequest(rid="p", input_ids=prompt,
                                   gconfig=_GREEDY))
        chain = _chain_of(pre, prompt[: len(prompt) - 1])
        sess = pre.export_session("p", refetchable=set(chain[:-1]))
        assert sess is not None
        assert not sess["meta"].get("meta_only")
        assert sess["k"] is not None
        assert pre.get_metrics()["kv_fabric_meta_only_exports_total"] == 0
    finally:
        pre.destroy()
