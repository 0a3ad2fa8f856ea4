"""Deterministic fault injection + graceful degradation (ISSUE 9).

Tier-1 chaos surface, all fast (stub engines, no jax):

  - FaultInjector semantics: seeded determinism, `at` / `p` / `times` /
    `match` gating, the four modes, counters, global configure/deactivate.
  - Seam behavior at each boundary the injector perturbs: HTTP send /
    recv / torn-body (retry + idempotency dedup), weight staging (torn
    frames rejected, retries re-cover), host-KV swap (faults degrade to
    re-prefill, never crash), task.run (failure accounting).
  - The short seeded chaos smoke: a 2-replica stub fleet + router +
    RemoteInfEngine replay a request wave under a 4-mode fault schedule;
    every request completes exactly once with streams identical to the
    fault-free function of the prompt.
"""

import asyncio
import struct
import threading
import time

import numpy as np
import pytest
from aiohttp import web

from areal_tpu.api.cli_args import (
    FaultInjectionConfig,
    GenerationHyperparameters,
    InferenceEngineConfig,
    JaxDecodeConfig,
)
from areal_tpu.api.io_struct import ModelRequest, ModelResponse
from areal_tpu.core import fault_injection
from areal_tpu.core.async_task_runner import AsyncTaskRunner
from areal_tpu.core.fault_injection import (
    FaultInjector,
    FaultPlan,
    FaultPoint,
    InjectedFault,
)
from areal_tpu.core.remote_inf_engine import RemoteInfEngine
from areal_tpu.core.weight_transfer import WeightStaging, pack_buckets
from areal_tpu.engine.kv_pool import HostKVEntry, HostKVStore
from areal_tpu.launcher.decode_server import DecodeServer
from areal_tpu.launcher.router import DecodeRouter
from areal_tpu.utils import name_resolve
from areal_tpu.utils.http import (
    HttpRequestError,
    arequest_with_retry,
    backoff_delays,
    close_current_session,
)


@pytest.fixture(autouse=True)
def _no_leaked_injector():
    fault_injection.deactivate()
    yield
    fault_injection.deactivate()


def _run_async(coro, timeout=60):
    result = {}

    def go():
        result["v"] = asyncio.run(coro)

    t = threading.Thread(target=go, daemon=True)
    t.start()
    t.join(timeout)
    assert not t.is_alive(), "async scenario timed out"
    return result.get("v")


# -- injector semantics ------------------------------------------------------


def test_fault_point_validation():
    with pytest.raises(ValueError):
        FaultPoint(site="x", mode="explode")
    p = FaultPoint(site="x", at=[1, "2"])
    assert p.at == (1, 2)


def test_plan_from_json_and_config():
    plan = FaultPlan.from_json(
        '[{"site": "a.b", "mode": "delay", "at": [0], "delay_s": 0.5}]',
        seed=7,
    )
    assert plan.seed == 7
    assert plan.points[0].mode == "delay"
    # dict form carries its own seed
    plan = FaultPlan.from_json('{"seed": 3, "points": [{"site": "a"}]}')
    assert plan.seed == 3 and plan.points[0].site == "a"
    # config form; disabled -> None
    assert FaultPlan.from_config(FaultInjectionConfig()) is None
    cfg = FaultInjectionConfig(
        enabled=True, seed=9, plan='[{"site": "s", "mode": "abort"}]'
    )
    plan = FaultPlan.from_config(cfg)
    assert plan.seed == 9 and plan.points[0].site == "s"


def test_at_times_and_match_gating():
    inj = FaultInjector(
        FaultPlan(
            seed=0,
            points=[
                FaultPoint(site="s.*", mode="abort", at=(1, 3), times=2,
                           match={"addr": "good"}),
            ],
        )
    )
    fired = []
    for i in range(6):
        try:
            inj.fire("s.x", addr="good-host")
        except InjectedFault:
            fired.append(i)
    assert fired == [1, 3]
    # non-matching context never fires (and keeps its own hit count)
    inj2 = FaultInjector(
        FaultPlan(points=[FaultPoint(site="s.*", match={"addr": "good"})])
    )
    for _ in range(4):
        inj2.fire("s.x", addr="other")
    assert inj2.snapshot() == {}


def test_probability_gate_is_seed_deterministic():
    def run(seed):
        inj = FaultInjector(
            FaultPlan(
                seed=seed,
                points=[FaultPoint(site="s", mode="abort", p=0.5, times=0)],
            )
        )
        hits = []
        for i in range(32):
            try:
                inj.fire("s")
            except InjectedFault:
                hits.append(i)
        return hits

    a, b = run(11), run(11)
    assert a == b and 0 < len(a) < 32
    assert run(12) != a  # a different seed draws a different schedule


def test_delay_and_torn_modes():
    inj = FaultInjector(
        FaultPlan(
            seed=5,
            points=[
                FaultPoint(site="d", mode="delay", at=(0,), delay_s=0.05,
                           jitter_s=0.05),
                FaultPoint(site="t", mode="torn", at=(0,)),
            ],
        )
    )
    t0 = time.monotonic()
    inj.fire("d")  # delay sleeps, never raises
    assert 0.05 <= time.monotonic() - t0 < 1.0
    data = b"x" * 100
    torn = inj.tear("t", data)
    assert 1 <= len(torn) < len(data)
    # the same seed reproduces the same tear fraction
    inj2 = FaultInjector(
        FaultPlan(seed=5, points=[
            FaultPoint(site="d", mode="delay", at=(0,), delay_s=0.05,
                       jitter_s=0.05),
            FaultPoint(site="t", mode="torn", at=(0,)),
        ])
    )
    inj2.fire("d")
    assert inj2.tear("t", data) == torn
    # non-torn points fall through tear() untouched
    inj3 = FaultInjector(
        FaultPlan(points=[FaultPoint(site="t", mode="abort", at=(0,))])
    )
    assert inj3.tear("t", data) == data


def test_afire_delay_and_counters():
    async def go():
        # NOTE per-point hit counters count visits that REACH the point:
        # visit 0 fires the first point (short-circuit), so the second
        # point's counter first ticks on visit 1 — its hit index 0
        inj = FaultInjector(
            FaultPlan(points=[
                FaultPoint(site="a", mode="delay", at=(0,), delay_s=0.03),
                FaultPoint(site="a", mode="error_after_effect", at=(0,)),
            ])
        )
        t0 = time.monotonic()
        await inj.afire("a")
        assert time.monotonic() - t0 >= 0.03
        with pytest.raises(InjectedFault) as ei:
            await inj.afire("a")
        assert ei.value.mode == "error_after_effect"
        return inj.snapshot()

    counters = _run_async(go())
    assert counters == {"a|delay": 1, "a|error_after_effect": 1}


def test_global_injector_fast_path():
    # inactive: module-level seams are no-ops
    fault_injection.fire("anything")
    assert fault_injection.tear("anything", b"zz") == b"zz"
    assert fault_injection.snapshot() == {}
    fault_injection.configure(
        FaultPlan(points=[FaultPoint(site="g", mode="abort", at=(0,))])
    )
    with pytest.raises(InjectedFault):
        fault_injection.fire("g")
    fault_injection.deactivate()
    fault_injection.fire("g")  # cleared


def test_injected_fault_is_catchable_degradation():
    """Every engine degradation path catches `Exception` — an injected
    fault must be one (and must not masquerade as cancellation)."""
    f = InjectedFault("s", "abort", FaultPoint(site="s"))
    assert isinstance(f, Exception)
    assert not isinstance(f, asyncio.CancelledError)


# -- seam: weight staging ----------------------------------------------------


def _bucket_frames(names_arrays, chunk_mb=10.0):
    return list(pack_buckets(names_arrays, chunk_mb=chunk_mb))


def test_weight_stage_seam_abort_and_retry():
    arr = np.arange(64, dtype=np.float32).reshape(8, 8)
    frames = _bucket_frames([("w", arr)])
    staging = WeightStaging()
    fault_injection.configure(
        FaultPlan(points=[FaultPoint(site="weight.stage.add", at=(0,))])
    )
    with pytest.raises(InjectedFault):
        staging.add_bucket(frames[0])
    assert len(staging) == 0  # nothing partially staged
    # the retry (fault exhausted) lands the full frame
    for f in frames:
        staging.add_bucket(f)
    np.testing.assert_array_equal(staging.finalize()["w"], arr)


def test_weight_stage_torn_frame_rejected():
    """A torn frame must raise (-> 5xx -> client bucket retry), never
    stage partial coverage."""
    arr = np.arange(256, dtype=np.float32)
    (frame,) = _bucket_frames([("w", arr)])
    staging = WeightStaging()
    fault_injection.configure(
        FaultPlan(
            seed=2,
            points=[FaultPoint(site="weight.stage.add", mode="torn", at=(0,))],
        )
    )
    with pytest.raises(ValueError, match="torn weight frame"):
        staging.add_bucket(frame)
    assert len(staging) == 0
    fault_injection.deactivate()
    staging.add_bucket(frame)  # full retry covers
    np.testing.assert_array_equal(staging.finalize()["w"], arr)


def test_unpack_rejects_short_payloads():
    from areal_tpu.core.weight_transfer import unpack_bucket_parts

    with pytest.raises(ValueError):
        unpack_bucket_parts(b"\x01")  # no header
    arr = np.ones(16, dtype=np.float32)
    (frame,) = _bucket_frames([("w", arr)])
    (mlen,) = struct.unpack_from("<Q", frame, 0)
    with pytest.raises(ValueError):
        unpack_bucket_parts(frame[: 8 + mlen - 2])  # torn manifest
    with pytest.raises(ValueError):
        unpack_bucket_parts(frame[:-4])  # torn tensor body


# -- seam: host-KV swap ------------------------------------------------------


def _host_entry(rid="r", nb=1):
    return HostKVEntry(
        rid=rid, k=np.zeros(4), v=np.zeros(4), nb=nb, covered=16,
        tokens=list(range(16)), rope_delta=0, base_key=np.zeros(2),
        ts=time.monotonic(),
    )


def test_kv_swap_seams_fire():
    store = HostKVStore(budget_bytes=1 << 20, block_nbytes=64, block_size=16)
    fault_injection.configure(
        FaultPlan(points=[
            FaultPoint(site="kv.swap_out", at=(0,)),
            FaultPoint(site="kv.swap_in", at=(0,)),
        ])
    )
    with pytest.raises(InjectedFault):
        store.put(_host_entry())
    store.put(_host_entry())  # fault exhausted: offload lands
    with pytest.raises(InjectedFault):
        store.take("r")
    e = store.take("r")
    assert e is not None and e.rid == "r"


# -- seam: task.run + failure accounting -------------------------------------


def test_task_run_seam_releases_capacity():
    runner = AsyncTaskRunner(name="chaos-test")
    runner.start()
    try:
        fault_injection.configure(
            FaultPlan(points=[FaultPoint(site="task.run", at=(0,))])
        )

        async def ok():
            return 42

        runner.submit(lambda: ok())
        runner.submit(lambda: ok())
        results = runner.wait(2, timeout=10)
        excs = [r for r in results if r.exception is not None]
        oks = [r for r in results if r.exception is None]
        assert len(excs) == 1 and isinstance(excs[0].exception, InjectedFault)
        assert len(oks) == 1 and oks[0].result == 42
        assert runner.inflight == 0  # the faulted task released its slot
    finally:
        runner.destroy()


# -- seam: client HTTP (send / recv / torn body) -----------------------------


class _CountingApp:
    """Tiny aiohttp endpoint: counts hits, returns a fixed JSON body."""

    def __init__(self):
        self.hits = 0
        self._runner = None
        self.addr = None

    async def _handler(self, request):
        self.hits += 1
        return web.json_response({"ok": True, "n": 123})

    async def start(self):
        app = web.Application()
        app.router.add_post("/gen", self._handler)
        self._runner = web.AppRunner(app)
        await self._runner.setup()
        site = web.TCPSite(self._runner, "127.0.0.1", 0)
        await site.start()
        self.addr = f"127.0.0.1:{self._runner.addresses[0][1]}"

    async def stop(self):
        await self._runner.cleanup()


def test_http_send_abort_is_pre_effect():
    """An abort at client.http.send is a clean loss: the server never saw
    the request; the transport retry delivers exactly one effect."""

    async def go():
        srv = _CountingApp()
        await srv.start()
        try:
            fault_injection.configure(
                FaultPlan(points=[FaultPoint(site="client.http.send", at=(0,))])
            )
            out = await arequest_with_retry(
                srv.addr, "/gen", payload={}, max_retries=3, retry_delay=0.01
            )
            assert out["ok"] is True
            return srv.hits
        finally:
            await close_current_session()
            await srv.stop()

    assert _run_async(go()) == 1


def test_http_recv_abort_is_error_after_effect():
    """An abort at client.http.recv loses the RESPONSE after the server
    processed the request — the retry is a duplicate delivery (two server
    hits); real /generate seams rely on the xid table to dedup it."""

    async def go():
        srv = _CountingApp()
        await srv.start()
        try:
            fault_injection.configure(
                FaultPlan(points=[
                    FaultPoint(site="client.http.recv",
                               mode="error_after_effect", at=(0,)),
                ])
            )
            out = await arequest_with_retry(
                srv.addr, "/gen", payload={}, max_retries=3, retry_delay=0.01
            )
            assert out["ok"] is True
            return srv.hits
        finally:
            await close_current_session()
            await srv.stop()

    assert _run_async(go()) == 2


def test_http_torn_body_retried():
    async def go():
        srv = _CountingApp()
        await srv.start()
        try:
            fault_injection.configure(
                FaultPlan(seed=4, points=[
                    FaultPoint(site="client.http.body", mode="torn", at=(0,)),
                ])
            )
            out = await arequest_with_retry(
                srv.addr, "/gen", payload={}, max_retries=3, retry_delay=0.01
            )
            assert out["n"] == 123
            return srv.hits
        finally:
            await close_current_session()
            await srv.stop()

    assert _run_async(go()) == 2


def test_error_body_is_structured():
    """4xx payloads surface as parsed dicts on HttpRequestError.body —
    the satellite replacing the stringified-exception regex."""

    async def go():
        app = web.Application()

        async def shed(request):
            return web.json_response(
                {"error": "shed", "retry_after": 0.25}, status=429
            )

        app.router.add_post("/gen", shed)
        runner = web.AppRunner(app)
        await runner.setup()
        site = web.TCPSite(runner, "127.0.0.1", 0)
        await site.start()
        addr = f"127.0.0.1:{runner.addresses[0][1]}"
        try:
            with pytest.raises(HttpRequestError) as ei:
                await arequest_with_retry(addr, "/gen", payload={})
            assert ei.value.status == 429
            assert ei.value.body["retry_after"] == 0.25
        finally:
            await close_current_session()
            await runner.cleanup()
        return True

    assert _run_async(go())


def test_backoff_delays_jittered_and_capped():
    ds = list(backoff_delays(1.0, 6, jitter=0.25, cap=8.0))
    assert len(ds) == 6
    for i, d in enumerate(ds):
        base = min(1.0 * (2**i), 8.0)
        assert base * 0.75 <= d <= base * 1.25
    # zero jitter: exact exponential
    assert list(backoff_delays(1.0, 3, jitter=0.0)) == [1.0, 2.0, 4.0]


# -- the seeded chaos smoke (stub fleet, exactly-once + stream identity) -----


class DetStubEngine:
    """Deterministic stub: the stream is a pure function of the prompt
    (the oracle contract), with per-rid generation counts recorded so
    duplicate engine-side generations are directly observable."""

    def __init__(self, n_tokens=4):
        self.n_tokens = n_tokens
        self.calls: dict[str, int] = {}
        self._version = 0

    def get_version(self):
        return self._version

    def get_metrics(self):
        return {"active_tokens": 0}

    @staticmethod
    def expected(input_ids, n_tokens=4):
        s = sum(input_ids) % 997
        return [(s + k) % 997 for k in range(n_tokens)]

    async def agenerate(self, req):
        self.calls[req.rid] = self.calls.get(req.rid, 0) + 1
        await asyncio.sleep(0.02)
        toks = self.expected(req.input_ids, self.n_tokens)
        return ModelResponse(
            input_tokens=list(req.input_ids),
            output_tokens=toks,
            output_logprobs=[0.0] * self.n_tokens,
            output_versions=[0] * self.n_tokens,
            stop_reason="stop",
            latency=0.02,
            ttft=0.02,
        )


async def _scenario_chaos_smoke():
    name_resolve.reconfigure(name_resolve.NameResolveConfig(type="memory"))
    engines = [DetStubEngine(), DetStubEngine()]
    servers = []
    addrs = []
    for eng in engines:
        srv = DecodeServer(JaxDecodeConfig(), engine=eng, shutdown_grace=0.2)
        addrs.append(await srv.start(host="127.0.0.1", port=0))
        servers.append(srv)
    router = DecodeRouter(
        "chaosexp", "t", addrs,
        health_poll_interval=0.15, dead_after_failures=8,
    )
    raddr = await router.start("127.0.0.1", 0)
    client = RemoteInfEngine(
        InferenceEngineConfig(
            experiment_name="chaosexp",
            trial_name="t",
            request_timeout=30,
            request_retries=3,
            fleet_failover_retries=2,
        )
    )
    client.addresses = list(addrs)
    n_reqs = 8
    prompts = {f"r{i}": [i + 1, i + 2, i + 3, 7 * i + 1] for i in range(n_reqs)}
    plan = FaultPlan(
        seed=77,
        points=[
            FaultPoint(site="client.http.send", mode="abort", at=(1,),
                       times=1, match={"endpoint": "/generate"}),
            FaultPoint(site="client.http.recv", mode="error_after_effect",
                       at=(0,), times=1, match={"endpoint": "/generate"}),
            FaultPoint(site="client.http.body", mode="torn", at=(2,),
                       times=1, match={"endpoint": "/generate"}),
            FaultPoint(site="server.generate", mode="delay", at=(1,),
                       times=1, delay_s=0.1),
        ],
    )
    results = {}
    try:
        await asyncio.sleep(0.4)
        fault_injection.configure(plan)

        async def one(rid):
            r = await client.agenerate(
                ModelRequest(
                    rid=rid,
                    input_ids=prompts[rid],
                    gconfig=GenerationHyperparameters(max_new_tokens=4),
                )
            )
            assert rid not in results, f"duplicate completion {rid}"
            results[rid] = list(r.output_tokens)

        await asyncio.gather(*[one(rid) for rid in prompts])
        counters = fault_injection.snapshot()
        # 0 lost, streams identical to the fault-free pure function
        assert len(results) == n_reqs
        for rid, toks in results.items():
            assert toks == DetStubEngine.expected(prompts[rid]), rid
        # 0 duplicated engine-side generations (replay served retries)
        per_rid = {}
        for eng in engines:
            for rid, c in eng.calls.items():
                per_rid[rid] = per_rid.get(rid, 0) + c
        assert per_rid == {rid: 1 for rid in prompts}, per_rid
        # the schedule exercised all four modes
        assert {k.split("|")[1] for k in counters} == {
            "abort", "error_after_effect", "delay", "torn",
        }, counters
        # error-after-effect + torn both forced an idempotency replay
        idem_hits = 0
        for a in addrs:
            m = await arequest_with_retry(a, "/metrics", method="GET",
                                          max_retries=1, timeout=5)
            idem_hits += m["idem_hits_total"]
        assert idem_hits >= 2, idem_hits
        return True
    finally:
        fault_injection.deactivate()
        await close_current_session()
        await router.stop()
        for srv in servers:
            await srv.stop()


def test_chaos_smoke_exactly_once():
    assert _run_async(_scenario_chaos_smoke(), timeout=90)


def test_config_arms_global_injector():
    """An enabled FaultInjectionConfig on the client config installs the
    process-global injector (the production wiring for chaos runs)."""
    cfg = InferenceEngineConfig(
        fault_injection=FaultInjectionConfig(
            enabled=True, seed=1,
            plan='[{"site": "cfg.site", "mode": "abort", "at": [0]}]',
        )
    )
    RemoteInfEngine(cfg)
    assert fault_injection.get() is not None
    with pytest.raises(InjectedFault):
        fault_injection.fire("cfg.site")
    # disabled config does NOT clear an armed injector (a chaos run arms
    # globally, then builds clients with default configs)
    RemoteInfEngine(InferenceEngineConfig())
    assert fault_injection.get() is not None
