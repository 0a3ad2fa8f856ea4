"""Fleet supervisor (ISSUE 13): the pure planner's invariants — hysteresis
bands, per-action cooldowns, the min-capacity floor, crash-loop gating,
replace/re-role priority — plus executor-level crash-loop escalation and
dead-replica replacement against stub handles (no HTTP, no jax)."""

import asyncio
import threading

import pytest

from areal_tpu.api.cli_args import SupervisorConfig
from areal_tpu.launcher import supervisor as supervisor_mod
from areal_tpu.launcher.supervisor import (
    FleetSnapshot,
    FleetSupervisor,
    ReplicaView,
    plan_actions,
)


def _pol(**kw):
    base = dict(
        min_replicas=1,
        max_replicas=8,
        util_inflight_target=8,
        scale_up_util=0.85,
        scale_down_util=0.30,
        scale_up_queue_depth=4,
        scale_up_cooldown_s=2.0,
        scale_down_cooldown_s=20.0,
        replace_cooldown_s=2.0,
        rerole_cooldown_s=30.0,
        spawn_max_attempts=3,
        rerole_enabled=True,
        rerole_band=0.25,
    )
    base.update(kw)
    return SupervisorConfig(**base)


def _fleet(n, roles=None, loads=None, alive=None, breakers=None):
    roles = roles or ["unified"] * n
    loads = loads or [0.0] * n
    alive = alive if alive is not None else [True] * n
    breakers = breakers or ["closed"] * n
    return tuple(
        ReplicaView(
            addr=f"r{i}:1",
            alive=alive[i],
            role=roles[i],
            breaker_state=breakers[i],
            load=loads[i],
        )
        for i in range(n)
    )


def _snap(**kw):
    base = dict(now=1000.0, replicas=_fleet(2))
    base.update(kw)
    return FleetSnapshot(**base)


# (name, snapshot, policy, expected-kind-or-None, extra-check)
PLAN_TABLE = [
    (
        "dead_band_plans_nothing",  # hysteresis: between the marks = hold
        _snap(util=0.5),
        _pol(),
        None,
        None,
    ),
    (
        "scale_up_on_queue_depth",
        _snap(queue_depth=4),
        _pol(),
        "scale_up",
        lambda a: a.role == "unified",
    ),
    (
        "scale_up_on_util_high_mark",
        _snap(util=0.9),
        _pol(),
        "scale_up",
        None,
    ),
    (
        "scale_up_on_sheds",
        _snap(shed_rate=2.0),
        _pol(),
        "scale_up",
        None,
    ),
    (
        "scale_up_respects_cooldown",
        _snap(util=0.9, last_action_t={"scale_up": 999.0}),
        _pol(scale_up_cooldown_s=2.0),
        None,
        None,
    ),
    (
        "scale_up_cooldown_elapsed",
        _snap(util=0.9, last_action_t={"scale_up": 997.0}),
        _pol(scale_up_cooldown_s=2.0),
        "scale_up",
        None,
    ),
    (
        "scale_up_capped_at_max",
        _snap(util=2.0, replicas=_fleet(3)),
        _pol(max_replicas=3),
        None,
        None,
    ),
    (
        "scale_up_waits_for_pending_spawn",
        _snap(util=2.0, pending_spawns=1),
        _pol(),
        None,
        None,
    ),
    (
        # crash-loop escalation: after spawn_max_attempts consecutive
        # failures the planner STOPS buying capacity — degraded > fork bomb
        "crash_loop_gives_up_after_n_attempts",
        _snap(util=2.0, queue_depth=50, spawn_failures=3),
        _pol(spawn_max_attempts=3),
        None,
        None,
    ),
    (
        "crash_loop_not_yet_final_attempt_still_spawns",
        _snap(util=2.0, spawn_failures=2),
        _pol(spawn_max_attempts=3),
        "scale_up",
        None,
    ),
    (
        "scale_down_when_idle_picks_least_loaded",
        _snap(util=0.1, replicas=_fleet(3, loads=[5.0, 1.0, 3.0])),
        _pol(scale_down_util=0.30),
        "scale_down",
        lambda a: a.target == "r1:1",
    ),
    (
        # the min-capacity floor no plan may violate
        "scale_down_blocked_at_floor",
        _snap(util=0.0, replicas=_fleet(2)),
        _pol(min_replicas=2),
        None,
        None,
    ),
    (
        "scale_down_respects_cooldown",
        _snap(util=0.0, replicas=_fleet(3), last_action_t={"scale_down": 990.0}),
        _pol(scale_down_cooldown_s=20.0),
        None,
        None,
    ),
    (
        # the global settle window: a just-finished replace resets the
        # scale-down clock even though no scale_down ever ran, so the
        # replacement's zero load can't read as fleet idleness
        "scale_down_blocked_right_after_replace",
        _snap(util=0.0, replicas=_fleet(3), last_action_t={"replace": 999.5}),
        _pol(scale_down_cooldown_s=2.0),
        None,
        None,
    ),
    (
        "scale_down_blocked_by_queue",
        _snap(util=0.1, queue_depth=1, replicas=_fleet(3)),
        _pol(),
        None,
        None,
    ),
    (
        "disruptive_single_flight",
        _snap(util=0.0, replicas=_fleet(3), disruptive_inflight=True),
        _pol(),
        None,
        None,
    ),
    (
        # restoring promised capacity beats every optimization
        "replace_dead_wins_over_scale_up",
        _snap(util=2.0, queue_depth=50, replicas=_fleet(3, alive=[True, False, True])),
        _pol(),
        "replace",
        lambda a: a.target == "r1:1" and a.reason == "dead",
    ),
    (
        "replace_breaker_open",
        _snap(replicas=_fleet(2, breakers=["closed", "open"])),
        _pol(),
        "replace",
        lambda a: a.target == "r1:1" and a.reason == "breaker_open",
    ),
    (
        "replace_respects_cooldown",
        _snap(replicas=_fleet(2, alive=[True, False]), last_action_t={"replace": 999.5}),
        _pol(replace_cooldown_s=2.0),
        None,
        None,
    ),
    (
        # mix shift: rebalancing existing capacity beats buying more,
        # even under scale-up pressure
        "rerole_wins_over_scale_up_on_mix_shift",
        _snap(
            util=0.9,
            prefill_share=0.7,
            replicas=_fleet(
                4,
                roles=["prefill", "decode", "decode", "decode"],
                loads=[0.0, 3.0, 1.0, 2.0],
            ),
        ),
        _pol(rerole_band=0.25),
        "rerole",
        lambda a: a.target == "r2:1" and a.role == "prefill",
    ),
    (
        "rerole_band_holds_then_pressure_scales_up_decode",
        _snap(
            util=0.9,
            prefill_share=0.4,  # |0.4 - 0.25| < band: inside hysteresis
            replicas=_fleet(4, roles=["prefill", "decode", "decode", "decode"]),
        ),
        _pol(rerole_band=0.25),
        "scale_up",
        lambda a: a.role == "decode",
    ),
    (
        # a fleet of only prefill replicas can decode nothing
        "rerole_never_flips_last_decode",
        _snap(
            util=0.5,  # dead band, so the only possible plan is a rerole
            prefill_share=1.0,
            replicas=_fleet(2, roles=["prefill", "decode"]),
        ),
        _pol(),
        None,
        None,
    ),
    (
        "rerole_flips_prefill_back_to_decode",
        _snap(
            prefill_share=0.0,
            replicas=_fleet(2, roles=["prefill", "decode"]),
        ),
        _pol(),
        "rerole",
        lambda a: a.target == "r0:1" and a.role == "decode",
    ),
    (
        "rerole_needs_disaggregated_fleet",
        _snap(util=0.5, prefill_share=0.9, replicas=_fleet(3)),
        _pol(),
        None,
        None,
    ),
    (
        "rerole_disabled_by_policy",
        _snap(
            util=0.5,
            prefill_share=0.7,
            replicas=_fleet(4, roles=["prefill", "decode", "decode", "decode"]),
        ),
        _pol(rerole_enabled=False),
        None,
        None,
    ),
]


@pytest.mark.parametrize(
    "name,snap,pol,expected,check",
    PLAN_TABLE,
    ids=[c[0] for c in PLAN_TABLE],
)
def test_plan_actions_table(name, snap, pol, expected, check):
    plan = plan_actions(snap, pol)
    assert len(plan) <= 1, f"{name}: more than one action per tick: {plan}"
    if expected is None:
        assert plan == [], f"{name}: expected no action, got {plan}"
    else:
        assert plan and plan[0].kind == expected, f"{name}: {plan}"
        if check is not None:
            assert check(plan[0]), f"{name}: {plan[0]}"


def test_plan_actions_is_pure():
    """Same frozen snapshot in, same plan out — no hidden state."""
    snap = _snap(util=0.9)
    pol = _pol()
    assert plan_actions(snap, pol) == plan_actions(snap, pol)


def test_min_floor_never_violated_under_sweep():
    """Property sweep: across a grid of pressures, no plan ever retires a
    replica when the fleet sits at (or below) the floor, and no plan ever
    contains more than one action."""
    pol = _pol(min_replicas=2)
    for n in (1, 2):
        for util in (0.0, 0.1, 0.3, 0.5, 0.9, 2.0):
            for queue in (0, 4, 50):
                for shed in (0.0, 1.0):
                    plan = plan_actions(
                        _snap(
                            replicas=_fleet(n),
                            util=util,
                            queue_depth=queue,
                            shed_rate=shed,
                        ),
                        pol,
                    )
                    assert len(plan) <= 1
                    assert all(a.kind != "scale_down" for a in plan), (
                        n, util, queue, shed, plan,
                    )


# -- executor: crash-loop escalation + replace against stub handles ---------


class _Handle:
    def __init__(self, addr):
        self.addr = addr
        self.killed = threading.Event()

    def kill(self):
        self.killed.set()


def _run(coro, timeout=60):
    result = {}

    def go():
        result["v"] = asyncio.run(coro)

    t = threading.Thread(target=go, daemon=True)
    t.start()
    t.join(timeout)
    assert not t.is_alive(), "supervisor scenario timed out"
    return result.get("v")


async def _settle_spawns(sup):
    for _ in range(200):
        await asyncio.sleep(0.005)
        if not any(s.spawning for s in sup._slots.values()):
            return
    raise AssertionError("spawn tasks never settled")


async def _scenario_crash_loop():
    clock = {"t": 100.0}
    attempts = []

    def spawn_fn(role):
        attempts.append(role)
        raise RuntimeError("broken image")

    cfg = SupervisorConfig(
        min_replicas=1,
        max_replicas=4,
        spawn_max_attempts=3,
        spawn_backoff_s=0.01,
        spawn_backoff_max_s=0.02,
        spawn_backoff_jitter=0.0,
        scale_up_cooldown_s=0.0,
        scale_up_queue_depth=1,
    )
    sup = FleetSupervisor(
        "127.0.0.1:1", spawn_fn, config=cfg, time_fn=lambda: clock["t"]
    )

    async def router():
        return {"queue_depth": 10}  # permanent pressure

    async def healths():
        return []

    sup._poll_router = router
    sup._poll_healths = healths

    for _ in range(20):
        await sup._tick()
        await _settle_spawns(sup)
        clock["t"] += 1.0
        if sup.get_metrics()["crash_loops_total"]:
            break
    m = sup.get_metrics()
    assert m["crash_loops_total"] == 1
    assert m["spawn_failures_total"] == 3
    assert len(attempts) == 3  # gave up after N — no fourth retry

    # degraded steady state: pressure persists, but the crash-looped slot
    # gates any further buying — the loop must not fork-bomb
    for _ in range(5):
        await sup._tick()
        await _settle_spawns(sup)
        clock["t"] += 1.0
    m = sup.get_metrics()
    assert len(attempts) == 3
    assert m["scale_ups_total"] == 1
    assert m["crash_looped_slots"] == 1
    return True


def test_executor_crash_loop_gives_up_and_degrades():
    assert _run(_scenario_crash_loop())


# The control plane under fire: each of the supervisor's own seams armed
# against the executor (what a whole supervised fleet was once run for).
UNDER_FIRE = {
    None: [],
    # the replace's first two spawns die: backoff and retry, no crash loop
    "spawn": [dict(site="supervisor.spawn", mode="abort", at=(0, 1), times=2)],
    # the supervisor dies at the kill: the next tick plans the replace again
    "kill": [dict(site="supervisor.kill", mode="abort", at=(0,), times=1)],
    # one failed probe of the healthy replica: a flap, below the dead threshold
    "health": [dict(site="supervisor.health", mode="abort", at=(0,), times=1,
                    match={"target": "ok:1"})],
}


async def _scenario_replace_dead(seam=None):
    from areal_tpu.core import fault_injection
    from areal_tpu.core.fault_injection import FaultPlan, FaultPoint

    clock = {"t": 100.0}
    spawned = []

    def spawn_fn(role):
        h = _Handle(f"new{len(spawned)}:1")
        spawned.append(h)
        return h

    cfg = SupervisorConfig(
        # floor == fleet size: the idle fleet must NOT plan a scale-down
        # while we watch the replace path (replace is always allowed)
        min_replicas=2,
        max_replicas=4,
        spawn_max_attempts=3,
        spawn_backoff_s=0.01,
        spawn_backoff_jitter=0.0,
        replace_cooldown_s=0.0,
        health_fail_threshold=2,
    )
    sup = FleetSupervisor(
        "127.0.0.1:1", spawn_fn, config=cfg, time_fn=lambda: clock["t"]
    )
    dead, ok = _Handle("dead:1"), _Handle("ok:1")
    sup.adopt(dead)
    sup.adopt(ok)

    async def router():
        return {}

    async def http(addr, path, **kw):
        # dead:1 fails every probe; everything else (incl. a respawned
        # handle) reports healthy
        if addr == "dead:1":
            raise ConnectionError("dead")
        return {"status": "ok"}

    sup._poll_router = router
    real_http = supervisor_mod.arequest_with_retry
    supervisor_mod.arequest_with_retry = http
    fault_injection.configure(FaultPlan(
        seed=13, points=[FaultPoint(**p) for p in UNDER_FIRE[seam]]))
    try:
        for _ in range(30):
            await sup._tick()
            await _settle_spawns(sup)
            if sup._disruptive_task is not None:
                # the replace runs as a task: let it finish before advancing
                await sup._disruptive_task
            clock["t"] += 1.0
            m = sup.get_metrics()
            # gauges lag one tick (the disruptive task runs after the
            # snapshot), so gate on the live slot table, not the gauges
            if m["replacements_total"] >= 1 and all(
                s.handle is not None for s in sup._slots.values()
            ):
                break
        await sup._tick()  # refresh gauges with the respawned handle
        fired = {k.split("|")[0] for k in fault_injection.snapshot()}
    finally:
        fault_injection.deactivate()
        supervisor_mod.arequest_with_retry = real_http
    m = sup.get_metrics()
    assert fired == ({f"supervisor.{seam}"} if seam else set())
    assert m["spawn_failures_total"] == (2 if seam == "spawn" else 0)
    assert m["crash_loops_total"] == 0 and m["pending_spawns"] == 0
    assert m["health_flaps_total"] == (1 if seam == "health" else 0)
    assert m["replacements_total"] == 1
    assert m["kills_total"] == 1
    assert dead.killed.is_set()
    assert not ok.killed.is_set()  # the healthy replica was untouched
    assert m["fleet_alive"] == 2
    addrs = {s.addr for s in sup._slots.values()}
    assert addrs == {"new0:1", "ok:1"}
    return True


@pytest.mark.parametrize("seam", list(UNDER_FIRE), ids=str)
def test_executor_replaces_dead_replica_and_respawns(seam):
    assert _run(_scenario_replace_dead(seam))


async def _scenario_hung_drain_rolls_back():
    """Three idle replicas over a floor of two: the plan retires one. Its
    first drain hangs past the deadline (the `supervisor.drain` seam): the
    action is rolled back and the victim keeps serving; a later tick drains
    it for good, and the fleet converges to the floor."""
    from areal_tpu.core import fault_injection
    from areal_tpu.core.fault_injection import FaultPlan, FaultPoint

    clock = {"t": 100.0}
    cfg = SupervisorConfig(min_replicas=2, max_replicas=4, scale_down_cooldown_s=0.0,
                           drain_deadline_s=0.05, health_fail_threshold=2)
    sup = FleetSupervisor("127.0.0.1:1", lambda role: None, config=cfg,
                          time_fn=lambda: clock["t"])
    handles = [_Handle(f"r{i}:1") for i in range(3)]
    for h in handles:
        sup.adopt(h)
    drains = []

    async def router():
        return {}

    async def http(addr, path, **kw):
        if path == "/drain":
            drains.append(addr)
        return {"status": "ok"}

    sup._poll_router = router
    real_http = supervisor_mod.arequest_with_retry
    supervisor_mod.arequest_with_retry = http
    fault_injection.configure(FaultPlan(seed=14, points=[
        FaultPoint(site="supervisor.drain", mode="delay", at=(0,), times=1, delay_s=0.5)]))
    try:
        rolled_back_alive = None
        for _ in range(30):
            await sup._tick()
            if sup._disruptive_task is not None:
                await sup._disruptive_task
            clock["t"] += 1.0
            m = sup.get_metrics()
            if m["drain_rollbacks_total"] and rolled_back_alive is None:
                rolled_back_alive = not any(h.killed.is_set() for h in handles)
            if m["scale_downs_total"]:
                break
        await sup._tick()
    finally:
        fault_injection.deactivate()
        supervisor_mod.arequest_with_retry = real_http
    m = sup.get_metrics()
    assert m["drain_rollbacks_total"] == 1 and rolled_back_alive
    assert m["scale_downs_total"] == 1 and m["kills_total"] == 1
    assert len(drains) == 1  # the hung drain never reached the replica
    assert sum(h.killed.is_set() for h in handles) == 1
    assert m["fleet_alive"] == 2 and m["pending_spawns"] == 0
    return True


def test_hung_drain_rolls_back_then_retires():
    assert _run(_scenario_hung_drain_rolls_back())


async def _scenario_endpoint():
    def spawn_fn(role):  # pragma: no cover — never called
        raise AssertionError("no spawn expected")

    sup = FleetSupervisor("127.0.0.1:1", spawn_fn, config=SupervisorConfig())

    async def router():
        return {}

    sup._poll_router = router
    addr = await sup.start(host="127.0.0.1", port=0)
    try:
        from areal_tpu.utils.http import (
            arequest_with_retry,
            close_current_session,
        )

        h = await arequest_with_retry(addr, "/health", method="GET")
        assert h["status"] == "ok"
        body = await arequest_with_retry(addr, "/supervisor", method="GET")
        # counters + gauges + slot table ride on one endpoint
        for key in (
            "scale_ups_total",
            "scale_downs_total",
            "replacements_total",
            "reroles_total",
            "crash_loops_total",
            "drain_rollbacks_total",
            "fleet_alive",
            "replica_seconds",
            "slots",
        ):
            assert key in body, key
        assert body["slots"] == []
        await close_current_session()
    finally:
        await sup.stop()
    return True


def test_supervisor_endpoint_serves_counters_and_gauges():
    assert _run(_scenario_endpoint())
