"""Self-healing fleet supervisor: SLO autoscaler over the decode fleet
(ISSUE 13 tentpole; ROADMAP item 1; Podracer arXiv:2104.06272 is the
blueprint — an anti-fragile actor fleet where the control plane, not the
operator, absorbs churn).

Every fleet primitive this loop composes already exists: `/drain`
migrates sessions with zero re-prefill (ISSUE 10), the router exposes
queue/shed/pressure metrics and requeues a dead replica's work
exactly-once (ISSUE 8), deadlines and circuit breakers bound failure
(ISSUE 9). The supervisor closes the loop. Each tick it

  1. polls the router's /metrics and every managed replica's /health,
  2. freezes the readings into an immutable FleetSnapshot,
  3. runs the PURE planner `plan_actions(snapshot, policy)` — hysteresis
     bands, per-action cooldowns, a min-capacity floor no plan may
     violate, at most one disruptive action in flight — and
  4. executes the plan through two seams: a `spawn_fn(role) -> handle`
     launcher callback (in-process replicas in tests, decode-server
     subprocesses via LocalLauncher.spawn_decode_server) and plain HTTP
     against the replicas (/drain, /set_role) + `handle.kill()`.

The four safe transitions:

  scale up    new slot -> spawn_fn with jittered-backoff retry; after
              `spawn_max_attempts` consecutive failures the slot is
              CRASH-LOOPED: the supervisor stops retrying it, records
              crash_loops_total, and continues with the degraded fleet
              (a broken image must not turn the control loop into a
              fork bomb).
  scale down  /drain to the survivors first; the victim is killed only
              after the drain COMMITS. A drain that exceeds
              drain_deadline_s is aborted and the action rolled back
              (drain_rollbacks_total; the victim keeps serving).
  replace     a dead or breaker-open replica is drained if still
              reachable, killed, and its slot respawned through the
              same crash-loop-escalating spawn machinery. Its queued
              work is NOT the supervisor's job: the router's
              dead_after_failures failover requeues in-flight qids and
              the clients' xid retries land exactly-once on the
              servers' idempotency tables (ISSUE 8/9 machinery).
  re-role     when the observed prefill work share (from the fleet's
              TTFT-split / busy-time metrics) drifts outside
              `rerole_band` of the provisioned prefill replica share,
              one replica is drained and flipped via /set_role —
              capacity is rebalanced without buying any.

Why drain-first is the safe transition: a drained replica has exported
every resumable session to survivors (zero re-prefill promotion on
resume) and parked nothing, so the subsequent kill destroys no state a
client still needs; the only cost is the failover latency of requests
in flight at the instant of the kill, which the exactly-once machinery
already bounds.

Fault seams (core/fault_injection.py): `supervisor.spawn` fires before
each spawn attempt (abort = spawn failure -> backoff/crash-loop),
`supervisor.drain` fires inside the drain deadline window (delay = a
hung drain -> rollback), `supervisor.health` fires before each replica
health probe (abort = health flap), `supervisor.kill` fires after a
drain commit but before the kill (abort = supervisor dying mid
transition; the next tick replans and the /drain in-progress guard +
idempotent re-drain make the retry safe).
"""

from __future__ import annotations

import argparse
import asyncio
import random
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Mapping, Protocol

from aiohttp import web

from areal_tpu.api.cli_args import SupervisorConfig
from areal_tpu.core import fault_injection, kv_fabric
from areal_tpu.utils import logging, name_resolve, names
from areal_tpu.utils.http import arequest_with_retry, close_current_session

logger = logging.getLogger("supervisor")

# Every handler AND the tick loop run on ONE asyncio event loop; _lock is
# an asyncio.Lock making multi-field updates atomic across the awaits
# inside the tick (poll -> snapshot -> plan -> dispatch). The registry
# declares the shared control-plane state that contract serializes.
_GUARDED_BY = {
    "FleetSupervisor._slots": "_lock",
    "FleetSupervisor._next_slot_id": "_lock",
    "FleetSupervisor._last_action_t": "_lock",
    "FleetSupervisor._disruptive_task": "_lock",
    "FleetSupervisor._last_tick_t": "_lock",
    "FleetSupervisor._prev_sheds": "_lock",
    "FleetSupervisor._prev_secs": "_lock",
    "FleetSupervisor._prefill_share": "_lock",
    "FleetSupervisor._replica_seconds": "_lock",
    "FleetSupervisor._counters": "_lock",
    "FleetSupervisor._gauges": "_lock",
}

# actions that remove/disturb live capacity; the planner emits at most
# one per tick and none while a previous one is still in flight
DISRUPTIVE_KINDS = frozenset({"scale_down", "replace", "rerole"})


# -- planner inputs/outputs (all frozen: the planner is pure) ------------
@dataclass(frozen=True)
class ReplicaView:
    """One replica as the planner sees it — a closed set of scalars so
    synthetic snapshots are trivial to table-test."""

    addr: str
    alive: bool = True
    role: str = "unified"
    breaker_state: str = "closed"  # "closed" | "open" | "half_open"
    load: float = 0.0  # router token-load estimate (scale-down victim pick)


@dataclass(frozen=True)
class FleetSnapshot:
    """Frozen metrics snapshot one tick plans over. `last_action_t`,
    `disruptive_inflight`, and `spawn_failures` fold the supervisor's own
    bookkeeping in, so cooldowns / single-disruptive-action / crash-loop
    gating are planner properties, unit-testable without a fleet."""

    now: float
    replicas: tuple[ReplicaView, ...]
    queue_depth: int = 0
    shed_rate: float = 0.0  # router sheds per second since last tick
    util: float = 0.0  # fleet demand / capacity, 0..inf
    # observed share of fleet compute spent on prompt prefill (None until
    # measured); drives re-role in a disaggregated fleet
    prefill_share: float | None = None
    last_action_t: Mapping[str, float] = field(default_factory=dict)
    disruptive_inflight: bool = False
    # consecutive spawn failures on the currently-pending slot (crash-loop
    # gate input); 0 when no spawn is pending
    spawn_failures: int = 0
    # slots mid-spawn or backing off: capacity already being added, so no
    # further scale-up is planned until they resolve
    pending_spawns: int = 0


@dataclass(frozen=True)
class Action:
    kind: str  # "scale_up" | "scale_down" | "replace" | "rerole"
    target: str | None = None  # replica addr (disruptive kinds)
    role: str = "unified"  # role to spawn with / flip to
    reason: str = ""


def _cooldown_of(policy: SupervisorConfig, kind: str) -> float:
    return {
        "scale_up": policy.scale_up_cooldown_s,
        "scale_down": policy.scale_down_cooldown_s,
        "replace": policy.replace_cooldown_s,
        "rerole": policy.rerole_cooldown_s,
    }[kind]


def _cooled(snap: FleetSnapshot, policy: SupervisorConfig, kind: str) -> bool:
    last = snap.last_action_t.get(kind)
    return last is None or (snap.now - last) >= _cooldown_of(policy, kind)


def _settled(snap: FleetSnapshot, window: float) -> bool:
    """True when NO action of any kind fired within `window` seconds.

    Scale-down uses this instead of its per-kind cooldown: a replace or
    scale-up means the fleet just changed shape, and the load signal a
    fresh replica reports (zero) is not evidence of idleness — retiring
    capacity right after surgery is how flaps happen.
    """
    if not snap.last_action_t:
        return True
    return (snap.now - max(snap.last_action_t.values())) >= window


def plan_actions(
    snap: FleetSnapshot, policy: SupervisorConfig
) -> list[Action]:
    """Pure policy: FleetSnapshot -> at most ONE Action.

    Priority order (first match wins):
      1. replace a dead / breaker-open replica — restoring promised
         capacity beats every optimization;
      2. re-role on a mix shift — rebalancing existing capacity is
         preferred over buying more (checked BEFORE scale-up);
      3. scale up under pressure (queue depth, sheds, or util above the
         high hysteresis mark);
      4. scale down when idle (util at/below the low mark, empty queue,
         no sheds).

    Invariants the caller can rely on: no plan drops the alive count
    below `min_replicas`; disruptive kinds are suppressed while one is
    in flight; every kind respects its cooldown (and scale-down waits
    out a global settle window after an action of ANY kind, so fresh
    capacity is never retired on the load it hasn't absorbed yet);
    spawns are suppressed
    once the pending slot has crash-looped (`spawn_failures >=
    spawn_max_attempts`) — the fleet degrades instead of fork-bombing.
    """
    alive = [r for r in snap.replicas if r.alive]
    n_alive = len(alive)
    floor = max(1, policy.min_replicas)
    can_spawn = snap.spawn_failures < max(1, policy.spawn_max_attempts)

    # 1. replace: dead first, then breaker-open (both are capacity the
    # fleet is paying for and not getting)
    if not snap.disruptive_inflight and _cooled(snap, policy, "replace"):
        broken = [r for r in snap.replicas if not r.alive] + [
            r for r in alive if r.breaker_state == "open"
        ]
        if broken:
            victim = broken[0]
            return [
                Action(
                    "replace",
                    target=victim.addr,
                    role=victim.role,
                    reason="dead" if not victim.alive else "breaker_open",
                )
            ]

    # 2. re-role: only for an already-disaggregated fleet (flipping a
    # unified fleet into roles is a topology decision, not autoscaling)
    disagg = any(r.role != "unified" for r in alive)
    if (
        policy.rerole_enabled
        and disagg
        and snap.prefill_share is not None
        and n_alive >= 2
        and not snap.disruptive_inflight
        and _cooled(snap, policy, "rerole")
    ):
        n_prefill = sum(1 for r in alive if r.role == "prefill")
        provisioned = n_prefill / n_alive
        mismatch = snap.prefill_share - provisioned
        if mismatch > policy.rerole_band:
            # more prefill work than prefill replicas: flip the least
            # loaded non-prefill replica — but never the last one (a
            # fleet of only prefill replicas can decode nothing)
            cands = sorted(
                (r for r in alive if r.role != "prefill"),
                key=lambda r: (r.load, r.addr),
            )
            if len(cands) >= 2:
                return [
                    Action(
                        "rerole",
                        target=cands[0].addr,
                        role="prefill",
                        reason=f"prefill_share={snap.prefill_share:.2f} "
                        f"> provisioned={provisioned:.2f}",
                    )
                ]
        elif mismatch < -policy.rerole_band and n_prefill >= 1:
            cands = sorted(
                (r for r in alive if r.role == "prefill"),
                key=lambda r: (r.load, r.addr),
            )
            return [
                Action(
                    "rerole",
                    target=cands[0].addr,
                    role="decode",
                    reason=f"prefill_share={snap.prefill_share:.2f} "
                    f"< provisioned={provisioned:.2f}",
                )
            ]

    # 3. scale up under pressure
    pressured = (
        snap.queue_depth >= max(1, policy.scale_up_queue_depth)
        or snap.shed_rate > 0.0
        or snap.util >= policy.scale_up_util
    )
    if (
        pressured
        and can_spawn
        and snap.pending_spawns == 0
        and n_alive + snap.pending_spawns < policy.max_replicas
        and _cooled(snap, policy, "scale_up")
    ):
        # new capacity joins the elastic pool: decode in a disaggregated
        # fleet (prefill count is re-role's business), unified otherwise
        return [
            Action(
                "scale_up",
                role="decode" if disagg else "unified",
                reason=f"queue={snap.queue_depth} shed_rate="
                f"{snap.shed_rate:.2f}/s util={snap.util:.2f}",
            )
        ]

    # 4. scale down when idle — hysteresis: util between the low and high
    # marks plans NOTHING (no flapping)
    idle = (
        snap.queue_depth == 0
        and snap.shed_rate <= 0.0
        and snap.util <= policy.scale_down_util
    )
    if (
        idle
        and n_alive > floor  # the min-capacity floor no plan may violate
        and not snap.disruptive_inflight
        # global settle window: any recent action (including a replace
        # or scale-up) resets the scale-down clock, so a just-spawned
        # replica's zero load can't be mistaken for fleet idleness
        and _settled(snap, policy.scale_down_cooldown_s)
    ):
        non_prefill = [r for r in alive if r.role != "prefill"]
        for victim in sorted(alive, key=lambda r: (r.load, r.addr)):
            if victim.role != "prefill" and len(non_prefill) <= 1:
                continue  # keep at least one decode-capable replica
            return [
                Action(
                    "scale_down",
                    target=victim.addr,
                    reason=f"util={snap.util:.2f} <= "
                    f"{policy.scale_down_util:.2f}",
                )
            ]
    return []


# -- executor ------------------------------------------------------------
class ReplicaHandle(Protocol):
    """What `spawn_fn` must return: a live replica's address plus a way
    to destroy it. LocalLauncher.spawn_decode_server returns a
    subprocess-backed one; tests/test_supervisor.py wraps stubs in it."""

    addr: str

    def kill(self) -> None: ...


class _Slot:
    """One managed replica position: either holds a live handle, or is
    pending a (re)spawn with backoff state, or is crash-looped."""

    __slots__ = (
        "slot_id",
        "role",
        "handle",
        "addr",
        "spawning",
        "fail_count",
        "next_spawn_t",
        "crash_looped",
        "health_fails",
    )

    def __init__(self, slot_id: int, role: str):
        self.slot_id = slot_id
        self.role = role
        self.handle: ReplicaHandle | None = None
        self.addr: str | None = None
        self.spawning = False
        self.fail_count = 0
        self.next_spawn_t = 0.0
        self.crash_looped = False
        self.health_fails = 0


class FleetSupervisor:
    """The control loop. Construct, `adopt()` any pre-existing replicas,
    then `await start()` on the event loop that will own it."""

    def __init__(
        self,
        router_addr: str,
        spawn_fn: Callable[[str], ReplicaHandle],
        *,
        config: SupervisorConfig | None = None,
        experiment_name: str = "",
        trial_name: str = "",
        time_fn: Callable[[], float] = time.monotonic,
    ):
        self.config = config or SupervisorConfig()
        self.router_addr = router_addr
        self._spawn_fn = spawn_fn
        self.experiment_name = experiment_name
        self.trial_name = trial_name
        self._time = time_fn
        # jitter source for spawn backoff (decision determinism is the
        # planner's job; backoff jitter exists to BREAK lockstep)
        self._rng = random.Random(0xA5CA1E)
        self._slots: dict[int, _Slot] = {}
        self._next_slot_id = 0
        self._last_action_t: dict[str, float] = {}
        self._disruptive_task: asyncio.Task | None = None
        self._last_tick_t: float | None = None
        self._prev_sheds: int | None = None
        # addr -> (prefill_secs_total, device_busy_s) at last tick, for
        # the prefill-share delta estimator
        self._prev_secs: dict[str, tuple[float, float]] = {}
        self._prefill_share: float | None = None
        self._replica_seconds = 0.0
        self._counters: dict[str, int] = dict(
            ticks_total=0,
            scale_ups_total=0,
            scale_downs_total=0,
            replacements_total=0,
            reroles_total=0,
            crash_loops_total=0,
            drain_rollbacks_total=0,
            spawn_attempts_total=0,
            spawn_failures_total=0,
            kills_total=0,
            health_flaps_total=0,
        )
        self._gauges: dict[str, Any] = dict(
            fleet_size=0,
            fleet_alive=0,
            pending_spawns=0,
            crash_looped_slots=0,
            queue_depth=0,
            shed_rate=0.0,
            util=0.0,
            prefill_share=0.0,
            disruptive_inflight=0,
        )
        # One asyncio event loop runs the tick loop AND every HTTP
        # handler; _lock makes multi-field control-plane updates atomic
        # across the awaits inside a tick (see _GUARDED_BY above).
        self._lock = asyncio.Lock()
        self._runner: web.AppRunner | None = None
        self._tick_task: asyncio.Task | None = None
        self.addr: str | None = None

    # -- fleet membership ------------------------------------------------
    def adopt(self, handle: ReplicaHandle, role: str = "unified") -> int:
        """Register a pre-existing replica as a managed slot. Call before
        start() (single-threaded setup) — the tick loop owns the slot
        table afterwards."""
        slot = _Slot(self._next_slot_id, role)
        self._next_slot_id += 1
        slot.handle = handle
        slot.addr = handle.addr
        self._slots[slot.slot_id] = slot
        return slot.slot_id

    def _slot_by_addr_locked(self, addr: str | None) -> _Slot | None:
        for s in self._slots.values():
            if s.addr == addr and s.handle is not None:
                return s
        return None

    def _survivors_locked(self, exclude: _Slot) -> list[str]:
        thresh = max(1, self.config.health_fail_threshold)
        return [
            s.addr
            for s in sorted(self._slots.values(), key=lambda s: s.slot_id)
            if s is not exclude
            and s.handle is not None
            and s.addr
            and s.health_fails < thresh
        ]

    # -- discovery plumbing ---------------------------------------------
    def _register(self, addr: str) -> None:
        if not (self.experiment_name and self.trial_name):
            return
        try:
            name_resolve.add(
                names.gen_server(self.experiment_name, self.trial_name, addr),
                addr,
                keepalive_ttl=None,
                replace=True,
            )
        except Exception as e:  # noqa: BLE001 — discovery best-effort
            logger.warning(f"register {addr} failed: {e!r}")

    def _deregister(self, addr: str | None) -> None:
        if not addr or not (self.experiment_name and self.trial_name):
            return
        try:
            name_resolve.delete(
                names.gen_server(self.experiment_name, self.trial_name, addr)
            )
        except Exception as e:  # noqa: BLE001 — already gone is fine
            logger.debug(f"deregister {addr}: {e!r}")

    # -- polling ---------------------------------------------------------
    async def _poll_router(self) -> dict[str, Any] | None:
        try:
            return await arequest_with_retry(
                self.router_addr,
                "/metrics",
                method="GET",
                timeout=self.config.health_timeout_s,
                max_retries=1,
            )
        except Exception as e:  # noqa: BLE001 — tick continues blind
            logger.warning(f"router metrics poll failed: {e!r}")
            return None

    async def _probe_health(self, slot: _Slot) -> tuple[int, bool]:
        try:
            await fault_injection.afire(
                "supervisor.health", target=slot.addr or ""
            )
            await arequest_with_retry(
                slot.addr,
                "/health",
                method="GET",
                timeout=self.config.health_timeout_s,
                max_retries=1,
            )
            return slot.slot_id, True
        except Exception as e:  # noqa: BLE001 — a failed poll IS the
            # signal: it feeds the consecutive-failure dead-marking
            logger.debug(f"health probe {slot.addr}: {e!r}")
            return slot.slot_id, False

    async def _poll_healths(self) -> list[tuple[int, bool]]:
        async with self._lock:
            live = [
                s
                for s in self._slots.values()
                if s.handle is not None and s.addr
            ]
        if not live:
            return []
        return list(
            await asyncio.gather(*(self._probe_health(s) for s in live))
        )

    def _fold_healths_locked(self, healths: list[tuple[int, bool]]) -> None:
        for sid, ok in healths:
            slot = self._slots.get(sid)
            if slot is None:
                continue
            if ok:
                # a blip that recovered before the dead threshold = flap
                if 0 < slot.health_fails < max(
                    1, self.config.health_fail_threshold
                ):
                    self._counters["health_flaps_total"] += 1
                slot.health_fails = 0
            else:
                slot.health_fails += 1

    # -- snapshot --------------------------------------------------------
    # metrics-consumer — every key read here must be produced by the
    # router /metrics surface (areal-lint AR303 checks the pairing)
    def _snapshot_locked(
        self, now: float, dt: float, router: dict[str, Any] | None
    ) -> FleetSnapshot:
        cfg = self.config
        router = router or {}
        breaker = router.get("breaker") or {}
        token_loads = router.get("token_loads") or {}
        request_counts = router.get("request_counts") or {}
        roles = router.get("roles") or {}
        pressure = router.get("pressure") or {}
        thresh = max(1, cfg.health_fail_threshold)

        views = []
        for slot in sorted(self._slots.values(), key=lambda s: s.slot_id):
            if slot.handle is None or not slot.addr:
                continue
            b = breaker.get(slot.addr) or {}
            views.append(
                ReplicaView(
                    addr=slot.addr,
                    alive=slot.health_fails < thresh,
                    role=str(roles.get(slot.addr, slot.role)),
                    breaker_state=str(b.get("state", "closed")),
                    load=float(token_loads.get(slot.addr, 0.0)),
                )
            )
        alive_addrs = [v.addr for v in views if v.alive]

        queue_depth = int(router.get("queue_depth", 0) or 0)
        sheds = int(router.get("queue_sheds_total", 0) or 0) + int(
            router.get("deadline_sheds_total", 0) or 0
        )
        shed_rate = 0.0
        if self._prev_sheds is not None and dt > 0:
            shed_rate = max(0, sheds - self._prev_sheds) / dt
        self._prev_sheds = sheds

        # util = demand / capacity: in-flight requests (router accounting,
        # present even when replicas export no /metrics) plus the queued
        # backlog, against the per-replica inflight target
        demand = (
            sum(int(request_counts.get(a, 0) or 0) for a in alive_addrs)
            + queue_depth
        )
        capacity = len(alive_addrs) * max(1, cfg.util_inflight_target)
        util = (demand / capacity) if capacity else (1.0 if demand else 0.0)

        # prefill work share: delta of prompt-prefill compute seconds over
        # delta of total busy seconds (prefill + decode), fleet-summed and
        # EWMA-smoothed — the TTFT-split counters behind the router's
        # pressure snapshots
        d_pre = d_busy = 0.0
        for addr, p in pressure.items():
            try:
                pre = float(p.get("prefill_secs_total", 0.0) or 0.0)
                busy = float(p.get("device_busy_s", 0.0) or 0.0)
            except (TypeError, ValueError):
                continue
            prev = self._prev_secs.get(addr)
            if prev is not None:
                d_pre += max(0.0, pre - prev[0])
                d_busy += max(0.0, busy - prev[1])
            self._prev_secs[addr] = (pre, busy)
        for addr in list(self._prev_secs):
            if addr not in pressure:
                del self._prev_secs[addr]
        total = d_pre + d_busy
        if total > 0:
            inst = d_pre / total
            self._prefill_share = (
                inst
                if self._prefill_share is None
                else 0.5 * self._prefill_share + 0.5 * inst
            )

        pending = [
            s
            for s in self._slots.values()
            if s.handle is None and not s.crash_looped
        ]
        spawn_failures = max(
            (
                s.fail_count
                for s in self._slots.values()
                if s.handle is None
            ),
            default=0,
        )
        return FleetSnapshot(
            now=now,
            replicas=tuple(views),
            queue_depth=queue_depth,
            shed_rate=shed_rate,
            util=util,
            prefill_share=self._prefill_share,
            last_action_t=dict(self._last_action_t),
            disruptive_inflight=(
                self._disruptive_task is not None
                and not self._disruptive_task.done()
            ),
            spawn_failures=spawn_failures,
            pending_spawns=len(pending),
        )

    # -- tick ------------------------------------------------------------
    async def _tick(self) -> None:
        now = self._time()
        router = await self._poll_router()
        healths = await self._poll_healths()
        async with self._lock:
            self._counters["ticks_total"] += 1
            dt = (
                now - self._last_tick_t
                if self._last_tick_t is not None
                else 0.0
            )
            self._last_tick_t = now
            self._fold_healths_locked(healths)
            snap = self._snapshot_locked(now, dt, router)
            n_alive = sum(1 for r in snap.replicas if r.alive)
            # replica-seconds: the capacity bill the autoscale bench
            # compares against a static fleet's
            self._replica_seconds += n_alive * dt
            self._gauges.update(
                fleet_size=len(snap.replicas),
                fleet_alive=n_alive,
                pending_spawns=snap.pending_spawns,
                crash_looped_slots=sum(
                    1 for s in self._slots.values() if s.crash_looped
                ),
                queue_depth=snap.queue_depth,
                shed_rate=round(snap.shed_rate, 6),
                util=round(snap.util, 6),
                prefill_share=round(snap.prefill_share or 0.0, 6),
                disruptive_inflight=int(snap.disruptive_inflight),
            )
            for act in plan_actions(snap, self.config):
                self._dispatch_locked(act, now)
            self._spawn_pending_locked(now)

    async def _tick_loop(self) -> None:
        while True:
            try:
                await self._tick()
            except Exception as e:  # noqa: BLE001 — keep the loop alive
                logger.warning(f"supervisor tick error: {e!r}")
            await asyncio.sleep(self.config.tick_interval_s)

    def _dispatch_locked(self, act: Action, now: float) -> None:
        if act.kind == "scale_up":
            slot = _Slot(self._next_slot_id, act.role)
            self._next_slot_id += 1
            slot.next_spawn_t = now
            self._slots[slot.slot_id] = slot
            self._last_action_t["scale_up"] = now
            self._counters["scale_ups_total"] += 1
            logger.info(
                f"scale_up -> slot {slot.slot_id} role={act.role} "
                f"({act.reason})"
            )
            return
        if act.kind not in DISRUPTIVE_KINDS:
            logger.warning(f"unknown action kind {act.kind!r}")
            return
        if (
            self._disruptive_task is not None
            and not self._disruptive_task.done()
        ):
            return  # one disruptive transition at a time
        slot = self._slot_by_addr_locked(act.target)
        if slot is None:
            return
        self._last_action_t[act.kind] = now
        logger.info(f"{act.kind} -> {act.target} ({act.reason})")
        self._disruptive_task = asyncio.create_task(
            self._run_disruptive(act, slot)
        )

    # -- spawn machinery -------------------------------------------------
    def _spawn_pending_locked(self, now: float) -> None:
        for slot in self._slots.values():
            if (
                slot.handle is None
                and not slot.crash_looped
                and not slot.spawning
                and slot.next_spawn_t <= now
            ):
                slot.spawning = True
                asyncio.get_running_loop().create_task(
                    self._spawn_slot(slot)
                )

    async def _spawn_slot(self, slot: _Slot) -> None:
        cfg = self.config
        loop = asyncio.get_running_loop()
        async with self._lock:
            self._counters["spawn_attempts_total"] += 1
        try:
            await fault_injection.afire(
                "supervisor.spawn",
                slot=str(slot.slot_id),
                role=slot.role,
            )
            handle = await loop.run_in_executor(
                None, self._spawn_fn, slot.role
            )
        except Exception as e:  # noqa: BLE001 — spawn failure is routine
            async with self._lock:
                slot.spawning = False
                slot.fail_count += 1
                self._counters["spawn_failures_total"] += 1
                if slot.fail_count >= max(1, cfg.spawn_max_attempts):
                    # crash-loop escalation: stop retrying, alert, degrade
                    slot.crash_looped = True
                    self._counters["crash_loops_total"] += 1
                    logger.warning(
                        f"slot {slot.slot_id} CRASH-LOOPED after "
                        f"{slot.fail_count} spawn failures: {e!r}"
                    )
                else:
                    backoff = min(
                        cfg.spawn_backoff_max_s,
                        cfg.spawn_backoff_s * (2 ** (slot.fail_count - 1)),
                    )
                    j = max(0.0, cfg.spawn_backoff_jitter)
                    if j:
                        backoff *= self._rng.uniform(1 - j, 1 + j)
                    slot.next_spawn_t = self._time() + backoff
                    logger.warning(
                        f"spawn attempt {slot.fail_count} for slot "
                        f"{slot.slot_id} failed: {e!r}; retry in "
                        f"{backoff:.2f}s"
                    )
            return
        async with self._lock:
            slot.spawning = False
            slot.handle = handle
            slot.addr = handle.addr
            slot.fail_count = 0
            slot.health_fails = 0
            peers = [
                s.addr
                for s in self._slots.values()
                if s.addr and s.addr != handle.addr and s.handle is not None
            ]
        # boot-config surface: one /info fetch per spawn, logged so a
        # mixed fleet (kv_dtype/weight_dtype drift makes replicas reject
        # each other's KV migrations as honest misses) is visible at
        # spawn time rather than at the first failed handoff
        try:
            info = await arequest_with_retry(
                handle.addr, "/info", method="GET", max_retries=1, timeout=5
            )
            logger.info(
                f"replica {handle.addr} booted: role={info.get('role')} "
                f"kv_dtype={info.get('kv_dtype')} "
                f"weight_dtype={info.get('weight_dtype')} "
                f"version={info.get('version')}"
            )
        except Exception as e:  # noqa: BLE001 — observability only; a
            # replica that cannot answer /info still registers and serves
            logger.debug(f"/info probe of {handle.addr} failed: {e!r}")
        if peers and getattr(cfg, "kv_fabric", True):
            # warm start: pull the siblings' hottest prefix blocks into
            # the new replica's host tier BEFORE the router sends traffic
            # (registration below), so its first requests promote instead
            # of prefilling from scratch. Best-effort — a failed warm-up
            # just means a cold cache.
            try:
                out = await arequest_with_retry(
                    handle.addr,
                    "/warm_start",
                    payload={
                        "peers": peers,
                        "max_sessions": int(
                            getattr(cfg, "warm_start_sessions", 4)
                        ),
                    },
                    timeout=self.config.drain_deadline_s,
                    max_retries=1,
                )
                logger.info(
                    f"slot {slot.slot_id} warm start: "
                    f"{out.get('sessions', 0)} sessions, "
                    f"{out.get('bytes', 0)} bytes from {len(peers)} peers"
                )
            except Exception as e:  # noqa: BLE001 — cold start is fine
                logger.warning(
                    f"warm start of {handle.addr} failed: {e!r}"
                )
        self._register(handle.addr)
        logger.info(
            f"slot {slot.slot_id} spawned {handle.addr} role={slot.role}"
        )

    # -- disruptive transitions ------------------------------------------
    async def _run_disruptive(self, act: Action, slot: _Slot) -> None:
        try:
            if act.kind == "scale_down":
                await self._do_scale_down(slot)
            elif act.kind == "replace":
                await self._do_replace(slot)
            elif act.kind == "rerole":
                await self._do_rerole(slot, act.role)
        except Exception as e:  # noqa: BLE001 — a failed transition is
            # retried by a later tick's plan; it must not kill the loop
            logger.warning(f"{act.kind} of {slot.addr} failed: {e!r}")

    # metrics-consumer — reads the router pressure map (AR303-paired)
    async def _refetchable_digest(
        self, survivors: list[str], victim: str | None
    ) -> str | None:
        """Union of the survivors' advertised fabric block keys (the
        kv_fabric_digest in the router's pressure snapshots): sessions
        whose blocks are all in this set drain as meta-only identity
        frames — a survivor can re-serve the bytes over /kv_fetch, so
        streaming them off the victim is pure waste."""
        router = await self._poll_router()
        if not router:
            return None
        pressure = router.get("pressure") or {}
        alive = set(survivors)
        keys: set[int] = set()
        for s, p in pressure.items():
            if s == victim or s not in alive:
                continue
            dig = (p or {}).get("kv_fabric_digest")
            if dig:
                keys |= set(kv_fabric.decode_digest(dig))
        if not keys:
            return None
        return kv_fabric.encode_digest(
            sorted(keys), cap=kv_fabric.DIGEST_HARD_CAP
        )

    async def _drain(self, slot: _Slot, survivors: list[str]) -> bool:
        """POST /drain bounded by drain_deadline_s. True = COMMITTED
        (every exportable session landed on a survivor); False = aborted
        (timeout/error) — the caller must roll back, not kill."""
        payload: dict[str, Any] = {"targets": survivors}
        if getattr(self.config, "kv_fabric", True):
            try:
                refetchable = await self._refetchable_digest(
                    survivors, slot.addr
                )
            except Exception as e:  # noqa: BLE001 — cheap-drain is an
                # optimization; a full-byte drain is always correct
                logger.debug(f"refetchable digest unavailable: {e!r}")
                refetchable = None
            if refetchable:
                payload["refetchable"] = refetchable

        async def _call():
            # the seam sits INSIDE the deadline window so an injected
            # delay is a hung drain, caught by the rollback path
            await fault_injection.afire(
                "supervisor.drain", target=slot.addr or ""
            )
            return await arequest_with_retry(
                slot.addr,
                "/drain",
                payload=payload,
                timeout=self.config.drain_deadline_s,
                max_retries=1,
            )

        try:
            resp = await asyncio.wait_for(
                _call(), timeout=self.config.drain_deadline_s
            )
        except Exception as e:  # noqa: BLE001 — hung/failed drain aborts
            logger.warning(f"drain of {slot.addr} did not commit: {e!r}")
            return False
        return bool(resp) and resp.get("status") == "ok"

    async def _kill(self, slot: _Slot) -> None:
        await fault_injection.afire(
            "supervisor.kill", target=slot.addr or ""
        )
        self._deregister(slot.addr)
        h = slot.handle
        if h is not None:
            try:
                await asyncio.get_running_loop().run_in_executor(
                    None, h.kill
                )
            except Exception as e:  # noqa: BLE001 — killing an
                # already-dead replica must not wedge the transition
                logger.debug(f"kill of {slot.addr}: {e!r}")
        async with self._lock:
            self._counters["kills_total"] += 1

    async def _do_scale_down(self, slot: _Slot) -> None:
        async with self._lock:
            survivors = self._survivors_locked(slot)
            if len(survivors) < max(1, self.config.min_replicas):
                return  # runtime floor guard (planner already enforces)
        if not await self._drain(slot, survivors):
            async with self._lock:
                self._counters["drain_rollbacks_total"] += 1
            logger.warning(
                f"scale_down of {slot.addr} rolled back (drain aborted)"
            )
            return
        await self._kill(slot)
        async with self._lock:
            self._slots.pop(slot.slot_id, None)
            self._counters["scale_downs_total"] += 1
        logger.info(f"scale_down committed: {slot.addr} retired")

    async def _do_replace(self, slot: _Slot) -> None:
        async with self._lock:
            survivors = self._survivors_locked(slot)
            reachable = slot.health_fails < max(
                1, self.config.health_fail_threshold
            )
        if survivors and reachable:
            # breaker-open but answering: salvage its sessions first. A
            # failed drain does NOT abort a replace — the replica is
            # broken either way, and the router's failover requeues what
            # the drain could not move.
            await self._drain(slot, survivors)
        await self._kill(slot)
        async with self._lock:
            old = slot.addr
            slot.handle = None
            slot.addr = None
            slot.fail_count = 0
            slot.health_fails = 0
            slot.next_spawn_t = self._time()
            self._counters["replacements_total"] += 1
        logger.info(f"replace: {old} killed; slot {slot.slot_id} respawning")

    async def _do_rerole(self, slot: _Slot, new_role: str) -> None:
        async with self._lock:
            survivors = self._survivors_locked(slot)
        if not survivors:
            return
        if not await self._drain(slot, survivors):
            async with self._lock:
                self._counters["drain_rollbacks_total"] += 1
            logger.warning(
                f"rerole of {slot.addr} rolled back (drain aborted)"
            )
            return
        resp = await arequest_with_retry(
            slot.addr,
            "/set_role",
            payload={"role": new_role},
            timeout=self.config.health_timeout_s,
            max_retries=2,
        )
        if resp.get("status") == "ok":
            async with self._lock:
                slot.role = new_role
                self._counters["reroles_total"] += 1
            logger.info(f"rerole committed: {slot.addr} -> {new_role}")

    # -- observability ---------------------------------------------------
    def get_metrics(self) -> dict[str, Any]:
        """Decision/action counters + per-tick fleet/SLO gauges. Reads
        without _lock: callers on other threads (bench) observe dict
        snapshots whose items are GIL-atomic scalars — same argument as
        the decode server's /metrics."""
        return {
            **self._counters,
            **self._gauges,
            "replica_seconds": round(self._replica_seconds, 3),
            "min_replicas": self.config.min_replicas,
            "max_replicas": self.config.max_replicas,
        }

    async def _supervisor_metrics(
        self, request: web.Request
    ) -> web.Response:
        async with self._lock:
            body = dict(self.get_metrics())
            body["slots"] = [
                {
                    "slot_id": s.slot_id,
                    "role": s.role,
                    "addr": s.addr,
                    "alive": s.handle is not None
                    and s.health_fails
                    < max(1, self.config.health_fail_threshold),
                    "spawning": s.spawning,
                    "fail_count": s.fail_count,
                    "crash_looped": s.crash_looped,
                    "health_fails": s.health_fails,
                }
                for s in sorted(
                    self._slots.values(), key=lambda s: s.slot_id
                )
            ]
        return web.json_response(body)

    async def _health(self, request: web.Request) -> web.Response:
        return web.json_response({"status": "ok"})

    # -- lifecycle -------------------------------------------------------
    def build_app(self) -> web.Application:
        app = web.Application()
        app.router.add_get("/health", self._health)
        # wire: external — ops surface (polled from outside the tree)
        app.router.add_get("/supervisor", self._supervisor_metrics)
        return app

    async def start(self, host: str = "0.0.0.0", port: int = 0) -> str:
        for slot in self._slots.values():
            if slot.addr:
                self._register(slot.addr)
        self._runner = web.AppRunner(self.build_app())
        await self._runner.setup()
        site = web.TCPSite(self._runner, host, port)
        await site.start()
        actual_port = self._runner.addresses[0][1]
        self.addr = f"{host}:{actual_port}"
        self._tick_task = asyncio.create_task(self._tick_loop())
        logger.info(
            f"fleet supervisor on {self.addr} (router {self.router_addr})"
        )
        return self.addr

    async def stop(self) -> None:
        if self._tick_task is not None:
            self._tick_task.cancel()
            self._tick_task = None
        if (
            self._disruptive_task is not None
            and not self._disruptive_task.done()
        ):
            self._disruptive_task.cancel()
        if self._runner is not None:
            await self._runner.cleanup()
            self._runner = None
        try:
            await close_current_session()  # this loop's cached client
        except Exception as e:  # noqa: BLE001 — teardown best-effort
            logger.debug(f"session close during stop: {e!r}")


def main(argv: list[str] | None = None) -> None:
    """Run a supervisor over a LocalLauncher-managed fleet: spawned
    replicas are decode-server subprocesses that self-register for the
    router to discover."""
    from areal_tpu.launcher.local import LocalLauncher

    p = argparse.ArgumentParser()
    p.add_argument("--experiment-name", required=True)
    p.add_argument("--trial-name", required=True)
    # knob: launcher-only — wiring, not a SupervisorConfig mirror
    p.add_argument("--router", required=True, help="router host:port")
    p.add_argument("--model-path", required=True)
    p.add_argument("--fileroot", default="/tmp/areal_tpu")
    p.add_argument("--host", default="0.0.0.0")
    p.add_argument("--port", type=int, default=0)
    p.add_argument("--min-replicas", type=int, default=1)
    p.add_argument("--max-replicas", type=int, default=8)
    p.add_argument(
        "--tick-interval", dest="tick_interval_s", type=float, default=1.0
    )
    # knob: launcher-only — forwarded verbatim to spawned decode servers
    p.add_argument(
        "--server-arg",
        action="append",
        default=[],
        help="extra decode_server CLI arg (repeatable)",
    )
    args = p.parse_args(argv)

    launcher = LocalLauncher(
        args.experiment_name, args.trial_name, args.fileroot
    )

    def spawn(role: str) -> ReplicaHandle:
        return launcher.spawn_decode_server(
            role,
            model_path=args.model_path,
            extra_args=list(args.server_arg),
        )

    cfg = SupervisorConfig(
        enabled=True,
        min_replicas=args.min_replicas,
        max_replicas=args.max_replicas,
        tick_interval_s=args.tick_interval_s,
    )
    sup = FleetSupervisor(
        args.router,
        spawn,
        config=cfg,
        experiment_name=args.experiment_name,
        trial_name=args.trial_name,
    )

    async def _serve():
        await sup.start(host=args.host, port=args.port)
        try:
            while True:
                await asyncio.sleep(3600)
        finally:
            await sup.stop()

    try:
        asyncio.run(_serve())
    except KeyboardInterrupt:
        launcher.stop_all()


if __name__ == "__main__":
    main()
