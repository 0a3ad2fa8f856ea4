"""Decode-fleet router: prefix-affinity scheduling, pressure-aware
admission with bounded queueing, and exactly-once failover.

Parity: realhf/system/gserver_manager.py:32 (GserverManager) — the service
that turns N independent decode servers into one fleet:

- **/schedule_request**: pick a server for a new generation request by
  policy — `prefix_affinity` (default), `round_robin`, `least_requests`,
  or `least_token_usage` — with qid affinity (all samples of one prompt
  group land on the same server, so its prefix cache works;
  gserver_manager.py:371-390). A request that resumes on the same weight
  version keeps its previous server (KV reuse). `prefix_affinity`
  additionally hashes the tokenized prompt prefix at block granularity
  (`prefix_block_tokens` x 1..`prefix_max_blocks`, longest match wins)
  into a per-server affinity map so GRPO group members, multi-turn
  sessions, and dup-prompt forks land on the replica already holding
  their donor KV blocks — overridden when the affine server is hot
  (`affinity_load_factor`).

  Admission is pressure-aware: the health poll snapshots each replica's
  kv-pool occupancy/fragmentation, host-tier state, and in-flight depth
  from `/metrics`; a request that would overflow EVERY replica's pool
  enters a bounded FIFO (deadline-based shedding; past `queue_max` or the
  deadline it is shed with 429 + Retry-After) instead of dogpiling the
  least-bad server and triggering a preemption storm.

- **/allocate_rollout**: the server-side staleness gate
  (gserver_manager.py:334 `is_staled`): expected_version =
  (trainer-consumed samples + running rollouts) // train_batch_size must
  not exceed current weight version + max_head_offpolicyness. The trainer
  publishes its consumed-sample counter under names.training_samples.
- **/finish_rollout**: decrement running, release load accounting.
- **/metrics**: routing observability — queue depth/sheds/timeouts,
  affinity hit rate, requeues, per-server pressure snapshots.

**Failover**: `dead_after_failures` consecutive failed health polls
declare a replica dead; its in-flight qids are requeued onto the
least-loaded survivors (so the clients' router-aware retries land there
deterministically) and every affinity entry pointing at the corpse is
drained. Exactly-once delivery is the pair of this requeue with the
decode servers' idempotency table (rid/xid dedup in
launcher/decode_server.py): a client retry can never double-generate or
double-count a rollout.

TPU-shape differences from the reference: weight versions come from the
decode servers' /health (they learn versions via the DCN push path, not
disk-reload polling), so the router polls health rather than orchestrating
`/update_weights_from_disk`; load metrics combine the servers' own
/metrics gauges with the router's routed-since-poll estimates.

Run: ``python -m areal_tpu.launcher.router --experiment-name e --trial-name t``
(servers discovered via name_resolve) or ``--servers host:p1,host:p2``.
"""

from __future__ import annotations

import argparse
import asyncio
import dataclasses
import math
import time
from collections import OrderedDict, defaultdict, deque
from typing import Any

from aiohttp import web

from areal_tpu.api.cli_args import RouterConfig
from areal_tpu.core import fault_injection, kv_fabric
from areal_tpu.utils import logging, name_resolve, names
from areal_tpu.utils.http import arequest_with_retry
from areal_tpu.utils.network import find_free_ports, gethostip

logger = logging.getLogger("rollout_router")

# consecutive /metrics failures before a server's measured token load is
# considered stale and dropped (least_token_usage then uses the estimate)
_METRICS_FAIL_LIMIT = 3

# Concurrency contract, checked by areal-lint (AR101/AR104; docs/ANALYSIS.md).
# Every handler AND the poll loop run on ONE aiohttp event loop; _lock is an
# asyncio.Lock making multi-field updates atomic across the awaits inside
# handlers. The registry declares the shared routing state that contract
# serializes (the lexical `async with self._lock` blocks are the guard).
_GUARDED_BY = {
    "DecodeRouter._rr": "_lock",
    "DecodeRouter._request_counts": "_lock",
    "DecodeRouter._token_usage": "_lock",
    "DecodeRouter._measured_tokens": "_lock",
    "DecodeRouter._est_since_poll": "_lock",
    "DecodeRouter._metrics_fail": "_lock",
    "DecodeRouter._health_fail": "_lock",
    "DecodeRouter._pressure": "_lock",
    "DecodeRouter._qid_to_server": "_lock",
    "DecodeRouter._qid_cost": "_lock",
    "DecodeRouter._qid_pending": "_lock",
    "DecodeRouter._qid_touched": "_lock",
    "DecodeRouter._prefix_map": "_lock",
    "DecodeRouter._fabric_index": "_lock",
    "DecodeRouter._waitq": "_lock",
    "DecodeRouter._counters": "_lock",
    "DecodeRouter._versions": "_lock",
    "DecodeRouter._running": "_lock",
    "DecodeRouter._submitted": "_lock",
    "DecodeRouter._accepted": "_lock",
    "DecodeRouter._breaker": "_lock",
    "DecodeRouter._roles": "_lock",
}

# /metrics keys the admission controller snapshots per replica
_PRESSURE_KEYS = (
    "running_requests",
    "queued_requests",
    "queued_tokens",
    "active_tokens",
    "kv_block_size",
    "kv_blocks_total",
    "kv_blocks_free",
    "kv_pool_fragmentation",
    "kv_tokens_allocated",
    "kv_host_pool_enabled",
    "kv_host_pool_occupancy",
    "prefix_cache_hit_rate",
    # fleet KV fabric: the per-replica block-index digest (content keys of
    # resident prefix blocks) drives remote-fetch routing hints; the hit /
    # avoided-token counters are summed fleet-wide on the router's /metrics
    "kv_dtype",
    "kv_fabric_digest",
    "kv_fabric_local_hits_total",
    "kv_fabric_remote_hits_total",
    "kv_fabric_fetch_bytes_total",
    "reprefill_tokens_avoided_total",
    # disaggregation observability: replica role + cross-replica KV
    # migration traffic, surfaced per-replica in the pressure snapshots
    # and summed fleet-wide on the router's /metrics
    "role",
    "kv_migrated_in_sessions_total",
    "kv_migrated_out_sessions_total",
    "kv_migrated_in_bytes_total",
    "kv_migrated_out_bytes_total",
    "kv_migrate_version_rejects_total",
    "ttft_prefill_p99_ms",
    "ttft_transfer_p99_ms",
    # fleet-supervisor inputs: the prefill/decode work-mix estimator
    # (launcher/supervisor.py) deltas these per tick for re-role decisions
    "prefill_secs_total",
    "device_busy_s",
)


class _Waiter:
    """One queued /schedule_request: resolved by the drain, or shed."""

    __slots__ = ("fut", "req", "enq_t", "deadline")

    def __init__(self, fut: asyncio.Future, req: dict, enq_t: float, deadline: float):
        self.fut = fut
        self.req = req
        self.enq_t = enq_t
        self.deadline = deadline


class DecodeRouter:
    def __init__(
        self,
        experiment_name: str = "",
        trial_name: str = "",
        servers: list[str] | None = None,
        *,
        config: RouterConfig | None = None,
        **overrides: Any,
    ):
        cfg = config or RouterConfig()
        if overrides:
            cfg = dataclasses.replace(cfg, **overrides)
        self.config = cfg
        self.experiment_name = experiment_name
        self.trial_name = trial_name
        self.schedule_policy = cfg.schedule_policy
        self.max_concurrent_rollouts = cfg.max_concurrent_rollouts
        self.max_head_offpolicyness = cfg.max_head_offpolicyness
        self.train_batch_size = max(1, cfg.train_batch_size)
        self.health_poll_interval = cfg.health_poll_interval

        self._seed_servers: list[str] = list(servers or [])
        self.servers: list[str] = list(self._seed_servers)
        self._rr = 0
        self._request_counts: dict[str, int] = defaultdict(int)
        self._token_usage: dict[str, float] = defaultdict(float)
        # least_token_usage inputs: the servers' own /metrics active-token
        # counts (measured, refreshed each poll) plus the estimated cost of
        # requests routed since that poll (not yet visible in the metrics).
        self._measured_tokens: dict[str, float] = {}
        self._est_since_poll: dict[str, float] = defaultdict(float)
        # consecutive failed /metrics polls per server: after
        # _METRICS_FAIL_LIMIT the measured base is dropped so _token_load
        # degrades to the router's own estimate instead of keeping an
        # arbitrarily stale measurement forever
        self._metrics_fail: dict[str, int] = defaultdict(int)
        # consecutive failed /health polls: crossing dead_after_failures
        # triggers failover (requeue + affinity drain) exactly once
        self._health_fail: dict[str, int] = defaultdict(int)
        # last /metrics pressure snapshot per server (admission inputs)
        self._pressure: dict[str, dict[str, Any]] = {}
        self._qid_to_server: dict[str, str] = {}
        self._qid_cost: dict[str, float] = {}
        # one qid may carry several in-flight requests (a GRPO group shares
        # its prompt's rid); release accounting one unit per finish
        self._qid_pending: dict[str, int] = {}
        # last-touched clock per qid (TTL expiry of leaked entries)
        self._qid_touched: dict[str, float] = {}
        # prefix-hash -> (server, last_used); recency-ordered (LRU + TTL)
        self._prefix_map: "OrderedDict[int, tuple[str, float]]" = OrderedDict()
        # fleet KV fabric: per-server resident block-key set, decoded from
        # the kv_fabric_digest each /metrics poll carries
        self._fabric_index: dict[str, set[int]] = {}
        # bounded FIFO of unschedulable requests (pressure everywhere)
        self._waitq: deque[_Waiter] = deque()
        self._counters: dict[str, int] = dict(
            schedules_total=0,
            affinity_hits_total=0,
            affinity_overrides_total=0,
            queue_enqueues_total=0,
            queue_admits_total=0,
            queue_sheds_total=0,
            queue_timeouts_total=0,
            client_requeues_total=0,
            requeues_total=0,
            failovers_total=0,
            expired_qids_total=0,
            expired_prefixes_total=0,
            breaker_trips_total=0,
            breaker_probes_total=0,
            breaker_probe_expiries_total=0,
            breaker_closes_total=0,
            deadline_sheds_total=0,
            disagg_schedules_total=0,
            fabric_local_routes_total=0,
            fabric_remote_hints_total=0,
        )
        # replica role ("unified" | "prefill" | "decode"), learned from
        # each /health poll: a disaggregated fleet schedules prefill by
        # prefix affinity and decode by kv-pool headroom
        self._roles: dict[str, str] = {}
        # per-replica circuit breaker (slow/erroring replicas are probed,
        # not hammered): state in {"closed", "open", "half_open"}, `bad` =
        # consecutive bad polls, `probes` = in-flight half-open probe
        # requests. A trip never touches affinity state — entries survive
        # and traffic returns through them once the breaker closes.
        # `probe_t` stamps the last probe charge: a probe whose client
        # died before completing (deadline shed) can never _release_qid,
        # so stale charges are expired on poll after breaker_probe_ttl_s
        # — without that, the breaker stays half-open with a full probe
        # budget FOREVER and the replica never re-enters rotation.
        # metrics-producer — per-server entries ride inside /metrics "breaker"
        self._breaker: dict[str, dict[str, Any]] = defaultdict(
            lambda: {"state": "closed", "bad": 0, "probes": 0, "probe_t": 0.0}
        )
        self._versions: dict[str, int] = {}
        self._running = 0  # guarded-by: _lock
        self._submitted = 0  # guarded-by: _lock
        self._accepted = 0  # guarded-by: _lock
        # One aiohttp event loop runs every handler AND _poll_loop; _lock
        # is an asyncio.Lock making multi-field load-accounting updates
        # atomic across the awaits inside handlers (areal-lint models all
        # async methods as one "eventloop" context — see docs/ANALYSIS.md).
        self._lock = asyncio.Lock()
        self._runner: web.AppRunner | None = None
        self._poll_task: asyncio.Task | None = None
        self.addr: str | None = None

    # -- fleet state ----------------------------------------------------
    def _discover(self) -> list[str]:
        # seed list is immutable: a server dropped after a failed health
        # poll re-enters the candidate set and returns once healthy again
        found: list[str] = []
        if self.experiment_name and self.trial_name:
            try:
                found = name_resolve.get_subtree(
                    names.gen_servers(self.experiment_name, self.trial_name)
                )
            except Exception as e:  # noqa: BLE001 — discovery best-effort
                logger.debug(f"server discovery failed: {e!r}")
                found = []
        return sorted(set(self._seed_servers) | set(found))

    async def _poll_loop(self) -> None:
        while True:
            try:
                servers = self._discover()

                # metrics-consumer — poll keys must be produced by the
                # decode-server /health + /metrics handlers (AR303)
                async def probe(s: str):
                    """health + metrics for one server, with the since-poll
                    estimate snapshotted at fetch time — requests routed
                    AFTER the snapshot are invisible to this measurement
                    and must survive the later subtraction. The trailing
                    element is the health RTT: the circuit breaker's
                    slow-replica signal (a replica that answers, slowly,
                    is degraded in a way a liveness bit cannot see)."""
                    t0 = time.monotonic()
                    try:
                        await fault_injection.afire("router.poll", server=s)
                        data = await arequest_with_retry(
                            s, "/health", method="GET", timeout=5,
                            max_retries=1,
                        )
                        version = int(data.get("version", 0))
                        role = str(data.get("role", "unified"))
                    except Exception:  # noqa: BLE001 — dead server drops out
                        logger.warning(f"server {s} failed health poll")
                        return (
                            s, None, None, 0.0, None,
                            time.monotonic() - t0, "unified",
                        )
                    rtt = time.monotonic() - t0
                    est_snapshot = self._est_since_poll[s]
                    try:
                        m = await arequest_with_retry(
                            s, "/metrics", method="GET", timeout=5,
                            max_retries=1,
                        )
                        # a server without real metrics answers {} — treat
                        # it as "no measurement" so the estimate fallback
                        # engages instead of a phantom zero load
                        load = (
                            float(m["active_tokens"])
                            + float(m.get("queued_tokens", 0.0))
                            if "active_tokens" in m
                            else None
                        )
                        pressure = (
                            {k: m[k] for k in _PRESSURE_KEYS if k in m}
                            if "active_tokens" in m
                            else None
                        )
                    except Exception as e:  # noqa: BLE001 — optional;
                        # the _metrics_fail counter escalates persistent
                        # failures to a warning at _METRICS_FAIL_LIMIT
                        logger.debug(f"metrics probe of {s} failed: {e!r}")
                        load = None
                        pressure = None
                    return s, version, load, est_snapshot, pressure, rtt, role

                # fan out: one hung server must not stale the whole fleet's
                # measurements for its full timeout
                probes = await asyncio.gather(*(probe(s) for s in servers))
                async with self._lock:
                    self._apply_probes_locked(servers, probes)
                    self._expire_locked(time.monotonic(), servers)
                    self._drain_queue_locked()
            except Exception as e:  # noqa: BLE001 — keep the loop alive
                logger.warning(f"router poll loop error: {e!r}")
            await asyncio.sleep(self.health_poll_interval)

    def _apply_probes_locked(self, servers: list[str], probes) -> None:
        """Fold one poll round into the fleet view: live-server set,
        versions, measured loads, pressure snapshots, and the
        failed-health / failed-metrics staleness counters (split out of
        _poll_loop so the staleness arithmetic unit-tests directly)."""
        versions = {p[0]: p[1] for p in probes if p[1] is not None}
        self.servers = [s for s in servers if s in versions]
        self._versions = versions
        for p in probes:
            s, v, load, est_snapshot, pressure = p[:5]
            # probes from older callers (unit tests) may omit the RTT/role
            rtt = p[5] if len(p) > 5 else None
            if v is not None:
                # role from /health (only live servers update it); the
                # pressure snapshot below carries it as a per-replica label
                self._roles[s] = p[6] if len(p) > 6 else "unified"
                if pressure is not None:
                    pressure = dict(pressure, role=self._roles[s])
            slow = (
                self.config.breaker_slow_s > 0
                and rtt is not None
                and rtt > self.config.breaker_slow_s
            )
            # erroring metrics count as a degradation signal only while a
            # measured base exists — servers that never export /metrics
            # must not trip the breaker by construction
            metrics_err = (
                v is not None and load is None and s in self._measured_tokens
            )
            self._breaker_update_locked(s, bad=(v is None) or slow or metrics_err)
            if v is None:
                self._health_fail[s] += 1
                if self._health_fail[s] == self.config.dead_after_failures:
                    self._failover_locked(s)
            else:
                self._health_fail[s] = 0
            if v is None or load is None:
                self._metrics_fail[s] += 1
                if (
                    self._metrics_fail[s] >= _METRICS_FAIL_LIMIT
                    and s in self._measured_tokens
                ):
                    del self._measured_tokens[s]
                    self._pressure.pop(s, None)
                    self._fabric_index.pop(s, None)
                continue
            self._metrics_fail[s] = 0
            self._measured_tokens[s] = load
            if pressure is not None:
                self._pressure[s] = pressure
                dig = pressure.get("kv_fabric_digest")
                if dig:
                    # stale keys age out with the next digest — a replica
                    # that evicted a block stops advertising it here
                    self._fabric_index[s] = set(kv_fabric.decode_digest(dig))
                else:
                    self._fabric_index.pop(s, None)
            # subtract only what the measurement could have
            # seen; later routings keep their estimated cost
            self._est_since_poll[s] = max(
                0.0, self._est_since_poll[s] - est_snapshot
            )

    # -- circuit breaker ------------------------------------------------
    def _breaker_update_locked(self, s: str, bad: bool) -> None:
        """Fold one poll outcome into the replica's breaker: trip after
        `breaker_trip_after` consecutive bad polls, go HALF-OPEN (probe
        traffic only) on the first healthy poll after a trip, relapse to
        open if a probe-phase poll goes bad again. CLOSING happens on
        probe-request completion (_release_qid), not here — re-entry is
        earned by serving a real request, not by answering a ping."""
        if not self.config.breaker_enabled:
            return
        b = self._breaker[s]
        if bad:
            b["bad"] += 1
            if (
                b["state"] == "closed"
                and b["bad"] >= self.config.breaker_trip_after
            ):
                b["state"] = "open"
                b["probes"] = 0
                self._counters["breaker_trips_total"] += 1
                logger.warning(
                    f"circuit breaker OPEN for {s} after {b['bad']} bad polls"
                )
            elif b["state"] == "half_open":
                b["state"] = "open"
                b["probes"] = 0
        else:
            b["bad"] = 0
            if b["state"] == "open":
                b["state"] = "half_open"
                b["probes"] = 0
                logger.info(f"circuit breaker HALF-OPEN for {s}: probing")

    def _breaker_admits(self, s: str) -> bool:
        """May a NEW request be routed to `s` right now? Open: no.
        Half-open: only while probe slots remain. Affinity entries for a
        tripped replica are preserved — they resume steering traffic the
        moment the breaker closes."""
        if not self.config.breaker_enabled:
            return True
        b = self._breaker[s]
        if b["state"] == "open":
            return False
        if b["state"] == "half_open":
            return b["probes"] < max(1, self.config.breaker_probe_requests)
        return True

    def _breaker_charge_locked(self, addr: str) -> None:
        """Account a scheduled request against a half-open breaker's
        probe budget."""
        if not self.config.breaker_enabled:
            return
        b = self._breaker[addr]
        if b["state"] == "half_open":
            b["probes"] += 1
            b["probe_t"] = time.monotonic()
            self._counters["breaker_probes_total"] += 1

    def _failover_locked(self, dead: str) -> None:
        """A replica crossed dead_after_failures: requeue its in-flight
        qids onto the least-loaded survivors (the clients' router-aware
        retries then land there deterministically — exactly-once paired
        with the servers' idempotency tables) and drain every affinity
        entry pointing at the corpse."""
        self._counters["failovers_total"] += 1
        survivors = [s for s in self.servers if s != dead]
        stale = [h for h, (s, _) in self._prefix_map.items() if s == dead]
        for h in stale:
            del self._prefix_map[h]
        moved = 0
        now = time.monotonic()
        for qid, srv in list(self._qid_to_server.items()):
            if srv != dead:
                continue
            pending = self._qid_pending.get(qid, 1)
            cost = self._qid_cost.get(qid, 0.0)
            self._request_counts[dead] = max(
                0, self._request_counts[dead] - pending
            )
            self._token_usage[dead] = max(0.0, self._token_usage[dead] - cost)
            self._est_since_poll[dead] = max(
                0.0, self._est_since_poll[dead] - cost
            )
            if survivors:
                new = min(survivors, key=self._token_load)
                self._qid_to_server[qid] = new
                self._qid_touched[qid] = now
                self._request_counts[new] += pending
                self._token_usage[new] += cost
                self._est_since_poll[new] += cost
                moved += 1
            else:
                # no survivor to carry the affinity: drop the entry; the
                # client's re-schedule queues until a replica returns
                self._qid_to_server.pop(qid, None)
                self._qid_cost.pop(qid, None)
                self._qid_pending.pop(qid, None)
                self._qid_touched.pop(qid, None)
        self._counters["requeues_total"] += moved
        # stale measurements must not keep the corpse looking admissible
        self._measured_tokens.pop(dead, None)
        self._pressure.pop(dead, None)
        # nor can a dead replica serve fabric fetches
        self._fabric_index.pop(dead, None)
        # death supersedes the breaker: a resurrected replica starts clean
        self._breaker.pop(dead, None)
        if moved or stale:
            logger.warning(
                f"failover: {dead} declared dead; requeued {moved} qids, "
                f"drained {len(stale)} prefix affinities"
            )

    def _expire_probes_locked(self, now: float) -> None:
        """Free half-open probe slots whose requests died with their
        clients (deadline shed before _release_qid): past
        breaker_probe_ttl_s the charge is dropped so the breaker can
        issue fresh probes instead of staying wedged half-open."""
        ttl = self.config.breaker_probe_ttl_s
        if not self.config.breaker_enabled or ttl <= 0:
            return
        for s, b in self._breaker.items():
            if (
                b["state"] == "half_open"
                and b["probes"] > 0
                and now - b.get("probe_t", 0.0) > ttl
            ):
                b["probes"] = 0
                self._counters["breaker_probe_expiries_total"] += 1
                logger.warning(
                    f"expired stale half-open probe charge for {s} "
                    f"(probe client died before completion)"
                )

    def _expire_locked(self, now: float, discovered: list[str]) -> None:
        """TTL/LRU expiry of routing state (a crashed client or a replaced
        fleet must not leak load accounting forever)."""
        self._expire_probes_locked(now)
        ttl = self.config.route_ttl_s
        if ttl > 0:
            for qid, t in list(self._qid_touched.items()):
                if now - t <= ttl:
                    continue
                # release every pending unit: the client that owned this
                # qid is gone, its /finish_request will never arrive
                while qid in self._qid_to_server:
                    self._release_qid(qid)
                self._qid_touched.pop(qid, None)
                self._counters["expired_qids_total"] += 1
            # _prefix_map is recency-ordered (touch == move_to_end), so
            # the stale entries are all at the front
            while self._prefix_map:
                h, (_, t) = next(iter(self._prefix_map.items()))
                if now - t <= ttl:
                    break
                del self._prefix_map[h]
                self._counters["expired_prefixes_total"] += 1
        while len(self._prefix_map) > self.config.route_max_entries:
            self._prefix_map.popitem(last=False)
            self._counters["expired_prefixes_total"] += 1
        over = len(self._qid_to_server) - self.config.route_max_entries
        if over > 0:
            oldest = sorted(self._qid_touched.items(), key=lambda kv: kv[1])
            for qid, _ in oldest[:over]:
                while qid in self._qid_to_server:
                    self._release_qid(qid)
                self._qid_touched.pop(qid, None)
                self._counters["expired_qids_total"] += 1
        # per-server counters for servers gone from discovery AND the seed
        # list (a server merely failing health stays — it may return)
        keep = set(discovered) | set(self._seed_servers)
        tracked = (
            set(self._request_counts)
            | set(self._token_usage)
            | set(self._est_since_poll)
            | set(self._metrics_fail)
            | set(self._health_fail)
            | set(self._measured_tokens)
            | set(self._pressure)
            | set(self._breaker)
            | set(self._roles)
            | set(self._fabric_index)
        )
        for s in tracked - keep:
            for d in (
                self._request_counts,
                self._token_usage,
                self._est_since_poll,
                self._metrics_fail,
                self._health_fail,
                self._measured_tokens,
                self._pressure,
                self._versions,
                self._breaker,
                self._roles,
                self._fabric_index,
            ):
                d.pop(s, None)

    @property
    def fleet_version(self) -> int:
        """Weight version of the fleet = min over servers (a conservative
        gate while a push is mid-fleet)."""
        return min(self._versions.values()) if self._versions else 0

    def _training_sample_cnt(self) -> int:
        try:
            return int(
                name_resolve.get(
                    names.training_samples(self.experiment_name, self.trial_name)
                )
            )
        except Exception as e:  # noqa: BLE001 — counter not published yet
            logger.debug(f"training-sample counter unavailable: {e!r}")
            return 0

    def _is_staled(self) -> bool:
        expected = (
            self._training_sample_cnt() + self._running
        ) // self.train_batch_size
        return expected > self.max_head_offpolicyness + self.fleet_version

    # -- scheduling -----------------------------------------------------
    def _token_load(self, s: str) -> float:
        """Current token load of a server: its last /metrics active-token
        count plus the estimated cost of requests routed there since that
        poll. Servers that never reported metrics fall back to the router's
        own full estimate (pre-/metrics behaviour)."""
        if s in self._measured_tokens:
            return self._measured_tokens[s] + self._est_since_poll[s]
        return self._token_usage[s]

    @staticmethod
    def _request_cost(req: dict[str, Any]) -> float:
        return float(req.get("prompt_len", 0)) + 0.4 * float(
            req.get("new_token_budget", 0)
        ) * float(req.get("group_size", 1))

    def _kv_headroom(self, s: str, need: float) -> float | None:
        """Tokens of pool capacity left on `s` after admitting a request
        needing `need` tokens, or None when the server never reported
        pressure (unknown => admissible, the pre-admission behaviour).
        Fragmented free blocks are subtracted (they cannot back another
        worst-case admission); a replica with the host KV tier enabled
        admits to the full pool — its evictions offload instead of
        dropping, so overflow degrades gracefully there."""
        p = self._pressure.get(s)
        if not p or not p.get("kv_blocks_total"):
            return None
        block = float(p.get("kv_block_size", 1) or 1)
        cap = float(p["kv_blocks_total"]) * block
        if not p.get("kv_host_pool_enabled"):
            cap *= self.config.kv_pressure_high
        frag = float(p.get("kv_pool_fragmentation", 0)) * block
        used = float(p.get("kv_tokens_allocated", 0.0)) + self._est_since_poll[s]
        return cap - frag - used - need

    def _admissible(self, s: str, need: float) -> bool:
        if not self._breaker_admits(s):
            return False
        limit = self.config.max_inflight_per_server
        if limit:
            p = self._pressure.get(s)
            if p is not None:
                depth = int(p.get("running_requests", 0)) + int(
                    p.get("queued_requests", 0)
                )
                if depth >= limit:
                    return False
        h = self._kv_headroom(s, need)
        return h is None or h >= 0.0

    def _fleet_kv_dtype(self) -> str:
        """KV dtype the fleet serves under (content-key salt). Replicas of
        one fleet share a dtype; any pressure snapshot carrying it wins."""
        for p in self._pressure.values():
            d = p.get("kv_dtype")
            if d:
                return str(d)
        return "bfloat16"

    def _fabric_chain(self, req: dict[str, Any]) -> list[int]:
        """Chained content keys of the request's prompt prefix — the SAME
        keys the engines index their pools under (kv_fabric.chain_keys,
        salted by weight version + kv dtype), so a router-side match is a
        statement about real resident KV bytes, not a hash collision or a
        stale-weights alias."""
        prefix = req.get("input_prefix")
        if not prefix:
            return []
        block = max(1, self.config.prefix_block_tokens)
        nb = min(len(prefix) // block, self.config.prefix_max_blocks)
        if nb <= 0:
            return []
        return kv_fabric.chain_keys(
            prefix,
            block,
            self.fleet_version,
            self._fleet_kv_dtype(),
            max_blocks=nb,
        )

    def _prefix_hashes(self, req: dict[str, Any]) -> list[int]:
        """Block-bucketed prompt-prefix content keys, longest first.

        Chained blake2b keys (not Python ``hash``): salted by weight
        version and kv dtype, so a weight flip retires every stale
        affinity entry instead of steering the new version's requests at
        KV computed under the old one, and identical across processes so
        the affinity map agrees with the replicas' own fabric digests."""
        return list(reversed(self._fabric_chain(req)))

    def _fabric_best_locked(
        self, chain: list[int], skip: str | None = None
    ) -> tuple[str | None, int]:
        """(server, blocks) of the longest resident run of `chain` across
        the fleet's advertised fabric digests, excluding `skip`."""
        best_s: str | None = None
        best_n = 0
        for s, keys in self._fabric_index.items():
            if s == skip or s not in self.servers:
                continue
            n = kv_fabric.longest_run(chain, keys)
            if n > best_n:
                best_s, best_n = s, n
        return best_s, best_n

    def _role_of(self, s: str) -> str:
        return self._roles.get(s, "unified")

    def _pick_locked(
        self, req: dict[str, Any]
    ) -> tuple[str | None, float, str | None]:
        """Choose server(s) for `req` -> (addr, prefix_discount_tokens,
        prefill_addr); addr None when no admissible server exists right
        now (the caller queues). The discount is the prompt work the
        chosen server SKIPS because it already holds the request's prefix
        KV (fork / suffix prefill instead of a full prefill) — the
        accounting charges the marginal cost, not the blind estimate, so
        affinity does not self-destruct by inflating the affine server's
        apparent load.

        Disaggregated fleets (prefill-role replicas alive): the request
        gets BOTH a decode home (picked by kv-pool headroom — the
        memory-bound resource that actually caps a decode replica) and a
        prefill replica (picked by prefix affinity — the prefill side is
        where donor-KV forks save the compute). prefill_addr None means
        no handoff: the decode server prefills itself, which is also the
        graceful degradation when every prefill replica is down/hot."""
        qid = req.get("qid")
        prev_url = req.get("previous_server_url")
        prev_version = req.get("previous_version")
        if (
            prev_url
            and prev_url in self.servers
            and prev_version == self.fleet_version
            and self._breaker_admits(prev_url)
        ):
            # resume with live KV on the same weights: the previous server
            # already holds the session — a prefill handoff would only
            # re-compute what is parked there
            return prev_url, 0.0, None
        if qid and qid in self._qid_to_server:
            cached = self._qid_to_server[qid]
            # a tripped breaker diverts even affine traffic — but the
            # mapping itself survives, so the qid returns home on close
            if cached in self.servers and self._breaker_admits(cached):
                return cached, 0.0, None
        need = self._request_cost(req)
        prefill_pool = [
            s for s in self.servers if self._role_of(s) == "prefill"
        ]
        decode_pool = [
            s for s in self.servers if self._role_of(s) != "prefill"
        ]
        if prefill_pool and decode_pool:
            return self._pick_disagg_locked(req, prefill_pool, decode_pool)
        candidates = [s for s in self.servers if self._admissible(s, need)]
        if not candidates:
            return None, 0.0, None
        policy = self.schedule_policy
        if policy == "prefix_affinity":
            addr, discount = self._pick_prefix_affine_locked(
                req, candidates, need
            )
            return addr, discount, None
        if policy == "round_robin":
            addr = candidates[self._rr % len(candidates)]
            self._rr += 1
        elif policy == "least_requests":
            addr = min(candidates, key=lambda s: self._request_counts[s])
        elif policy == "least_token_usage":
            addr = min(candidates, key=self._token_load)
        else:
            raise web.HTTPBadRequest(
                reason=f"unknown schedule policy {policy}"
            )
        return addr, 0.0, None

    def _pick_disagg_locked(
        self,
        req: dict[str, Any],
        prefill_pool: list[str],
        decode_pool: list[str],
    ) -> tuple[str | None, float, str | None]:
        """Role-aware pick: decode home by kv-pool headroom, prefill by
        prefix affinity. A handed-off request costs the decode replica
        only its DECODE share (the prompt KV arrives over the wire), so
        the decode accounting discounts the full prompt."""
        prompt_cost = float(req.get("prompt_len", 0))
        decode_need = max(self._request_cost(req) - prompt_cost, 0.0)
        decode_cands = [
            s for s in decode_pool if self._admissible(s, decode_need)
        ]
        if not decode_cands:
            return None, 0.0, None
        headrooms = {
            s: self._kv_headroom(s, decode_need) for s in decode_cands
        }
        if all(h is not None for h in headrooms.values()):
            # memory-bound role: the replica with the most pool headroom
            # absorbs the longest-lived KV working set
            addr = max(decode_cands, key=lambda s: headrooms[s])
        else:
            addr = min(decode_cands, key=self._token_load)
        prefill_cands = [
            s for s in prefill_pool if self._admissible(s, prompt_cost)
        ]
        prefill_addr = None
        if prefill_cands:
            # compute-bound role: prefix affinity lands GRPO siblings /
            # session turns where their donor KV already sits, turning
            # full prefills into forks/suffix passes
            prefill_addr, _ = self._pick_prefix_affine_locked(
                req, prefill_cands, prompt_cost
            )
            # transient charge, self-correcting at the next metrics poll
            # (the prefill replica's own /metrics absorbs the real load)
            self._est_since_poll[prefill_addr] += prompt_cost
        discount = prompt_cost if prefill_addr is not None else 0.0
        self._counters["disagg_schedules_total"] += 1
        return addr, discount, prefill_addr

    def _pick_prefix_affine_locked(
        self, req: dict[str, Any], candidates: list[str], need: float
    ) -> tuple[str, float]:
        hashes = self._prefix_hashes(req)
        block = max(1, self.config.prefix_block_tokens)
        now = time.monotonic()
        best = min(candidates, key=self._token_load)
        chosen = None
        discount = 0.0
        for i, h in enumerate(hashes):  # longest prefix first
            ent = self._prefix_map.get(h)
            if ent is None or ent[0] not in self.servers:
                continue
            affine = ent[0]
            # tokens of prompt the affine server's prefix cache covers
            matched = (len(hashes) - i) * block
            saved = min(matched, float(req.get("prompt_len", 0)))
            # affinity-vs-load override, by MARGINAL cost: routing here
            # costs load + (need - saved); routing to the least-loaded
            # candidate costs load_best + need, padded by the factor. A
            # hot (or inadmissible) affine server must not melt further
            # while siblings idle.
            hot = affine not in candidates or (
                self._token_load(affine) + need - saved
                > self.config.affinity_load_factor
                * (self._token_load(best) + need)
            )
            if hot:
                self._counters["affinity_overrides_total"] += 1
                break
            self._counters["affinity_hits_total"] += 1
            chosen = affine
            discount = saved
            break
        if chosen is None and hashes and getattr(self.config, "kv_fabric", True):
            # no affinity entry — but a candidate may hold the blocks
            # anyway (content-dedup'd from another request line, or
            # fabric-fetched earlier): route by advertised resident run,
            # priced with the same marginal-cost override as affinity
            chain = hashes[::-1]
            run_of = {
                s: kv_fabric.longest_run(chain, self._fabric_index[s])
                for s in candidates
                if s in self._fabric_index
            }
            cand = max(run_of, key=lambda s: run_of[s]) if run_of else None
            if cand is not None and run_of[cand] > 0:
                saved = min(
                    run_of[cand] * block, float(req.get("prompt_len", 0))
                )
                if (
                    self._token_load(cand) + need - saved
                    <= self.config.affinity_load_factor
                    * (self._token_load(best) + need)
                ):
                    chosen = cand
                    discount = saved
                    self._counters["fabric_local_routes_total"] += 1
        if chosen is None:
            chosen = best
        for h in hashes:
            self._prefix_map[h] = (chosen, now)
            self._prefix_map.move_to_end(h)
        return chosen, discount

    def _try_schedule_locked(self, req: dict[str, Any]) -> dict[str, Any] | None:
        """Pick + account, or None when every replica is saturated."""
        addr, discount, prefill_addr = self._pick_locked(req)
        if addr is None:
            return None
        qid = req.get("qid")
        fabric_hint = None
        if getattr(self.config, "kv_fabric", True):
            chain = self._fabric_chain(req)
            if chain:
                block = max(1, self.config.prefix_block_tokens)
                local = kv_fabric.longest_run(
                    chain, self._fabric_index.get(addr, frozenset())
                )
                peer, run = self._fabric_best_locked(chain, skip=addr)
                if peer is not None and run > local:
                    # marginal-cost model: the peer holds `run - local`
                    # more blocks than the chosen replica — fetching them
                    # over the wire costs kv_fabric_fetch_cost_factor of
                    # prefilling them, so the discount is the residual
                    factor = min(
                        max(
                            float(
                                getattr(
                                    self.config,
                                    "kv_fabric_fetch_cost_factor",
                                    0.25,
                                )
                            ),
                            0.0,
                        ),
                        1.0,
                    )
                    saved = (run - local) * block * (1.0 - factor)
                    prompt_len = float(req.get("prompt_len", 0))
                    discount = min(discount + saved, prompt_len)
                    fabric_hint = {
                        "peer": peer,
                        "keys": kv_fabric.encode_digest(chain[:run]),
                    }
                    self._counters["fabric_remote_hints_total"] += 1
        cost = max(self._request_cost(req) - discount, 0.0)
        self._counters["schedules_total"] += 1
        self._breaker_charge_locked(addr)
        self._request_counts[addr] += 1
        self._token_usage[addr] += cost
        self._est_since_poll[addr] += cost
        if qid:
            self._qid_to_server[qid] = addr
            self._qid_cost[qid] = self._qid_cost.get(qid, 0.0) + cost
            self._qid_pending[qid] = self._qid_pending.get(qid, 0) + 1
            self._qid_touched[qid] = time.monotonic()
        out = {"url": addr, "version": self.fleet_version}
        if fabric_hint is not None:
            # the decode server pulls these blocks from `peer` over the
            # migration wire before admission (decode_server._fabric_prefetch)
            out["kv_fabric"] = fabric_hint
        if prefill_addr is not None:
            # disaggregated fleet: the client runs the prompt on this
            # replica first (/prefill streams the KV to `url`), then
            # /generate on `url` resumes it with zero re-prefill
            out["prefill_url"] = prefill_addr
        return out

    def _drain_queue_locked(self) -> None:
        """Admit queued requests in FIFO order while pressure allows; an
        unschedulable head blocks the tail (ordering fairness)."""
        while self._waitq:
            w = self._waitq[0]
            if w.fut.done():  # already shed by its own deadline
                self._waitq.popleft()
                continue
            out = self._try_schedule_locked(w.req)
            if out is None:
                break
            self._waitq.popleft()
            self._counters["queue_admits_total"] += 1
            w.fut.set_result(out)

    def _shed_response(self, why: str) -> web.Response:
        ra = self.config.retry_after_s
        return web.json_response(
            {"url": None, "reason": why, "retry_after": ra},
            status=429,
            headers={"Retry-After": str(max(1, math.ceil(ra)))},
        )

    # -- handlers -------------------------------------------------------
    async def _schedule_request(self, request: web.Request) -> web.Response:
        req = await request.json()
        await fault_injection.afire(
            "router.schedule", qid=str(req.get("qid") or "")
        )
        loop = asyncio.get_running_loop()
        # the client ships its remaining deadline budget: a request must
        # not sit in the admission queue longer than its owner will wait
        # for the answer (holding it past that only wastes a queue slot
        # and schedules work nobody collects)
        try:
            deadline_s = float(req.get("deadline_s") or 0.0)
        except (TypeError, ValueError):
            deadline_s = 0.0
        hold = self.config.queue_timeout_s
        if deadline_s > 0.0:
            hold = min(hold, deadline_s)
        async with self._lock:
            if req.get("requeue") and req.get("qid"):
                # a router-aware client retry re-schedules the SAME logical
                # request: release the prior unit so accounting stays
                # balanced (its /finish_request fires only once)
                self._release_qid(req.get("qid"))
                self._counters["client_requeues_total"] += 1
            out = self._try_schedule_locked(req)
            if out is not None:
                return web.json_response(out)
            if hold <= 0.0:
                # budget already spent: shed immediately, don't queue
                self._counters["deadline_sheds_total"] += 1
                return self._shed_response("request deadline exhausted")
            if len(self._waitq) >= self.config.queue_max:
                self._counters["queue_sheds_total"] += 1
                return self._shed_response("admission queue full")
            now = time.monotonic()
            w = _Waiter(loop.create_future(), req, now, now + hold)
            self._waitq.append(w)
            self._counters["queue_enqueues_total"] += 1
        try:
            out = await asyncio.wait_for(w.fut, timeout=hold)
        except asyncio.TimeoutError:
            async with self._lock:
                try:
                    self._waitq.remove(w)
                except ValueError:
                    pass
                self._counters["queue_timeouts_total"] += 1
                if hold < self.config.queue_timeout_s:
                    self._counters["deadline_sheds_total"] += 1
            return self._shed_response("admission deadline exceeded")
        return web.json_response(out)

    async def _allocate_rollout(self, request: web.Request) -> web.Response:
        req = await request.json()
        async with self._lock:
            has_capacity = self._running < self.max_concurrent_rollouts
            staled = self._is_staled()
            if has_capacity and not staled:
                self._running += 1
                self._submitted += 1
                return web.json_response({"success": True, "reason": ""})
            reason = []
            if not has_capacity:
                reason.append(
                    f"capacity: {self._running} >= {self.max_concurrent_rollouts}"
                )
            if staled:
                reason.append(
                    f"staled: version {self.fleet_version} + offpolicyness "
                    f"{self.max_head_offpolicyness} exceeded"
                )
            return web.json_response(
                {"success": False, "reason": "; ".join(reason)}
            )

    def _release_qid(self, qid: str | None) -> None:
        """Release ONE in-flight unit of a qid's load accounting."""
        if not qid or qid not in self._qid_to_server:
            return
        addr = self._qid_to_server[qid]
        # a completed request against a half-open replica is the probe
        # succeeding: the breaker closes and full traffic (plus the
        # replica's surviving affinity entries) returns
        if self.config.breaker_enabled:
            b = self._breaker[addr]
            if b["state"] == "half_open" and b["probes"] > 0:
                b["probes"] -= 1
                b["state"] = "closed"
                b["bad"] = 0
                self._counters["breaker_closes_total"] += 1
                logger.info(f"circuit breaker CLOSED for {addr} (probe ok)")
        pending = self._qid_pending.get(qid, 1)
        unit_cost = self._qid_cost.get(qid, 0.0) / max(1, pending)
        self._request_counts[addr] = max(0, self._request_counts[addr] - 1)
        self._token_usage[addr] = max(
            0.0, self._token_usage[addr] - unit_cost
        )
        self._est_since_poll[addr] = max(
            0.0, self._est_since_poll[addr] - unit_cost
        )
        if pending <= 1:
            self._qid_to_server.pop(qid, None)
            self._qid_cost.pop(qid, None)
            self._qid_pending.pop(qid, None)
            self._qid_touched.pop(qid, None)
        else:
            self._qid_pending[qid] = pending - 1
            self._qid_cost[qid] = self._qid_cost[qid] - unit_cost

    async def _finish_rollout(self, request: web.Request) -> web.Response:
        req = await request.json()
        async with self._lock:
            self._running = max(0, self._running - 1)
            if req.get("accepted"):
                self._accepted += 1
            self._release_qid(req.get("qid"))
            self._drain_queue_locked()
            return web.json_response({"success": True})

    async def _finish_request(self, request: web.Request) -> web.Response:
        """Release a /schedule_request's load accounting WITHOUT touching
        the rollout-lifecycle counters (clients that only use routing —
        not /allocate_rollout — call this per completed generation)."""
        req = await request.json()
        async with self._lock:
            self._release_qid(req.get("qid"))
            self._drain_queue_locked()
            return web.json_response({"success": True})

    async def _health(self, request: web.Request) -> web.Response:
        async with self._lock:
            return web.json_response(
                {
                    "status": "ok",
                    "servers": self.servers,
                    "versions": self._versions,
                    "running": self._running,
                    "submitted": self._submitted,
                    "accepted": self._accepted,
                    "request_counts": dict(self._request_counts),
                    "token_loads": {
                        s: self._token_load(s) for s in self.servers
                    },
                }
            )

    async def _metrics(self, request: web.Request) -> web.Response:
        """Routing observability: queue/shedding state, affinity quality,
        failover activity, and the per-server pressure snapshots the
        admission controller is acting on — what the ops layer reads to
        judge routing quality."""
        async with self._lock:
            sched = self._counters["schedules_total"]
            hits = self._counters["affinity_hits_total"]
            # fleet-wide KV migration traffic, summed from the replicas'
            # pressure snapshots ("migrated" = sessions landed in a host
            # tier after a prefill handoff or a drain)
            mig_sessions = sum(
                int(p.get("kv_migrated_in_sessions_total", 0) or 0)
                for p in self._pressure.values()
            )
            mig_bytes = sum(
                int(p.get("kv_migrated_in_bytes_total", 0) or 0)
                for p in self._pressure.values()
            )

            # fleet-aggregate KV-fabric effectiveness (the bench's and the
            # supervisor's primary signal: tokens the fleet did NOT
            # re-prefill thanks to content-addressed reuse)
            def _fleet_sum(key: str) -> int:
                return sum(
                    int(p.get(key, 0) or 0) for p in self._pressure.values()
                )

            return web.json_response(
                {
                    "kv_fabric_local_hits_total": _fleet_sum(
                        "kv_fabric_local_hits_total"
                    ),
                    "kv_fabric_remote_hits_total": _fleet_sum(
                        "kv_fabric_remote_hits_total"
                    ),
                    "kv_fabric_fetch_bytes_total": _fleet_sum(
                        "kv_fabric_fetch_bytes_total"
                    ),
                    "reprefill_tokens_avoided_total": _fleet_sum(
                        "reprefill_tokens_avoided_total"
                    ),
                    "fabric_indexed_servers": len(self._fabric_index),
                    "schedule_policy": self.schedule_policy,
                    "servers": self.servers,
                    "roles": {s: self._role_of(s) for s in self.servers},
                    "kv_migrated_sessions_total": mig_sessions,
                    "kv_migrated_bytes_total": mig_bytes,
                    "queue_depth": sum(
                        1 for w in self._waitq if not w.fut.done()
                    ),
                    "queue_max": self.config.queue_max,
                    **self._counters,
                    "affinity_hit_rate": (
                        round(hits / sched, 6) if sched else 0.0
                    ),
                    "tracked_qids": len(self._qid_to_server),
                    "tracked_prefixes": len(self._prefix_map),
                    "running": self._running,
                    "request_counts": dict(self._request_counts),
                    "token_loads": {
                        s: self._token_load(s) for s in self.servers
                    },
                    "pressure": {
                        s: dict(p) for s, p in self._pressure.items()
                    },
                    "breaker": {
                        s: dict(b) for s, b in self._breaker.items()
                    },
                }
            )

    # -- lifecycle ------------------------------------------------------
    def build_app(self) -> web.Application:
        app = web.Application()
        app.router.add_get("/health", self._health)
        app.router.add_get("/metrics", self._metrics)
        app.router.add_post("/schedule_request", self._schedule_request)
        # production clients gate locally (core/staleness_manager) and only
        # route here; this is the reference-protocol server-side gate
        # wire: external
        app.router.add_post("/allocate_rollout", self._allocate_rollout)
        # wire: external — paired with /allocate_rollout for external clients
        app.router.add_post("/finish_rollout", self._finish_rollout)
        app.router.add_post("/finish_request", self._finish_request)
        return app

    async def start(self, host: str = "0.0.0.0", port: int = 0) -> str:
        self._runner = web.AppRunner(self.build_app())
        await self._runner.setup()
        site = web.TCPSite(self._runner, host, port)
        await site.start()
        actual_port = self._runner.addresses[0][1]
        report_host = gethostip() if host in ("0.0.0.0", "::") else host
        self.addr = f"{report_host}:{actual_port}"
        self._poll_task = asyncio.create_task(self._poll_loop())
        if self.experiment_name and self.trial_name:
            name_resolve.add(
                names.rollout_router(self.experiment_name, self.trial_name),
                self.addr,
                replace=True,
            )
        logger.info(f"rollout router on {self.addr}")
        return self.addr

    async def stop(self) -> None:
        if self._poll_task is not None:
            self._poll_task.cancel()
            self._poll_task = None
        if self._runner is not None:
            await self._runner.cleanup()
            self._runner = None


def main(argv: list[str] | None = None) -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--experiment-name", default="")
    p.add_argument("--trial-name", default="")
    # knob: launcher-only — seed list, not a RouterConfig mirror
    p.add_argument("--servers", default="", help="comma-separated host:port")
    p.add_argument("--host", default="0.0.0.0")
    p.add_argument("--port", type=int, default=0)
    defaults = RouterConfig()
    p.add_argument("--schedule-policy", default=defaults.schedule_policy)
    p.add_argument(
        "--max-concurrent-rollouts", type=int,
        default=defaults.max_concurrent_rollouts,
    )
    p.add_argument(
        "--max-head-offpolicyness", type=int,
        default=defaults.max_head_offpolicyness,
    )
    p.add_argument(
        "--train-batch-size", type=int, default=defaults.train_batch_size
    )
    p.add_argument(
        "--health-poll-interval", type=float,
        default=defaults.health_poll_interval,
    )
    p.add_argument("--queue-max", type=int, default=defaults.queue_max)
    p.add_argument(
        "--queue-timeout-s", type=float, default=defaults.queue_timeout_s
    )
    p.add_argument(
        "--kv-pressure-high", type=float, default=defaults.kv_pressure_high
    )
    p.add_argument(
        "--route-ttl-s", type=float, default=defaults.route_ttl_s
    )
    args = p.parse_args(argv)
    # join the experiment's shared discovery store (launcher-provided env)
    # — without this a standalone router process can neither discover the
    # decode servers nor register its own address for the clients
    name_resolve.reconfigure_from_env()

    async def _serve():
        router = DecodeRouter(
            args.experiment_name,
            args.trial_name,
            [s for s in args.servers.split(",") if s],
            config=RouterConfig(
                schedule_policy=args.schedule_policy,
                max_concurrent_rollouts=args.max_concurrent_rollouts,
                max_head_offpolicyness=args.max_head_offpolicyness,
                train_batch_size=args.train_batch_size,
                health_poll_interval=args.health_poll_interval,
                queue_max=args.queue_max,
                queue_timeout_s=args.queue_timeout_s,
                kv_pressure_high=args.kv_pressure_high,
                route_ttl_s=args.route_ttl_s,
            ),
        )
        await router.start(args.host, args.port)
        await asyncio.Event().wait()

    asyncio.run(_serve())


if __name__ == "__main__":
    main()
