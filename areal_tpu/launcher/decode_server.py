"""HTTP decode server: the TPU-native analogue of an SGLang/vLLM server.

Wraps a `JaxDecodeEngine` behind the JSON-over-HTTP control plane that
`RemoteInfEngine` speaks. Parity targets: the server side of
areal/engine/sglang_remote.py (endpoint set) and
areal/launcher/sglang_server.py (subprocess wrapper: health wait +
name_resolve registration).

Endpoints:
  GET  /health                  -> {"status": "ok", "version": N}
  GET  /info                    -> model/config metadata
  POST /generate                -> one completion w/ token logprobs+versions;
                                   an optional "xid" delivery id makes the
                                   call idempotent: a retry of an in-flight
                                   submission awaits the SAME engine future
                                   and a replay of a completed one returns
                                   the cached response (exactly-once under
                                   client retry + router failover-requeue)
  POST /pause_generation        -> pause on chunk boundary; {"abort": true}
                                   flushes in-flight requests, which return
                                   stop_reason="interrupt" (partial rollout)
  POST /continue_generation
  POST /update_weights_from_disk  {"path": ..., "version": optional}
  POST /update_weights_from_tensor?push_id=ID   framed weight bucket; stages
                                   with generation LIVE (no pause)
  POST /commit_weights            {"version", "push_id", "lora_scale"?} —
                                   the only pause window: install + stamp
                                   version atomically; stale push_id -> 409
  POST /abort_weights             {"push_id"} — drop staging for a failed push
  POST /set_version               {"version": N}

Disaggregated prefill/decode (--role {unified,prefill,decode}):

  POST /prefill                   run ONLY the prompt prefill (body like
                                   /generate + optional "target" decode
                                   replica + "xid"); the parked session is
                                   then streamed server→server to the
                                   target over the KV wire format, where
                                   it lands in the host tier and the
                                   client's /generate resumes it with
                                   ZERO re-prefill. Transfer failures
                                   degrade: the decode replica simply
                                   re-prefills (honest miss).
  POST /kv_recv?xid=ID            one framed KV bucket (pack_kv_session);
                                   staged per-xid with interval-merged
                                   coverage — duplicate/re-split retry
                                   frames are safe, torn frames are
                                   rejected before a byte stages
  POST /kv_commit                 {"xid"} — finalize + import the staged
                                   session(s); idempotent per xid (a
                                   retried commit replays the cached
                                   result, never double-imports)
  POST /drain                     {"targets": [addr...]} — park in-flight
                                   generations (clients resume via their
                                   interrupt loop) and stream every
                                   parked + host-tier session to the
                                   targets: scale-down without losing a
                                   single session to re-prefill

Generation runs on the engine's background scheduler thread; the aiohttp
loop only brokers futures, so thousands of streams multiplex over one
static-shape decode program.
"""

from __future__ import annotations

import argparse
import asyncio
import dataclasses
import os
import socket
import time
from collections import OrderedDict
from typing import Any

from aiohttp import web

from areal_tpu.api.cli_args import (
    GenerationHyperparameters,
    InferenceEngineConfig,
    JaxDecodeConfig,
)
from areal_tpu.api.io_struct import ModelRequest, WeightUpdateMeta
from areal_tpu.core import fault_injection, kv_fabric
from areal_tpu.utils import logging, names
from areal_tpu.utils import name_resolve

logger = logging.getLogger("decode_server")

_GCONFIG_FIELDS = {f.name for f in dataclasses.fields(GenerationHyperparameters)}


def _parse_gconfig(d: dict[str, Any]) -> GenerationHyperparameters:
    return GenerationHyperparameters(
        **{k: v for k, v in d.items() if k in _GCONFIG_FIELDS}
    )


class DecodeServer:
    def __init__(
        self,
        config: JaxDecodeConfig,
        inference_config: InferenceEngineConfig | None = None,
        tokenizer: Any = None,
        engine: Any = None,
        shutdown_grace: float = 5.0,
    ):
        from areal_tpu.engine.jax_decode import JaxDecodeEngine

        self.config = config
        # how long stop() waits for in-flight handlers before cancelling
        # them (aiohttp shutdown_timeout); short so a killed replica's
        # clients fail fast into their router-aware failover retry
        self.shutdown_grace = shutdown_grace
        self.engine = engine or JaxDecodeEngine(
            config, inference_config or InferenceEngineConfig(), tokenizer
        )
        self._owns_engine = engine is None
        self._runner: web.AppRunner | None = None
        self.addr: str | None = None
        # Threading model (docs/architecture.md "Threading model and lock
        # hierarchy"): every handler runs on ONE aiohttp event loop, so
        # handler-local state below is single-threaded between awaits;
        # critical sections that span an await (pause/commit windows) are
        # serialized by _ctl_lock. areal-lint (AR101) models async handlers
        # as one "eventloop" context for the same reason.
        # Set by /pause_generation, cleared by /continue_generation: a weight
        # update must not cancel a pause the client asked for explicitly.
        self._client_paused = False  # guarded-by: _ctl_lock
        # Serialises pause/continue/weight-swap: a /continue_generation must
        # not resume decoding in the middle of an in-flight swap, or tokens
        # from the new weights would carry the old version stamp.
        self._ctl_lock = asyncio.Lock()
        # Buckets staged by /update_weights_from_tensor until /commit_weights.
        from areal_tpu.core.weight_transfer import WeightStaging

        self._weight_staging = WeightStaging()  # guarded-by: _ctl_lock
        self._staging_push_id: str | None = None  # guarded-by: _ctl_lock
        self._staging_t0: float | None = None  # guarded-by: _ctl_lock
        # last frame arrival for the crash-mid-stage reaper: staging whose
        # feed went silent for weight_staging_ttl_s is dropped (push-id
        # epoch cleared) the next time a weight endpoint runs
        self._staging_last_frame_t: float | None = None  # guarded-by: _ctl_lock
        self._last_commit_version: int | None = None  # guarded-by: _ctl_lock
        self._last_commit_push_id: str | None = None  # guarded-by: _ctl_lock
        # weight-sync observability (server side); merged into /metrics.
        # /metrics reads it without _ctl_lock: the read happens between
        # awaits on the same loop, so it observes an atomic snapshot.
        self._sync_stats = dict(  # guarded-by: _ctl_lock
            n_pushes=0,
            wire_bytes=0,
            # bf16-equivalent bytes of the frames received — raw/sent is
            # the int8 weight-serving compression ratio (ISSUE 16)
            wire_bytes_raw=0,
            staging_secs=0.0,
            commit_pause_secs=0.0,
            aborted_pushes=0,
            reaped_pushes=0,
        )
        # Idempotency table (exactly-once failover, ISSUE 8): xid ->
        # {"done": False, "fut": Future} while a submission is in flight,
        # {"done": True, "resp": dict, "t": monotonic} afterwards. All
        # reads/writes happen on the one aiohttp event loop with no await
        # between check-and-insert, so the table needs no lock; duplicates
        # await the in-flight future via asyncio.shield (a shed duplicate
        # must not cancel the original generation). Bounded by
        # config.idempotency_entries (LRU) + idempotency_ttl_s (completed
        # entries only — in-flight ones are naturally bounded by the
        # engine's concurrency).
        self._idem: "OrderedDict[str, dict[str, Any]]" = OrderedDict()
        self._idem_hits = 0
        # -- cross-replica KV migration state (ISSUE 10) ----------------
        # All accessed only between awaits on the one aiohttp event loop
        # (same single-context argument as _idem above — no lock needed).
        # Per-xid staging for inbound KV sessions: the sender may re-send
        # every frame on a retry; WeightStaging's interval-merged coverage
        # absorbs duplicates, and a torn frame is rejected before staging.
        self._kv_staging: "OrderedDict[str, dict[str, Any]]" = OrderedDict()
        # xid -> completed /kv_commit response: a retried commit (sender
        # replaying a migration whose response was lost) returns the
        # cached result instead of importing twice — the exactly-once
        # half the sender's full-stream replay relies on.
        self._kv_done: "OrderedDict[str, dict[str, Any]]" = OrderedDict()
        self._migrate_stats = dict(
            out_sessions=0,
            out_bytes=0,
            out_failures=0,
            in_frames=0,
            in_commits=0,
            commit_dedups=0,
            transfer_secs=0.0,
        )
        # In-progress /drain guard (drains are serialized per server):
        # while a drain runs, this holds its result future; concurrent
        # /drain calls await it and replay the first result instead of
        # double-exporting the same sessions. Claimed with no await after
        # the done-check, so the check-and-set is event-loop-atomic.
        self._drain_inflight: asyncio.Future | None = None
        # -- fleet KV fabric (ISSUE 17) ---------------------------------
        # Outbound-fetch dedup: concurrent /generate's carrying the same
        # router hint await ONE peer fetch instead of each pulling the
        # same blocks (event-loop-atomic claim, like _idem). Stats merge
        # into /metrics under "kv_fabric".
        self._fabric_inflight: dict[str, asyncio.Future] = {}
        self._fabric_stats = dict(
            fetch_attempts=0,
            fetch_sessions=0,
            fetch_bytes=0,
            fetch_failures=0,
            serve_sessions=0,
            serve_bytes=0,
            warm_start_sessions=0,
            warm_start_bytes=0,
        )

    # -- handlers -------------------------------------------------------
    async def _health(self, request: web.Request) -> web.Response:
        return web.json_response(
            {
                "status": "ok",
                "version": self.engine.get_version(),
                # the router's role-aware scheduler reads this: prefill
                # replicas are picked by prefix affinity, decode replicas
                # by kv-pool headroom
                "role": getattr(self.config, "role", "unified"),
            }
        )

    async def _info(self, request: web.Request) -> web.Response:
        return web.json_response(
            {
                "model_path": self.config.model_path,
                "role": getattr(self.config, "role", "unified"),
                "kv_migrate_chunk_mb": getattr(
                    self.config, "kv_migrate_chunk_mb", 64.0
                ),
                "context_length": self.config.context_length,
                "max_running_requests": self.config.max_running_requests,
                "decode_runahead_chunks": self.config.decode_runahead_chunks,
                "kv_dtype": getattr(self.config, "kv_dtype", "fp"),
                "weight_dtype": getattr(self.config, "weight_dtype", "fp"),
                "kv_host_pool_mb": self.config.kv_host_pool_mb,
                "paged_attn_impl": self.config.paged_attn_impl,
                "spec_decode": self.config.spec_decode,
                "spec_k": self.config.spec_k,
                "spec_ngram_max": self.config.spec_ngram_max,
                "version": self.engine.get_version(),
            }
        )

    def _prune_idem(self) -> None:
        now = time.monotonic()
        ttl = self.config.idempotency_ttl_s
        for xid in list(self._idem):
            ent = self._idem[xid]
            if ent["done"] and now - ent["t"] > ttl:
                del self._idem[xid]
        while len(self._idem) > max(1, self.config.idempotency_entries):
            # oldest completed entry first; in-flight entries only under
            # pathological pressure (they are few: engine concurrency)
            victim = next(
                (x for x, e in self._idem.items() if e["done"]),
                next(iter(self._idem)),
            )
            del self._idem[victim]

    async def _generate(self, request: web.Request) -> web.Response:
        body = await request.json()
        xid = body.get("xid")
        # pre-effect seam: an abort here rejects the request before any
        # engine state moves (clean client retry); a delay is the
        # slow-replica shape the router's circuit breaker must absorb
        await fault_injection.afire(
            "server.generate",
            rid=str(body.get("rid") or ""), xid=str(xid or ""),
            addr=str(self.addr or ""),
        )
        if xid is not None:
            ent = self._idem.get(xid)
            if ent is not None:
                # duplicate delivery (client transport retry, or a retry
                # after failover raced the original): never re-generate
                self._idem_hits += 1
                if ent["done"]:
                    self._idem.move_to_end(xid)
                    return web.json_response(
                        {**ent["resp"], "dedup": "completed"}
                    )
                out = await asyncio.shield(ent["fut"])
                return web.json_response({**out, "dedup": "in_progress"})
            ent = {
                "done": False,
                "fut": asyncio.get_running_loop().create_future(),
                "t": time.monotonic(),
            }
            self._idem[xid] = ent
        hint = body.get("kv_fabric")
        if hint and getattr(self.config, "kv_fabric", True):
            # router says a sibling holds this prefix: pull the block
            # runs into the host tier before admission looks for them
            await self._fabric_prefetch(hint)
        req = ModelRequest(
            rid=body.get("rid") or ModelRequest().rid,
            input_ids=[int(t) for t in body["input_ids"]],
            gconfig=_parse_gconfig(body.get("gconfig", {})),
            image_data=body.get("image_data"),
        )
        try:
            resp = await self.engine.agenerate(req)
        except BaseException as e:
            if xid is not None and self._idem.get(xid) is ent:
                del self._idem[xid]
                if not ent["fut"].done():
                    ent["fut"].set_exception(e)
                    # mark retrieved: with no duplicate awaiting, an
                    # unconsumed future exception would log noise
                    ent["fut"].exception()
            raise
        out = {
            "output_tokens": resp.output_tokens,
            "output_logprobs": resp.output_logprobs,
            "output_versions": resp.output_versions,
            "output_reveal_steps": resp.output_reveal_steps,
            "stop_reason": resp.stop_reason,
            "latency": resp.latency,
            "ttft": resp.ttft,
            "itl": resp.itl,
        }
        if xid is not None and self._idem.get(xid) is ent:
            self._idem[xid] = {"done": True, "resp": out, "t": time.monotonic()}
            self._idem.move_to_end(xid)
            if not ent["fut"].done():
                ent["fut"].set_result(out)
            self._prune_idem()
        return web.json_response(out)

    async def _metrics(self, request: web.Request) -> web.Response:
        """Live engine load counters (running/queued requests, active KV
        tokens, generated-token totals, prefix-cache hit mix) plus the
        decode-loop timing split (itl_p50_ms/itl_p99_ms: device-only
        inter-token latency; device_idle_frac: host-gap fraction the
        run-ahead scheduler hides). The router's least_token_usage policy
        polls this — parity with the per-server token accounting of
        realhf/system/gserver_manager.py:261-339."""
        get = getattr(self.engine, "get_metrics", None)
        if get is None:
            # 404, not {}: the router must fall back to its own estimates
            # rather than record a phantom zero load
            raise web.HTTPNotFound(reason="engine exports no metrics")
        out = dict(get())
        ws = dict(self._sync_stats, staged_tensors=len(self._weight_staging))
        ws["wire_bytes_sent"] = ws["wire_bytes"]
        # raw/sent: 1.0 on fp pushes, ~2x once the producer ships int8
        # kernels (weight_transfer.raw_wire_nbytes)
        ws["weight_sync_compression"] = (
            round(ws["wire_bytes_raw"] / ws["wire_bytes_sent"], 4)
            if ws["wire_bytes_sent"]
            else 1.0
        )
        out["weight_sync"] = ws
        # rid-dedup observability: table occupancy + duplicate deliveries
        # prevented (the exactly-once evidence tests/test_chaos.py reads)
        out["idem_entries"] = len(self._idem)
        out["idem_hits_total"] = self._idem_hits
        # KV-migration observability (server side): sessions/bytes
        # streamed out, inbound frames/commits, commit dedups (the
        # exactly-once evidence), and abandoned transfers (degraded to
        # re-prefill). The engine's own kv_migrated_* counters sit next
        # to these at the top level.
        out["kv_migrate"] = dict(
            self._migrate_stats,
            staging_xids=len(self._kv_staging),
            done_xids=len(self._kv_done),
        )
        # fleet KV fabric (server side): prefetches issued/served, bytes
        # moved, failures (each one a degraded-to-local-prefill), and
        # warm-start pulls. Engine-side kv_fabric_* counters (hits,
        # tokens avoided, digest) are already in `out`.
        out["kv_fabric"] = dict(
            self._fabric_stats, inflight=len(self._fabric_inflight)
        )
        return web.json_response(out)

    async def _pause(self, request: web.Request) -> web.Response:
        try:
            body = await request.json()
        except Exception as e:  # noqa: BLE001 — body is optional
            logger.debug(f"/pause body ignored: {e!r}")
            body = {}
        # pause_generation blocks until the scheduler is idle — run it off
        # the event loop so in-flight /generate futures can resolve.
        async with self._ctl_lock:
            self._client_paused = True
            await asyncio.get_running_loop().run_in_executor(
                None, self.engine.pause_generation
            )
            aborted = 0
            if body.get("abort"):
                aborted = self.engine.abort_all()
        return web.json_response({"status": "ok", "aborted": aborted})

    async def _continue(self, request: web.Request) -> web.Response:
        async with self._ctl_lock:
            self._client_paused = False
            self.engine.continue_generation()
        return web.json_response({"status": "ok"})

    async def _update_weights_from_disk(
        self, request: web.Request
    ) -> web.Response:
        body = await request.json()
        meta = WeightUpdateMeta(type="disk", path=body["path"])
        version = body.get("version")

        def _swap():
            # Hold the pause across swap + version bump so no token is ever
            # produced by the new weights under the old version stamp.
            self.engine.pause_generation()
            try:
                self.engine.update_weights_from_disk(meta)
                if version is not None:
                    self.engine.set_version(int(version))
            finally:
                if not self._client_paused:
                    self.engine.continue_generation()

        async with self._ctl_lock:
            await asyncio.get_running_loop().run_in_executor(None, _swap)
        return web.json_response(
            {"status": "ok", "version": self.engine.get_version()}
        )

    async def _set_version(self, request: web.Request) -> web.Response:
        body = await request.json()
        self.engine.set_version(int(body["version"]))
        return web.json_response({"status": "ok"})

    # -- "dcn" in-memory weight push (areal_tpu/core/weight_transfer.py) --
    # Buckets stage with generation LIVE (the handler never pauses the
    # engine — the scheduler thread keeps emitting tokens while bytes
    # accumulate); only the commit's install pays a pause, inside
    # engine.update_weights_from_tensor.
    def _reap_stale_staging_locked(self) -> None:
        """Crash-mid-stage recovery (caller holds _ctl_lock): a push whose
        frame feed went silent for `weight_staging_ttl_s` is dead — its
        learner crashed or lost connectivity mid-stage. Drop the staging
        and clear the push-id epoch so the next push starts clean instead
        of multi-GiB zombie staging lingering until an operator notices.
        (The client independently aborts its own incomplete push on
        reconnect; this reaper covers clients that never come back.)"""
        ttl = self.config.weight_staging_ttl_s
        if ttl <= 0 or self._staging_last_frame_t is None:
            return
        if time.monotonic() - self._staging_last_frame_t <= ttl:
            return
        if len(self._weight_staging._bufs) or len(self._weight_staging):
            logger.warning(
                f"reaping stale weight staging (push {self._staging_push_id}, "
                f"silent > {ttl:.0f}s)"
            )
            self._sync_stats["reaped_pushes"] += 1
        self._weight_staging.reset()
        self._staging_push_id = None
        self._staging_t0 = None
        self._staging_last_frame_t = None

    async def _update_weights_from_tensor(
        self, request: web.Request
    ) -> web.Response:
        payload = await request.read()
        push_id = request.query.get("push_id")
        await fault_injection.afire(
            "server.weights.stage",
            push_id=str(push_id or ""), addr=str(self.addr or ""),
        )
        async with self._ctl_lock:
            self._reap_stale_staging_locked()
            # Push ids are timestamp-ordered (remote_inf_engine): a NEWER id
            # invalidates whatever a previous (failed / abandoned) push left
            # behind; an OLDER id is a stale straggler frame whose retry
            # must stop rather than wipe the current push's staging.
            if push_id is not None:
                cur = self._staging_push_id
                if cur is not None and push_id < cur:
                    return web.json_response(
                        {"status": "error", "message": "stale push_id"},
                        status=409,
                    )
                if push_id != cur:
                    self._weight_staging.reset()
                    self._staging_push_id = push_id
                    self._staging_t0 = time.monotonic()
            elif self._staging_t0 is None:
                self._staging_t0 = time.monotonic()
            self._weight_staging.add_bucket(payload)
            self._staging_last_frame_t = time.monotonic()
            self._sync_stats["wire_bytes"] += len(payload)
            # after add_bucket: a torn frame raised above, so the manifest
            # parsed here is the one whose bytes were actually staged
            from areal_tpu.core.weight_transfer import frame_raw_nbytes

            self._sync_stats["wire_bytes_raw"] += frame_raw_nbytes(payload)
        return web.json_response(
            {"status": "ok", "staged": len(self._weight_staging)}
        )

    async def _commit_weights(self, request: web.Request) -> web.Response:
        body = await request.json()
        version = body.get("version")
        push_id = body.get("push_id")
        lora_scale = body.get("lora_scale")
        await fault_injection.afire(
            "server.weights.commit",
            push_id=str(push_id or ""), addr=str(self.addr or ""),
        )
        async with self._ctl_lock:
            self._reap_stale_staging_locked()
            # Version fence: a commit may only land for the push whose
            # buckets are currently staged. A commit carrying a stale
            # push_id (its staging was superseded or aborted) must be
            # rejected — committing whatever newer push happens to be
            # staged would mix weight versions.
            if push_id is not None and push_id != self._staging_push_id:
                if (
                    push_id == self._last_commit_push_id
                    and version is not None
                    and self._last_commit_version == int(version)
                ):
                    # idempotent retry of an already-applied commit
                    return web.json_response(
                        {"status": "ok", "version": self.engine.get_version()}
                    )
                return web.json_response(
                    {"status": "error", "message": "stale push_id"},
                    status=409,
                )
            if not len(self._weight_staging):
                # Idempotent retry: a commit whose response got lost leaves
                # empty staging + the version already stamped — succeed.
                if (
                    version is not None
                    and self._last_commit_version == int(version)
                ):
                    return web.json_response(
                        {"status": "ok", "version": self.engine.get_version()}
                    )
                return web.json_response(
                    {"status": "error", "message": "no staged weights"},
                    status=400,
                )
            try:
                staged = self._weight_staging.finalize()

                def _install():
                    kw = {}
                    if lora_scale is not None:
                        kw["lora_scale"] = float(lora_scale)
                    self.engine.update_weights_from_tensor(
                        staged, version=version, **kw
                    )

                t_commit = time.monotonic()
                await asyncio.get_running_loop().run_in_executor(
                    None, _install
                )
                self._sync_stats["commit_pause_secs"] += (
                    time.monotonic() - t_commit
                )
            except Exception as e:
                # A wedged staging area would poison every later push —
                # clear it so the learner can retry from scratch. Malformed
                # pushes (bad names/shapes/missing lora_scale) are 400 so
                # the client surfaces the real message instead of retrying
                # a 500 into a confusing stale-push 409.
                self._weight_staging.reset()
                self._staging_push_id = None
                self._staging_t0 = None
                self._staging_last_frame_t = None
                status = 400 if isinstance(e, (ValueError, KeyError)) else 500
                return web.json_response(
                    {"status": "error", "message": str(e)}, status=status
                )
            if self._staging_t0 is not None:
                # transfer window: first bucket arrival → commit start
                self._sync_stats["staging_secs"] += (
                    t_commit - self._staging_t0
                )
                self._staging_t0 = None
            self._sync_stats["n_pushes"] += 1
            self._last_commit_version = (
                int(version) if version is not None else None
            )
            self._last_commit_push_id = push_id
            self._staging_push_id = None
            self._staging_last_frame_t = None
        return web.json_response(
            {"status": "ok", "version": self.engine.get_version()}
        )

    async def _abort_weights(self, request: web.Request) -> web.Response:
        """Explicitly drop staging for a failed/abandoned push. Without
        this, a crashed client leaves multi-GiB staging resident until the
        next push's id happens to reset it."""
        try:
            body = await request.json()
        except Exception as e:  # noqa: BLE001 — body is optional
            logger.debug(f"/abort_weights body ignored: {e!r}")
            body = {}
        push_id = body.get("push_id")
        async with self._ctl_lock:
            if push_id is not None and self._staging_push_id not in (
                None,
                push_id,
            ):
                # a newer push owns the staging area now — nothing to drop
                return web.json_response({"status": "ok", "dropped": 0})
            dropped = len(self._weight_staging._bufs) + len(
                self._weight_staging
            )
            self._weight_staging.reset()
            self._staging_push_id = None
            self._staging_t0 = None
            self._staging_last_frame_t = None
            if dropped:
                self._sync_stats["aborted_pushes"] += 1
        return web.json_response({"status": "ok", "dropped": dropped})

    # -- disaggregated prefill/decode: KV-session migration -------------
    # Transfer shape mirrors the weight push (frames -> staging -> one
    # commit) because it IS the same plumbing: pack_kv_session frames ride
    # WeightStaging's interval-merged coverage, so the sender's recovery
    # story is "replay the whole session under the same xid" — duplicate
    # frames merge, the commit dedups, and the handoff lands exactly once.
    _MIGRATE_TIMEOUT_S = 60.0
    _KV_STAGING_MAX = 64
    _KV_DONE_MAX = 1024

    def _prune_kv_maps(self) -> None:
        now = time.monotonic()
        ttl = self.config.idempotency_ttl_s
        for xid in list(self._kv_done):
            if now - self._kv_done[xid]["t"] > ttl:
                del self._kv_done[xid]
        while len(self._kv_done) > self._KV_DONE_MAX:
            self._kv_done.popitem(last=False)
        # staging whose feed went silent is a crashed sender: the replay
        # (same xid) restarts from an empty staging area harmlessly
        for xid in list(self._kv_staging):
            if now - self._kv_staging[xid]["last_t"] > ttl:
                del self._kv_staging[xid]
        while len(self._kv_staging) > self._KV_STAGING_MAX:
            victim, _ = self._kv_staging.popitem(last=False)
            logger.warning(f"kv staging {victim} dropped (map full)")

    async def _stream_kv(
        self,
        target: str,
        sess: dict[str, Any],
        rid: str,
        xid: str,
        retries: int = 2,
    ) -> dict[str, Any] | None:
        """Stream one exported session dict to `target` under delivery id
        `xid` (frames -> /kv_recv -> /kv_commit). Shared by session
        migration, fabric block fetches and warm starts — so the
        `kv.migrate.*` fault seams cover all three. Meta-only sessions
        (cheap drain) ride the same wire as a single metadata frame."""
        from areal_tpu.core.weight_transfer import pack_kv_session
        from areal_tpu.utils.http import arequest_with_retry

        frames = list(
            pack_kv_session(
                sess["meta"],
                sess.get("k"),
                sess.get("v"),
                ks=sess.get("ks"),
                vs=sess.get("vs"),
                chunk_mb=getattr(self.config, "kv_migrate_chunk_mb", 64.0),
            )
        )
        nbytes = sum(len(f) for f in frames)
        t0 = time.monotonic()
        last: Exception | None = None
        for attempt in range(retries + 1):
            try:
                for frame in frames:
                    # send seam: an abort models the sender dying
                    # mid-stream — the replay (same xid) must land the
                    # session exactly once
                    await fault_injection.afire(
                        "kv.migrate.send",
                        rid=rid, xid=xid, target=target, attempt=attempt,
                    )
                    await arequest_with_retry(
                        target,
                        f"/kv_recv?xid={xid}",
                        data=frame,
                        max_retries=2,
                        timeout=self._MIGRATE_TIMEOUT_S,
                    )
                out = await arequest_with_retry(
                    target,
                    "/kv_commit",
                    payload={"xid": xid, "rid": rid},
                    max_retries=2,
                    timeout=self._MIGRATE_TIMEOUT_S,
                )
                dt = time.monotonic() - t0
                self._migrate_stats["out_sessions"] += 1
                self._migrate_stats["out_bytes"] += nbytes
                self._migrate_stats["transfer_secs"] += dt
                return {"bytes": nbytes, "secs": dt, "commit": out}
            except Exception as e:  # noqa: BLE001 — replay, then degrade
                last = e
                if attempt < retries:
                    logger.warning(
                        f"kv migration of {rid} to {target} failed "
                        f"({e!r}); replaying under xid {xid}"
                    )
        self._migrate_stats["out_failures"] += 1
        logger.warning(
            f"kv migration of {rid} to {target} abandoned ({last!r}); "
            "the session resumes with a re-prefill"
        )
        return None

    async def _migrate_session_out(
        self,
        target: str,
        rid: str,
        xid: str,
        retries: int = 2,
        refetchable: "set[int] | None" = None,
    ) -> dict[str, Any] | None:
        """Export `rid` and stream it to `target` under delivery id `xid`.

        The export MOVES the session out of this engine first; a transfer
        that fails past its replay budget therefore degrades to a
        re-prefill on whichever replica the session resumes on — never a
        wedged handler. The budget is two full-stream replays (same xid):
        a mid-transfer sender death and a torn frame are INDEPENDENT
        failures, and a budget of one means any two of them composing on
        one session silently downgrades the handoff to a re-prefill.
        Re-sent frames interval-merge and the commit is idempotent, so
        however many replays run, the handoff lands exactly once.

        `refetchable` (cheap drain): content keys the surviving fleet can
        serve — sessions fully covered by them export meta-only (no KV
        bytes on the wire; the resume re-fetches blocks on demand)."""
        loop = asyncio.get_running_loop()
        sess = await loop.run_in_executor(
            None, self.engine.export_session, rid, refetchable
        )
        if sess is None:
            return None
        out = await self._stream_kv(target, sess, rid, xid, retries=retries)
        if out is not None:
            out["meta_only"] = bool(sess["meta"].get("meta_only"))
        return out

    # -- fleet KV fabric (content-addressed block fetch) ----------------
    async def _kv_fetch(self, request: web.Request) -> web.Response:
        """Serve content-keyed block runs to a sibling: resolve the
        requested chain (or the `top` longest resident chains, for a warm
        start) and PUSH the matching sessions to `target` over the
        migration wire. Copy semantics — nothing local is dropped; a
        failed push degrades to a re-prefill on the requester."""
        import uuid as _uuid

        body = await request.json()
        target = str(body.get("target") or "")
        if not target or target == self.addr:
            return web.json_response(
                {"status": "error", "message": "target required"}, status=400
            )
        keys = body.get("keys")
        if isinstance(keys, str):
            keys = kv_fabric.decode_digest(keys)
        keys = [int(x) for x in (keys or [])]
        top = int(body.get("top") or 0)
        if not keys and top <= 0:
            return web.json_response(
                {"status": "error", "message": "keys or top required"},
                status=400,
            )
        loop = asyncio.get_running_loop()
        sessions = await loop.run_in_executor(
            None,
            lambda: self.engine.export_fabric_blocks(
                keys=keys or None, top=top
            ),
        )
        served = 0
        nbytes = 0
        xid_base = str(body.get("xid") or f"fab-{_uuid.uuid4().hex[:12]}")
        for i, sess in enumerate(sessions):
            moved = await self._stream_kv(
                target, sess, sess["meta"]["rid"], f"{xid_base}-{i}"
            )
            if moved is not None:
                served += 1
                nbytes += moved["bytes"]
        self._fabric_stats["serve_sessions"] += served
        self._fabric_stats["serve_bytes"] += nbytes
        return web.json_response(
            {
                "status": "ok",
                "resolved": len(sessions),
                "sessions": served,
                "bytes": nbytes,
            }
        )

    async def _fabric_prefetch(self, hint: dict[str, Any]) -> None:
        """Act on a router hint ({"peer": addr, "keys": digest}) BEFORE
        the engine sees the request: pull the matching block runs from
        the peer so admission finds them in the host tier. Concurrent
        requests carrying the same hint await one fetch (event-loop
        dedup). Every failure degrades to a local prefill — the stream
        stays bit-identical, it just pays the prefill the fabric would
        have skipped."""
        from areal_tpu.utils.http import arequest_with_retry

        peer = str(hint.get("peer") or "")
        keys = hint.get("keys")
        if not peer or not keys or peer == self.addr:
            return
        dedup = keys if isinstance(keys, str) else ",".join(map(str, keys))
        fut = self._fabric_inflight.get(dedup)
        if fut is not None:
            try:
                await asyncio.shield(fut)
            except Exception as e:  # noqa: BLE001 — the original logs it
                logger.debug(f"awaited in-flight fabric fetch failed: {e!r}")
            return
        fut = asyncio.get_running_loop().create_future()
        # no await between the get above and this claim: loop-atomic
        self._fabric_inflight[dedup] = fut
        self._fabric_stats["fetch_attempts"] += 1
        try:
            out = await arequest_with_retry(
                peer,
                "/kv_fetch",
                payload={"keys": keys, "target": self.addr},
                max_retries=1,
                timeout=float(
                    getattr(self.config, "kv_fabric_fetch_timeout_s", 30.0)
                ),
            )
            self._fabric_stats["fetch_sessions"] += int(
                out.get("sessions") or 0
            )
            self._fabric_stats["fetch_bytes"] += int(out.get("bytes") or 0)
            fut.set_result(out)
        except Exception as e:  # noqa: BLE001 — degrade, never wedge
            self._fabric_stats["fetch_failures"] += 1
            logger.warning(
                f"fabric prefetch from {peer} failed ({e!r}); "
                "degrading to local prefill"
            )
            fut.set_result(None)
        finally:
            self._fabric_inflight.pop(dedup, None)

    async def _warm_start(self, request: web.Request) -> web.Response:
        """Cold-start warm-up: ask each peer to push its longest resident
        block runs here before this replica takes traffic. Best-effort —
        a peer that cannot serve simply contributes nothing."""
        from areal_tpu.utils.http import arequest_with_retry

        body = await request.json()
        peers = [
            p for p in body.get("peers") or [] if p and p != self.addr
        ]
        k = int(body.get("max_sessions") or 4)
        if not peers or k <= 0:
            return web.json_response(
                {"status": "error", "message": "peers required"}, status=400
            )
        sessions = nbytes = failures = 0
        for peer in peers:
            try:
                out = await arequest_with_retry(
                    peer,
                    "/kv_fetch",
                    payload={"top": k, "target": self.addr},
                    max_retries=1,
                    timeout=float(
                        getattr(self.config, "kv_fabric_fetch_timeout_s", 30.0)
                    ),
                )
                sessions += int(out.get("sessions") or 0)
                nbytes += int(out.get("bytes") or 0)
            except Exception as e:  # noqa: BLE001 — best-effort warm-up
                failures += 1
                logger.warning(f"warm start from {peer} failed: {e!r}")
        self._fabric_stats["warm_start_sessions"] += sessions
        self._fabric_stats["warm_start_bytes"] += nbytes
        return web.json_response(
            {
                "status": "ok",
                "peers": len(peers),
                "sessions": sessions,
                "bytes": nbytes,
                "failures": failures,
            }
        )

    async def _prefill(self, request: web.Request) -> web.Response:
        """Prefill-only generation (the prefill role's hot path): run the
        prompt, park the KV, optionally hand the session to a decode
        replica. Idempotent per xid like /generate."""
        body = await request.json()
        xid = body.get("xid")
        await fault_injection.afire(
            "server.prefill",
            rid=str(body.get("rid") or ""), xid=str(xid or ""),
            addr=str(self.addr or ""),
        )
        if xid is not None:
            ent = self._idem.get(xid)
            if ent is not None:
                self._idem_hits += 1
                if ent["done"]:
                    self._idem.move_to_end(xid)
                    return web.json_response(
                        {**ent["resp"], "dedup": "completed"}
                    )
                out = await asyncio.shield(ent["fut"])
                return web.json_response({**out, "dedup": "in_progress"})
            ent = {
                "done": False,
                "fut": asyncio.get_running_loop().create_future(),
                "t": time.monotonic(),
            }
            self._idem[xid] = ent
        req = ModelRequest(
            rid=body.get("rid") or ModelRequest().rid,
            input_ids=[int(t) for t in body["input_ids"]],
            gconfig=_parse_gconfig(body.get("gconfig", {})),
            image_data=body.get("image_data"),
        )
        target = body.get("target")
        try:
            resp = await self.engine.aprefill(req)
            out: dict[str, Any] = {
                "status": "ok",
                "stop_reason": resp.stop_reason,
                "latency": resp.latency,
                "migrated": False,
                "kv_bytes": 0,
            }
            if target and target != self.addr:
                moved = await self._migrate_session_out(
                    target, req.rid, xid or f"pf-{req.rid}"
                )
                if moved is not None:
                    out["migrated"] = True
                    out["kv_bytes"] = moved["bytes"]
                    out["transfer_secs"] = moved["secs"]
        except BaseException as e:
            if xid is not None and self._idem.get(xid) is ent:
                del self._idem[xid]
                if not ent["fut"].done():
                    ent["fut"].set_exception(e)
                    ent["fut"].exception()
            raise
        if xid is not None and self._idem.get(xid) is ent:
            self._idem[xid] = {"done": True, "resp": out, "t": time.monotonic()}
            self._idem.move_to_end(xid)
            if not ent["fut"].done():
                ent["fut"].set_result(out)
            self._prune_idem()
        return web.json_response(out)

    async def _kv_recv(self, request: web.Request) -> web.Response:
        """Stage one inbound KV frame under its migration xid."""
        payload = await request.read()
        xid = request.query.get("xid") or ""
        if not xid:
            return web.json_response(
                {"status": "error", "message": "xid required"}, status=400
            )
        # recv seam: an abort models the receiver dying with the frame in
        # hand; torn truncates it in flight — the manifest length-check
        # rejects the torn frame (500) and the sender's frame retry
        # re-covers the byte ranges
        await fault_injection.afire(
            "kv.migrate.recv", xid=xid, addr=str(self.addr or "")
        )
        payload = fault_injection.tear("kv.migrate.recv", payload, xid=xid)
        if xid in self._kv_done:
            # straggler frame of an already-committed migration (the
            # sender replayed after losing the commit response): drop it,
            # the commit retry will hit the dedup cache
            return web.json_response({"status": "ok", "staged": 0})
        ent = self._kv_staging.get(xid)
        if ent is None:
            from areal_tpu.core.weight_transfer import WeightStaging

            ent = {"staging": WeightStaging(), "t0": time.monotonic()}
            self._kv_staging[xid] = ent
        ent["last_t"] = time.monotonic()
        ent["staging"].add_bucket(payload)  # torn frame -> ValueError -> 500
        self._migrate_stats["in_frames"] += 1
        self._prune_kv_maps()
        return web.json_response(
            {"status": "ok", "staged": len(ent["staging"])}
        )

    async def _kv_commit(self, request: web.Request) -> web.Response:
        """Finalize + import a staged migration; idempotent per xid."""
        body = await request.json()
        xid = str(body.get("xid") or "")
        done = self._kv_done.get(xid)
        if done is not None:
            # the sender lost our response and replayed: never import twice
            self._kv_done.move_to_end(xid)
            self._migrate_stats["commit_dedups"] += 1
            return web.json_response({**done["resp"], "dedup": True})
        ent = self._kv_staging.get(xid)
        if ent is None:
            return web.json_response(
                {"status": "error", "message": f"no staged kv for {xid!r}"},
                status=400,
            )
        from areal_tpu.core.weight_transfer import unpack_kv_sessions

        try:
            sessions = unpack_kv_sessions(ent["staging"].finalize())
            if not sessions:
                raise ValueError("no complete kv session staged")
        except (RuntimeError, ValueError) as e:
            # incomplete/malformed/empty: KEEP the staging so the
            # sender's replay can top up the missing byte ranges and
            # re-commit
            return web.json_response(
                {"status": "error", "message": str(e)}, status=400
            )
        del self._kv_staging[xid]
        loop = asyncio.get_running_loop()
        t0 = time.monotonic()
        counts = {
            "ok": 0, "stale_version": 0, "kv_dtype_mismatch": 0, "rejected": 0,
        }
        rids = []
        for meta, k, v, scales in sessions:
            ks, vs = scales if scales is not None else (None, None)
            verdict = await loop.run_in_executor(
                None, self.engine.import_session, meta, k, v, ks, vs
            )
            counts[verdict] = counts.get(verdict, 0) + 1
            if verdict == "ok":
                rids.append(meta["rid"])
        resp = {
            "status": "ok",
            "imported": counts["ok"],
            "stale_version": counts["stale_version"],
            "kv_dtype_mismatch": counts["kv_dtype_mismatch"],
            "rejected": counts["rejected"],
            "rids": rids,
        }
        self._kv_done[xid] = {"resp": resp, "t": time.monotonic()}
        self._migrate_stats["in_commits"] += 1
        self._migrate_stats["transfer_secs"] += time.monotonic() - t0
        self._prune_kv_maps()
        return web.json_response(resp)

    async def _drain(self, request: web.Request) -> web.Response:
        """Stream every resumable session to the target replicas (scale-
        down / maintenance): in-flight generations are parked first (their
        clients resume through the interrupt loop and the router lands
        them on a survivor, where the migrated KV makes the resume a
        zero-re-prefill promotion).

        Drains are serialized per server: a /drain arriving while one is
        already running (a supervisor retry racing an operator) awaits the
        in-flight drain and REPLAYS its result instead of exporting the
        same sessions twice — each concurrent export would mint fresh
        drain-xids, so without this guard the idempotency tables on the
        targets could not dedup the double import."""
        body = await request.json()
        targets = [t for t in body.get("targets") or [] if t and t != self.addr]
        if not targets:
            return web.json_response(
                {"status": "error", "message": "targets required"}, status=400
            )
        if (
            self._drain_inflight is not None
            and not self._drain_inflight.done()
        ):
            # shield: a duplicate whose client gives up must not cancel
            # the original drain mid-export
            resp = await asyncio.shield(self._drain_inflight)
            return web.json_response(dict(resp, dedup="in_progress"))
        fut = asyncio.get_running_loop().create_future()
        # no await between the done-check above and this assignment: the
        # check-and-claim is atomic on the one event loop
        self._drain_inflight = fut
        try:
            resp = await self._drain_once(body, targets)
            status = 200
        except Exception as e:  # noqa: BLE001 — waiters need a result,
            # not a never-retrieved exception
            resp = {"status": "error", "message": repr(e)}
            status = 500
        fut.set_result(resp)
        return web.json_response(resp, status=status)

    async def _drain_once(
        self, body: dict[str, Any], targets: list[str]
    ) -> dict[str, Any]:
        import uuid as _uuid

        loop = asyncio.get_running_loop()
        async with self._ctl_lock:
            await loop.run_in_executor(None, self.engine.pause_generation)
            aborted = (
                self.engine.abort_all()
                if body.get("abort_active", True)
                else 0
            )
            if not self._client_paused:
                self.engine.continue_generation()
        # fleet fabric cheap drain: blocks the survivors can re-fetch by
        # content key travel as a single meta-only frame (identity, not
        # kilobytes of KV) — the supervisor passes the union of survivor
        # digests as `refetchable`
        refetchable: set[int] | None = None
        rf = body.get("refetchable")
        if rf is not None and getattr(self.config, "kv_fabric", True):
            if isinstance(rf, str):
                rf = kv_fabric.decode_digest(rf)
            refetchable = {int(x) for x in rf}
        rids = self.engine.list_exportable_sessions()
        drained = failed = meta_only = 0
        total_bytes = 0
        # kwarg only when a digest was supplied: plain drains keep the
        # pre-fabric `_migrate_session_out(target, rid, xid)` call shape
        # (overridable seam — see tests/test_fleet.py's slow_migrate)
        kw = {} if refetchable is None else {"refetchable": refetchable}
        for i, rid in enumerate(rids):
            xid = f"drain-{_uuid.uuid4().hex[:12]}"
            moved = await self._migrate_session_out(
                targets[i % len(targets)], rid, xid, **kw
            )
            if moved is None:
                failed += 1
            else:
                drained += 1
                total_bytes += moved["bytes"]
                if moved.get("meta_only"):
                    meta_only += 1
        return {
            "status": "ok",
            "aborted": aborted,
            "sessions": len(rids),
            "drained": drained,
            "failed": failed,
            "meta_only": meta_only,
            "bytes": total_bytes,
        }

    async def _set_role(self, request: web.Request) -> web.Response:
        """Flip this replica's role (the supervisor's re-role transition,
        issued only after a committed /drain). The role only steers the
        router's scheduler — every replica serves every endpoint — so the
        flip is a config write here plus the next /health poll on the
        router side."""
        body = await request.json()
        role = str(body.get("role", "")).lower()
        if role not in ("unified", "prefill", "decode"):
            return web.json_response(
                {"status": "error", "message": f"bad role {role!r}"},
                status=400,
            )
        old = getattr(self.config, "role", "unified")
        self.config.role = role
        logger.info(f"role flipped {old} -> {role}")
        return web.json_response(
            {"status": "ok", "old_role": old, "role": role}
        )

    # -- lifecycle ------------------------------------------------------
    def build_app(self) -> web.Application:
        app = web.Application(client_max_size=1024**3)
        app.router.add_get("/health", self._health)
        app.router.add_get("/info", self._info)
        app.router.add_get("/metrics", self._metrics)
        app.router.add_post("/generate", self._generate)
        app.router.add_post("/pause_generation", self._pause)
        app.router.add_post("/continue_generation", self._continue)
        app.router.add_post(
            "/update_weights_from_disk", self._update_weights_from_disk
        )
        app.router.add_post(
            "/update_weights_from_tensor", self._update_weights_from_tensor
        )
        app.router.add_post("/commit_weights", self._commit_weights)
        app.router.add_post("/abort_weights", self._abort_weights)
        app.router.add_post("/set_version", self._set_version)
        app.router.add_post("/prefill", self._prefill)
        app.router.add_post("/kv_recv", self._kv_recv)
        app.router.add_post("/kv_commit", self._kv_commit)
        app.router.add_post("/kv_fetch", self._kv_fetch)
        app.router.add_post("/warm_start", self._warm_start)
        app.router.add_post("/drain", self._drain)
        app.router.add_post("/set_role", self._set_role)
        return app

    async def start(
        self,
        host: str = "0.0.0.0",
        port: int = 0,
        prewarm: dict[str, Any] | None = None,
    ) -> str:
        """Initialize the engine, optionally prewarm, THEN bind the HTTP
        listener. `prewarm` (kwargs for `engine.prewarm`) must run before
        the port exists: once the listener is up, a /generate or /pause
        arriving mid-warmup would make the wave sizes nondeterministic
        (or trip prewarm's external-pause guard and kill startup)."""
        if self._owns_engine:
            self.engine.initialize()
        if prewarm is not None:
            await asyncio.get_running_loop().run_in_executor(
                None, lambda: self.engine.prewarm(**prewarm)
            )
        self._runner = web.AppRunner(
            self.build_app(), shutdown_timeout=self.shutdown_grace
        )
        await self._runner.setup()
        site = web.TCPSite(self._runner, host, port)
        await site.start()
        actual_port = self._runner.addresses[0][1]
        ip = _local_ip() if host in ("0.0.0.0", "::") else host
        self.addr = f"{ip}:{actual_port}"
        logger.info(f"decode server listening on {self.addr}")
        return self.addr

    async def stop(self) -> None:
        if self._runner is not None:
            await self._runner.cleanup()
            self._runner = None
        if self._owns_engine:
            self.engine.destroy()

    def register(self, experiment_name: str, trial_name: str, server_id: str):
        assert self.addr is not None
        name_resolve.add(
            names.gen_server(experiment_name, trial_name, server_id),
            self.addr,
            keepalive_ttl=None,
            replace=True,
        )


def _local_ip() -> str:
    try:
        s = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
        s.connect(("8.8.8.8", 80))
        ip = s.getsockname()[0]
        s.close()
        return ip
    except OSError:
        return "127.0.0.1"


async def _serve(args: argparse.Namespace) -> None:
    config = JaxDecodeConfig(
        model_path=args.model_path,
        dtype=args.dtype,
        role=args.role,
        kv_migrate_chunk_mb=args.kv_migrate_chunk_mb,
        kv_import_pool_mb=args.kv_import_pool_mb,
        context_length=args.context_length,
        max_running_requests=args.max_running_requests,
        new_tokens_per_chunk=args.new_tokens_per_chunk,
        decode_runahead_chunks=args.decode_runahead_chunks,
        kv_dtype=args.kv_dtype,
        weight_dtype=args.weight_dtype,
        kv_host_pool_mb=args.kv_host_pool_mb,
        paged_attn_impl=args.paged_attn_impl,
        spec_decode=args.spec_decode,
        spec_k=args.spec_k,
        spec_ngram_max=args.spec_ngram_max,
        random_seed=args.seed,
        tensor_parallel_size=args.tensor_parallel_size,
    )
    tokenizer = None
    if args.model_path and not args.skip_tokenizer_init and not args.scratch_model:
        try:
            from transformers import AutoTokenizer

            tokenizer = AutoTokenizer.from_pretrained(args.model_path)
        except Exception as e:  # noqa: BLE001
            logger.warning(f"tokenizer load failed ({e}); stop-on-eos disabled")
    server = DecodeServer(config, tokenizer=tokenizer)
    if args.scratch_model:
        # Offline smoke mode: serve a from-scratch tiny model described by a
        # JSON ModelConfig dict — lets launcher E2E tests (and air-gapped
        # demo runs) exercise the full DECOUPLED path without HF downloads.
        import json as _json

        import jax as _jax

        from areal_tpu.models.qwen2 import ModelConfig, init_params

        mc = ModelConfig(
            **{
                # JSON turns the dataclass's tuple fields into lists
                **{
                    k: tuple(v) if isinstance(v, list) else v
                    for k, v in _json.loads(args.scratch_model).items()
                },
                "dtype": args.dtype,
                "param_dtype": args.dtype,
            }
        )
        server.engine.set_model(init_params(mc, _jax.random.PRNGKey(args.seed)), mc)
    # Deterministic jit warmup BEFORE the HTTP listener binds (and so also
    # before registering with the router): live traffic must never pay a
    # first-compile (see JaxDecodeEngine.prewarm — which batched-prefill
    # variant traffic compiles is arrival-timing dependent, so
    # serving-warmed engines still hit compile stalls), and a request or
    # /pause arriving mid-warmup would break wave determinism or trip
    # prewarm's external-pause guard.
    prewarm = (
        dict(
            prompt_len=args.prewarm_prompt_len,
            new_tokens=args.prewarm_new_tokens,
        )
        if args.prewarm_prompt_len > 0
        else None
    )
    await server.start(args.host, args.port, prewarm=prewarm)
    stop = asyncio.Event()
    loop = asyncio.get_running_loop()
    if args.experiment_name and args.trial_name:
        server.register(
            args.experiment_name, args.trial_name, args.server_id or server.addr
        )
        # Self-terminate when the trainer broadcasts a terminal status —
        # servers must not linger after the experiment ends (reference:
        # ExpStatus watch, realhf master_worker.py:485-495).
        from areal_tpu.utils.experiment import watch_until_terminal

        watch_until_terminal(
            args.experiment_name,
            args.trial_name,
            lambda status: loop.call_soon_threadsafe(stop.set),
        )
    try:
        await stop.wait()
    finally:
        await server.stop()


def main(argv: list[str] | None = None) -> None:
    p = argparse.ArgumentParser(description="areal_tpu decode server")
    p.add_argument("--model-path", default="")
    p.add_argument("--dtype", default="bfloat16")
    p.add_argument(
        "--role",
        default="unified",
        choices=["unified", "prefill", "decode"],
        help="disaggregated fleet role: 'prefill' replicas run prompt "
             "prefills (/prefill) and stream the KV to decode replicas "
             "over the bucketed KV wire; 'decode' replicas import those "
             "sessions and resume them with zero re-prefill; 'unified' "
             "(default) does both. Roles steer the router — every role "
             "still serves every endpoint, so a degraded fleet keeps "
             "working",
    )
    p.add_argument(
        "--kv-migrate-chunk-mb",
        type=float,
        default=64.0,
        help="frame size (MiB per HTTP body) for migrated KV sessions",
    )
    p.add_argument(
        "--kv-import-pool-mb",
        type=float,
        default=256.0,
        help="host-tier budget (MiB) created lazily when a migration "
             "arrives while --kv-host-pool-mb is 0 — imported sessions "
             "need a host tier to land in",
    )
    p.add_argument("--context-length", type=int, default=32768)
    p.add_argument("--max-running-requests", type=int, default=64)
    p.add_argument("--new-tokens-per-chunk", type=int, default=128)
    p.add_argument(
        "--decode-runahead-chunks",
        type=int,
        default=1,
        help="chunks the scheduler keeps dispatched on the device while "
             "the host post-processes the previous one (0 = legacy "
             "synchronous loop; output is bit-identical either way)",
    )
    p.add_argument(
        "--kv-dtype",
        default="fp",
        choices=["fp", "int8"],
        help="paged-pool storage: 'fp' keeps kv_cache_dtype; 'int8' "
             "stores the pool quantized with per-row/per-head scales — "
             "~2x the resident "
             "sessions per MB, and swaps/migration ship the quantized "
             "bytes as-is (mixed-dtype fleets reject imports as honest "
             "misses). Drift is bounded at a tiny preset "
             "(tests/test_kv_quant.py), unmeasured at a real model's widths",
    )
    p.add_argument(
        "--weight-dtype",
        default="fp",
        choices=["fp", "int8"],
        help="serving dtype of the dense matmul kernels: 'fp' serves "
             "--dtype verbatim (the numerics oracle); 'int8' serves "
             "per-output-channel absmax int8 + f32 scales — weight HBM and "
             "push wire bytes ~halve, decode runs the fused dequant-matmul "
             "(Pallas on TPU). The trainer's WeightUpdateMeta.weight_dtype "
             "must match: quantized kernels travel as '.../q' + "
             "'.../scale' wire leaves. Drift is bounded at a tiny preset "
             "(tests/test_weight_quant.py), unmeasured at a real model's "
             "widths",
    )
    p.add_argument(
        "--kv-host-pool-mb",
        type=float,
        default=0.0,
        help="host-RAM KV tier budget in MiB (0 disables): eviction "
             "offloads parked/preempted slots' KV blocks to pinned host "
             "memory and a resume swaps them back asynchronously instead "
             "of re-prefilling — kv_pool_tokens becomes a working-set "
             "knob, not a capacity wall",
    )
    p.add_argument(
        "--paged-attn-impl",
        default="auto",
        choices=["auto", "pallas", "xla"],
        help="kernel for the in-pool attention read: 'pallas' (TPU "
             "split-KV flash-decode; needs page_size %% 128 == 0), 'xla' "
             "(gather-per-block fallback), 'auto' picks per backend",
    )
    p.add_argument(
        "--spec-decode",
        default="off",
        choices=["off", "ngram"],
        help="draft-free speculative decoding: 'ngram' drafts from each "
             "request's own context (prompt lookup) and verifies all "
             "draft positions in one chunk — token streams and logprobs "
             "stay bit-identical to 'off'",
    )
    p.add_argument(
        "--spec-k",
        type=int,
        default=4,
        help="max draft tokens proposed (and verified) per chunk per slot",
    )
    p.add_argument(
        "--spec-ngram-max",
        type=int,
        default=3,
        help="longest trailing n-gram matched against the request's own "
             "earlier context when drafting",
    )
    p.add_argument(
        "--tp-size",
        dest="tensor_parallel_size",
        type=int,
        default=1,
        help="gen-side tensor parallelism (alloc grammar's server t dim)",
    )
    p.add_argument("--seed", type=int, default=1)
    p.add_argument("--host", default="0.0.0.0")
    p.add_argument("--port", type=int, default=int(os.environ.get("PORT", 0)))
    p.add_argument("--experiment-name", default=os.environ.get("AREAL_EXPERIMENT_NAME", ""))
    p.add_argument("--trial-name", default=os.environ.get("AREAL_TRIAL_NAME", ""))
    # knob: launcher-only — discovery identity, not a JaxDecodeConfig mirror
    p.add_argument("--server-id", default="")
    p.add_argument("--skip-tokenizer-init", action="store_true")
    # knob: launcher-only — smoke/E2E harness switch, not a config mirror
    p.add_argument(
        "--scratch-model",
        default="",
        help="JSON ModelConfig dict: serve that geometry from a seed "
             "(offline smoke / launcher E2E) instead of loading --model-path",
    )
    # knob: launcher-only — boot-time compile hint, not a config mirror
    p.add_argument(
        "--prewarm-prompt-len",
        type=int,
        default=0,
        help="if >0, deterministically compile the hot decode-path jit "
             "variants at this prompt length before registering with the "
             "router (JaxDecodeEngine.prewarm); production servers should "
             "set this to their typical prompt length",
    )
    # knob: launcher-only — boot-time compile hint, not a config mirror
    p.add_argument(
        "--prewarm-new-tokens",
        type=int,
        default=1,
        help="generation length of the prewarm requests (raise to the "
             "typical response length to also compile the decode chunk at "
             "every KV bucket the context growth reaches)",
    )
    args = p.parse_args(argv)
    # join the experiment's shared discovery store (launcher-provided env)
    name_resolve.reconfigure_from_env()
    asyncio.run(_serve(args))


if __name__ == "__main__":
    main()
