"""Prewarm/startup hardening (round 5's review) — tier-1, CPU, fast.

1. decode_server binds its HTTP listener only AFTER prewarm finishes, so a
   request or /pause can never land mid-warmup.
2. prewarm's load-bearing guards are RuntimeError, not assert — `python -O`
   must not silently cancel an externally held pause.
3. prewarm warns when a wave's promised batched-prefill variant never
   compiled (KV-pool pressure split the wave).
"""

import asyncio
import logging
import threading

import pytest

from areal_tpu.api.cli_args import InferenceEngineConfig, JaxDecodeConfig
from areal_tpu.engine.jax_decode import JaxDecodeEngine
from areal_tpu.launcher.decode_server import DecodeServer


def _engine():
    return JaxDecodeEngine(
        JaxDecodeConfig(context_length=96, max_running_requests=4),
        InferenceEngineConfig(),
    )


def test_prewarm_requires_initialize():
    eng = _engine()
    with pytest.raises(RuntimeError, match="initialize"):
        eng.prewarm(prompt_len=8)


def test_prewarm_refuses_external_pause():
    eng = _engine()
    # Simulate an initialized engine holding an external pause (the
    # weight-update window): prewarm must refuse — and must do so even
    # under `python -O`, hence RuntimeError, not assert.
    eng._thread = threading.Thread(target=lambda: None)
    eng._gen_paused.set()
    with pytest.raises(RuntimeError, match="un-paused"):
        eng.prewarm(prompt_len=8)


def test_prewarm_wave_warning():
    eng = _engine()
    eng._batched_prefill_fns = {(64, 4): object()}
    # the areal_tpu root logger has propagate=False, so capture with a
    # handler attached directly to the module logger
    records: list[logging.LogRecord] = []

    class _Cap(logging.Handler):
        def emit(self, record):
            records.append(record)

    log = logging.getLogger("areal_tpu.jax_decode")
    cap = _Cap(level=logging.WARNING)
    log.addHandler(cap)
    try:
        eng._warn_wave_not_compiled(64, 4)  # compiled: silent
        assert not records
        eng._warn_wave_not_compiled(64, 8)  # promised but missing: warn
        assert any(
            "B=8" in r.getMessage() and "not compiled" in r.getMessage()
            for r in records
        )
        records.clear()
        eng._warn_wave_not_compiled(64, 1)  # single prefill: not batched
        assert not records
    finally:
        log.removeHandler(cap)


class _StubEngine:
    """Engine double for DecodeServer lifecycle tests: records whether the
    HTTP listener existed at each call."""

    def __init__(self):
        self.calls = []
        self.server: DecodeServer | None = None

    def initialize(self):
        self.calls.append(("initialize", self.server._runner is None))

    def prewarm(self, **kw):
        # The listener must NOT be bound yet: no socket, no addr.
        self.calls.append(
            (
                "prewarm",
                self.server._runner is None and self.server.addr is None,
            )
        )

    def get_version(self):
        return 0

    def destroy(self):
        self.calls.append(("destroy", True))


def test_server_prewarms_before_binding():
    stub = _StubEngine()
    server = DecodeServer(JaxDecodeConfig(), engine=stub)
    server._owns_engine = True  # exercise initialize() ordering too
    stub.server = server

    async def run():
        addr = await server.start(
            host="127.0.0.1", port=0, prewarm=dict(prompt_len=8)
        )
        assert addr
        await server.stop()

    asyncio.run(run())
    names = [c[0] for c in stub.calls]
    assert names[:2] == ["initialize", "prewarm"]
    assert all(flag for _, flag in stub.calls), stub.calls


def test_server_start_without_prewarm_unchanged():
    stub = _StubEngine()
    server = DecodeServer(JaxDecodeConfig(), engine=stub)
    stub.server = server

    async def run():
        addr = await server.start(host="127.0.0.1", port=0)
        assert addr
        await server.stop()

    asyncio.run(run())
    assert [c[0] for c in stub.calls] == []  # not owned: no engine calls
