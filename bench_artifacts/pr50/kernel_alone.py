"""`%ssm_step` and `%ssm_scan` alone at the cell's shapes on the chip (ISSUE
50): 26 layers' float32 states of [16, 5120] in one pool at 256 slots, all
live, seven eighths live and one eighth live (the work list: a slot that is
not live costs nothing), device time a call from a trace of 20 calls each
against the call's own bytes (`benchmark/lib/flops_ssm.py`); the prefill's
scan at 1,024 tokens, one sequence and a wave of eight, as the kernel and as
the plain-JAX chunked scan it stands in for; then `tools/tpu_smoke.py`'s two
new cases.

    chiprun -- python bench_artifacts/pr50/kernel_alone.py
"""

import os
import sys
import tempfile
from types import SimpleNamespace

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "tools"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import tpu_smoke  # noqa: E402

from areal_tpu.models.qwen2 import _ssm_chunk_scan  # noqa: E402
from areal_tpu.ops.ssm_scan import ssm_scan  # noqa: E402
from areal_tpu.ops.ssm_step import ssm_step  # noqa: E402
from benchmark.lib import flops_ssm, xplane  # noqa: E402

CALLS = 20
N, DI, LAYERS, R = 16, 5120, 26, 256
CFG = SimpleNamespace(hidden_size=2560, ssm_expand=2, ssm_state_size=N, ssm_dt_rank=160,
                      linear_conv_kernel_dim=4)
out = os.path.join(tempfile.gettempdir(), "pr50_kernel_alone")  # traces: too large to bring back


def traced(fn, pattern, name):
    jax.block_until_ready(fn())
    d = os.path.join(out, name)
    jax.profiler.start_trace(d)
    for _ in range(CALLS):
        r = fn()
    jax.block_until_ready(r)
    jax.profiler.stop_trace()
    trace = xplane.load(xplane.find_xplane(d))
    return xplane.op_time(trace, pattern, 0.0, float("inf")) / CALLS


def step_case(live_every):
    ks = jax.random.split(jax.random.PRNGKey(7), 6)
    S = jax.random.normal(ks[0], (LAYERS, 1 + R, N, DI), jnp.float32).at[:, 0].set(0)
    dt = jax.random.uniform(ks[1], (R, DI), jnp.float32, 1e-3, 0.1)
    u = jax.random.normal(ks[2], (R, DI))
    B, C = jax.random.normal(ks[3], (R, N)), jax.random.normal(ks[4], (R, N))
    A = -jnp.broadcast_to(jnp.arange(1, N + 1, dtype=jnp.float32)[:, None], (N, DI))
    D = jnp.ones((DI,))
    active = (jnp.arange(R) % live_every[1]) < live_every[0]
    step = jax.jit(lambda S, li: ssm_step(S, dt, u, B, C, A, D, li, active, impl="pallas",
                                          interpret=False)[1], donate_argnums=0)
    box = [S]

    def call():
        box[0] = step(box[0], jnp.int32(19))
        return box[0]

    s = traced(call, "^%ssm_step[. ]", f"step_{live_every[0]}_{live_every[1]}")
    live = int(active.sum())
    need = flops_ssm.ssm_step_needed_seconds(CFG, live, "TPU v5 lite", calls=1)
    print(f"%ssm_step 256 slots x [16, 5120] float32, {live} live: {1e6 * s:.1f} us a call, "
          f"{1e6 * s / live:.3f} us a live slot; its bytes {need['bytes'] / 1e6:.1f} MB at 819 GB/s "
          f"{1e6 * need['seconds']:.1f} us: {100 * need['seconds'] / s:.1f}%", flush=True)


def scan_case(wave, T=1024):
    ks = jax.random.split(jax.random.PRNGKey(3), 5)
    lead = (wave,) if wave else ()
    u = jax.random.normal(ks[0], lead + (T, DI))
    dt = jax.random.uniform(ks[1], lead + (T, DI), jnp.float32, 1e-3, 0.1)
    B, C = jax.random.normal(ks[2], lead + (T, N)), jax.random.normal(ks[3], lead + (T, N))
    A = -jnp.broadcast_to(jnp.arange(1, N + 1, dtype=jnp.float32)[:, None], (N, DI))
    seg = jnp.zeros(T, jnp.int32)

    def form(impl):
        one = lambda u, dt, B, C: ssm_scan(  # noqa: E731
            u, dt, B, C, A, scan=lambda *a: _ssm_chunk_scan(*a, seg), impl=impl, interpret=False)
        return jax.jit(jax.vmap(one) if wave else one)

    kernel, plain = form("pallas"), form("xla")
    yk, hk = kernel(u, dt, B, C)
    yp, hp = plain(u, dt, B, C)
    err = max(float(jnp.max(jnp.abs(yk - yp))), float(jnp.max(jnp.abs(hk - hp))))
    d = os.path.join(out, f"scan_{wave}")
    jax.profiler.start_trace(d)
    for _ in range(3):
        a = kernel(u, dt, B, C)
        b = plain(u, dt, B, C)
    jax.block_until_ready((a, b))
    jax.profiler.stop_trace()
    trace = xplane.load(xplane.find_xplane(d))
    k = xplane.module_time(trace, "^jit_", 0.0, float("inf"))
    ks_ = xplane.op_time(trace, "^%ssm_scan[. ]", 0.0, float("inf")) / 3
    total = k["seconds"] / 3
    tokens = max(wave, 1) * T
    print(f"scan, {max(wave, 1)} x {T} tokens of 5,120 channels: %ssm_scan {1e3 * ks_:.2f} ms "
          f"({1e6 * ks_ / tokens:.2f} us a token), kernel + plain-JAX programs together "
          f"{1e3 * total:.2f} ms a pair of calls: the plain scan {1e3 * (total - ks_):.2f} ms "
          f"({1e6 * (total - ks_) / tokens:.2f} us a token); largest |difference| {err:.2e}",
          flush=True)


failed = 0
for live in ((1, 1), (7, 8), (1, 8)):
    step_case(live)
for wave in (0, 8):
    scan_case(wave)
for name, _, thunk in tpu_smoke.cases():
    if name.startswith("ssm_step") or "20/1/128" in name:
        ok, detail = thunk()
        failed += not ok
        print(f"{'OK  ' if ok else 'FAIL'} {name}: {detail}", flush=True)
sys.exit(1 if failed else 0)
