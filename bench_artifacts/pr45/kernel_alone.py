"""`%kda_step` alone against `%gdn_step` at the same shapes on the chip
(ISSUE 45): six layers' float32 states of 32 heads of 128 x 128 in one pool,
64 and 128 slots, the decay one number a head (`%gdn_step`) or a vector over
the key lanes (`%kda_step`: the same bytes but for the decay's column block),
device time a call from a trace of 20 calls each; then `tools/tpu_smoke.py`'s
two new cases and the latent kernel at the cell's shape (128 slots x 64
columns, 32 heads).

    chiprun -- python bench_artifacts/pr45/kernel_alone.py
"""

import os
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "tools"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import tpu_smoke  # noqa: E402

from areal_tpu.ops.gdn_step import gdn_step  # noqa: E402
from benchmark.lib import xplane  # noqa: E402

CALLS = 20


def inputs(R, lanes, n=6, Hv=32, dk=128, dv=128):
    ks = jax.random.split(jax.random.PRNGKey(7), 6)
    S = jax.random.normal(ks[0], (n, 1 + R, Hv, dk, dv), jnp.float32).at[:, 0].set(0)
    q = jax.random.normal(ks[1], (R, Hv, dk)) * dk ** -0.5
    k = jax.random.normal(ks[2], (R, Hv, dk))
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[3], (R, Hv, dv))
    g = -0.5 * jax.random.uniform(ks[4], (R, Hv, dk) if lanes else (R, Hv))
    beta = jax.random.uniform(ks[5], (R, Hv))
    return S, (q, k, v, g, beta)


def timed(R, lanes, trace_dir):
    S, xs = inputs(R, lanes)
    step = jax.jit(lambda S, *a: gdn_step(S, *a, 4, None, impl="pallas", interpret=False)[1],
                   donate_argnums=0)
    S = jax.block_until_ready(step(S, *xs))
    jax.profiler.start_trace(trace_dir)
    for _ in range(CALLS):
        S = step(S, *xs)
    jax.block_until_ready(S)
    jax.profiler.stop_trace()
    trace = xplane.load(xplane.find_xplane(trace_dir))
    name = "^%kda_step[. ]" if lanes else "^%gdn_step[. ]"
    seconds = xplane.op_time(trace, name, 0.0, float("inf"))
    nbytes = R * 2 * 32 * 128 * 128 * 4
    us = 1e6 * seconds / CALLS
    return us, 100.0 * (nbytes / 819e9) / (seconds / CALLS)


failed = 0
out = os.path.join(tempfile.gettempdir(), "pr45_kernel_alone")  # traces: too large to bring back
for R in (64, 128):
    row = {}
    for lanes in (False, True):
        row[lanes] = timed(R, lanes, os.path.join(out, f"R{R}_{int(lanes)}"))
    (gdn_us, gdn_pct), (kda_us, kda_pct) = row[False], row[True]
    print(f"{R} slots x 32 heads of 128x128 float32: %gdn_step {gdn_us:.1f} us a call "
          f"({gdn_pct:.1f}% of the state's bytes at 819 GB/s), %kda_step {kda_us:.1f} us "
          f"({kda_pct:.1f}%): {100 * (kda_us / gdn_us - 1):+.1f}%", flush=True)
for name, _, thunk in tpu_smoke.cases():
    if "kda_step" in name or "32 heads x (512" in name or name.startswith("gdn_step"):
        ok, detail = thunk()
        failed += not ok
        print(f"{'OK  ' if ok else 'FAIL'} {name}: {detail}", flush=True)
for mix in ("ragged", "deep"):
    ok, detail = tpu_smoke.latent_case(mix, nH=32, R=128, nb=64, L=2, layer=1, scale=192 ** -0.5)
    failed += not ok
    print(f"{'OK  ' if ok else 'FAIL'} the cell's shape (128 slots x 64 columns, 32 heads), {mix}: "
          f"{detail}", flush=True)
sys.exit(1 if failed else 0)
