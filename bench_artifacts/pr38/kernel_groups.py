"""How many live pages a loop iteration of the latent kernel scores together
(`ops/paged_attention_latent.py:PAGES_PER_GROUP`): the kernel at the cell's
shape (64 slots x 128 columns x 128 heads x 640 lanes, ragged depths, every
third slot not active), host clock over 20 queued calls, a group size a line.

    chiprun -- python bench_artifacts/pr38/kernel_groups.py
"""

import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from areal_tpu.ops import paged_attention_latent as m  # noqa: E402
from areal_tpu.ops.paged_attention import live_block_range  # noqa: E402

R, nH, D, dv, bsz, nb, L, layer = 64, 128, 640, 512, 128, 128, 2, 1
keys = jax.random.split(jax.random.PRNGKey(9), 2)
lanes = jnp.arange(D) < 576
pool = jax.random.normal(keys[0], (L, R * nb + 1, bsz, D), jnp.bfloat16) * lanes
q = (jax.random.normal(keys[1], (R, nH, D), jnp.bfloat16) * lanes).astype(jnp.bfloat16)
bt = jnp.arange(1, R * nb + 1, dtype=jnp.int32).reshape(R, nb)
r = np.arange(R)
span = nb * bsz
for mix in ("ragged", "deep"):
    length = np.full(R, span) if mix == "deep" else (17 + 61 * r) * nb // 10 % span + 1
    active = jnp.asarray(np.ones(R, bool) if mix == "deep" else r % 3 != 1)
    valid = jnp.arange(span)[None, :] < jnp.asarray(length)[:, None]
    live = live_block_range(valid, bsz, active)
    n_pages = int((live[1] - live[0]).sum())
    ref = None
    for pages in (1, 2, 4, 8):
        fn = jax.jit(lambda q, pool, valid, pages=pages: m._latent_pallas(
            q, pool, bt, valid, jnp.int32(layer), dv, 0.11472, False,
            live_block_range(valid, bsz, active), pages=pages))
        out = jax.block_until_ready(fn(q, pool, valid))
        ref = out if ref is None else ref
        t0 = time.perf_counter()
        for _ in range(20):
            out = fn(q, pool, valid)
        jax.block_until_ready(out)
        ms = (time.perf_counter() - t0) / 20 * 1e3
        err = float(jnp.abs(out.astype(jnp.float32) - ref.astype(jnp.float32)).max())
        print(f"{mix} pages={pages}: ms_per_call={ms:.3f} us_per_live_page={1e3 * ms / n_pages:.3f} "
              f"({n_pages} pages) max|out - pages=1|={err:.4f}", flush=True)
