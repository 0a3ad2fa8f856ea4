"""How many live pages a loop iteration of the paged decode kernel scores
together (`ops/paged_attention.py:group_pages`): the kernel alone at the
rollout cells' shapes, the parent's kernel (when the parent commit is unpacked
under `_parent/`: `git archive <parent> | tar -x -C _parent`) and this one at
1, 2, 4 and 8 pages in one process, a kernel and group a line with the group
the rule gives. A call is timed inside one program that makes `INNER` of them
in a row, each taking the one before's output as its queries (the work list
read once, before the loop), host clock over `ROUNDS` queued programs, the
least of three: a lone call of 100-200 us is under what the host takes to
dispatch one.

    chiprun -- python bench_artifacts/pr42/kernel_groups.py
"""

import importlib.util
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from areal_tpu.ops import paged_attention as new  # noqa: E402
from areal_tpu.ops.kv_quant import quantize_kv  # noqa: E402

old = None
_parent = os.path.join(ROOT, "_parent/areal_tpu/ops/paged_attention.py")
if os.path.exists(_parent):
    spec = importlib.util.spec_from_file_location("paged_attention_parent", _parent)
    old = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(old)

BSZ, INNER, ROUNDS = 128, 25, 8

# name: slots, heads, kv heads, head size, table columns, (least, most) tokens
# a live slot holds, share of slots live, queries a slot, int8 pool, groups
SHAPES = {
    "dense_cell": (128, 12, 2, 128, 10, (150, 450), 1.0, 1, False, (1, 2, 4, 8)),
    "dense_bucket256": (128, 12, 2, 128, 2, (100, 250), 1.0, 1, False, (1, 2, 4, 8)),
    "dense_int8": (128, 12, 2, 128, 10, (150, 450), 1.0, 1, True, (1, 2, 4, 8)),
    "qwen3next": (64, 16, 2, 256, 64, (1024, 6600), 1.0, 1, False, (1, 2, 4, 8)),
    "kexaone_full": (64, 64, 8, 128, 64, (1024, 6000), 1.0, 1, False, (1, 2, 4, 8)),
    "kexaone_ring": (64, 64, 8, 128, 2, (129, 256), 0.7, 1, False, (1, 2, 4, 8)),
    # (eight pages of 1 MiB a pool do not fit VMEM)
    "olmoe": (64, 16, 16, 128, 10, (150, 450), 1.0, 1, False, (1, 2, 4)),
    "sdar_block4": (128, 32, 4, 128, 10, (150, 450), 1.0, 4, False, (1, 2, 4, 8)),
}


def batch(name):
    R, nH, nKV, hd, nb, (least, most), share, W, int8, _ = SHAPES[name]
    rng = np.random.default_rng(len(name))
    L, layer, n_blocks = 2, 1, R * nb + 1
    keys = jax.random.split(jax.random.PRNGKey(11), 3)
    pools = [jax.random.normal(k, (L, n_blocks, BSZ, nKV, hd), jnp.bfloat16) for k in keys[:2]]
    rows = lambda a: a.reshape(L, n_blocks, BSZ, nKV * hd)  # noqa: E731
    if int8:
        pools = [(rows(d), jnp.swapaxes(s, -1, -2)) for d, s in map(quantize_kv, pools)]
    else:
        pools = [rows(p) for p in pools]
    q = jax.random.normal(keys[2], (R, W, nH, hd), jnp.bfloat16)
    bt = jnp.asarray(rng.permutation(np.arange(1, n_blocks)).reshape(R, nb), jnp.int32)
    length = np.minimum(rng.integers(least, most + 1, R), nb * BSZ - W)
    pos = length[:, None] + np.arange(W)[None, :]
    valid = jnp.asarray(np.arange(nb * BSZ)[None, None, :] <= pos[:, :, None])
    active = jnp.asarray(rng.random(R) < share)
    return q, pools[0], pools[1], bt, valid, active, layer


def timed(fn, *args):
    jax.block_until_ready(fn(*args))
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(ROUNDS):
            out = fn(*args)
        jax.block_until_ready(out)
        best = min(best, (time.perf_counter() - t0) / (ROUNDS * INNER) * 1e6)
    return best


def main():
    assert jax.default_backend() == "tpu", jax.default_backend()
    for name, shape in SHAPES.items():
        R, nH, nKV, hd, nb, _, _, W, int8, groups = shape
        q, kp, vp, bt, valid, active, layer = batch(name)
        lo, hi = new.live_block_range(valid, BSZ, active)
        columns = int((hi - lo).sum())
        rule = new.group_pages(BSZ, nKV * hd, 1 if int8 else 2, W, nb)

        def call(mod, inner, **kw):
            def many(q, kp, vp, valid):
                live = mod.live_block_range(valid, BSZ, active)
                if "pages" in kw:
                    live = (*live, *mod.slot_schedule(*live, kw["pages"]))
                one = lambda _, q: mod._paged_pallas(  # noqa: E731
                    q, kp, vp, bt, valid, jnp.int32(layer), hd ** -0.5, False,
                    "paged_attention", live, **kw)
                return one(0, q) if inner == 1 else jax.lax.fori_loop(0, inner, one, q)

            return jax.jit(many)

        first = None
        for tag, mod, kw in [("parent", old, {})] * (old is not None) + [
                (f"pages={p}", new, {"pages": p}) for p in groups]:
            try:
                out = jax.block_until_ready(call(mod, 1, **kw)(q, kp, vp, valid))
                us = timed(call(mod, INNER, **kw), q, kp, vp, valid)
            except Exception as e:  # noqa: BLE001 — a group Mosaic refuses is a finding
                print(f"{name} {tag}: FAILED {type(e).__name__}: {str(e)[:200]}", flush=True)
                continue
            first = out if first is None else first
            err = float(jnp.abs(out.astype(jnp.float32) - first.astype(jnp.float32)).max())
            print(f"{name} {tag}: us_per_call={us:.2f} live_columns={columns} "
                  f"us_per_live_column={us / max(columns, 1):.3f} max|out - first|={err:.5f} "
                  f"bits_equal={bool(jnp.array_equal(out, first))} (rule -> {rule})", flush=True)


if __name__ == "__main__":
    main()
