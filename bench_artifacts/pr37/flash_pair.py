"""Parent against change, the flash kernels alone, on the chip (PR 37).

Run from a checkout of the change that holds the parent commit unpacked under
`_parent/` (`git archive <parent> | tar -x -C _parent`):

    chiprun -- python bench_artifacts/pr37/flash_pair.py

For each case: outputs, lse and the three gradients of both modules compared
to the bit, then the forward alone and forward + backward timed (queued calls,
one wait), parent, change, change, parent."""

import importlib.util
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def load(name, path):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


new = load("flash_new", os.path.join(ROOT, "areal_tpu/ops/flash_attention.py"))
old = load("flash_old", os.path.join(ROOT, "_parent/areal_tpu/ops/flash_attention.py"))


def packed_row(T, seed, mean_len=473, pad=600):
    """The train cells' packing: sequences of `mean_len` tokens on average
    end to end, a pad tail."""
    rng = np.random.RandomState(seed)
    seg = np.full(T, -1, np.int32)
    start = sid = 0
    while start < T - pad:
        end = min(start + int(rng.randint(1, 2 * mean_len)), T - pad)
        seg[start:end] = sid
        start, sid = end, sid + 1
    return seg


def case(name, nH, nKV, hd, seg_q, seg_k, qpos, kpos, n=40):
    Tq, Tk = len(seg_q), len(seg_k)
    ks = jax.random.split(jax.random.PRNGKey(7), 5)
    bf = jnp.bfloat16
    q = jax.random.normal(ks[0], (Tq, nH, hd), bf)
    k = jax.random.normal(ks[1], (Tk, nKV, hd), bf)
    v = jax.random.normal(ks[2], (Tk, nKV, hd), bf)
    do = jax.random.normal(ks[3], (Tq, nH, hd), bf)
    dlse = jax.random.normal(ks[4], (Tq, nH), jnp.float32)
    ids = tuple(jnp.asarray(x, jnp.int32) for x in (seg_q, seg_k, qpos, kpos))

    def fns(mod):
        fwd = jax.jit(lambda q, k, v: mod.flash_attention_chunk(q, k, v, *ids, interpret=False))

        def both(q, k, v):
            (o, lse), vjp = jax.vjp(
                lambda q, k, v: mod.flash_attention_chunk(q, k, v, *ids, interpret=False), q, k, v)
            return (o, lse) + vjp((do, dlse))

        return fwd, jax.jit(both)

    def ms(f):
        jax.block_until_ready(f(q, k, v))
        t0 = time.perf_counter()
        for _ in range(n):
            out = f(q, k, v)
        jax.block_until_ready(out)
        return (time.perf_counter() - t0) / n * 1e3

    def kernels(f, tag):
        """Device time a call by operation, from a profiler trace of n calls."""
        from tools.trace_report import report

        d = os.path.join(ROOT, "chiprun_out", "pr37_traces", f"{name.split(' [')[0].replace(' ', '_')}_{Tq}_{tag}")
        jax.block_until_ready(f(q, k, v))
        with jax.profiler.trace(d):
            for _ in range(n):
                out = f(q, k, v)
            jax.block_until_ready(out)
        rows = report(d, top=14)["kernels"]
        flash = {r[0].split(" ")[0]: round(r[2] / n * 1e3, 4) for r in rows if "flash" in r[0]}
        rest = sum(r[2] for r in rows if "flash" not in r[0]) / n * 1e3
        top = [(r[0][:40], round(r[2] / n * 1e3, 4)) for r in rows if "flash" not in r[0]][:6]
        print(f"  trace {tag}: {flash} other_ops_ms={rest:.4f} top_other={top}", flush=True)

    (fwd_o, all_o), (fwd_n, all_n) = fns(old), fns(new)
    equal = [bool(jnp.array_equal(a, b)) for a, b in zip(all_o(q, k, v), all_n(q, k, v))]
    lo_q, hi_q, lo_k, hi_k = new.walk_runs(*map(np.asarray, ids), 512, 512)
    t = {}
    for tag, f in (("fwd_old", fwd_o), ("fwd_new", fwd_n), ("all_old", all_o), ("all_new", all_n),
                   ("all_new2", all_n), ("fwd_new2", fwd_n), ("all_old2", all_o), ("fwd_old2", fwd_o)):
        t[tag] = ms(f)
    mean = lambda a: (t[a] + t[a + "2"]) / 2  # noqa: E731
    if os.environ.get("PAIR_TRACE", "1") == "1":
        kernels(all_o, "old")
        kernels(all_n, "new")
    print(
        f"{name}: equal(out,lse,dq,dk,dv)={equal} walk_q={int((hi_q - lo_q).sum())} "
        f"walk_k={int((hi_k - lo_k).sum())} pairs={len(lo_q) * len(lo_k)} | "
        f"fwd ms old {mean('fwd_old'):.3f} new {mean('fwd_new'):.3f} | "
        f"bwd (all - fwd) ms old {mean('all_old') - mean('fwd_old'):.3f} "
        f"new {mean('all_new') - mean('fwd_new'):.3f} | raw {({k_: round(v_, 3) for k_, v_ in t.items()})}",
        flush=True,
    )
    return all(equal)


def main():
    assert jax.default_backend() == "tpu", jax.default_backend()
    ok = True
    ar = lambda n, off=0: np.arange(n, dtype=np.int32) + off  # noqa: E731
    for seed in (1, 2):
        seg = packed_row(8192, seed)
        ok &= case(f"0.5B row [14,8192,64] packed seed {seed}", 14, 2, 64, seg, seg, ar(8192), ar(8192))
    row = packed_row(16384, 3, pad=1200)
    shard = lambda i: row[i * 4096:(i + 1) * 4096]  # noqa: E731
    ok &= case("ring step [12,4096,128] own shard", 12, 2, 128, shard(1), shard(1), ar(4096, 4096), ar(4096, 4096))
    ok &= case("ring step [12,4096,128] shard before", 12, 2, 128, shard(1), shard(0), ar(4096, 4096), ar(4096))
    ok &= case("ring step [12,4096,128] two shards before (dead)", 12, 2, 128, shard(2), shard(0), ar(4096, 8192), ar(4096))
    one = np.zeros(8192, np.int32)
    ok &= case("one long segment [14,8192,64]", 14, 2, 64, one, one, ar(8192), ar(8192), n=10)
    print("RESULT:", "PASS" if ok else "NOT EQUAL")
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
