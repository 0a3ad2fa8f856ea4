"""Step 0 of PR 43: the three flash kernels alone at bf16 inputs, parent
against change, on the chip (the pattern of `bench_artifacts/pr37/flash_pair.py`).

Run from a checkout of the change that holds the parent commit unpacked under
`_parent/` (`git archive <parent> | tar -x -C _parent`):

    chiprun -- python bench_artifacts/pr43/flash_pair.py

Sides, each a module of its own in this one process:

- `parent`: `_parent/areal_tpu/ops/flash_attention.py` (q, k, v and dO cast
  to float32, `%flash_dkv` contracting dimension 0 of `p` and `ds`);
- `change`: this tree's module (operands as given, `%flash_dkv` scoring the
  transposed block);
- `dkv_dim0`: the change with `%flash_dkv` in the parent's form
  (`dkv_dim0.py`): the alternative the table decided against. In call k1,
  before the choice, this side was named `change` and today's `change` was
  `change_dkvT`;
- `no_mxu`, `no_exp`, `no_mask`: three throw-away readings of the change for
  the stop rule's split, its text with every `dot_general` replaced by a
  slice and a broadcast of the right shape, with every `exp` replaced by the
  identity, and with the mask's select taken out of the scores: what a live
  pair costs without its matmuls, without its exponentials, without its
  mask. Their results are wrong on purpose; only their time is read.

For each case and side: device us a call of `%flash_fwd`, `%flash_dq` and
`%flash_dkv` from a profiler trace of `n` calls of forward + backward, and
the largest |difference| from the parent's output, `lse`, dq, dk and dv
(beside the largest |value| of the parent's). `FLASH_PAIR_TINY=1` rehearses
the script on the CPU (interpret mode, 512 tokens, no times)."""

import importlib.util
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)
TINY = os.environ.get("FLASH_PAIR_TINY") == "1"
OUT = os.path.join(ROOT, "chiprun_out", "pr43_traces")

NO_MXU = '''
def _no_mxu(a, b, dims, preferred_element_type):
    """A `dot_general`'s shape from its operands, no product."""
    (ca,), (cb,) = dims[0]
    m, n = a.shape[1 - ca], b.shape[1 - cb]
    f32 = preferred_element_type
    if ca == 1 and cb == 1:  # a b^T: a's first column along the lanes
        return jnp.broadcast_to(a[:, :1].astype(f32), (m, n))
    if ca == 1:  # a b: the first n lanes of a, b's first row down the sublanes
        return a[:, :n].astype(f32) + b[:1, :n].astype(f32)
    return a[:m, :n].astype(f32) + b[:1, :n].astype(f32)  # a^T b at square blocks
'''


def load(name, path, edit=None):
    if edit is not None:
        src = edit(open(path).read())
        assert src != open(path).read(), f"{name}: the edit found nothing to replace"
        path = os.path.join(ROOT, "chiprun_out", f"pr43_{name}.py")
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(path, "w") as f:
            f.write(src)
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def sides():
    here = os.path.join(ROOT, "areal_tpu/ops/flash_attention.py")
    out = {
        "parent": load("flash_parent", os.path.join(ROOT, "_parent/areal_tpu/ops/flash_attention.py")),
        "change": load("flash_change", here),
        "dkv_dim0": load("flash_dkv_dim0", here),
        "no_mxu": load("flash_no_mxu", here, lambda s: s.replace(
            "jax.lax.dot_general(", "_no_mxu(").replace("\n_NEG_INF = -1e30\n", "\n_NEG_INF = -1e30\n" + NO_MXU)),
        "no_exp": load("flash_no_exp", here, lambda s: s.replace("jnp.exp(", "(")),
        "no_mask": load("flash_no_mask", here, lambda s: s.replace(
            "jnp.where(mask, s * sm_scale, _NEG_INF)", "s * sm_scale")),
    }
    variant = load("dkv_dim0", os.path.join(HERE, "dkv_dim0.py"))
    out["dkv_dim0"]._bwd_dkv_kernel = variant.kernel(out["dkv_dim0"])
    return out


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


def kernel_us(f, args, n, tag):
    """Device us a call by flash kernel, from a profiler trace of n calls."""
    from tools.trace_report import report

    d = os.path.join(OUT, tag)
    jax.block_until_ready(f(*args))
    with jax.profiler.trace(d):
        for _ in range(n):
            out = f(*args)
        jax.block_until_ready(out)
    rows = report(d, top=30)["kernels"]
    us = {}
    for name, _incl, own, _events in rows:
        for kern in ("flash_fwd", "flash_dq", "flash_dkv"):
            if kern in name:
                us[kern] = us.get(kern, 0.0) + own / n * 1e6
    other = sum(r[2] for r in rows if "flash" not in r[0]) / n * 1e6
    return us, other


def case(mods, name, nH, nKV, hd, seg_q, seg_k, qpos, kpos, n=20):
    Tq, Tk = len(seg_q), len(seg_k)
    ks = jax.random.split(jax.random.PRNGKey(7), 5)
    bf = jnp.bfloat16
    q = jax.random.normal(ks[0], (Tq, nH, hd), bf)
    k = jax.random.normal(ks[1], (Tk, nKV, hd), bf)
    v = jax.random.normal(ks[2], (Tk, nKV, hd), bf)
    do = jax.random.normal(ks[3], (Tq, nH, hd), bf)
    dlse = jax.random.normal(ks[4], (Tq, nH), jnp.float32)
    ids = tuple(jnp.asarray(x, jnp.int32) for x in (seg_q, seg_k, qpos, kpos))

    def both(mod):
        def f(q, k, v):
            (o, lse), vjp = jax.vjp(
                lambda q, k, v: mod.flash_attention_chunk(q, k, v, *ids, interpret=TINY), q, k, v)
            return (o, lse) + vjp((do, dlse))

        return jax.jit(f)

    print(f"== {name}", flush=True)
    f32 = lambda xs: [np.asarray(x, np.float32) for x in xs]  # noqa: E731
    want = f32(both(mods["parent"])(q, k, v))
    print("   largest |value| of the parent's (out, lse, dq, dk, dv): "
          + " ".join(f"{np.abs(w[np.abs(w) < 1e29]).max():.4g}" for w in want), flush=True)
    total = {}
    for side, mod in mods.items():
        f = both(mod)
        got = f32(f(q, k, v))
        diff = " ".join(f"{np.abs(g - w).max():.3g}" for g, w in zip(got, want))
        if TINY:
            print(f"   {side:12s} max|diff| (out, lse, dq, dk, dv) = {diff}", flush=True)
            continue
        us, other = kernel_us(f, (q, k, v), n, f"{name.split(' ')[0]}_{side}")
        total[side] = sum(us.values())
        print(f"   {side:12s} us a call: fwd {us.get('flash_fwd', 0):9.1f} dq {us.get('flash_dq', 0):9.1f} "
              f"dkv {us.get('flash_dkv', 0):9.1f} sum {total[side]:9.1f} "
              f"({100 * (total[side] / total['parent'] - 1):+.1f}% of the parent's) other ops {other:8.1f} | "
              f"max|diff| (out, lse, dq, dk, dv) = {diff}", flush=True)


def main():
    if not TINY:
        assert jax.default_backend() == "tpu", jax.default_backend()
    mods = sides()
    ar = lambda n, off=0: np.arange(n, dtype=np.int32) + off  # noqa: E731
    if TINY:
        seg = packed_row(512, 1, mean_len=60, pad=40)
        case(mods, "tiny [4,512,64]", 4, 2, 64, seg, seg, ar(512), ar(512))
        return 0
    for seed in (1, 2):
        seg = packed_row(8192, seed)
        case(mods, f"row0p5b_s{seed} [14,8192,64] packed_row seed {seed}", 14, 2, 64, seg, seg, ar(8192), ar(8192))
    row = packed_row(16384, 3, pad=1200)
    shard = lambda i: row[i * 4096:(i + 1) * 4096]  # noqa: E731
    case(mods, "ring_own [12,4096,128] flash_attention_chunk, own shard", 12, 2, 128,
         shard(1), shard(1), ar(4096, 4096), ar(4096, 4096))
    case(mods, "ring_before [12,4096,128] flash_attention_chunk, the shard before", 12, 2, 128,
         shard(1), shard(0), ar(4096, 4096), ar(4096))
    one = np.zeros(8192, np.int32)
    case(mods, "one_long [14,8192,64] one sequence of 8,192 tokens", 14, 2, 64, one, one, ar(8192), ar(8192), n=8)
    case(mods, "one_long_128 [12,8192,128] one sequence of 8,192 tokens", 12, 2, 128, one, one, ar(8192), ar(8192), n=8)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
