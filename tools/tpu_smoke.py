"""Lowering + numerics gate on the chip for every Pallas kernel in areal_tpu/ops.

CPU tests run the kernels in interpret mode: they validate the math, never
Mosaic's lowering rules (block shapes, tile alignment, VMEM). This script
compiles each `pl.pallas_call` with `interpret=False` at Qwen2.5-0.5B's
shapes (14 query / 2 KV heads, head_dim 64, page 128, vocab 151,936) plus the
other head families the repo supports, runs it, and compares it against the
op's own XLA implementation. `chip_smoke.py` runs it as its kernel phase.

Usage: python tools/tpu_smoke.py      (fails unless JAX's device is a TPU)
"""

from __future__ import annotations

import functools
import os
import sys
import time
import traceback

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from areal_tpu.ops.flash_attention import (
    PADDING_SEGMENT,
    flash_attention,
    flash_attention_chunk,
)
from areal_tpu.ops.kv_quant import quantize_kv
from areal_tpu.ops.paged_attention import (
    live_block_range,
    paged_attention,
    paged_attention_qlen,
)
from areal_tpu.ops.quant import quantize_absmax
from areal_tpu.ops.quant_matmul import quant_einsum

# Qwen2.5-0.5B
N_HEADS, N_KV, HEAD_DIM, PAGE, HIDDEN, MLP, VOCAB = 14, 2, 64, 128, 896, 4864, 151936


@functools.partial(jax.jit, static_argnames="sm_scale")
def _masked_attention(q, k, v, mask, sm_scale):
    """Dense f32 reference: q [Tq,nH,hd], k/v [Tk,nKV,hd], mask [Tq,Tk].
    Returns (out [Tq,nH,hd] f32, lse [Tq,nH])."""
    group = q.shape[1] // k.shape[1]
    kf = jnp.repeat(k, group, axis=1).astype(jnp.float32)
    vf = jnp.repeat(v, group, axis=1).astype(jnp.float32)
    s = jnp.einsum("qhd,khd->hqk", q.astype(jnp.float32), kf) * sm_scale
    s = jnp.where(mask[None], s, -1e30)
    lse = jax.nn.logsumexp(s, axis=-1)
    p = jnp.where(mask[None], jnp.exp(s - lse[..., None]), 0.0)
    return jnp.einsum("hqk,khd->qhd", p, vf), lse.T


def _packed_segments(T):
    """Three packed segments and a pad tail."""
    idx = jnp.arange(T)
    seg = jnp.where(idx < T // 3, 0, jnp.where(idx < 2 * T // 3, 1, 2))
    pad_from = max(T - max(T // 8, 1), 1)
    return jnp.where(idx >= pad_from, PADDING_SEGMENT, seg).astype(jnp.int32), pad_from


def _many_segments(T):
    """A trainer's row: sequences of 40-700 tokens packed end to end (most
    512-blocks hold a boundary, most block pairs no valid pair) and a pad
    tail of a block and a half."""
    rng = np.random.RandomState(T)
    pad_from = T - 768
    seg = np.full(T, PADDING_SEGMENT, np.int32)
    start = sid = 0
    while start < pad_from:
        end = min(start + int(rng.randint(40, 700)), pad_from)
        seg[start:end] = sid
        start, sid = end, sid + 1
    return jnp.asarray(seg), pad_from


def _segment_mask(seg, rows):
    """[len(rows), T]: which keys of a packed row each of `rows` may see."""
    pos = jnp.arange(seg.shape[0])
    return (
        (seg[rows][:, None] == seg[None, :])
        & (pos[rows][:, None] >= pos[None, :])
        & (seg[rows][:, None] != PADDING_SEGMENT)
    )


def _rel(a, b):
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    return float(jnp.max(jnp.abs(a - b)) / (1e-3 + jnp.max(jnp.abs(b))))


def _ms_per_call(fn, *args, n=20):
    """Host clock over `n` queued calls and one wait (`fn` already compiled)."""
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / n * 1e3


def _one_segment(T):
    """One sequence over the whole row: every block pair under the diagonal
    is live, so a walk is as long as the grid it replaced."""
    return jnp.zeros((T,), jnp.int32), T


# bf16 inputs against XLA's float32 dense reference, read on the chip once
# (PR 43, call k2: the kernels' products take bf16 operands and `p`, `ds`
# rounded to bf16, as the float32-cast operands before them did inside the
# MXU): forward 0.0106-0.0129 on whole rows up to 4,096 tokens (0.0003-0.0036
# on the 128-query slice of the 8,192- and 32,768-token rows; 0.0121 under
# vmap, 0.0020 a ring chunk), gradients 0.0054-0.0091 of their largest entry
# (0.0081 a ring chunk), `lse` under 5e-5.
FLASH_TOL = 0.03
FLASH_LSE_TOL = 1e-3


def flash_case(T, nH, nKV, hd, segments=_packed_segments):
    """flash_attention forward + backward against the dense reference; the
    detail ends with the time of a forward and of forward + backward a call
    (the kernels and the wrapper's transposes around them)."""
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(kq, (T, nH, hd), jnp.bfloat16)
    k = jax.random.normal(kk, (T, nKV, hd), jnp.bfloat16)
    v = jax.random.normal(kv, (T, nKV, hd), jnp.bfloat16)
    seg, pad_from = segments(T)
    sm_scale = hd**-0.5
    w = (seg != PADDING_SEGMENT)[:, None, None].astype(jnp.float32)

    def loss_flash(q, k, v):
        o = flash_attention(q, k, v, seg, sm_scale=sm_scale, interpret=False)
        return jnp.sum((o.astype(jnp.float32) * w) ** 2)

    run_fwd = jax.jit(
        lambda q, k, v: flash_attention(
            q, k, v, seg, sm_scale=sm_scale, interpret=False
        )
    )
    run_grad = jax.jit(jax.grad(loss_flash, argnums=(0, 1, 2)))
    o_flash, g_flash = run_fwd(q, k, v), run_grad(q, k, v)
    times = (f" ms_per_call: fwd {_ms_per_call(run_fwd, q, k, v):.3f}"
             f" fwd+bwd {_ms_per_call(run_grad, q, k, v):.3f}")
    mask_rows = functools.partial(_segment_mask, seg)

    if T >= 8192:
        # A dense [T, T] reference is infeasible here, which is the point of
        # the case: check a 128-query slice that attends the whole prefix,
        # and that the backward ran and is finite.
        rows = jnp.arange(pad_from - 128, pad_from)
        o_ref, _ = _masked_attention(q[rows], k, v, mask_rows(rows), sm_scale)
        fwd = float(jnp.max(jnp.abs(o_flash[rows].astype(jnp.float32) - o_ref)))
        finite = all(bool(jnp.all(jnp.isfinite(g))) for g in g_flash)
        return fwd < FLASH_TOL and finite, f"fwd_maxerr={fwd:.4f} bwd_finite={finite}" + times

    mask = mask_rows(jnp.arange(T))

    def loss_ref(q, k, v):
        o, _ = _masked_attention(q, k, v, mask, sm_scale)
        return jnp.sum((o.astype(q.dtype).astype(jnp.float32) * w) ** 2)

    o_ref, _ = _masked_attention(q, k, v, mask, sm_scale)
    valid = np.asarray(seg != PADDING_SEGMENT)
    fwd = float(
        jnp.max(jnp.abs((o_flash.astype(jnp.float32) - o_ref)[valid]))
    )
    g_ref = jax.jit(jax.grad(loss_ref, argnums=(0, 1, 2)))(q, k, v)
    bwd = max(_rel(a, b) for a, b in zip(g_flash, g_ref))
    return (fwd < FLASH_TOL and bwd < FLASH_TOL,
            f"fwd_maxerr={fwd:.4f} bwd_relerr={bwd:.4f}" + times)


def flash_vmap_case(B=4, T=256, nH=12, nKV=2, hd=128):
    """flash_attention under `jax.vmap` with each row's own segment ids, as
    the decode engine's batched prefill calls it: one prompt a row, each of
    another length, the rest of the row pad."""
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(kq, (B, T, nH, hd), jnp.bfloat16)
    k = jax.random.normal(kk, (B, T, nKV, hd), jnp.bfloat16)
    v = jax.random.normal(kv, (B, T, nKV, hd), jnp.bfloat16)
    lens = jnp.asarray([T - 37 * b for b in range(B)])
    seg = jnp.where(
        jnp.arange(T)[None, :] < lens[:, None], 0, PADDING_SEGMENT
    ).astype(jnp.int32)
    sm_scale = hd**-0.5
    batched = jax.jit(jax.vmap(
        lambda q, k, v, seg: flash_attention(
            q, k, v, seg, sm_scale=sm_scale, interpret=False
        )
    ))
    out = batched(q, k, v, seg)
    err = 0.0
    for b in range(B):
        mask = _segment_mask(seg[b], jnp.arange(T))
        o_ref, _ = _masked_attention(q[b], k[b], v[b], mask, sm_scale)
        valid = np.asarray(seg[b] != PADDING_SEGMENT)
        err = max(err, float(
            jnp.max(jnp.abs((out[b].astype(jnp.float32) - o_ref)[valid]))
        ))
    return err < FLASH_TOL, (f"fwd_maxerr={err:.4f} rows={B} "
                             f"ms_per_call: fwd {_ms_per_call(batched, q, k, v, seg):.3f}")


def flash_chunk_case(later=False, T=1024, nH=N_HEADS, nKV=N_KV, hd=HEAD_DIM):
    """flash_attention_chunk (ring attention's per-step kernel), forward
    (out, lse) and backward through both outputs: local queries against a
    kv chunk that sits EARLIER in the stream and is half dead (its first
    512-block is another segment's), or against one that sits LATER and is
    wholly dead (no kernel visits a block: out 0, lse -1e30, gradients 0)."""
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(kq, (T, nH, hd), jnp.bfloat16)
    k = jax.random.normal(kk, (T, nKV, hd), jnp.bfloat16)
    v = jax.random.normal(kv, (T, nKV, hd), jnp.bfloat16)
    seg_k = jnp.where(jnp.arange(T) < T // 2, 0, 1).astype(jnp.int32)
    seg_q = jnp.ones((T,), jnp.int32)  # continues the chunk's second segment
    qpos = jnp.arange(T, dtype=jnp.int32) + T
    kpos = qpos + T if later else qpos - T
    sm_scale = hd**-0.5
    mask = (seg_q[:, None] == seg_k[None, :]) & (qpos[:, None] >= kpos[None, :])

    def flash(q, k, v):
        return flash_attention_chunk(
            q, k, v, seg_q, seg_k, qpos, kpos, sm_scale=sm_scale,
            interpret=False,
        )

    def ref(q, k, v):
        o, lse = _masked_attention(q, k, v, mask, sm_scale)
        return o.astype(q.dtype), lse

    def loss(fn):
        def f(q, k, v):
            o, lse = fn(q, k, v)
            return jnp.sum(o.astype(jnp.float32) ** 2) + jnp.sum(lse)

        return f

    run_fwd = jax.jit(flash)
    run_grad = jax.jit(jax.grad(loss(flash), argnums=(0, 1, 2)))
    o, lse = run_fwd(q, k, v)
    o_ref, lse_ref = ref(q, k, v)
    g = run_grad(q, k, v)
    g_ref = jax.jit(jax.grad(loss(ref), argnums=(0, 1, 2)))(q, k, v)
    fwd = float(jnp.max(jnp.abs(o.astype(jnp.float32) - o_ref.astype(jnp.float32))))
    lse_err = float(jnp.max(jnp.abs(lse - lse_ref)))
    bwd = max(_rel(a, b) for a, b in zip(g, g_ref))
    return (
        fwd < FLASH_TOL and lse_err < FLASH_LSE_TOL and bwd < FLASH_TOL,
        f"fwd_maxerr={fwd:.4f} lse_maxerr={lse_err:.6f} bwd_relerr={bwd:.4f} "
        f"ms_per_call: fwd {_ms_per_call(run_fwd, q, k, v):.3f} "
        f"fwd+bwd {_ms_per_call(run_grad, q, k, v):.3f}",
    )


def _paged_pool(keys, L, n_blocks, nKV, hd, int8):
    """The engine's pool, every layer stacked: [L, n_blocks, page, nKV*hd]
    in bf16, or (int8 rows, f32 scales [L, n_blocks, nKV, page])."""
    shape = (L, n_blocks, PAGE, nKV, hd)
    kp = jax.random.normal(keys[0], shape, jnp.bfloat16)
    vp = jax.random.normal(keys[1], shape, jnp.bfloat16)
    rows = lambda a: a.reshape(L, n_blocks, PAGE, nKV * hd)  # noqa: E731
    if not int8:
        return rows(kp), rows(vp)
    (kq, ks), (vq, vs) = quantize_kv(kp), quantize_kv(vp)
    return (rows(kq), jnp.swapaxes(ks, -1, -2)), (rows(vq), jnp.swapaxes(vs, -1, -2))


def _maxerr(a, b, rows=None):
    d = jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32))
    return float(jnp.max(d if rows is None else d[rows]))


def paged_ragged_case(mix, W, int8, nH, nKV, hd, R=16, nb=10, L=2, layer=1, pages=None):
    """The kernel's walk over the live (slot, block column) pairs: slots of
    ragged depth under one `nb`, some not active with tables that still
    name blocks. Equal to the bit, on the active slots, to the kernel told
    every column is live (what it computed before it had a range), within
    the usual error of the XLA read, zeros on the rest. `mix`: "empty"
    (two slots of sixteen active), "30%" (about 30% of the block columns
    live: the dense rollout cell's share), "all" (every slot at full depth:
    nothing to skip, the walk's own price), "one-deep" (one slot at `nb`,
    the rest one column: the batch a grid of slots x `nb` served worst).
    `pages` names the group of columns a loop iteration scores where the
    shapes' own (`group_pages`) is not meant: the result then stays within a
    bf16 rounding or two of the walk a column an iteration."""
    n_blocks = R * nb + 1
    keys = jax.random.split(jax.random.PRNGKey(5), 3)
    kp, vp = _paged_pool(keys, L, n_blocks, nKV, hd, int8)
    q = jax.random.normal(keys[2], (R, W, nH, hd), jnp.bfloat16)
    bt = jnp.arange(1, n_blocks, dtype=jnp.int32).reshape(R, nb)
    span = nb * PAGE
    r = np.arange(R)
    if mix == "all":
        base, active = np.full(R, span - W), np.ones(R, bool)
    elif mix == "empty":
        base, active = 40 + 97 * r, (r == 3) | (r == 12)
    elif mix == "one-deep":
        base, active = np.where(r == 5, span - W, 9 + 7 * r), np.ones(R, bool)
    else:  # active slots hold 1 to nb columns, a deep one sets nb
        base = np.where(r == 5, span - W, (17 + 61 * r) * nb // 10)
        active = r % 3 != 1
    pos = jnp.asarray(base)[:, None] + jnp.arange(W)[None, :]
    valid = jnp.arange(span)[None, None, :] <= pos[:, :, None]
    active = jnp.asarray(active)
    live = live_block_range(valid, PAGE, active)
    every = (jnp.zeros(R, jnp.int32), jnp.full(R, nb, jnp.int32))
    if W == 1:
        args, fn = (q[:, 0], kp, vp, bt, valid[:, 0], jnp.int32(layer)), paged_attention
    else:
        args, fn = (q, kp, vp, bt, valid, jnp.int32(layer)), paged_attention_qlen
    def kernel(pages):
        return jax.jit(lambda rng, *a: fn(
            *a, impl="pallas", interpret=False, live=rng, pages=pages))

    out, walked = kernel(pages)(live, *args), kernel(pages)(every, *args)
    ref = jax.jit(lambda *a: fn(*a, impl="xla"))(*args)
    exact = bool(jnp.all(jnp.where(active.reshape(R, *[1] * (out.ndim - 1)),
                                   out == walked, out == 0)))
    err = _maxerr(out, ref, active)
    share = float((live[1] - live[0]).sum()) / (R * nb)
    ok = exact and err < 0.03 and bool(jnp.all(jnp.isfinite(out)))
    note = f"live={share:.0%} exact={exact} maxerr={err:.4f}"
    if pages is not None:
        regroup = _maxerr(out, kernel(1)(live, *args))
        ok, note = ok and regroup < 0.01, f"{note} max|out - one page|={regroup:.5f}"
    return ok, note


def paged_ring_case(nH=64, nKV=8, hd=128, R=16, window=128, L=4, layer=3, pages=None):
    """A mixed stack's window layers (K-EXAONE): the ring's two pages a
    slot read through a two-column table under `%paged_attention_window`,
    the window in the mask, slots before and past their first lap, one of
    them not active."""
    from areal_tpu.models.qwen2 import _PAGED_KERNELS, _ring_valid, ring_pages

    pages = ring_pages(window, PAGE)
    keys = jax.random.split(jax.random.PRNGKey(6), 3)
    kp, vp = _paged_pool(keys, L, 1 + R * pages, nKV, hd, False)
    q = jax.random.normal(keys[2], (R, nH, hd), jnp.bfloat16)
    bt = 1 + jnp.arange(R * pages, dtype=jnp.int32).reshape(R, pages)
    pos = 5 + 83 * jnp.arange(R)
    valid = _ring_valid(pos, window, PAGE, pages)
    active = jnp.arange(R) != 7
    args = (q, kp, vp, bt, valid, jnp.int32(layer))
    out = jax.jit(lambda *a: paged_attention(
        *a, impl="pallas", interpret=False, kernel_name=_PAGED_KERNELS["window"],
        live=live_block_range(a[4], PAGE, active), pages=pages))(*args)
    ref = jax.jit(lambda *a: paged_attention(*a, impl="xla"))(*args)
    err = _maxerr(out, ref, active)
    ok = err < 0.03 and bool(jnp.all(jnp.isfinite(out))) and not bool(jnp.any(out[7]))
    return ok, f"pages={pages} maxerr={err:.4f}"


def paged_case(W, int8, nH, nKV, hd, R=16, nb=4, L=3, layer=2):
    """Paged decode (W == 1) / speculative verify (W > 1) kernel against
    the op's XLA implementation, slots at different lengths. The pool is
    the engine's: every layer stacked, [L, n_blocks, page, nKV*hd], read
    at a layer index that is traced, as the layer scan's is."""
    n_blocks = R * nb + 1
    keys = jax.random.split(jax.random.PRNGKey(2), 3)
    kp, vp = _paged_pool(keys, L, n_blocks, nKV, hd, int8)
    q = jax.random.normal(keys[2], (R, W, nH, hd), jnp.bfloat16)
    bt = jnp.arange(1, n_blocks, dtype=jnp.int32).reshape(R, nb)
    # slot r's first query sits at position 3 + 31 r (crosses page edges)
    base = 3 + 31 * jnp.arange(R)
    pos = base[:, None] + jnp.arange(W)[None, :]
    valid = jnp.arange(nb * PAGE)[None, None, :] <= pos[:, :, None]
    if W == 1:
        args = (q[:, 0], kp, vp, bt, valid[:, 0], jnp.int32(layer))
        fn = paged_attention
    else:
        args = (q, kp, vp, bt, valid, jnp.int32(layer))
        fn = paged_attention_qlen
    out = jax.jit(lambda *a: fn(*a, impl="pallas", interpret=False))(*args)
    ref = jax.jit(lambda *a: fn(*a, impl="xla"))(*args)
    err = _maxerr(out, ref)
    # the XLA impl rounds probabilities (and dequantized rows) to bf16
    return err < 0.03 and bool(jnp.all(jnp.isfinite(out))), f"maxerr={err:.4f}"


def paged_block_case(nH=32, nKV=4, hd=128, B=4, R=16, nb=4, L=3, layer=2):
    """The block-diffusion forward's read (`paged_attention_qlen` under the
    kernel name `paged_attention_block`): B queries a slot that ALL see to
    their block's last row, blocks aligned to position 0, slots at different
    depths (a block that straddles a page edge among them), against the op's
    XLA implementation."""
    n_blocks = R * nb + 1
    keys = jax.random.split(jax.random.PRNGKey(8), 3)
    kp, vp = _paged_pool(keys, L, n_blocks, nKV, hd, False)
    q = jax.random.normal(keys[2], (R, B, nH, hd), jnp.bfloat16)
    bt = jnp.arange(1, n_blocks, dtype=jnp.int32).reshape(R, nb)
    base = B * (31 * jnp.arange(R))  # slot 1's block is rows 124..127, slot 2's 248..251
    end = (base + B - 1)[:, None, None]
    valid = jnp.broadcast_to(jnp.arange(nb * PAGE)[None, None, :] <= end, (R, B, nb * PAGE))
    args = (q, kp, vp, bt, valid, jnp.int32(layer))
    out = jax.jit(lambda *a: paged_attention_qlen(
        *a, impl="pallas", interpret=False, kernel_name="paged_attention_block"))(*args)
    ref = jax.jit(lambda *a: paged_attention_qlen(*a, impl="xla"))(*args)
    err = _maxerr(out, ref)
    return err < 0.03 and bool(jnp.all(jnp.isfinite(out))), f"maxerr={err:.4f}"


def latent_case(mix, nH=128, C=512, rope=64, R=16, nb=10, L=5, layer=3, scale=0.11472):
    """The latent decode kernel (`ops/paged_attention_latent.py`) at
    DeepSeek-V2's widths: 128 heads against ONE shared row of 512 + 64 lanes
    stored at 640, the first 512 summed, over a five-layer pool read at a
    traced layer index; against the same arithmetic in `jax.numpy` at
    float32 on the gathered rows. `mix`: "ragged" (slots of 1 to `nb`
    columns, every third not active with a table that still names blocks:
    zeros there), "deep" (every slot at full depth). `us_per_live_page` is
    from the host's clock, wrapper and work list included."""
    from areal_tpu.ops.paged_attention_latent import paged_attention_latent

    D = -(-(C + rope) // 128) * 128
    n_blocks = R * nb + 1
    keys = jax.random.split(jax.random.PRNGKey(9), 2)
    lanes = jnp.arange(D) < C + rope  # the pad lanes hold zeros, as the engine writes them
    pool = jax.random.normal(keys[0], (L, n_blocks, PAGE, D), jnp.bfloat16) * lanes
    q = (jax.random.normal(keys[1], (R, nH, D), jnp.bfloat16) * lanes).astype(jnp.bfloat16)
    bt = jnp.arange(1, n_blocks, dtype=jnp.int32).reshape(R, nb)
    span, r = nb * PAGE, np.arange(R)
    if mix == "deep":
        length, active = np.full(R, span), np.ones(R, bool)
    else:
        length = np.where(r == 5, span, (17 + 61 * r) * nb // 10 % span + 1)
        active = r % 3 != 1
    valid = jnp.arange(span)[None, :] < jnp.asarray(length)[:, None]
    active = jnp.asarray(active)
    kernel = jax.jit(lambda q, pool, valid: paged_attention_latent(
        q, pool, bt, valid, jnp.int32(layer), dv=C, sm_scale=scale, impl="pallas",
        interpret=False, live=live_block_range(valid, PAGE, active)))
    out = kernel(q, pool, valid)

    def plain(q, pool, valid):
        with jax.default_matmul_precision("highest"):
            rows = pool[layer][bt].reshape(R, span, D).astype(jnp.float32)
            s = jnp.einsum("rnd,rsd->rns", q.astype(jnp.float32), rows) * scale
            p = jax.nn.softmax(jnp.where(valid[:, None, :], s, -jnp.inf), axis=-1)
            return jnp.einsum("rns,rsd->rnd", p, rows[..., :C])

    ref = jax.jit(plain)(q, pool, valid)
    err = _maxerr(out, ref, active)
    zeros = not bool(jnp.any(out[~active]))
    ok = err < 0.03 and zeros and bool(jnp.all(jnp.isfinite(out)))
    live_lo, live_hi = live_block_range(valid, PAGE, active)
    pages = int((live_hi - live_lo).sum())
    ms = _ms_per_call(kernel, q, pool, valid)
    return ok, (f"maxerr={err:.4f} inactive_zero={zeros} ms_per_call={ms:.3f} "
                f"us_per_live_page={1e3 * ms / pages:.3f} ({pages} pages)")


def gdn_step_case(n=6, layer=4, R=64, Hv=32, dk=128, dv=128, dead_every=7, lanes=False):
    """The Gated DeltaNet decode step (`ops/gdn_step.py`) at Qwen3-Next's
    state shape: six linear layers' float32 states in one pool, the kernel
    against the op's `jax.numpy` arithmetic; some slots not active, whose
    rows, the other layers' and the null row must come back to the bit.
    `lanes`: the decay a vector over the key lanes (Kimi Delta Attention:
    the call named `kda_step`)."""
    from areal_tpu.ops.gdn_step import gdn_step

    ks = jax.random.split(jax.random.PRNGKey(7), 6)
    S = jax.random.normal(ks[0], (n, 1 + R, Hv, dk, dv), jnp.float32).at[:, 0].set(0)
    q = jax.random.normal(ks[1], (R, Hv, dk)) * dk ** -0.5
    k = jax.random.normal(ks[2], (R, Hv, dk))
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[3], (R, Hv, dv))
    g = -4.0 * jax.random.uniform(ks[4], (R, Hv, dk) if lanes else (R, Hv))
    beta = jax.random.uniform(ks[5], (R, Hv))
    active = jnp.arange(R) % dead_every != 3
    o, S1 = jax.jit(lambda *a: gdn_step(*a, layer, active, impl="pallas", interpret=False))(
        S, q, k, v, g, beta)
    o_ref, S_ref = jax.jit(lambda *a: gdn_step(*a, layer, active, impl="xla"))(
        S, q, k, v, g, beta)
    err = max(_maxerr(S1[layer], S_ref[layer]), _maxerr(o, o_ref, active))
    kept = (bool(jnp.array_equal(S1[:, 0], S[:, 0]))
            and bool(jnp.array_equal(jnp.delete(S1, layer, axis=0), jnp.delete(S, layer, axis=0)))
            and bool(jnp.array_equal(S1[layer, 1:][~active], S[layer, 1:][~active])))
    return err < 1e-4 and kept and bool(jnp.all(jnp.isfinite(o))), f"maxerr={err:.2e} kept={kept}"


def ssm_step_case(n=26, layer=19, R=256, N=16, Di=5120, dead_every=7):
    """The Mamba-1 decode step (`ops/ssm_step.py`) at AI21-Jamba2-3B's state
    shape and the cell's 256 slots: 26 state-space layers' float32 states in
    one pool, the layer's place traced (as inside a scanned run), the kernel
    over the live slots' work list against the op's `jax.numpy` arithmetic;
    some slots not active, whose rows, the other layers' and the null row
    must come back to the bit and whose outputs read 0."""
    from areal_tpu.ops.ssm_step import ssm_step

    ks = jax.random.split(jax.random.PRNGKey(11), 6)
    S = jax.random.normal(ks[0], (n, 1 + R, N, Di), jnp.float32).at[:, 0].set(0)
    dt = jax.random.uniform(ks[1], (R, Di), jnp.float32, 1e-3, 0.1)
    u = jax.random.normal(ks[2], (R, Di))
    B, C = jax.random.normal(ks[3], (R, N)), jax.random.normal(ks[4], (R, N))
    A = -jnp.broadcast_to(jnp.arange(1, N + 1, dtype=jnp.float32)[:, None], (N, Di))
    D = jax.random.normal(ks[5], (Di,))
    active = jnp.arange(R) % dead_every != 3
    li = jnp.int32(layer)
    y, S1 = jax.jit(lambda S, li: ssm_step(S, dt, u, B, C, A, D, li, active, impl="pallas",
                                           interpret=False))(S, li)
    y_ref, S_ref = jax.jit(lambda S, li: ssm_step(S, dt, u, B, C, A, D, li, active,
                                                  impl="xla"))(S, li)
    err = max(_maxerr(S1[layer], S_ref[layer]), _maxerr(y, y_ref))
    kept = (bool(jnp.array_equal(S1[:, 0], S[:, 0]))
            and bool(jnp.array_equal(jnp.delete(S1, layer, axis=0), jnp.delete(S, layer, axis=0)))
            and bool(jnp.array_equal(S1[layer, 1:][~active], S[layer, 1:][~active]))
            and bool(jnp.all(y[~active] == 0)))
    return err < 1e-4 and kept and bool(jnp.all(jnp.isfinite(y))), f"maxerr={err:.2e} kept={kept}"


def moe_case(T, layers=1, li=0, H=2048, M=1024, E=64, K=8, dead_every=5, published=None):
    """The exact MoE mixture (`models/qwen2.py:moe_mlp`: pairs sorted by
    expert, XLA's grouped matmul for `jax.lax.ragged_dot`) at OLMoE's
    published widths against every expert run densely on every token in
    float32 and masked by the top-k; some rows are dead slots, the live
    groups are uneven and every seventh expert is empty (row 0 of the
    router kernel votes it down).

    With `layers` > 1, as a stacked layer loop of the decode engine calls
    it (`_scan_stacked`): the grouped matmul's operand is the kernels of
    all the layers `[layers*E, H, M]`, layer `li`'s experts are the groups
    from `li*E` on and the others are empty; also timed against the same
    call on that layer's own `[E, H, M]` kernels, since the empty groups
    must cost nothing.

    With `published`, a chip's share of the experts (K-EXAONE: 16 held of
    128): the router is `published` wide, every token picks its K among all
    of them, and the E held here compute the pairs that land on them.

    At 64 tokens the 512 pair rows are few a group, so `grouped_matmul_rows`
    lays them out at 640 (the grouped matmul's 128-row tile); a 2,048-token
    bucket's 16,384 keep the 512-row tile: `rows=` in the detail."""

    from areal_tpu.models.qwen2 import ModelConfig, grouped_matmul_rows, moe_mlp

    E_pub = published or E
    cfg = ModelConfig(hidden_size=H, num_experts=E, num_experts_published=published,
                      num_experts_per_tok=K, moe_intermediate_size=M, norm_topk_prob=False)
    ks = jax.random.split(jax.random.PRNGKey(3), 5)
    G = layers * E
    router = jax.random.normal(ks[0], (H, E_pub), jnp.bfloat16) / H**0.5
    stack = {
        "gate_kernel": jax.random.normal(ks[1], (G, H, M), jnp.bfloat16) / H**0.5,
        "up_kernel": jax.random.normal(ks[2], (G, H, M), jnp.bfloat16) / H**0.5,
        "down_kernel": jax.random.normal(ks[3], (G, M, H), jnp.bfloat16) / M**0.5,
    }
    # the layer's own kernels, and what the engine's call hands over
    p = {k: w[li * E:(li + 1) * E] for k, w in stack.items()}
    p["router_kernel"] = router.at[0, ::7].set(-10.0)
    called = p if layers == 1 else {**p, **stack, "first_group": jnp.int32(li * E)}
    x = jax.random.normal(ks[4], (T, H), jnp.bfloat16).at[:, 0].set(8.0)
    valid = jnp.arange(T) % dead_every != 0

    def dense(p, x):
        with jax.default_matmul_precision("highest"):
            x32 = x.astype(jnp.float32)
            probs = jax.nn.softmax(x32 @ p["router_kernel"].astype(jnp.float32), -1)
            w, idx = jax.lax.top_k(probs, K)
            # the weights of the experts held here (the first E of the router's width)
            dw = (jax.nn.one_hot(idx, E_pub) * w[..., None]).sum(1)[:, :E] * valid[:, None]

            def one(acc, xs):
                g, u, d, w_e = (a.astype(jnp.float32) for a in xs)
                return acc + w_e[:, None] * ((jax.nn.silu(x32 @ g) * (x32 @ u)) @ d), None

            return jax.lax.scan(one, jnp.zeros((T, H), jnp.float32),
                                (p["gate_kernel"], p["up_kernel"], p["down_kernel"], dw.T))[0]

    mlp = jax.jit(lambda p, x: moe_mlp(p, x, cfg, valid=valid, with_load=True))
    y, _, load = mlp(called, x)
    ref = jax.jit(dense)(p, x)
    err = _rel(y.astype(jnp.float32), ref)
    dead = float(jnp.abs(y[~valid].astype(jnp.float32)).max())
    # every valid pair is computed here or, with a share held, counted absent
    absent = int(load[2]) if published else 0
    ok = (err < 0.03 and dead == 0.0 and int(load[0]) > 0
          and int(load[0]) + absent == int(valid.sum()) * K)
    detail = (f"relerr={err:.4f} dead_rows_max={dead} pairs={int(load[0])} hot={int(load[1])} "
              f"rows={T * K}->{grouped_matmul_rows(T * K, E)}")
    if layers == 1:
        return ok, detail

    t_own, t_stack = (
        _ms_per_call(lambda p: mlp(p, x)[0], p, n=30) for p in (p, called)
    )
    return ok and t_stack < 1.25 * t_own, (
        f"{detail} ms_per_call: {G} groups {t_stack:.3f}, {E} groups {t_own:.3f}")


def quant_matmul_case(k_dims, out_dims, T=16):
    """Int8 dequant-matmul kernel against the op's XLA implementation."""
    kx, kw = jax.random.split(jax.random.PRNGKey(3))
    nc = len(k_dims)
    x = jax.random.normal(kx, (T, *k_dims), jnp.bfloat16)
    w = jax.random.normal(kw, (*k_dims, *out_dims), jnp.float32) * 0.02
    wq, ws = quantize_absmax(w, axis=tuple(range(nc)))
    out = quant_einsum(x, wq, ws, nc, impl="pallas", interpret=False)
    ref = quant_einsum(x, wq, ws, nc, impl="xla")  # quant_einsum is jitted
    err = _rel(out, ref)
    return err < 0.02, f"relerr={err:.4f}"


def fused_xent_case(T=1024):
    """bf16 vocab-chunked LM loss (XLA, no Pallas) against the dense loss
    at the full vocab: the trainer's head."""
    from areal_tpu.ops.fused_xent import chunked_label_logprobs
    from areal_tpu.utils.functional import gather_logprobs

    kh, kw, kl = jax.random.split(jax.random.PRNGKey(4), 3)
    h = jax.random.normal(kh, (T, HIDDEN), jnp.bfloat16) * 0.5
    w = jax.random.normal(kw, (HIDDEN, VOCAB), jnp.bfloat16) * 0.02
    labels = jax.random.randint(kl, (T,), 0, VOCAB)

    def fused(h, w):
        return -chunked_label_logprobs(h, w, labels).mean()

    def dense(h, w):
        logits = jnp.einsum("th,hv->tv", h, w, preferred_element_type=jnp.float32)
        return -gather_logprobs(logits, labels).mean()

    lf, gf = jax.jit(jax.value_and_grad(fused, argnums=(0, 1)))(h, w)
    ld, gd = jax.jit(jax.value_and_grad(dense, argnums=(0, 1)))(h, w)
    val = abs(float(lf) - float(ld)) / max(abs(float(ld)), 1e-6)

    def nrel(a, b):
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        return float(jnp.linalg.norm(a - b) / (jnp.linalg.norm(b) + 1e-6))

    grad = max(nrel(a, b) for a, b in zip(gf, gd))
    return val < 0.01 and grad < 0.05, f"val_relerr={val:.5f} grad_relerr={grad:.4f}"


def cases():
    """(name, path, thunk): path is "default" for kernels the default
    configuration runs, else the option that selects the kernel."""
    out = []
    # (nH, nKV) families: qwen2.5-0.5B (14,2), 7B (28,4), 1.5B (12,2), MHA
    # (8,8); head dims 64 and 128; a ragged packed length; a 32k stream
    # (a dense [32k, 32k] f32 score matrix would be 4 GiB per head)
    for T, nH, nKV, hd in (
        (512, 14, 2, 64),
        (4096, 14, 2, 64),
        (130, 14, 2, 64),
        (32768, 14, 2, 64),
        (1024, 28, 4, 128),
        (512, 12, 2, 128),
        (512, 8, 8, 128),
        (2048, 16, 8, 64),
    ):
        out.append((
            f"flash_attention fwd+bwd T={T} {nH}/{nKV}/{hd}",
            "default",
            lambda a=(T, nH, nKV, hd): flash_case(*a),
        ))
    # the trainer's packing: the walk over live block pairs at work (the
    # 8,192 row is `train-0.5b-gsm8k`'s call, 4,096 at 128 a ring shard's)
    for T, nH, nKV, hd in ((4096, 14, 2, 64), (4096, 12, 2, 128), (8192, 14, 2, 64)):
        out.append((
            f"flash_attention fwd+bwd many segments + pad tail T={T} {nH}/{nKV}/{hd}",
            "default",
            lambda a=(T, nH, nKV, hd): flash_case(*a, segments=_many_segments),
        ))
    # one sequence a row: nothing to leave out, the walk as long as a grid
    out.append((
        "flash_attention fwd+bwd one segment T=8192 14/2/64",
        "default",
        lambda: flash_case(8192, 14, 2, 64, segments=_one_segment),
    ))
    out.append((
        "flash_attention under vmap, a segment row each, B=4 T=256 12/2/128",
        "default (batched prefill)",
        flash_vmap_case,
    ))
    for later, what in ((False, "earlier, half-dead"), (True, "later, dead")):
        out.append((
            f"flash_attention_chunk fwd+bwd T=1024 14/2/64 ({what} kv chunk)",
            "default on >1 chip (ring)",
            lambda later=later: flash_chunk_case(later),
        ))
    # the decode engine's head shapes: 0.5B (the loop below), 1.5B and
    # OLMoE (the rollout cells): rows of 128, of 256 and of 2,048 lanes
    for nH, nKV, hd in ((N_HEADS, N_KV, HEAD_DIM), (12, 2, 128), (16, 16, 128)):
        heads = f"{nH}/{nKV}/{hd}"
        for W, int8, path in (
            (1, False, "default"),
            (1, True, 'kv_dtype="int8"'),
            (5, False, 'spec_decode="ngram"'),
            (5, True, 'spec_decode="ngram" + kv_dtype="int8"'),
        ):
            op = "paged_attention" if W == 1 else f"paged_attention_qlen W={W}"
            out.append((
                f"{op} {'int8' if int8 else 'bf16'} {heads} page={PAGE}",
                path,
                lambda a=(W, int8, nH, nKV, hd): paged_case(*a),
            ))
    # K-EXAONE's 64/8/128: the full layer's pool, and the window layers'
    # ring through its two-column table under a kernel name of its own
    out.append((
        f"paged_attention bf16 64/8/128 page={PAGE}", "default",
        lambda: paged_case(1, False, 64, 8, 128),
    ))
    out.append((
        f"paged_attention_window bf16 64/8/128 ring of 2 pages page={PAGE}",
        "default for a mixed stack", paged_ring_case,
    ))
    # the walk over the live (slot, column) pairs, at the four rollout
    # cells' widths and depths: mostly empty slots, the dense cell's 30% of
    # columns live, nothing to skip, one deep slot among shallow ones
    for (nH, nKV, hd), other, nb in (
        ((12, 2, 128), (1, True), 10), ((16, 16, 128), (5, False), 10),
        ((64, 8, 128), (5, True), 10), ((16, 2, 256), (5, False), 64),
    ):
        mixes = [("30%", 1, False), ("all", 1, False), ("one-deep", 1, False), ("30%", *other)]
        for mix, W, int8 in ([("empty", 1, False)] if nb == 10 else []) + mixes:
            out.append((
                f"paged_attention live range, {mix} W={W} "
                f"{'int8' if int8 else 'bf16'} {nH}/{nKV}/{hd} nb={nb}",
                "default",
                lambda a=(mix, W, int8, nH, nKV, hd), nb=nb: paged_ragged_case(*a, nb=nb),
            ))
    # a group of live columns a loop iteration, named (the cases above take
    # the group their shapes give: 8 at 12/2/128 clipped to `nb`, 4 at
    # 16/2/256, 2 at 64/8/128 and for the ring, 1 at 16/16/128 and `W > 1`):
    # the dense cell's and Qwen3-Next's rows at every group, a short last
    # group, a group wider than the table, int8 strips, the ring at 1 and 8
    for (nH, nKV, hd), nb, int8, groups in (
        ((12, 2, 128), 10, False, (1, 2, 4, 8)), ((12, 2, 128), 10, True, (2, 8)),
        ((16, 2, 256), 64, False, (2, 4, 8)), ((12, 2, 128), 3, False, (8,)),
    ):
        for g in groups:
            out.append((
                f"paged_attention group of {g}, 30% W=1 "
                f"{'int8' if int8 else 'bf16'} {nH}/{nKV}/{hd} nb={nb}",
                "default",
                lambda a=(int8, nH, nKV, hd), nb=nb, g=g: paged_ragged_case(
                    "30%", 1, *a, nb=nb, pages=g),
            ))
    for g in (1, 8):
        out.append((
            f"paged_attention_window group of {g}, bf16 64/8/128 ring of 2 pages",
            "default for a mixed stack", lambda g=g: paged_ring_case(pages=g),
        ))
    # Qwen3-Next: the state update of a linear layer, and its gated full
    # attention's head shape through the paged kernel (decode and ragged)
    out.append(("gdn_step 6 layers x 64 slots x 32 heads of 128x128 float32, in place",
                "default for models with linear layers", gdn_step_case))
    # Kimi-Linear: the same frame under a vector decay, at the cell's 128 slots
    out.append(("kda_step 6 layers x 128 slots x 32 heads of 128x128 float32, decay a key lane",
                "default for models with Kimi Delta Attention layers",
                lambda: gdn_step_case(R=128, lanes=True)))
    # AI21-Jamba2-3B: the state-space layers' update over the live slots' work
    # list, and its two attention layers' head shape (20 query heads, ONE kv head)
    out.append(("ssm_step 26 layers x 256 slots x [16, 5120] float32, in place, layer traced",
                "default for models with state-space layers", ssm_step_case))
    out.append(("paged_attention W=1 bf16 20/1/128", "default for jamba",
                lambda: paged_case(1, False, 20, 1, 128)))
    out.append(("paged_attention W=1 bf16 16/2/256", "default for qwen3_next",
                lambda: paged_case(1, False, 16, 2, 256)))
    out.append(("paged_attention live range, 30% W=1 bf16 16/2/256 nb=10",
                "default for qwen3_next", lambda: paged_ragged_case("30%", 1, False, 16, 2, 256)))
    # OLMoE's experts: a decode step's rows and a batched prefill's
    for T in (64, 2048):
        out.append((
            f"moe_mlp exact top-8 of 64 (ragged_dot) T={T} 2048x1024",
            "default for MoE models",
            lambda T=T: moe_case(T),
        ))
    out.append((
        "moe_mlp 64 live of 512 groups (8 layers' experts in place) T=64 2048x1024",
        "default for MoE models with stacked layers",
        lambda: moe_case(64, layers=8, li=5),
    ))
    # K-EXAONE's share of a layer: 16 held of 128 experts, 3-4 pair rows each
    out.append((
        "moe_mlp top-8 of 128, 16 held (ragged_dot) T=64 6144x2048",
        "default for MoE models with a share of the experts held",
        lambda: moe_case(64, H=6144, M=2048, E=16, published=128),
    ))
    # SDAR-30B-A3B: a block-diffusion forward's read (4 queries a slot under
    # the block's horizon) and its experts (128 slots x 4 positions x top-8 =
    # 4,096 pair rows over 128 experts of 768, laid out at 4,224)
    out.append((f"paged_attention_block W=4 bf16 32/4/128 page={PAGE}",
                "default for block-diffusion models", paged_block_case))
    out.append((
        "moe_mlp exact top-8 of 128 (ragged_dot) T=512 2048x768",
        "default for block-diffusion MoE models",
        lambda: moe_case(512, M=768, E=128),
    ))
    # DeepSeek-V2: the absorbed attention's read of the latent pool (128 heads
    # against one 576-wide row at 640 lanes), and a routing group's share of
    # a layer: 20 held of 160 experts of 1,536
    for mix in ("ragged", "deep"):
        out.append((f"paged_attention_latent bf16 128 heads x (512 + 64), {mix} page={PAGE}",
                    "default for latent-attention models", lambda mix=mix: latent_case(mix)))
    # Kimi-Linear: the same kernel at 32 heads, two latent layers, no rotation
    out.append((f"paged_attention_latent bf16 32 heads x (512 + 64), ragged page={PAGE}",
                "default for kimi_linear",
                lambda: latent_case("ragged", nH=32, L=2, layer=1, scale=192 ** -0.5)))
    out.append((
        "moe_mlp top-6 of 160, 20 held (ragged_dot) T=64 5120x1536",
        "default for MoE models with a share of the experts held",
        lambda: moe_case(64, H=5120, M=1536, E=20, K=6, published=160),
    ))
    for k_dims, out_dims in (
        ((HIDDEN,), (N_HEADS, HEAD_DIM)),  # q
        ((HIDDEN,), (N_KV, HEAD_DIM)),  # k, v
        ((N_HEADS, HEAD_DIM), (HIDDEN,)),  # o
        ((HIDDEN,), (MLP,)),  # gate, up
        ((MLP,), (HIDDEN,)),  # down
    ):
        out.append((
            f"quant_matmul {k_dims}->{out_dims}",
            'weight_dtype="int8"',
            lambda a=(k_dims, out_dims): quant_matmul_case(*a),
        ))
    out.append((f"fused_xent bf16 vocab={VOCAB} (XLA)", "default",
                fused_xent_case))
    return out


def run_all() -> list[dict]:
    """Run every case; one verdict per kernel. A lowering failure is a
    verdict ("refused" with the compiler's message), not a crash."""
    if jax.devices()[0].platform != "tpu":
        raise RuntimeError(
            f"tpu_smoke needs a TPU, found {jax.devices()[0].platform!r}"
        )
    verdicts = []
    for name, path, thunk in cases():
        try:
            ok, detail = thunk()
            status = "lowered+matches" if ok else "lowered+MISMATCH"
        except Exception as e:  # noqa: BLE001 — the verdict IS the error
            ok, status = False, "refused"
            detail = f"{type(e).__name__}: {e}"[:600]
            traceback.print_exc()
        verdicts.append(dict(kernel=name, path=path, ok=ok, status=status,
                             detail=detail))
        print(f"{'OK  ' if ok else 'FAIL'} {name} [{path}]: {status} {detail}",
              flush=True)
    return verdicts


def main() -> int:
    verdicts = run_all()
    bad = [v for v in verdicts if not v["ok"]]
    print("RESULT:", "PASS" if not bad else f"{len(bad)} FAILURES")
    return 1 if bad else 0


if __name__ == "__main__":
    raise SystemExit(main())
