"""In-pool paged-attention decode: the op, the two model steps and the engine.

There is one decode path: the pool is the chunk's carry, each step scatters
its one row per slot and attends through the block table
(`models/qwen2.decode_step_paged`, `verify_step_paged`). What holds it:

- the op against a plain reference, and the Pallas kernel (interpret mode
  here) against the XLA gather;
- the kernel's live range of block columns: the helper against a brute-force
  `any()`, the kernel under it equal to the bit to the kernel that walks
  every column, and a dead column's pages never read;
- the kernel's walk over the live (slot, column) pairs alone: the chain
  (`slot_schedule`) against a brute-force list, and the kernel equal to the
  bit to the `(R, nb)` grid it replaced (kept below as the reference) over
  empty slots at every place in the chain, one deep slot, and random
  batches;
- the decode step against the trainer's `forward` over prompt + token (what
  a decode log-probability has to equal for PPO), and its write contract:
  the row at `(layer, bt[r, p // bsz], p % bsz)` holds the new K/V, an
  inactive slot's write lands in null block 0, every other byte stays;
- the verify step against W sequential decode steps;
- the engine on both attention impls over the full scheduling surface
  (prefix forks, same-wave duplicates, suffix prefill, retire-mid-chunk
  under run-ahead, frequency penalty, top-p), with and without speculation,
  and streams that do not depend on where a chunk ends.
"""

import asyncio
from dataclasses import replace

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from _numerics import assert_logprobs_close

from areal_tpu.api.cli_args import (
    GenerationHyperparameters,
    InferenceEngineConfig,
    JaxDecodeConfig,
)
from areal_tpu.api.io_struct import ModelRequest
from areal_tpu.engine.jax_decode import JaxDecodeEngine
from areal_tpu.models.qwen2 import (
    ModelConfig,
    decode_step_paged,
    forward,
    init_params,
    prefill,
    verify_step_paged,
)
from areal_tpu.ops.kv_quant import quantize_kv, split_pool
from areal_tpu.ops.paged_attention import (
    GROUP_BYTES,
    MAX_GROUP_PAGES,
    group_pages,
    live_block_range,
    paged_attention,
    paged_attention_qlen,
    pool_group_pages,
    resolve_impl,
    slot_schedule,
    work_list,
)

TINY = ModelConfig(
    vocab_size=64,
    hidden_size=32,
    intermediate_size=64,
    num_hidden_layers=2,
    num_attention_heads=4,
    num_key_value_heads=2,
    dtype="float32",
    param_dtype="float32",
)


# ---------------------------------------------------------------------------
# op level
# ---------------------------------------------------------------------------


_LAYERS, _LAYER = 3, 1  # the op reads ONE layer of the whole stacked pool


def _random_pool(rng, n_blocks, bsz, nKV, hd, dtype=np.float32):
    """The stored layout: [L, n_blocks, bsz, nKV*hd], heads side by side."""
    shape = (_LAYERS, n_blocks, bsz, nKV * hd)
    k = rng.standard_normal(shape).astype(np.float32)
    v = rng.standard_normal(shape).astype(np.float32)
    return jnp.asarray(k, dtype), jnp.asarray(v, dtype)


def _dense_reference(q, kp, vp, bt, valid, layer=_LAYER):
    """Gather + plain masked softmax attention in f64-free numpy."""
    R, nH, hd = q.shape
    bsz, nKV = kp.shape[2], kp.shape[3] // hd
    nb = bt.shape[1]
    group = nH // nKV
    kc = np.asarray(kp)[layer][np.asarray(bt).reshape(-1)].reshape(
        R, nb * bsz, nKV, hd
    )
    vc = np.asarray(vp)[layer][np.asarray(bt).reshape(-1)].reshape(
        R, nb * bsz, nKV, hd
    )
    qg = np.asarray(q).reshape(R, nKV, group, hd)
    out = np.zeros((R, nH, hd), np.float32)
    for r in range(R):
        for k_h in range(nKV):
            for g in range(group):
                s = kc[r, :, k_h] @ qg[r, k_h, g] / np.sqrt(hd)
                s = np.where(np.asarray(valid)[r], s, -1e30)
                p = np.exp(s - s.max())
                p /= p.sum()
                out[r, k_h * group + g] = p @ vc[r, :, k_h]
    return out


def test_paged_attention_xla_vs_dense(cpu_devices):
    rng = np.random.default_rng(0)
    R, nH, nKV, hd, bsz, nb, n_blocks = 3, 4, 2, 8, 16, 3, 12
    kp, vp = _random_pool(rng, n_blocks, bsz, nKV, hd)
    q = jnp.asarray(rng.standard_normal((R, nH, hd)).astype(np.float32))
    bt = jnp.asarray(
        rng.choice(np.arange(1, n_blocks), size=(R, nb), replace=False)
        .astype(np.int32)
    )
    lengths = np.array([5, 17, nb * bsz - 1], np.int32)
    valid = jnp.asarray(np.arange(nb * bsz)[None, :] <= lengths[:, None])
    out = paged_attention(q, kp, vp, bt, valid, _LAYER, impl="xla")
    ref = _dense_reference(q, kp, vp, bt, valid)
    np.testing.assert_allclose(np.asarray(out), ref, atol=1e-5, rtol=1e-5)


def test_paged_attention_pallas_vs_xla(cpu_devices):
    """The split-KV online-softmax kernel (interpret mode on CPU) must
    match the gather fallback on every slot, including slots whose valid
    span ends mid-block and a fully-masked (length-0 equivalent) row."""
    rng = np.random.default_rng(1)
    R, nH, nKV, hd, bsz, nb, n_blocks = 4, 8, 2, 16, 16, 4, 20
    kp, vp = _random_pool(rng, n_blocks, bsz, nKV, hd)
    q = jnp.asarray(rng.standard_normal((R, nH, hd)).astype(np.float32))
    bt = jnp.asarray(
        rng.choice(np.arange(1, n_blocks), size=(R, nb), replace=False)
        .astype(np.int32)
    )
    lengths = np.array([0, 9, 30, nb * bsz - 1], np.int32)
    valid = jnp.asarray(np.arange(nb * bsz)[None, :] <= lengths[:, None])
    a = paged_attention(q, kp, vp, bt, valid, _LAYER, impl="xla")
    b = paged_attention(
        q, kp, vp, bt, valid, jnp.int32(_LAYER), impl="pallas", interpret=True
    )
    np.testing.assert_allclose(
        np.asarray(a), np.asarray(b), atol=1e-5, rtol=1e-5
    )


@pytest.mark.parametrize(
    "nH,nKV,hd", [(14, 2, 64), (12, 2, 128)], ids=["0.5b", "1.5b"]
)
def test_paged_attention_pallas_at_published_heads(cpu_devices, nH, nKV, hd):
    """The kernel at the head shapes the chip runs (Qwen2.5-0.5B: 14/2 of
    64; the rollout cell's 1.5B: 12/2 of 128) and its page of 128 rows,
    reading layer `_LAYER` of a bf16 4-D pool through the table, against a
    float32 einsum over the gathered rows."""
    rng = np.random.default_rng(4)
    R, bsz, nb = 3, 128, 2
    n_blocks = 1 + R * nb
    kp, vp = _random_pool(rng, n_blocks, bsz, nKV, hd, jnp.bfloat16)
    q = jnp.asarray(rng.standard_normal((R, nH, hd)), jnp.bfloat16)
    bt = jnp.asarray(
        rng.permutation(np.arange(1, n_blocks)).astype(np.int32).reshape(R, nb)
    )
    lengths = np.array([0, 130, nb * bsz - 1], np.int32)
    valid = jnp.asarray(np.arange(nb * bsz)[None, :] <= lengths[:, None])
    out = paged_attention(
        q, kp, vp, bt, valid, jnp.int32(_LAYER), impl="pallas", interpret=True
    )
    f32 = lambda a: jnp.asarray(a, jnp.float32)  # noqa: E731
    kc = f32(kp)[_LAYER][bt].reshape(R, nb * bsz, nKV, hd)
    vc = f32(vp)[_LAYER][bt].reshape(R, nb * bsz, nKV, hd)
    qg = f32(q).reshape(R, nKV, nH // nKV, hd)
    scores = jnp.einsum("rkgd,rskd->rkgs", qg, kc) / np.sqrt(hd)
    scores = jnp.where(valid[:, None, None, :], scores, -1e30)
    ref = jnp.einsum(
        "rkgs,rskd->rkgd", jax.nn.softmax(scores, axis=-1), vc
    ).reshape(R, nH, hd)
    # the output is rounded to bf16 once; everything before it is f32
    np.testing.assert_allclose(
        np.asarray(f32(out)), np.asarray(ref), atol=2e-2, rtol=2e-2
    )


# -- the live range of block columns ----------------------------------------
# A grid step outside its slot's `[lo, hi)` neither fetches nor scores. What
# a live slot reads back is equal to the bit to a kernel told every column
# is live (what the kernel did before it had a range): a column with no
# valid row adds exactly nothing to the online softmax.

_RB, _RNB = 16, 10  # page rows, block columns a slot
# (position of the slot's first query, active, table): an empty slot, one
# live column of ten, all ten live, a retired slot whose table still names
# blocks deep in the pool, a slot that ends mid-page
_RAGGED = [(0, False, "null"), (5, True, "own"), (_RNB * _RB - 4, True, "own"),
           (100, False, "own"), (3 * _RB + 7, True, "own")]


def _stored_pools(rng, n_blocks, bsz, nKV, hd, int8=False, dtype=jnp.bfloat16):
    """(k pool, v pool) as the engine stores them, every layer stacked:
    `[L, n_blocks, bsz, nKV*hd]`, or (int8 rows, f32 scales `[L, n_blocks,
    nKV, bsz]`)."""
    shape = (_LAYERS, n_blocks, bsz, nKV, hd)
    kp = jnp.asarray(rng.standard_normal(shape), dtype)
    vp = jnp.asarray(rng.standard_normal(shape), dtype)
    rows = lambda a: a.reshape(_LAYERS, n_blocks, bsz, nKV * hd)  # noqa: E731
    if not int8:
        return rows(kp), rows(vp)
    (kq, ks), (vq, vs) = quantize_kv(kp), quantize_kv(vp)
    return (rows(kq), jnp.swapaxes(ks, -1, -2)), (rows(vq), jnp.swapaxes(vs, -1, -2))


def _ragged_slots(rng, W=1, int8=False, window=None, dtype=jnp.bfloat16):
    """(q [R, W, nH, hd], k pool, v pool, table, valid [R, W, span], active)
    over `_RAGGED`'s slots; `window` adds a sliding window to the mask."""
    R, nH, nKV, hd = len(_RAGGED), 8, 2, 16
    n_blocks = 1 + R * _RNB
    kp, vp = _stored_pools(rng, n_blocks, _RB, nKV, hd, int8, dtype)
    q = jnp.asarray(rng.standard_normal((R, W, nH, hd)), dtype)
    bt = rng.permutation(np.arange(1, n_blocks)).astype(np.int32).reshape(R, _RNB)
    for r, (_, _, table) in enumerate(_RAGGED):
        if table == "null":
            bt[r] = 0
    pos = np.array([p for p, _, _ in _RAGGED])[:, None] + np.arange(W)[None, :]
    pos = np.minimum(pos, _RNB * _RB - 1)
    s = np.arange(_RNB * _RB)[None, None, :]
    valid = s <= pos[:, :, None]
    if window is not None:
        valid &= s > pos[:, :, None] - window
    active = np.array([a for _, a, _ in _RAGGED])
    return q, kp, vp, jnp.asarray(bt), jnp.asarray(valid), jnp.asarray(active)


def _read(q, kp, vp, bt, valid, live, **kw):
    """The Pallas kernel (interpret mode) under the range `live`, through
    the entry point its width calls for: a column an iteration (the walk the
    bit-for-bit cases below were written against) unless `pages` names a
    group, None for the one the shapes give."""
    kw.setdefault("pages", 1)
    if q.shape[1] == 1:
        return paged_attention(
            q[:, 0], kp, vp, bt, valid[:, 0], jnp.int32(_LAYER), impl="pallas",
            interpret=True, live=live, **kw)[:, None]
    return paged_attention_qlen(
        q, kp, vp, bt, valid, jnp.int32(_LAYER), impl="pallas", interpret=True,
        live=live, **kw)


def _all_live(R, nb):
    return jnp.zeros(R, jnp.int32), jnp.full(R, nb, jnp.int32)


def _bits(a):
    return np.asarray(a.astype(jnp.float32)).view(np.uint32)


@pytest.mark.parametrize("W", [1, 3], ids=["decode", "verify3"])
@pytest.mark.parametrize("int8", [False, True], ids=["bf16", "int8"])
def test_live_range_is_exact_over_ragged_slots(cpu_devices, int8, W):
    q, kp, vp, bt, valid, active = _ragged_slots(
        np.random.default_rng(11), W=W, int8=int8)
    live = live_block_range(valid, _RB, active)
    lo, hi = (np.asarray(x) for x in live)
    first = [p for p, _, _ in _RAGGED]
    assert lo.tolist() == [0] * len(_RAGGED)
    assert hi.tolist() == [
        min((p + W - 1) // _RB + 1, _RNB) if a else 0
        for p, (_, a, _) in zip(first, _RAGGED)]
    assert 1 in hi and _RNB in hi  # one live column of ten, and all ten
    out = _read(q, kp, vp, bt, valid, live)
    ref = _read(q, kp, vp, bt, valid, _all_live(*bt.shape))
    on = np.asarray(active)
    np.testing.assert_array_equal(_bits(out)[on], _bits(ref)[on])
    # a slot that is not active is not read at all: zeros, whatever its table
    assert not np.asarray(out.astype(jnp.float32))[~on].any()
    # and left to itself the op reads the range off the mask it is given
    own = _read(q, kp, vp, bt, valid, None)
    np.testing.assert_array_equal(_bits(own)[on], _bits(ref)[on])


@pytest.mark.parametrize("W", [1, 3], ids=["decode", "verify3"])
def test_live_range_skips_the_columns_before_a_window(cpu_devices, W):
    """A uniform sliding-window stack: the columns wholly behind the window
    are dead too, `lo > 0`."""
    window = 2 * _RB + 3
    q, kp, vp, bt, valid, active = _ragged_slots(
        np.random.default_rng(12), W=W, window=window)
    live = live_block_range(valid, _RB, active)
    lo, hi = (np.asarray(x) for x in live)
    deep = _RAGGED[2][0]
    assert lo[2] == max(deep - window + 1, 0) // _RB > 0 and hi[2] == _RNB
    assert lo[1] == 0 and hi[1] == 1
    out = _read(q, kp, vp, bt, valid, live)
    ref = _read(q, kp, vp, bt, valid, _all_live(*bt.shape))
    on = np.asarray(active)
    np.testing.assert_array_equal(_bits(out)[on], _bits(ref)[on])


@pytest.mark.parametrize("W", [1, 2], ids=["decode", "verify2"])
def test_live_range_keeps_both_columns_of_a_ring(cpu_devices, W):
    """A mixed stack's window layers: a two-column table over the slot's
    ring pages (`models/qwen2._ring_valid`). Past the first page both
    columns hold rows inside the window; only slots that are not active
    are skipped."""
    from areal_tpu.models.qwen2 import _PAGED_KERNELS, _ring_valid, ring_pages

    rng = np.random.default_rng(13)
    bsz = window = 16
    pages = ring_pages(window, bsz)
    assert pages == 2
    R, nH, nKV, hd = 4, 8, 2, 16
    kp, vp = _random_pool(rng, 1 + R * pages, bsz, nKV, hd, jnp.bfloat16)
    q = jnp.asarray(rng.standard_normal((R, W, nH, hd)), jnp.bfloat16)
    bt = 1 + jnp.arange(R * pages, dtype=jnp.int32).reshape(R, pages)
    pos = np.array([3, 40, 77, 21])[:, None] + np.arange(W)[None, :]
    valid = _ring_valid(jnp.asarray(pos.reshape(-1)), window, bsz, pages)
    valid = valid.reshape(R, W, pages * bsz)
    active = jnp.asarray([True, True, False, True])
    live = live_block_range(valid, bsz, active)
    assert np.asarray(live[0]).tolist() == [0, 0, 0, 0]
    assert np.asarray(live[1]).tolist() == [1, 2, 0, 2]
    kw = dict(kernel_name=_PAGED_KERNELS["window"])
    out = _read(q, kp, vp, bt, valid, live, **kw)
    ref = _read(q, kp, vp, bt, valid, _all_live(R, pages), **kw)
    on = np.asarray(active)
    np.testing.assert_array_equal(_bits(out)[on], _bits(ref)[on])


@pytest.mark.parametrize("W", [None, 1, 4], ids=["2d", "W1", "W4"])
@pytest.mark.parametrize("with_active", [False, True], ids=["all", "active"])
def test_live_block_range_against_brute_force(cpu_devices, with_active, W):
    """Masks with holes, empty rows and single cells: `lo` is the first
    column holding a valid row of any query, `hi` one past the last."""
    rng = np.random.default_rng(14)
    R, nb, bsz = 12, 7, 8
    shape = (R, nb * bsz) if W is None else (R, W, nb * bsz)
    valid = rng.random(shape) < 0.02
    valid[0] = False  # nothing to attend
    valid[1] = True  # everything
    valid[2] = False
    valid[2][..., 3 * bsz] = True  # one cell, the first row of column 3
    active = rng.random(R) < 0.7 if with_active else None
    lo, hi = live_block_range(
        jnp.asarray(valid), bsz, None if active is None else jnp.asarray(active))
    assert lo.dtype == hi.dtype == jnp.int32 and lo.shape == hi.shape == (R,)
    for r in range(R):
        cols = valid[r].reshape(-1, nb, bsz).any(axis=(0, 2))
        if active is not None and not active[r]:
            cols[:] = False
        want = (0, 0)
        if cols.any():
            want = (int(np.argmax(cols)), nb - int(np.argmax(cols[::-1])))
        assert (int(lo[r]), int(hi[r])) == want, r
    if active is None:
        assert (int(lo[2]), int(hi[2])) == (3, 4)


@pytest.mark.parametrize("pages", [1, 4])
@pytest.mark.parametrize("int8", [False, True], ids=["bf16", "int8"])
def test_dead_columns_are_never_read(cpu_devices, int8, pages):
    """Every page no live column names, the null block among them, holds
    NaN (an int8 pool: NaN scales). A kernel that scored such a page would
    return NaN (0 x NaN); the live slots read back finite and equal to the
    bit to what they read from the clean pool."""
    W = 2
    q, kp, vp, bt, valid, active = _ragged_slots(
        np.random.default_rng(15), W=W, int8=int8,
        window=None if int8 else 3 * _RB)
    live = live_block_range(valid, _RB, active)
    lo, hi = (np.asarray(x) for x in live)
    named = {int(b) for r in range(bt.shape[0])
             for b in np.asarray(bt)[r, lo[r]:hi[r]]}
    dead = np.array(sorted(set(range(kp[0].shape[1] if int8 else kp.shape[1]))
                           - named))
    assert 0 in dead and len(dead) > len(named)

    def poison(pool):
        if int8:
            data, scales = pool
            return data, scales.at[:, dead].set(jnp.nan)
        return pool.at[:, dead].set(jnp.nan)

    clean = _read(q, kp, vp, bt, valid, live, pages=pages)
    out = _read(q, poison(kp), poison(vp), bt, valid, live, pages=pages)
    on = np.asarray(active)
    assert np.isfinite(np.asarray(out.astype(jnp.float32))).all()
    np.testing.assert_array_equal(_bits(out)[on], _bits(clean)[on])
    # the kernel that walks every column does read them
    walked = _read(q, poison(kp), poison(vp), bt, valid, _all_live(*bt.shape))
    assert np.isnan(np.asarray(walked.astype(jnp.float32))[on]).any()


# -- the walk over the live (slot, column) pairs ------------------------------
# The kernel's grid is the slots; a grid step loops over its slot's live
# columns and the page copies chain from one slot into the next. What it
# replaced, kept here as the reference: a grid over every (slot, column),
# a step outside its slot's range doing nothing. Same body, same order over
# a slot's columns: equal to the bit.


def _grid_walk(q, kp, vp, bt, valid, live):
    """The `(R, nb)`-grid range kernel (PR 31's), interpret mode: q
    [R, W, nH, hd] with nH a multiple of 8, valid [R, W, nb*bsz]."""
    import functools

    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    (kd, ks), (vd, vs) = split_pool(kp), split_pool(vp)
    quant = ks is not None
    R, W, nH, hd = q.shape
    bsz, D = kd.shape[2:]
    nb, nKV, rows = bt.shape[1], D // hd, W * nH
    kv_of_head = np.arange(nH) // (nH // nKV)
    onehot = np.zeros((nH, nKV), np.float32)
    onehot[np.arange(nH), kv_of_head] = 1.0
    q_exp = (q[:, :, :, None, :] * jnp.asarray(onehot, q.dtype)[:, :, None]).reshape(R, rows, D)
    mask = valid.astype(jnp.int32).reshape(R, W, nb, bsz).swapaxes(1, 2)
    sm_scale = 1.0 / np.sqrt(hd)

    def kernel(bt_ref, lo_ref, hi_ref, mask_ref, q_ref, *refs):
        if quant:
            sel_ref, k_ref, ks_ref, v_ref, vs_ref, o_ref, acc_ref, m_ref, l_ref = refs
        else:
            k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref = refs
        r, b = pl.program_id(0), pl.program_id(1)

        @pl.when(b == 0)
        def _init():
            m_ref[:] = jnp.full_like(m_ref, -1e30)
            l_ref[:] = jnp.zeros_like(l_ref)
            acc_ref[:] = jnp.zeros_like(acc_ref)

        def head_rows(sc_ref):
            sc = sc_ref[...]
            return sum(sel_ref[h] * sc[h : h + 1, :] for h in range(sc.shape[0]))

        @pl.when((lo_ref[r] <= b) & (b < hi_ref[r]))
        def _live_column():
            s = jax.lax.dot_general(
                q_ref[0].astype(jnp.float32), k_ref[...].astype(jnp.float32),
                (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
            s = s * sm_scale
            if quant:
                s = s * head_rows(ks_ref)
            m2 = jnp.broadcast_to(
                mask_ref[0, 0][:, None, :], (W, rows // W, bsz)).reshape(rows, bsz)
            s = jnp.where(m2 != 0, s, -1e30)
            m_prev = m_ref[:]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new)
            p = jnp.where(m_new > -1e30 / 2, p, 0.0)
            l_ref[:] = l_ref[:] * alpha + jnp.sum(p, axis=1, keepdims=True)
            m_ref[:] = m_new
            if quant:
                p = p * head_rows(vs_ref)
            acc_ref[:] = acc_ref[:] * alpha + jax.lax.dot_general(
                p, v_ref[...].astype(jnp.float32), (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

        @pl.when(b == nb - 1)
        def _finalize():
            l = l_ref[:]
            o_ref[0] = (acc_ref[:] / jnp.where(l > 0.0, l, 1.0)).astype(o_ref.dtype)

    def column(r, b, lo, hi):
        return jnp.clip(b, lo[r], jnp.maximum(hi[r] - 1, lo[r]))

    def page(r, b, bt, lo, hi):
        return _LAYER, jnp.where(hi[r] > lo[r], bt[r, column(r, b, lo, hi)], 0), 0, 0

    kv_spec = pl.BlockSpec((None, None, bsz, D), page)
    sc_spec = pl.BlockSpec((None, None, nKV, bsz), page)
    in_specs = [
        pl.BlockSpec((1, 1, W, bsz), lambda r, b, bt, lo, hi: (r, column(r, b, lo, hi), 0, 0)),
        pl.BlockSpec((1, rows, D), lambda r, b, *_: (r, 0, 0)),
    ]
    if quant:
        sel = jnp.asarray(np.tile(onehot, (W, 1)).T[:, :, None])
        in_specs += [pl.BlockSpec((nKV, rows, 1), lambda r, b, *_: (0, 0, 0)),
                     kv_spec, sc_spec, kv_spec, sc_spec]
        operands = (sel, kd, ks, vd, vs)
    else:
        in_specs += [kv_spec, kv_spec]
        operands = (kd, vd)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(R, nb), in_specs=in_specs,
            out_specs=pl.BlockSpec((1, rows, D), lambda r, b, *_: (r, 0, 0)),
            scratch_shapes=[pltpu.VMEM((rows, D), jnp.float32),
                            pltpu.VMEM((rows, 1), jnp.float32),
                            pltpu.VMEM((rows, 1), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((R, rows, D), q.dtype), interpret=True,
    )(bt, *(x.astype(jnp.int32) for x in live[:2]), mask, q_exp, *operands)
    out = out.reshape(R, W, nH, nKV, hd)
    return out[:, :, np.arange(nH), kv_of_head]


def _columns_batch(rng, cols, lo_cols=None, W=1, int8=False, bsz=_RB):
    """A batch whose slot r holds the live columns `[lo_cols[r], cols[r])`
    (none, and not active, where `cols[r] == 0`), every other slot ending
    mid-page, over tables that name blocks everywhere."""
    cols = np.asarray(cols)
    R, nb = len(cols), max(int(cols.max()), 2)
    nH, nKV, hd = 8, 2, 16
    n_blocks = 1 + R * nb
    kp, vp = _stored_pools(rng, n_blocks, bsz, nKV, hd, int8)
    q = jnp.asarray(rng.standard_normal((R, W, nH, hd)), jnp.bfloat16)
    bt = rng.permutation(np.arange(1, n_blocks)).astype(np.int32).reshape(R, nb)
    last = np.maximum(cols * bsz - 1 - rng.integers(0, bsz - W + 1, R), W - 1)
    pos = (last - (W - 1))[:, None] + np.arange(W)[None, :]
    s = np.arange(nb * bsz)[None, None, :]
    valid = s <= pos[:, :, None]
    if lo_cols is not None:
        valid &= s >= (np.asarray(lo_cols) * bsz)[:, None, None]
    active = cols > 0
    return q, kp, vp, jnp.asarray(bt), jnp.asarray(valid), jnp.asarray(active)


# where the empty slots sit in the chain, and how deep the others are
_CHAINS = {
    "empty_first": [0, 0, 3, 1, 2],
    "empty_last": [2, 1, 4, 0, 0],
    "empty_between": [1, 0, 0, 0, 5, 0, 2],
    "one_live_slot": [0, 0, 6, 0],
    "all_empty": [0, 0, 0],
    "one_deep_slot": [1, 1, 10, 1, 1, 1],
    "all_live": [4, 4, 4, 4],
    "single_columns": [1, 1, 1, 1, 1],
}


@pytest.mark.parametrize("chain", list(_CHAINS))
def test_pair_walk_equals_the_grid_walk(cpu_devices, chain):
    cols = np.array(_CHAINS[chain])
    q, kp, vp, bt, valid, active = _columns_batch(np.random.default_rng(21), cols)
    live = live_block_range(valid, _RB, active)
    assert (np.asarray(live[1]) - np.asarray(live[0])).tolist() == cols.tolist()
    out = _read(q, kp, vp, bt, valid, (*live, *slot_schedule(*live)))
    ref = _grid_walk(q, kp, vp, bt, valid, live)
    np.testing.assert_array_equal(_bits(out), _bits(ref))
    on = np.asarray(active)
    assert not np.asarray(out.astype(jnp.float32))[~on].any()
    if on.any():
        every = _read(q, kp, vp, bt, valid, _all_live(*bt.shape))
        np.testing.assert_array_equal(_bits(out)[on], _bits(every)[on])
        # and within the usual error of the XLA read
        xla = paged_attention_qlen(q, kp, vp, bt, valid, jnp.int32(_LAYER), impl="xla")
        np.testing.assert_allclose(
            np.asarray(out.astype(jnp.float32))[on], np.asarray(xla.astype(jnp.float32))[on],
            atol=3e-2, rtol=3e-2)


@pytest.mark.parametrize("seed", range(6))
def test_pair_walk_equals_the_grid_walk_on_random_batches(cpu_devices, seed):
    """Random depths, windows (`lo > 0`), empty slots, both pools and both
    widths: the chain never names a wrong page or a stale buffer."""
    rng = np.random.default_rng(100 + seed)
    R, nb = int(rng.integers(2, 9)), int(rng.integers(2, 8))
    cols = rng.integers(0, nb + 1, R) * (rng.random(R) < 0.75)
    cols[rng.integers(R)] = nb
    lo_cols = np.where(rng.random(R) < 0.5, rng.integers(0, nb, R), 0)
    lo_cols = np.minimum(lo_cols, np.maximum(cols - 1, 0))
    W, int8 = (1, 3)[seed % 2], seed % 3 == 0
    q, kp, vp, bt, valid, active = _columns_batch(rng, cols, lo_cols, W=W, int8=int8)
    live = live_block_range(valid, _RB, active)
    lo, hi = (np.asarray(x) for x in live)
    assert lo.tolist() == np.where(cols > 0, lo_cols, 0).tolist() and hi.tolist() == cols.tolist()
    out = _read(q, kp, vp, bt, valid, live)  # the op chains the range it is given
    ref = _grid_walk(q, kp, vp, bt, valid, live)
    np.testing.assert_array_equal(_bits(out), _bits(ref))


@pytest.mark.parametrize("pages", [1, 2, 4, 8])
@pytest.mark.parametrize("int8", [False, True], ids=["bf16", "int8"])
def test_pair_walk_never_reads_a_page_outside_the_pairs(cpu_devices, int8, pages):
    """Empty slots inside the chain with tables that name blocks, every page
    no live pair names NaN: the copy a slot's last column starts is the next
    LIVE slot's first page, never an empty slot's; a short last group copies
    the pages the slot has and no other, and what its buffer holds beyond
    them is masked out."""
    cols = np.array([0, 2, 0, 0, 1, 3, 0])
    lo_cols = np.array([0, 1, 0, 0, 0, 2, 0])
    q, kp, vp, bt, valid, active = _columns_batch(
        np.random.default_rng(22), cols, lo_cols, W=2, int8=int8)
    live = live_block_range(valid, _RB, active)
    named = {int(np.asarray(bt)[r, c]) for r in range(len(cols))
             for c in range(lo_cols[r], cols[r])}
    n_blocks = (kp[0] if int8 else kp).shape[1]
    dead = np.array(sorted(set(range(n_blocks)) - named))

    def poison(pool):
        if int8:
            return pool[0], pool[1].at[:, dead].set(jnp.nan)
        return pool.at[:, dead].set(jnp.nan)

    clean = _read(q, kp, vp, bt, valid, live, pages=pages)
    out = _read(q, poison(kp), poison(vp), bt, valid, live, pages=pages)
    assert np.isfinite(np.asarray(out.astype(jnp.float32))).all()
    np.testing.assert_array_equal(_bits(out), _bits(clean))


# ---------------------------------------------------------------------------
# a GROUP of live columns a loop iteration (`group_pages`): one score matmul,
# one softmax update and one weighted sum a group. The softmax is associative
# but not bit-stable under regrouping, so a group is held to the walk a column
# an iteration within a bf16 rounding or two, and to the XLA read as that is.
# ---------------------------------------------------------------------------


def _grouped_batch(case, rng, int8):
    """(q, kp, vp, bt, valid, active) of a named batch."""
    if case == "ragged":  # an empty slot, one column of ten, all ten, mid-page ends
        return _ragged_slots(rng, int8=int8)
    if case == "window":  # `lo > 0`: the groups start inside the table
        return _ragged_slots(rng, int8=int8, window=2 * _RB + 3)
    return _columns_batch(rng, np.array(_CHAINS[case]), int8=int8)


@pytest.mark.parametrize("pages", [2, 4, 8])
@pytest.mark.parametrize("int8", [False, True], ids=["bf16", "int8"])
@pytest.mark.parametrize(
    "case", ["ragged", "window", "one_deep_slot", "single_columns"])
def test_grouped_walk_is_the_walk_a_column(cpu_devices, case, int8, pages):
    """Ragged ranges, a slot with no live column, short last groups (10 = 8 + 2,
    5 = 4 + 1), `lo > 0`, a table narrower than the group (`single_columns`:
    two columns), both pools."""
    q, kp, vp, bt, valid, active = _grouped_batch(case, np.random.default_rng(31), int8)
    live = live_block_range(valid, _RB, active)
    one = _read(q, kp, vp, bt, valid, live, pages=1)
    out = _read(q, kp, vp, bt, valid, live, pages=pages)
    on = np.asarray(active)
    f32 = lambda a: np.asarray(a.astype(jnp.float32))  # noqa: E731
    assert np.isfinite(f32(out)).all() and not f32(out)[~on].any()
    np.testing.assert_allclose(f32(out)[on], f32(one)[on], atol=2e-2, rtol=2e-2)
    xla = paged_attention_qlen(q, kp, vp, bt, valid, jnp.int32(_LAYER), impl="xla")
    np.testing.assert_allclose(f32(out)[on], f32(xla)[on], atol=3e-2, rtol=3e-2)
    if pages == 4:
        # told every column is live the groups are the same ones (`lo == 0`)
        # or others (a window): the result is the mask's either way
        every = _read(q, kp, vp, bt, valid, _all_live(*bt.shape), pages=pages)
        np.testing.assert_allclose(f32(every)[on], f32(one)[on], atol=2e-2, rtol=2e-2)


@pytest.mark.parametrize("pages", [2, 8])
def test_grouped_ring_reads_both_pages_in_one_group(cpu_devices, pages):
    """A mixed stack's window ring: two columns a slot, one group (a group of
    eight is clipped to the table's two by the rule, and scores six masked
    pages when it is named anyway)."""
    from areal_tpu.models.qwen2 import _PAGED_KERNELS, _ring_valid, ring_pages

    rng = np.random.default_rng(13)
    bsz = window = 16
    ring = ring_pages(window, bsz)
    R, nH, nKV, hd = 4, 8, 2, 16
    kp, vp = _random_pool(rng, 1 + R * ring, bsz, nKV, hd, jnp.bfloat16)
    q = jnp.asarray(rng.standard_normal((R, 1, nH, hd)), jnp.bfloat16)
    bt = 1 + jnp.arange(R * ring, dtype=jnp.int32).reshape(R, ring)
    valid = _ring_valid(jnp.asarray([3, 40, 77, 21]), window, bsz, ring)[:, None]
    active = jnp.asarray([True, True, False, True])
    live = live_block_range(valid, bsz, active)
    kw = dict(kernel_name=_PAGED_KERNELS["window"])
    one = _read(q, kp, vp, bt, valid, live, pages=1, **kw)
    out = _read(q, kp, vp, bt, valid, live, pages=pages, **kw)
    on = np.asarray(active)
    f32 = lambda a: np.asarray(a.astype(jnp.float32))  # noqa: E731
    np.testing.assert_allclose(f32(out)[on], f32(one)[on], atol=2e-2, rtol=2e-2)
    assert not f32(out)[~on].any()
    assert pool_group_pages(kp, 1, ring) == 2


@pytest.mark.parametrize("int8", [False, True], ids=["bf16", "int8"])
def test_rules_group_is_what_the_op_takes(cpu_devices, int8):
    """Left to itself the op takes `group_pages` of its shapes (eight here,
    over ten columns), with the chain it builds or `work_list`'s."""
    q, kp, vp, bt, valid, active = _ragged_slots(np.random.default_rng(33), int8=int8)
    assert pool_group_pages(kp, 1, _RNB) == 8
    live = live_block_range(valid, _RB, active)
    named = _read(q, kp, vp, bt, valid, live, pages=8)
    own = _read(q, kp, vp, bt, valid, live, pages=None)
    np.testing.assert_array_equal(_bits(own), _bits(named))
    chained = _read(q, kp, vp, bt, valid, work_list(valid[:, 0], kp, active), pages=None)
    np.testing.assert_array_equal(_bits(chained), _bits(named))
    assert len(work_list(valid, kp, active)) == 4


# bsz, D, itemsize, W, nb -> pages: the five rollout cells that run the kernel
# (their deepest bucket), the buckets and tables narrower than a group, and
# what takes one page by the rule
_RULE = {
    "rollout-1.5b-gsm8k": ((128, 256, 2, 1, 10), 8),
    "rollout-1.5b-gsm8k 256-token bucket": ((128, 256, 2, 1, 2), 2),
    "rollout-1.5b-gsm8k 512-token bucket": ((128, 256, 2, 1, 4), 4),
    "rollout-1.5b-gsm8k int8 pool": ((128, 256, 1, 1, 10), 8),
    "rollout-qwen3next-mixedlen": ((128, 512, 2, 1, 64), 4),
    "rollout-kexaone-mixedlen full": ((128, 1024, 2, 1, 64), 2),
    "rollout-kexaone-mixedlen ring": ((128, 1024, 2, 1, 2), 2),
    "rollout-olmoe-gsm8k": ((128, 2048, 2, 1, 10), 1),
    "rollout-sdar-gsm8k block of 4": ((128, 512, 2, 4, 10), 1),
    "verify chunk of 5": ((128, 256, 2, 5, 10), 1),
    "a float32 pool": ((128, 256, 4, 1, 10), 4),
    "0.5B": ((128, 128, 2, 1, 10), 8),
    "one column": ((128, 256, 2, 1, 1), 1),
    "a row wider than the budget": ((128, 8192, 2, 1, 10), 1),
}


@pytest.mark.parametrize("cell", list(_RULE))
def test_group_rule_is_arithmetic_on_the_shapes(cell):
    """`group_pages` is a pure function of five ints: it takes and returns
    Python ints, traces nothing, compiles nothing and reads no device (no jax
    event fires while it runs), and its group's pages in both pools stay
    within `GROUP_BYTES` and `MAX_GROUP_PAGES` and the table."""
    import jax.monitoring

    shape, pages = _RULE[cell]
    events = []
    listener = lambda name, *a, **k: events.append(name)  # noqa: E731
    jax.monitoring.register_event_listener(listener)
    jax.monitoring.register_event_duration_secs_listener(listener)
    try:
        with jax.transfer_guard("disallow"):
            got = group_pages(*shape)
            bsz, D, itemsize, W, nb = shape
            pool = jax.ShapeDtypeStruct((2, 9, bsz, D), {1: jnp.int8, 2: jnp.bfloat16,
                                                       4: jnp.float32}[itemsize])
            assert pool_group_pages(pool, W, nb) == got
    finally:
        from jax._src import monitoring

        monitoring.unregister_event_listener(listener)
        monitoring.unregister_event_duration_listener(listener)
    assert type(got) is int and got == pages and not events, (got, events)
    assert 1 <= got <= min(MAX_GROUP_PAGES, nb)
    assert got == 1 or got * 2 * bsz * D * itemsize <= GROUP_BYTES


def _kernel_primitives(q, kp, vp, bt, valid, pages):
    """{primitive name: count} over the paged kernel's whole jaxpr (loops and
    conditionals walked) at a group of `pages`."""
    from collections import Counter

    from jax._src import core

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            yield eqn.primitive.name
            for sub in core.jaxprs_in_params(eqn.params):
                yield from walk(sub)

    traced = jax.make_jaxpr(lambda *a: paged_attention(
        *a, jnp.int32(_LAYER), impl="pallas", interpret=True, pages=pages)
    )(q[:, 0], kp, vp, bt, valid[:, 0])
    (call,) = [e for e in traced.jaxpr.eqns if e.primitive.name == "pallas_call"]
    return Counter(walk(call.params["jaxpr"]))


@pytest.mark.parametrize("int8", [False, True], ids=["bf16", "int8"])
def test_kernels_text_is_a_pages_whatever_the_group(cpu_devices, int8):
    """What a set-up traces and lowers for each chunk program: a group's
    copies are a loop over the pages it has, so the kernel at eight pages has
    the copy starts and waits, the conditionals, the loops and the matmuls of
    the kernel at two, and beyond them a mask row's load a page (unrolled a
    page, with a conditional each, a chunk program's trace and lowering took
    0.7 s more on the chip's host and a warm set-up 3 s: PERF.md section 5)."""
    q, kp, vp, bt, valid, _ = _ragged_slots(np.random.default_rng(35), int8=int8)
    two, eight = (_kernel_primitives(q, kp, vp, bt, valid, g) for g in (2, 8))
    pools = 4 if int8 else 2
    for name in ("dma_start", "dma_wait", "cond", "while", "dot_general"):
        assert two[name] == eight[name] > 0, (name, two[name], eight[name])
    assert two["dma_start"] == 2 * pools and two["dma_wait"] == pools
    assert sum(eight.values()) - sum(two.values()) <= 6 * 4, (two, eight)


@pytest.mark.parametrize("pages", [2, 3, 8])
@pytest.mark.parametrize("pattern", ["random", "all_empty", "all_live", "ends_empty"])
def test_slot_schedule_counts_groups(cpu_devices, pattern, pages):
    """Over groups of `pages` columns `start[r]` is the number of groups of
    the slots before `r` (a slot's last may be short) and `nxt` is what it
    was: against the list of groups, in order."""
    rng = np.random.default_rng(29)
    R, nb = 11, 13
    lo = rng.integers(0, nb, R)
    hi = np.minimum(lo + rng.integers(0, 12, R), nb)
    if pattern == "all_empty":
        hi = lo.copy()
    elif pattern == "all_live":
        lo, hi = np.zeros(R, int), np.full(R, nb)
    elif pattern == "ends_empty":
        hi[[0, 1, R - 1]] = lo[[0, 1, R - 1]]
    args = jnp.asarray(lo, jnp.int32), jnp.asarray(hi, jnp.int32)
    start, nxt = slot_schedule(*args, pages)
    groups = [(r, c) for r in range(R) for c in range(lo[r], hi[r], pages)]
    for r in range(R):
        assert int(start[r]) == sum(1 for s, _ in groups if s < r), r
    np.testing.assert_array_equal(np.asarray(nxt), np.asarray(slot_schedule(*args)[1]))
    assert start.dtype == nxt.dtype == jnp.int32


@pytest.mark.parametrize("pattern", ["random", "all_empty", "all_live", "ends_empty"])
def test_slot_schedule_against_brute_force(cpu_devices, pattern):
    """`start[r]`: the live columns before slot r, so the walk's i-th pair is
    column `i - start[r]` of the slot it falls in; `nxt[r]`: the next slot
    with a live column, R if none. Against the list of pairs, in order."""
    rng = np.random.default_rng(23)
    R, nb = 11, 6
    lo = rng.integers(0, nb, R)
    hi = np.minimum(lo + rng.integers(0, 4, R), nb)
    if pattern == "all_empty":
        hi = lo.copy()
    elif pattern == "all_live":
        lo, hi = np.zeros(R, int), np.full(R, nb)
    elif pattern == "ends_empty":
        hi[[0, 1, R - 1]] = lo[[0, 1, R - 1]]
    start, nxt = slot_schedule(jnp.asarray(lo, jnp.int32), jnp.asarray(hi, jnp.int32))
    assert start.dtype == nxt.dtype == jnp.int32 and start.shape == nxt.shape == (R,)
    pairs = [(r, c) for r in range(R) for c in range(lo[r], hi[r])]
    for r in range(R):
        assert int(start[r]) == sum(1 for s, _ in pairs if s < r), r
        later = [s for s, _ in pairs if s > r]
        assert int(nxt[r]) == (min(later) if later else R), r
    # the chain, followed from the first live slot, lists every pair once
    walked, r = [], min((s for s, _ in pairs), default=R)
    while r < R:
        walked += [(r, int(lo[r]) + j) for j in range(int(hi[r] - lo[r]))]
        assert len(walked) - int(hi[r] - lo[r]) == int(start[r])
        r = int(nxt[r])
    assert walked == pairs


@pytest.mark.parametrize("W", [1, 3], ids=["decode", "verify3"])
def test_mixed_stack_steps_read_each_kind_under_its_range(cpu_devices, W):
    """Window and full layers in one stack (ring and paged pool side by
    side): the decode and the verify step through the kernel, each kind of
    layer under the live range of its own mask, against the XLA read; a
    slot that is not active in the batch."""
    from areal_tpu.models.qwen2 import ring_pages

    cfg = ModelConfig.from_hf_config(dict(
        model_type="exaone_moe", vocab_size=64, hidden_size=32, intermediate_size=48,
        num_hidden_layers=5, num_attention_heads=4, num_key_value_heads=2, head_dim=8,
        rope_parameters={"rope_theta": 10000.0, "rope_type": "default"}, rms_norm_eps=1e-5,
        sliding_window=8,
        layer_types=["sliding_attention"] * 3 + ["full_attention", "sliding_attention"],
        first_k_dense_replace=1, mlp_layer_types=["dense"] + ["sparse"] * 4, num_experts=4,
        num_experts_per_tok=2, moe_intermediate_size=16, num_shared_experts=1,
        scoring_func="sigmoid", norm_topk_prob=True, routed_scaling_factor=2.5, n_group=1,
        topk_group=1, num_nextn_predict_layers=0, tie_word_embeddings=False,
        max_position_embeddings=512), dtype="float32", param_dtype="float32")
    params = init_params(cfg, jax.random.PRNGKey(3))
    bsz, R, nb = 4, 3, 8
    pages = ring_pages(cfg.sliding_window, bsz)
    L = cfg.cache_layers
    rng = np.random.default_rng(16)

    def pools():
        z = lambda n, blocks: jnp.asarray(  # noqa: E731
            rng.standard_normal((n, blocks, bsz, 2 * 8)), jnp.float32)
        return {"full": z(len(L["full"]), 1 + R * nb),
                "window": z(len(L["window"]), 1 + R * pages)}

    kp, vp = pools(), pools()
    bt = jnp.asarray(1 + np.arange(R * nb).reshape(R, nb), jnp.int32)
    pos = jnp.asarray([2, 21, 13], jnp.int32)
    active = jnp.asarray([True, True, False])
    toks = jnp.asarray(rng.integers(1, 64, (R, W)), jnp.int32)

    def step(impl):
        if W == 1:
            return decode_step_paged(params, toks[:, 0], pos, kp, vp, bt, cfg,
                                     active=active, attn_impl=impl)
        return verify_step_paged(params, toks, pos, kp, vp, bt, cfg, active=active,
                                 attn_impl=impl)

    (la, ka, va), (lb, kb, vb) = step("xla"), step("pallas")
    on = np.asarray(active)
    np.testing.assert_allclose(np.asarray(la)[on], np.asarray(lb)[on], atol=1e-4, rtol=1e-4)
    # the null block 0 takes the row of the slot that is not active: not read
    for kind in ("full", "window"):
        for a, b in ((ka, kb), (va, vb)):
            np.testing.assert_allclose(
                np.asarray(a[kind])[:, 1:], np.asarray(b[kind])[:, 1:], atol=1e-5)


def test_resolve_impl(cpu_devices):
    assert resolve_impl("xla") == "xla"
    assert resolve_impl("pallas") == "pallas"
    assert resolve_impl("auto") in ("pallas", "xla")
    with pytest.raises(ValueError):
        resolve_impl("cuda")


# ---------------------------------------------------------------------------
# model steps: against the trainer's forward, the write contract, verify
# against sequential decode
# ---------------------------------------------------------------------------

_L, _NKV, _HD = TINY.num_hidden_layers, TINY.num_key_value_heads, TINY.head_dim_
_BSZ, _NB = 8, 4  # a slot spans 32 rows
_PROMPTS = [21, 7, 13]  # prompt + the token fed: slot 0's step lands in block 2
_ACTIVE = np.array([True, True, False])  # slot 2 is parked with KV it must keep


def _prefilled_pool(params, seed=2):
    """A pool of noise with each slot's prompt (all but its last token)
    prefilled into the slot's blocks, as the engine's prefill leaves it.
    Returns (kp, vp, bt, ids per slot); the block ids are shuffled."""
    rng = np.random.default_rng(seed)
    R = len(_PROMPTS)
    n_blocks = 1 + R * _NB + 2  # null block 0, the slots', two nobody owns
    shape = (_L, n_blocks, _BSZ, _NKV * _HD)
    kp = rng.standard_normal(shape).astype(np.float32)
    vp = rng.standard_normal(shape).astype(np.float32)
    bt = rng.permutation(np.arange(1, 1 + R * _NB)).astype(np.int32).reshape(R, _NB)
    ids = [rng.integers(1, TINY.vocab_size, T) for T in _PROMPTS]
    for r, x in enumerate(ids):
        n = len(x) - 1
        _, ks, vs = prefill(params, x[:-1], np.arange(n), TINY, with_logits=False)
        for pool, rows in ((kp, ks), (vp, vs)):
            rows = np.asarray(rows).reshape(_L, n, _NKV * _HD)
            for p in range(n):
                pool[:, bt[r, p // _BSZ], p % _BSZ] = rows[:, p]
    return jnp.asarray(kp), jnp.asarray(vp), jnp.asarray(bt), ids


def _written(bt, positions, active):
    """Mask [n_blocks, bsz] of the rows a step may write: each active
    slot's `(bt[r, p // bsz], p % bsz)` and, if a slot is inactive, row 0 of
    null block 0."""
    mask = np.zeros((1 + bt.size + 2, _BSZ), bool)
    for r, ps in enumerate(positions.reshape(len(active), -1)):
        for p in ps:
            if active[r]:
                mask[bt[r, p // _BSZ], p % _BSZ] = True
            else:
                mask[0, 0] = True
    return mask


def _assert_rows_are_prefills(params, kp, vp, bt, r, ids, positions):
    """Slot r's pool rows at `positions` hold the K/V a prefill of `ids`
    computes there, in every layer."""
    T = len(ids)
    _, ks, vs = prefill(params, ids, np.arange(T), TINY, with_logits=False)
    for pool, rows in ((kp, ks), (vp, vs)):
        for p in positions:
            np.testing.assert_allclose(
                np.asarray(pool)[:, bt[r, p // _BSZ], p % _BSZ],
                np.asarray(rows)[:, p].reshape(_L, -1),
                rtol=1e-5, atol=1e-5, err_msg=f"slot {r} position {p}",
            )


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_decode_step_paged_matches_forward(cpu_devices, impl):
    """Prefill a prompt into the pool, step once, and the step's logits are
    the trainer's: `forward` over prompt + token at the same positions (the
    slow `test_model_families.py` check, on the step that ships)."""
    params = init_params(TINY, jax.random.PRNGKey(0))
    kp, vp, bt, ids = _prefilled_pool(params)
    tokens = jnp.asarray([x[-1] for x in ids], jnp.int32)
    positions = jnp.asarray([len(x) - 1 for x in ids], jnp.int32)
    logits, _, _ = decode_step_paged(
        params, tokens, positions, kp, vp, bt, TINY,
        active=jnp.asarray(_ACTIVE), attn_impl=impl,
    )
    for r in np.flatnonzero(_ACTIVE):
        T = len(ids[r])
        ref = forward(
            params, ids[r], np.arange(T), np.zeros(T, np.int32), TINY
        )[-1]
        assert_logprobs_close(
            jax.nn.log_softmax(logits[r]), jax.nn.log_softmax(ref), f"slot {r}"
        )


def test_decode_step_paged_write_contract(cpu_devices):
    """After one step the row at `(layer, bt[r, p // bsz], p % bsz)` holds
    the new K/V of every active slot (what a prefill of prompt + token
    computes for that position), the inactive slot's write lands in null
    block 0, and every other byte of both pools is what it was."""
    params = init_params(TINY, jax.random.PRNGKey(0))
    kp, vp, bt, ids = _prefilled_pool(params)
    tokens = jnp.asarray([x[-1] for x in ids], jnp.int32)
    positions = np.asarray([len(x) - 1 for x in ids], np.int32)
    _, kp2, vp2 = decode_step_paged(
        params, tokens, jnp.asarray(positions), kp, vp, bt, TINY,
        active=jnp.asarray(_ACTIVE), attn_impl="xla",
    )
    bt = np.asarray(bt)
    mask = _written(bt, positions, _ACTIVE)
    assert mask.sum() == _ACTIVE.sum() + 1
    for before, after in ((kp, kp2), (vp, vp2)):
        before, after = np.asarray(before), np.asarray(after)
        np.testing.assert_array_equal(after[:, ~mask], before[:, ~mask])
        # the parked slot's row went to the null block, not to its own
        assert (after[:, 0, 0] != before[:, 0, 0]).any()
    for r in np.flatnonzero(_ACTIVE):
        _assert_rows_are_prefills(params, kp2, vp2, bt, r, ids[r], positions[r:r + 1])


def test_verify_step_paged_write_contract(cpu_devices):
    """The same for the verify step's W rows a slot: they land at
    `positions0 + j` through the table (here across a block boundary),
    hold what a prefill computes there, and nothing else changes."""
    W = 3
    params = init_params(TINY, jax.random.PRNGKey(0))
    kp, vp, bt, ids = _prefilled_pool(params)
    rng = np.random.default_rng(5)
    ext = [np.concatenate([x, rng.integers(1, TINY.vocab_size, W - 1)]) for x in ids]
    base = np.asarray([len(x) - 1 for x in ids], np.int32)
    tokens = jnp.asarray(np.stack([x[-W:] for x in ext]), jnp.int32)
    _, kp2, vp2 = verify_step_paged(
        params, tokens, jnp.asarray(base), kp, vp, bt, TINY,
        active=jnp.asarray(_ACTIVE), attn_impl="xla",
    )
    bt = np.asarray(bt)
    positions = base[:, None] + np.arange(W)
    assert positions[1, 0] // _BSZ != positions[1, -1] // _BSZ
    mask = _written(bt, positions, _ACTIVE)
    assert mask.sum() == W * _ACTIVE.sum() + 1
    for before, after in ((kp, kp2), (vp, vp2)):
        np.testing.assert_array_equal(
            np.asarray(after)[:, ~mask], np.asarray(before)[:, ~mask]
        )
    for r in np.flatnonzero(_ACTIVE):
        _assert_rows_are_prefills(params, kp2, vp2, bt, r, ext[r], positions[r])


@pytest.mark.parametrize("W", [2, 4])
def test_verify_step_paged_matches_sequential_decode(cpu_devices, W):
    """One verify forward over W positions gives the logits and the pool
    that W decode steps give, fed the same tokens one at a time: the
    contract the engine's speculative accept relies on."""
    params = init_params(TINY, jax.random.PRNGKey(0))
    kp, vp, bt, ids = _prefilled_pool(params)
    rng = np.random.default_rng(6)
    tokens = np.concatenate(
        [np.asarray([[x[-1]] for x in ids]),
         rng.integers(1, TINY.vocab_size, (len(ids), W - 1))], axis=1,
    ).astype(np.int32)
    base = jnp.asarray([len(x) - 1 for x in ids], jnp.int32)
    active = jnp.asarray(_ACTIVE)
    logits_v, kp_v, vp_v = verify_step_paged(
        params, jnp.asarray(tokens), base, kp, vp, bt, TINY,
        active=active, attn_impl="xla",
    )
    kp_s, vp_s = kp, vp
    for j in range(W):
        logits_j, kp_s, vp_s = decode_step_paged(
            params, jnp.asarray(tokens[:, j]), base + j, kp_s, vp_s, bt, TINY,
            active=active, attn_impl="xla",
        )
        for r in np.flatnonzero(_ACTIVE):
            assert_logprobs_close(
                jax.nn.log_softmax(logits_v[r, j]),
                jax.nn.log_softmax(logits_j[r]),
                f"slot {r} column {j}",
            )
    for seq, ver in ((kp_s, kp_v), (vp_s, vp_v)):
        np.testing.assert_allclose(
            np.asarray(ver)[:, 1:], np.asarray(seq)[:, 1:], rtol=1e-5, atol=1e-5
        )


# ---------------------------------------------------------------------------
# engine level: the full trace on both attention impls, chunk boundaries
# ---------------------------------------------------------------------------

_BASE = [1, 5, 9, 13, 2, 4, 6, 8]  # shared prompt for fork coverage


def _engine(impl: str = "auto", **kw):
    cfg = JaxDecodeConfig(
        context_length=kw.pop("context_length", 256),
        max_running_requests=kw.pop("max_running_requests", 4),
        new_tokens_per_chunk=kw.pop("new_tokens_per_chunk", 4),
        page_size=kw.pop("page_size", 16),
        decode_runahead_chunks=kw.pop("decode_runahead_chunks", 1),
        paged_attn_impl=impl,
        dtype="float32",
        kv_cache_dtype="float32",
        random_seed=7,
        **kw,
    )
    eng = JaxDecodeEngine(cfg, InferenceEngineConfig())
    eng.set_model(init_params(TINY, jax.random.PRNGKey(0)), TINY)
    eng.initialize()
    return eng


def _run_trace(eng):
    """One request trace hitting forks, suffix prefill, retire-mid-chunk
    and the sampler variants; returns responses in a deterministic order."""

    async def main():
        g = GenerationHyperparameters(greedy=True, max_new_tokens=10)
        # wave of duplicates (same-wave dup fork) + distinct prompts
        wave = await asyncio.gather(
            eng.agenerate(ModelRequest(input_ids=list(_BASE), gconfig=g)),
            eng.agenerate(ModelRequest(input_ids=list(_BASE), gconfig=g)),
            eng.agenerate(ModelRequest(input_ids=[2, 7, 11, 3], gconfig=g)),
            # stop token likely mid-chunk: retire-mid-chunk reconcile under
            # run-ahead (the chunk after the stop is already dispatched)
            eng.agenerate(
                ModelRequest(
                    input_ids=[9, 9, 1, 4],
                    gconfig=replace(g, max_new_tokens=9, stop_token_ids=[1]),
                )
            ),
        )
        # conversation extension PAST the 64-token shared-prefix floor:
        # long donor finishes, then a request re-submits donor prompt +
        # answer + a new suffix -> fork + suffix prefill
        long_prompt = [(i % 60) + 1 for i in range(70)]
        donor = await eng.agenerate(
            ModelRequest(input_ids=list(long_prompt), gconfig=g)
        )
        ext = await eng.agenerate(
            ModelRequest(
                input_ids=list(long_prompt)
                + list(donor.output_tokens)
                + [5, 3],
                gconfig=g,
            )
        )
        # sampled variants: freq penalty and top-p classes share a batch
        sampled = await asyncio.gather(
            eng.agenerate(
                ModelRequest(
                    input_ids=[1, 2, 3],
                    gconfig=GenerationHyperparameters(
                        temperature=1.0,
                        top_p=0.9,
                        max_new_tokens=8,
                        frequency_penalty=0.7,
                    ),
                )
            ),
            eng.agenerate(
                ModelRequest(
                    input_ids=[4, 5, 6],
                    gconfig=GenerationHyperparameters(
                        temperature=0.8, top_p=1.0, max_new_tokens=8
                    ),
                )
            ),
        )
        return list(wave) + [donor, ext] + list(sampled)

    return asyncio.run(main())


def _trace_and_metrics(impl, **kw):
    eng = _engine(impl, **kw)
    try:
        out = _run_trace(eng)
        m = eng.get_metrics()
    finally:
        eng.destroy()
    return out, m


@pytest.mark.parametrize("spec", ["off", "ngram"])
def test_engine_impl_parity(cpu_devices, spec):
    """The XLA gather and the Pallas split-KV kernel (interpret mode here)
    give the same streams across forks, same-wave duplicates, suffix
    prefill, retire-mid-chunk under run-ahead, and freq-penalty/top-p
    sampling: identical tokens and stop reasons, log-probabilities to 1e-4
    (the kernel reduces in another order), also with speculation on."""
    xla, m_xla = _trace_and_metrics("xla", spec_decode=spec)
    pal, m_pal = _trace_and_metrics("pallas", spec_decode=spec)
    assert len(xla) == len(pal)
    for i, (a, b) in enumerate(zip(xla, pal)):
        assert a.output_tokens == b.output_tokens, i
        assert a.stop_reason == b.stop_reason, i
        np.testing.assert_allclose(
            np.asarray(a.output_logprobs),
            np.asarray(b.output_logprobs),
            atol=1e-4,
            err_msg=str(i),
        )
    # the trace really exercised the sharing paths, on both engines
    for m in (m_xla, m_pal):
        assert m["prefix_forks_total"] >= 1, m
        assert m["suffix_prefills_total"] >= 1, m
        assert m["prefix_cache_hit_rate"] > 0.0, m
        assert (m["spec_chunks_total"] > 0) == (spec == "ngram"), m


@pytest.mark.parametrize("greedy", [True, False], ids=["greedy", "sampled"])
def test_streams_do_not_depend_on_chunk_boundaries(cpu_devices, greedy):
    """The default engine reads every row back through the pool, so a stream
    is the same whether its chunks end every 2 tokens or every 8: same
    tokens, log-probabilities to float32 rounding (two compiled programs)."""
    g = GenerationHyperparameters(
        greedy=greedy, temperature=0.9, top_p=0.95, max_new_tokens=19
    )
    prompts = [[3, 1, 4, 1, 5], [9, 2, 6, 5, 3, 5, 8, 9, 7, 9, 3, 2, 3, 8], [2, 7]]

    def run(n_chunk):
        eng = _engine(new_tokens_per_chunk=n_chunk)
        try:
            # one at a time: admission order fixes each slot's sampling key
            return [
                eng.generate(ModelRequest(input_ids=list(p), gconfig=g), timeout=300)
                for p in prompts
            ]
        finally:
            eng.destroy()

    for i, (a, b) in enumerate(zip(run(2), run(8))):
        assert len(a.output_tokens) == 19
        assert a.output_tokens == b.output_tokens, i
        assert_logprobs_close(a.output_logprobs, b.output_logprobs, i)


def test_block_table_upload_dirty_tracking(cpu_devices):
    """Steady-state chunks must NOT re-upload the block table: uploads
    are keyed on (allocator mutation version, nb), so a long generation
    with a stable slot set uploads only when admission/retire/growth
    actually moved the table."""
    eng = _engine("xla", new_tokens_per_chunk=2)
    try:

        async def main():
            g = GenerationHyperparameters(greedy=True, max_new_tokens=24)
            return await eng.agenerate(
                ModelRequest(input_ids=[3, 1, 4], gconfig=g)
            )

        asyncio.run(main())
        m = eng.get_metrics()
    finally:
        eng.destroy()
    # 24 tokens at 2/chunk = 12 chunks; table mutates only at admission
    # and on block-boundary growth (page_size 16 -> at most a few times)
    assert m["chunks_dispatched_total"] >= 12
    assert m["block_table_uploads_total"] < m["chunks_dispatched_total"], m
    assert m["block_table_uploads_total"] >= 1


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_prewarm_covers_paged_variants(cpu_devices, impl):
    """Prewarm on a paged engine must ghost-compile the paged chunk
    variants (and the patch fn) so the first overlapped dispatch never
    traces: after prewarm, serving requests of ragged lengths compiles
    nothing new. A chunk program is keyed by its depth `nb` and by nothing
    that depends on how ragged the batch is (the kernel reads each slot's
    columns off the device), so one program a depth serves them all."""
    eng = _engine(impl)
    try:
        eng.prewarm(prompt_len=8, new_tokens=40, sampler_top_ps=(1.0,))
        compiled = set(eng._chunk_fns)
        assert compiled, "prewarm compiled no chunk variants"
        assert eng._patch_fn is not None
        assert len({k[:2] for k in compiled}) == 1 and len({k[2] for k in compiled}) == len(compiled)

        async def main():
            # one request a page deep beside three of a few tokens
            return await asyncio.gather(*(
                eng.agenerate(ModelRequest(
                    input_ids=[3, 1, 4, 1, 5, 9, 2, 6][:n],
                    gconfig=GenerationHyperparameters(greedy=True, max_new_tokens=new)))
                for n, new in ((8, 40), (3, 4), (5, 9), (2, 1))))

        asyncio.run(main())
        assert set(eng._chunk_fns) == compiled, (
            "live traffic needed a chunk variant prewarm did not compile"
        )
    finally:
        eng.destroy()


def test_fragmentation_metric(cpu_devices):
    """kv_pool_fragmentation counts the free-block remainder that cannot
    back another max-context admission."""
    eng = _engine("xla", context_length=64, page_size=16, kv_pool_tokens=112)
    try:
        m = eng.get_metrics()
        # 7 usable blocks, max_bps = 4 -> one full-context reservation
        # fits, 3 blocks are structural remainder
        assert m["kv_blocks_free"] == 7
        assert m["kv_pool_fragmentation"] == 3
    finally:
        eng.destroy()
