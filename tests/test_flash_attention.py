"""Flash attention kernel vs dense reference (interpret mode on CPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from areal_tpu.models.qwen2 import PADDING_SEGMENT, segment_causal_mask
from areal_tpu.ops.flash_attention import (
    _NEG_INF,
    _flash,
    _mask_for,
    block_liveness,
    flash_attention,
    flash_attention_chunk,
    live_block_counts,
    live_runs,
    walk_runs,
)


def dense_reference(q, k, v, seg):
    """[T, nH, hd] x [T, nKV, hd] -> [T, nH, hd], causal-within-segment."""
    T, nH, hd = q.shape
    nKV = k.shape[1]
    group = nH // nKV
    qf = q.astype(jnp.float32).reshape(T, nKV, group, hd)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    s = jnp.einsum("tkgd,skd->kgts", qf, kf) / np.sqrt(hd)
    mask = segment_causal_mask(seg)
    s = jnp.where(mask[None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    # zero fully-masked (padding) rows
    valid = (seg != PADDING_SEGMENT)[None, None, :, None]
    p = jnp.where(valid, p, 0.0)
    o = jnp.einsum("kgts,skd->tkgd", p, vf)
    return o.reshape(T, nH, hd)


def make_inputs(T, nH, nKV, hd, seed=0, n_seqs=3, pad=0):
    rng = np.random.RandomState(seed)
    q = jnp.asarray(rng.randn(T, nH, hd), dtype=jnp.float32) * 0.5
    k = jnp.asarray(rng.randn(T, nKV, hd), dtype=jnp.float32) * 0.5
    v = jnp.asarray(rng.randn(T, nKV, hd), dtype=jnp.float32) * 0.5
    body = T - pad
    cuts = np.sort(rng.choice(np.arange(1, body), size=n_seqs - 1, replace=False))
    seg = np.zeros(T, dtype=np.int32)
    prev = 0
    for si, c in enumerate(list(cuts) + [body]):
        seg[prev:c] = si
        prev = c
    seg[body:] = PADDING_SEGMENT
    return q, k, v, jnp.asarray(seg)


# packings the block-liveness skip treats differently: (n_seqs, pad) of
# `make_inputs`. Many short segments leave most block pairs dead; a single
# segment leaves every pair under the diagonal live; all pad leaves none.
PACKINGS = {
    "many_short": lambda T: dict(n_seqs=T // 24, pad=T // 5),
    "single_segment": lambda T: dict(n_seqs=1, pad=0),
    "all_pad": lambda T: dict(n_seqs=1, pad=T),
    # the last outer block (of 128 tokens, and the one before it at T = 512)
    # is pad alone: a grid step that walks nothing
    "pad_block": lambda T: dict(n_seqs=3, pad=T // 2),
}


def brute_force_liveness(seg_q, seg_k, qpos, kpos, block_q, block_k):
    """[nq, nk]: does `_mask_for` over the block pair hold any valid pair."""
    mask = np.asarray(_mask_for(*map(jnp.asarray, (seg_q, seg_k, qpos, kpos))))
    nq, nk = len(seg_q) // block_q, len(seg_k) // block_k
    return mask.reshape(nq, block_q, nk, block_k).any(axis=(1, 3))


def random_packing(T, seed, mean_len, pad):
    """Sequences of random lengths packed end to end, then a pad tail."""
    rng = np.random.RandomState(seed)
    seg = np.full(T, PADDING_SEGMENT, np.int32)
    start = sid = 0
    while start < T - pad:
        end = min(start + int(rng.randint(1, 2 * mean_len)), T - pad)
        seg[start:end] = sid
        start, sid = end, sid + 1
    return seg


@pytest.mark.parametrize(
    "T,block,mean_len,pad",
    [
        (2048, 128, 40, 300),  # several sequences a block
        (2048, 128, 150, 1),  # a boundary in most blocks
        (4096, 512, 473, 700),  # the trainer's cell: 16 sequences a row
        (2048, 128, 5000, 0),  # one sequence: the causal triangle
        (1024, 128, 100, 1024),  # all pad: nothing live
        (1024, 256, 60, 511),  # the pad tail starts inside a block
    ],
)
def test_liveness_is_exact_on_contiguous_packings(T, block, mean_len, pad):
    for seed in range(3):
        seg = random_packing(T, seed, mean_len, pad)
        pos = np.arange(T, dtype=np.int32)
        live = block_liveness(seg, seg, pos, pos, block, block)
        brute = brute_force_liveness(seg, seg, pos, pos, block, block)
        np.testing.assert_array_equal(live, brute)
        # contiguous segments in order: no run has a hole, a walk is its live pairs
        n_live = int(brute.sum())
        assert live_block_counts(seg, pos, T, block) == (n_live, (n_live, n_live), brute.size)
    if pad == T:
        assert not live.any()
    if mean_len > T:
        np.testing.assert_array_equal(live, np.tril(np.ones_like(live)))


@pytest.mark.parametrize("seed", range(4))
def test_liveness_never_kills_a_valid_pair_when_ids_are_scattered(seed):
    """No assumption that ids are monotone or contiguous: random ids with
    pads sprinkled in, random positions. The rule may keep a block that
    holds no pair, never drop one that does."""
    bq, bk = 128, 256
    seg_q, seg_k, qpos, kpos = scattered_ids(seed)
    live = block_liveness(seg_q, seg_k, qpos, kpos, bq, bk)
    brute = brute_force_liveness(seg_q, seg_k, qpos, kpos, bq, bk)
    assert not (brute & ~live).any()
    assert not live.all()  # and it does skip something here



def scattered_ids(seed, Tq=1024, Tk=768):
    """Ids that are neither monotone nor contiguous, in bands so that some
    intervals do not overlap, pads sprinkled in, random positions."""
    rng = np.random.RandomState(seed)
    seg_q = rng.randint(-1, 6, Tq).astype(np.int32)
    seg_k = rng.randint(-1, 6, Tk).astype(np.int32)
    seg_q[seg_q >= 0] += 7 * (np.arange(Tq) // 256)[seg_q >= 0]
    seg_k[seg_k >= 0] += 7 * (np.arange(Tk) // 256)[seg_k >= 0]
    qpos = rng.randint(0, 2000, Tq).astype(np.int32)
    kpos = rng.randint(0, 2000, Tk).astype(np.int32)
    return seg_q, seg_k, qpos, kpos


def check_work_list(seg_q, seg_k, qpos, kpos, bq, bk):
    """`walk_runs` against brute force over `_mask_for`: every pair that
    holds a valid (query, key) is in its query block's walk and in its key
    block's; a walk starts and ends on a pair the liveness rule keeps (no
    walk is longer than its run), and a block with none walks nothing. The
    same on JAX arrays as on NumPy. Returns (brute, runs)."""
    brute = brute_force_liveness(seg_q, seg_k, qpos, kpos, bq, bk)
    live = np.asarray(block_liveness(seg_q, seg_k, qpos, kpos, bq, bk))
    runs = walk_runs(seg_q, seg_k, qpos, kpos, bq, bk)
    on_device = walk_runs(*map(jnp.asarray, (seg_q, seg_k, qpos, kpos)), bq, bk)
    for a, b in zip(runs, on_device):
        assert a.dtype == np.int32 and b.dtype == jnp.int32
        np.testing.assert_array_equal(a, np.asarray(b))
    for (lo, hi), table, brute_t in (
        (runs[:2], live, brute), (runs[2:], live.T, brute.T),
    ):
        assert lo.shape == hi.shape == table.shape[:1]
        inner = np.arange(table.shape[1])
        walked = (lo[:, None] <= inner) & (inner < hi[:, None])
        assert not (brute_t & ~walked).any()
        for i in range(len(lo)):
            if table[i].any():
                assert table[i, lo[i]] and table[i, hi[i] - 1]
            else:
                assert lo[i] == hi[i] == 0
    return brute, runs


@pytest.mark.parametrize(
    "T,block,mean_len,pad",
    [
        (2048, 128, 40, 300),  # many short segments, a pad tail
        (4096, 512, 473, 700),  # the trainer's cell
        (2048, 128, 5000, 0),  # one long segment: every run starts at 0
        (1024, 128, 100, 1024),  # all pad: every walk empty
        (1024, 128, 60, 520),  # outer blocks that are pad alone
    ],
    ids=["many_short", "trainer_cell", "one_long", "all_pad", "pad_blocks"],
)
def test_work_list_on_contiguous_packings(T, block, mean_len, pad):
    """In-order contiguous segments: a run has no hole, so the three walks
    are the live pairs and nothing else."""
    for seed in range(3):
        seg = random_packing(T, seed, mean_len, pad)
        if mean_len > T:
            seg[:] = 0
        pos = np.arange(T, dtype=np.int32)
        brute, (lo_q, hi_q, lo_k, hi_k) = check_work_list(seg, seg, pos, pos, block, block)
        assert (hi_q - lo_q).sum() == (hi_k - lo_k).sum() == brute.sum()
        if mean_len > T:
            np.testing.assert_array_equal(lo_q, 0)
            np.testing.assert_array_equal(hi_q, np.arange(T // block) + 1)
            np.testing.assert_array_equal(hi_k, T // block)
        if pad >= block:
            assert (hi_q[-(pad // block):] == 0).all()


@pytest.mark.parametrize("seed", range(4))
def test_work_list_walks_the_holes_of_non_monotone_ids(seed):
    """Scattered ids: a run may hold dead pairs (they are walked, and add
    nothing); it never misses a live one, and `live_runs` of any table is its
    rows' first and last set entry."""
    brute, (lo_q, hi_q, lo_k, hi_k) = check_work_list(*scattered_ids(seed), 128, 256)
    assert (hi_q - lo_q).sum() >= brute.sum() and (hi_k - lo_k).sum() >= brute.sum()
    table = np.random.RandomState(seed).rand(3, 7, 9) < 0.3
    table[1, 2] = False
    lo, hi = live_runs(table)
    for idx in np.ndindex(3, 7):
        on = np.flatnonzero(table[idx])
        want = (on[0], on[-1] + 1) if len(on) else (0, 0)
        assert (lo[idx], hi[idx]) == want


def test_forward_past_32_key_blocks():
    """40 key blocks a query block: the walk's `[nq]` vectors, whatever nk."""
    T, nH, nKV, hd = 5120, 1, 1, 32
    q, k, v, seg = make_inputs(T, nH, nKV, hd, seed=3, n_seqs=9, pad=200)
    out = flash_attention(q, k, v, seg, block_q=128, block_k=128, interpret=True)
    ref = dense_reference(q, k, v, seg)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


def _flash_both_ways(q, k, v, seg_q, seg_k, qpos, kpos, block):
    """(out, lse, dq, dk, dv) of the kernels on their work list and of the
    same kernels walking every block pair."""
    q4, k4, v4 = (jnp.swapaxes(x, 0, 1)[None] for x in (q, k, v))
    ids = tuple(jnp.asarray(x)[None] for x in (seg_q, seg_k, qpos, kpos))
    rng = np.random.RandomState(11)
    do = jnp.asarray(rng.randn(*q4.shape), jnp.float32)
    dlse = jnp.asarray(rng.randn(*q4.shape[:3]), jnp.float32)
    flash = _flash(q.shape[-1] ** -0.5, block, block, True)

    def run(runs):
        runs = tuple(jnp.asarray(r, jnp.int32)[None] for r in runs)
        (o, lse), vjp = jax.vjp(lambda q4, k4, v4: flash(q4, k4, v4, *ids, runs), q4, k4, v4)
        return (o, lse) + vjp((do, dlse))

    nq, nk = len(seg_q) // block, len(seg_k) // block
    every = (np.zeros(nq), np.full(nq, nk), np.zeros(nk), np.full(nk, nq))
    return run(walk_runs(seg_q, seg_k, qpos, kpos, block, block)), run(every)


@pytest.mark.parametrize("packing", list(PACKINGS))
def test_walk_equals_all_live_to_the_bit(packing):
    T, nH, nKV, hd, block = 512, 4, 2, 32, 128
    q, k, v, seg = make_inputs(T, nH, nKV, hd, seed=4, **PACKINGS[packing](T))
    pos = jnp.arange(T, dtype=jnp.int32)
    live = np.asarray(block_liveness(seg, seg, pos, pos, block, block))
    assert {"many_short": 0 < live.sum() < 10, "single_segment": live.sum() == 10,
            "all_pad": live.sum() == 0, "pad_block": 0 < live[:2].sum() and not live[2:].any(),
            }[packing], live
    skipping, forced = _flash_both_ways(q, k, v, seg, seg, pos, pos, block)
    for a, b, name in zip(skipping, forced, ("out", "lse", "dq", "dk", "dv")):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b), err_msg=name)


def test_dead_ring_chunk_equals_all_live_to_the_bit():
    """A visiting kv chunk from LATER in the stream: no block is visited,
    the output is 0, lse is _NEG_INF and every gradient is 0, as when the
    whole chunk is computed and masked."""
    T, nH, nKV, hd, block = 256, 4, 2, 32, 128
    q, k, v, seg = make_inputs(T, nH, nKV, hd, seed=6, n_seqs=2, pad=0)
    qpos = jnp.arange(T, dtype=jnp.int32)
    kpos = qpos + T
    assert not np.asarray(block_liveness(seg, seg, qpos, kpos, block, block)).any()
    skipping, forced = _flash_both_ways(q, k, v, seg, seg, qpos, kpos, block)
    for a, b, name in zip(skipping, forced, ("out", "lse", "dq", "dk", "dv")):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b), err_msg=name)
    out, lse = flash_attention_chunk(
        q, k, v, seg, seg, qpos, kpos, block_q=block, block_k=block, interpret=True)
    assert not np.asarray(out).any()
    np.testing.assert_array_equal(np.asarray(lse), np.float32(_NEG_INF))
    assert not any(np.asarray(g).any() for g in skipping[2:])


@pytest.mark.parametrize(
    "T,nH,nKV,hd,packing",
    [
        (256, 4, 4, 64, dict(pad=0)),
        (256, 4, 2, 64, dict(pad=37)),  # GQA + ragged pad tail
        (384, 8, 2, 32, dict(pad=5)),
    ]
    + [(512, 4, 2, 32, PACKINGS[name](512)) for name in PACKINGS],
    ids=["mha", "gqa_pad_tail", "gqa8"] + list(PACKINGS),
)
def test_forward_matches_dense(T, nH, nKV, hd, packing):
    q, k, v, seg = make_inputs(T, nH, nKV, hd, **packing)
    out = flash_attention(q, k, v, seg, block_q=128, block_k=128, interpret=True)
    ref = dense_reference(q, k, v, seg)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


@pytest.mark.slow
def test_backward_matches_dense():
    T, nH, nKV, hd = 256, 4, 2, 32
    q, k, v, seg = make_inputs(T, nH, nKV, hd, pad=19, seed=1)

    def loss_flash(q, k, v):
        o = flash_attention(q, k, v, seg, block_q=128, block_k=128, interpret=True)
        return jnp.sum(jnp.sin(o))

    def loss_dense(q, k, v):
        return jnp.sum(jnp.sin(dense_reference(q, k, v, seg)))

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(gf, gd, "qkv"):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=2e-4, rtol=2e-4, err_msg=name
        )


@pytest.mark.parametrize("packing", list(PACKINGS))
def test_gradients_match_dense(packing):
    T, nH, nKV, hd = 256, 2, 1, 32
    q, k, v, seg = make_inputs(T, nH, nKV, hd, seed=8, **PACKINGS[packing](T))

    def loss(attend):
        return lambda q, k, v: jnp.sum(jnp.sin(attend(q, k, v)))

    gf = jax.grad(loss(lambda q, k, v: flash_attention(
        q, k, v, seg, block_q=128, block_k=128, interpret=True)), argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(loss(lambda q, k, v: dense_reference(q, k, v, seg)),
                  argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(gf, gd, "qkv"):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=2e-4, rtol=2e-4, err_msg=name
        )


def test_vmap_with_a_segment_row_each():
    """As `prefill_batched` calls it: the model forward under `jax.vmap`,
    each row its own prompt length, so each row its own bounds table."""
    B, T, nH, nKV, hd = 3, 256, 4, 2, 32
    rows = [make_inputs(T, nH, nKV, hd, seed=20 + b, n_seqs=1 + 2 * b, pad=40 * b)
            for b in range(B)]
    q, k, v, seg = (jnp.stack(x) for x in zip(*rows))
    attend = lambda q, k, v, seg: flash_attention(  # noqa: E731
        q, k, v, seg, block_q=128, block_k=128, interpret=True)
    out = jax.jit(jax.vmap(attend))(q, k, v, seg)
    for b in range(B):
        np.testing.assert_array_equal(
            np.asarray(out[b]), np.asarray(attend(q[b], k[b], v[b], seg[b])))
        np.testing.assert_allclose(
            np.asarray(out[b]), np.asarray(dense_reference(q[b], k[b], v[b], seg[b])),
            atol=2e-5, rtol=2e-5)


def test_vmap_of_the_gradients_equals_row_by_row():
    """The pipelined trainer reaches the backward kernels under `jax.vmap`
    (a vmap over stages around each stage's vjp): rows fold into the
    kernels' batch axis there too, each with its own work list."""
    B, T, nH, nKV, hd = 2, 256, 2, 1, 32
    rows = [make_inputs(T, nH, nKV, hd, seed=40 + b, n_seqs=2 + 3 * b, pad=130 * b)
            for b in range(B)]
    q, k, v, seg = (jnp.stack(x) for x in zip(*rows))

    def grads(q, k, v, seg):
        loss = lambda q, k, v: jnp.sum(jnp.sin(flash_attention(  # noqa: E731
            q, k, v, seg, block_q=128, block_k=128, interpret=True)))
        return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    batched = jax.jit(jax.vmap(grads))(q, k, v, seg)
    one_row = jax.jit(grads)  # compiled as well: `delta` is XLA's sum on both sides
    for b in range(B):
        for got, want in zip(batched, one_row(q[b], k[b], v[b], seg[b])):
            np.testing.assert_array_equal(np.asarray(got[b]), np.asarray(want))


def test_nonaligned_length_padding():
    # T not a multiple of the block: wrapper pads and slices back.
    T, nH, nKV, hd = 200, 2, 2, 32
    q, k, v, seg = make_inputs(T, nH, nKV, hd, pad=0, seed=2, n_seqs=2)
    out = flash_attention(q, k, v, seg, block_q=128, block_k=128, interpret=True)
    ref = dense_reference(q, k, v, seg)
    assert out.shape == (T, nH, hd)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


def test_segment_isolation():
    # Tokens in one segment must not see another segment even acausally.
    T, nH, nKV, hd = 128, 2, 2, 32
    rng = np.random.RandomState(3)
    q = jnp.asarray(rng.randn(T, nH, hd), dtype=jnp.float32)
    k = jnp.asarray(rng.randn(T, nKV, hd), dtype=jnp.float32)
    v = jnp.asarray(rng.randn(T, nKV, hd), dtype=jnp.float32)
    seg = jnp.asarray(np.repeat([0, 1], T // 2).astype(np.int32))
    out = flash_attention(q, k, v, seg, block_q=128, block_k=128, interpret=True)
    # Perturb segment 0's k/v: segment 1 outputs must not change.
    k2 = k.at[: T // 2].add(10.0)
    v2 = v.at[: T // 2].add(10.0)
    out2 = flash_attention(q, k2, v2, seg, block_q=128, block_k=128, interpret=True)
    np.testing.assert_allclose(
        np.asarray(out[T // 2 :]), np.asarray(out2[T // 2 :]), atol=1e-6
    )
    assert not np.allclose(np.asarray(out[: T // 2]), np.asarray(out2[: T // 2]))


@pytest.mark.slow
def test_model_forward_flash_vs_dense():
    # Full decoder forward parity between attention implementations.
    from areal_tpu.models.qwen2 import (
        ModelConfig,
        forward,
        init_params,
        segment_ids_from_cu_seqlens,
    )

    base = dict(
        vocab_size=128,
        hidden_size=64,
        intermediate_size=128,
        num_hidden_layers=2,
        num_attention_heads=4,
        num_key_value_heads=2,
        dtype="float32",
        param_dtype="float32",
    )
    cfg_d = ModelConfig(**base, attn_impl="dense")
    cfg_f = ModelConfig(**base, attn_impl="flash")
    params = init_params(cfg_d, jax.random.PRNGKey(0))
    T = 160
    rng = np.random.RandomState(0)
    ids = jnp.asarray(rng.randint(1, 128, (T,)), dtype=jnp.int32)
    cu = np.array([0, 70, 150], dtype=np.int32)
    seg = np.asarray(segment_ids_from_cu_seqlens(cu, T))
    seg[150:] = PADDING_SEGMENT
    seg = jnp.asarray(seg)
    pos = jnp.asarray(
        np.concatenate([np.arange(70), np.arange(80), np.zeros(10)]).astype(np.int32)
    )
    out_d = forward(params, ids, pos, seg, cfg_d)
    out_f = forward(params, ids, pos, seg, cfg_f)
    np.testing.assert_allclose(
        np.asarray(out_d[:150]), np.asarray(out_f[:150]), atol=3e-4, rtol=3e-4
    )


# ---------------------------------------------------------------------------
# The matmuls' operand dtype is the caller's; the softmax stays float32
# ---------------------------------------------------------------------------


def kernel_equations(dtype):
    """{kernel name: every equation of its traced body, loops and
    conditionals walked} of the three kernels at `dtype` inputs."""
    from jax._src import core

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            yield eqn
            for sub in core.jaxprs_in_params(eqn.params):
                yield from walk(sub)

    T, nH, nKV, hd = 256, 4, 2, 64
    q, k = jnp.zeros((T, nH, hd), dtype), jnp.zeros((T, nKV, hd), dtype)
    seg = jnp.zeros((T,), jnp.int32)
    loss = lambda q, k, v: flash_attention(  # noqa: E731
        q, k, v, seg, block_q=128, block_k=128, interpret=True).astype(jnp.float32).sum()
    traced = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, k, k)
    return {
        eqn.params["name"]: list(walk(eqn.params["jaxpr"]))
        for eqn in walk(traced.jaxpr) if eqn.primitive.name == "pallas_call"
    }


# (products of the kernel, equations of its body at float32 inputs: PR 42's
# for `%flash_fwd` and `%flash_dq`, whose casts of float32 blocks to float32
# traced to nothing; `%flash_dkv` had 162 and scores the transposed block now)
KERNEL_BODIES = {"flash_fwd": (2, 246), "flash_dq": (3, 221), "flash_dkv": (4, 161)}


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("kernel", list(KERNEL_BODIES))
def test_kernels_matmul_operands_follow_the_callers_dtype(kernel, dtype):
    """What says the mechanism engaged (it is static a call, so no counter
    can say more): in the kernel's traced body every `dot_general` takes
    operands of the dtype the kernel was called with and gives float32; no
    `[block, hd]` block is converted to float32 (the casts of q, k, v and dO
    that made a v5e multiply in several bf16 passes); what is rounded to the
    inputs' dtype besides the result blocks is `[Bq, Bk]`: `p` and `ds`, once
    for each product that consumes them. At float32 inputs nothing is
    converted at all and the body has the equations it had with the casts
    (`%flash_dkv`: one fewer, its block transposed). No product contracts
    dimension 0 of an operand: Mosaic would transpose the block for it."""
    products, f32_equations = KERNEL_BODIES[kernel]
    body = kernel_equations(jnp.dtype(dtype))[kernel]
    dots = [e for e in body if e.primitive.name == "dot_general"]
    assert len(dots) == products
    for e in dots:
        assert [str(v.aval.dtype) for v in e.invars] == [dtype, dtype], e
        assert e.outvars[0].aval.dtype == jnp.float32, e
        assert e.params["preferred_element_type"] == jnp.float32, e
        (lhs, rhs), batch = e.params["dimension_numbers"]
        assert lhs == (1,) and rhs in ((0,), (1,)) and batch == ((), ()), e
    casts = [(e.invars[0].aval, e.outvars[0].aval) for e in body
             if e.primitive.name == "convert_element_type" and e.invars[0].aval.shape]
    if dtype == "float32":
        assert not casts, casts
        assert len(body) == f32_equations
        return
    assert not [c for c in casts if c[1].dtype == jnp.float32], casts
    rounded = [src.shape for src, dst in casts if dst.dtype == jnp.bfloat16]
    # `p` for `p v`; `ds` for `ds k`; `p` and `ds` for `p^T dO` and `ds^T q`
    assert rounded.count((128, 128)) == {"flash_fwd": 1, "flash_dq": 1, "flash_dkv": 2}[kernel]
    # the output block (`o`, `dq`); `dk_h` and `dv_h` leave in float32
    assert rounded.count((128, 64)) == (0 if kernel == "flash_dkv" else 1), rounded
    assert len(rounded) == len(casts)


def dense_branch(q, k, v, seg):
    """The arithmetic of `models/qwen2.py:attention`'s dense branch, which
    every cell's `correct` passes through in the decode engine's `prefill`:
    scores from the operands as given into float32, a float32 softmax
    rounded to the inputs' dtype, the weighted sum in that dtype."""
    T, nH, hd = q.shape
    nKV = k.shape[1]
    qg = q.reshape(T, nKV, nH // nKV, hd)
    scores = jnp.einsum("tkgd,skd->kgts", qg, k).astype(jnp.float32) / np.sqrt(hd)
    scores = jnp.where(segment_causal_mask(seg)[None, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    probs = jnp.where((seg != PADDING_SEGMENT)[None, None, :, None], probs, 0)
    return jnp.einsum("kgts,skd->tkgd", probs, v).reshape(T, nH, hd)


def bf16_case(T, nH, nKV, hd, seed, mean_len, pad):
    """bf16 q, k, v (and a float32 weight for each output entry, so that a
    loss has a gradient of unit scale) over a packed row with a pad tail."""
    rng = np.random.RandomState(seed)
    q, k, v = (jnp.asarray(rng.randn(T, n, hd), jnp.bfloat16) for n in (nH, nKV, nKV))
    w = jnp.asarray(rng.randn(T, nH, hd), jnp.float32)
    return q, k, v, w, jnp.asarray(random_packing(T, seed, mean_len, pad))


def errors_against_float32(attend, q, k, v, w, seg):
    """Largest |difference| of `attend`'s output and of its dq, dk, dv under
    the loss sum(w * out) from `dense_reference`'s on the same values in
    float32, and the four float32 arrays' largest |value|."""
    real = np.asarray(seg) != PADDING_SEGMENT

    def both(fn, *x):
        loss = lambda q, k, v: jnp.sum(w * fn(q, k, v).astype(jnp.float32))  # noqa: E731
        return [fn(*x), *jax.grad(loss, argnums=(0, 1, 2))(*x)]

    f32 = [x.astype(jnp.float32) for x in (q, k, v)]
    want = [np.asarray(x)[real] for x in both(lambda q, k, v: dense_reference(q, k, v, seg), *f32)]
    got = [np.asarray(x, np.float32)[real] for x in both(attend, q, k, v)]
    assert all(g.dtype == np.float32 and np.isfinite(g).all() for g in got)
    return ([float(np.abs(g - x).max()) for g, x in zip(got, want)],
            [float(np.abs(x).max()) for x in want])


def test_bf16_inputs_are_as_close_to_float32_as_the_dense_path():
    """The 0.5B head shape (14/2 heads of 64: a GQA group of 7) cut to 1,024
    tokens, four packed sequences and a pad tail of 150, bf16 inputs, two
    blocks of 512 a side: the kernels' output, dq, dk and dv against the
    float32 reference on the same values, held to TWICE the error of
    `attention()`'s dense branch on the same bf16 inputs, and `lse` to the
    order of a float32 sum. Read on this CPU (interpret mode; largest
    |difference| of out, dq, dk, dv; the float32 arrays' largest |value| are
    3.25, 3.93, 7.22, 11.32): the kernels 0.0093, 0.0145, 0.0301, 0.0392, the
    dense branch 0.0145, 0.0141, 0.0243, 0.0392: the same rounding of `p`,
    and of `ds` where XLA's gradient rounds `dp` too. The kernels before this
    change (float32 products of the same bf16 blocks) read 0.0078, 0.0145,
    0.0213, 0.0392: the rounding of the results to bf16 is most of every
    figure, and all of dv's. `lse` is within 2e-5 of float32's."""
    T, nH, nKV, hd = 1024, 14, 2, 64
    q, k, v, w, seg = bf16_case(T, nH, nKV, hd, seed=3, mean_len=200, pad=150)
    assert len(np.unique(np.asarray(seg))) == 5 and int((np.asarray(seg) < 0).sum()) == 150
    flash, scale = errors_against_float32(
        lambda q, k, v: flash_attention(q, k, v, seg, interpret=True), q, k, v, w, seg)
    dense, _ = errors_against_float32(lambda q, k, v: dense_branch(q, k, v, seg), q, k, v, w, seg)
    for name, f, d, s in zip(("out", "dq", "dk", "dv"), flash, dense, scale):
        assert 0 < f <= 2 * d and d < 0.02 * s, (name, f, d, s)
    pos = jnp.arange(T, dtype=jnp.int32)
    _, lse = flash_attention_chunk(q, k, v, seg, seg, pos, pos, interpret=True)
    qf, kf = q.astype(jnp.float32), k.astype(jnp.float32)
    s = jnp.einsum("tkgd,skd->tkgs", qf.reshape(T, nKV, nH // nKV, hd), kf) / np.sqrt(hd)
    s = jnp.where(segment_causal_mask(seg)[:, None, None, :], s, -jnp.inf)
    real = np.asarray(seg) != PADDING_SEGMENT
    want = np.asarray(jax.nn.logsumexp(s, axis=-1).reshape(T, nH))[real]
    assert lse.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(lse)[real], want, atol=2e-5, rtol=0)
