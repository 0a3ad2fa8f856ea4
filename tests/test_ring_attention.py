"""Ring attention on the 8-virtual-device CPU mesh vs single-shard reference."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from areal_tpu.api.alloc_mode import ParallelStrategy
from areal_tpu.ops.flash_attention import block_liveness
from areal_tpu.ops.ring_attention import _shard_positions, ring_flash_attention
from areal_tpu.parallel import mesh as mesh_lib
from areal_tpu.utils.data import zigzag_indices
from tests.test_flash_attention import (
    PACKINGS,
    _flash_both_ways,
    bf16_case,
    brute_force_liveness,
    check_work_list,
    dense_branch,
    dense_reference,
    errors_against_float32,
    make_inputs,
    random_packing,
)


@pytest.fixture()
def sp_mesh(cpu_devices):
    mesh = mesh_lib.build_mesh(
        ParallelStrategy(data_parallel_size=2, context_parallel_size=2,
                         tensor_parallel_size=2)
    )
    mesh_lib.set_current_mesh(mesh)
    yield mesh
    mesh_lib.set_current_mesh(None)


@pytest.mark.slow
def test_ring_matches_dense(sp_mesh):
    # ring over dp*sp = 4 shards, tp=2 sharding the 4 query heads.
    T, nH, nKV, hd = 512, 4, 2, 32
    q, k, v, seg = make_inputs(T, nH, nKV, hd, pad=41, n_seqs=4)
    out = ring_flash_attention(q, k, v, seg, mesh=sp_mesh, interpret=True)
    ref = dense_reference(q, k, v, seg)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5
    )


@pytest.mark.slow
def test_ring_gradients_match(sp_mesh):
    T, nH, nKV, hd = 512, 4, 2, 32
    q, k, v, seg = make_inputs(T, nH, nKV, hd, pad=17, seed=5, n_seqs=3)

    def loss_ring(q, k, v):
        o = ring_flash_attention(q, k, v, seg, mesh=sp_mesh, interpret=True)
        return jnp.sum(jnp.sin(o))

    def loss_dense(q, k, v):
        return jnp.sum(jnp.sin(dense_reference(q, k, v, seg)))

    gr = jax.grad(loss_ring, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(gr, gd, "qkv"):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=3e-4, rtol=3e-4, err_msg=name
        )


@pytest.mark.parametrize("zigzag", [False, True], ids=["contiguous", "zigzag"])
@pytest.mark.parametrize("n,mean_len", [(4, 473), (4, 5000), (2, 90), (8, 250)])
def test_liveness_of_every_ring_step(n, mean_len, zigzag):
    """Each (query shard, visiting kv shard) of a packed row, with the ring
    body's own position maps: a block pair is live wherever the mask over it
    has a true entry; on the contiguous layout exactly there; and a step
    that brings a later shard, or one with no sequence in common, is dead."""
    Tl, block = 1024, 128
    T = n * Tl
    seg_row = random_packing(T, seed=n, mean_len=mean_len, pad=T // 11)
    if zigzag:
        seg_row = seg_row[zigzag_indices(T, n)]
    shard = lambda x, i: x[i * Tl:(i + 1) * Tl]  # noqa: E731
    positions = [np.asarray(_shard_positions(jnp.int32(i), Tl, n, zigzag))
                 for i in range(n)]
    dead_steps = walked = n_live = 0
    for qi in range(n):
        for ki in range(n):
            args = (shard(seg_row, qi), shard(seg_row, ki), positions[qi],
                    positions[ki], block, block)
            live = block_liveness(*args)
            # the step's work list: every live pair in a walk, an earlier,
            # the same and a later shard alike
            brute, (lo_q, hi_q, lo_k, hi_k) = check_work_list(*args)
            assert not (brute & ~live).any(), (qi, ki)
            if not zigzag:
                np.testing.assert_array_equal(live, brute, err_msg=f"{qi},{ki}")
                assert (hi_q - lo_q).sum() == (hi_k - lo_k).sum() == brute.sum()
                if ki > qi:
                    assert not live.any() and not hi_q.any() and not hi_k.any()
            dead_steps += not live.any()
            walked += int((hi_q - lo_q).sum())
            n_live += int(live.sum())
    # zig-zag: a shard's two chunks can leave a hole in a run, which is walked
    assert walked >= n_live and (zigzag or walked == n_live)
    assert walked < 0.5 * n * n * (Tl // block) ** 2
    # the whole ring visits far fewer blocks than it holds
    assert dead_steps >= (n * (n - 1) // 2 if not zigzag else 0)
    if mean_len < Tl and n > 2 and not zigzag:
        assert dead_steps > n * (n - 1) // 2  # also steps two shards back


@pytest.mark.parametrize("zigzag", [False, True], ids=["contiguous", "zigzag"])
@pytest.mark.parametrize("src", [0, 1, 2], ids=["earlier", "same", "later"])
def test_ring_step_walk_equals_all_live_to_the_bit(src, zigzag):
    """One ring step of query shard 1 of 3 (256 tokens, two blocks) against
    the shard before it, itself and the one after, with the ring body's own
    position maps: outputs, lse and the three gradients of the kernels on
    their work list equal those of a walk over every block pair."""
    n, Tl, nH, nKV, hd, block = 3, 256, 4, 2, 32, 128
    T = n * Tl
    q, k, v, _ = make_inputs(Tl, nH, nKV, hd, seed=31 + src)
    seg_row = random_packing(T, seed=5, mean_len=150, pad=60)
    if zigzag:
        seg_row = seg_row[zigzag_indices(T, n)]
    shard = lambda i: jnp.asarray(seg_row[i * Tl:(i + 1) * Tl])  # noqa: E731
    qpos, kpos = (_shard_positions(jnp.int32(i), Tl, n, zigzag) for i in (1, src))
    walk, forced = _flash_both_ways(q, k, v, shard(1), shard(src), qpos, kpos, block)
    for a, b, name in zip(walk, forced, ("out", "lse", "dq", "dk", "dv")):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b), err_msg=name)
    if src == 2 and not zigzag:
        assert not np.asarray(walk[0]).any()


@pytest.mark.parametrize("packing", list(PACKINGS))
def test_ring_matches_dense_on_packings(sp_mesh, packing):
    """The ring over 4 shards of 128 tokens, where most ring steps of
    `many_short` and every step of `all_pad` are skipped whole."""
    T, nH, nKV, hd = 512, 4, 2, 32
    q, k, v, seg = make_inputs(T, nH, nKV, hd, seed=13, **PACKINGS[packing](T))
    out = ring_flash_attention(q, k, v, seg, mesh=sp_mesh, interpret=True)
    ref = dense_reference(q, k, v, seg)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5
    )


def test_ring_fallback_no_mesh():
    # No mesh registered: silently uses the single-shard kernel.
    T, nH, nKV, hd = 256, 2, 2, 32
    q, k, v, seg = make_inputs(T, nH, nKV, hd, pad=0, seed=7, n_seqs=2)
    out = ring_flash_attention(q, k, v, seg, mesh=None, interpret=True)
    ref = dense_reference(q, k, v, seg)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5
    )


def test_ring_under_jit_with_sharded_inputs(sp_mesh):
    # The real call pattern: inside jit, token axis sharded over (dp, sp).
    T, nH, nKV, hd = 512, 4, 2, 32
    q, k, v, seg = make_inputs(T, nH, nKV, hd, pad=9, seed=9, n_seqs=4)
    tok_sharding = mesh_lib.packed_sharding(sp_mesh)
    q = jax.device_put(q, jax.sharding.NamedSharding(
        sp_mesh, jax.sharding.PartitionSpec(("dp", "sp"), None, None)))
    seg_s = jax.device_put(seg, tok_sharding)

    @jax.jit
    def f(q, k, v, seg):
        return ring_flash_attention(q, k, v, seg, mesh=sp_mesh, interpret=True)

    out = f(q, k, v, seg_s)
    ref = dense_reference(q, k, v, seg)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5
    )


def test_ring_bf16_inputs_are_as_close_to_float32_as_the_dense_path(cpu_devices):
    """Two ring steps (dp=2: each shard of 512 tokens scores itself, then
    the other's keys) at bf16 inputs, the 1.5B head shape cut to 4/2 heads
    of 128: the kernels multiply in bf16 a step, the ring merges their
    float32 `lse` and bf16 `o_s` outside and differentiates through `lse`.
    Output, dq, dk and dv against the float32 reference, held to twice the
    error of `attention()`'s dense branch on the same bf16 inputs. Read on
    this CPU (largest |difference| of out, dq, dk, dv; the float32 arrays'
    largest |value| 3.05, 2.74, 4.79, 6.61): the ring 0.0082, 0.0105, 0.0161,
    0.0276, the dense branch 0.0152, 0.0214, 0.0228, 0.0276."""
    mesh = mesh_lib.build_mesh(ParallelStrategy(data_parallel_size=2), cpu_devices[:2])
    T, nH, nKV, hd = 1024, 4, 2, 128
    q, k, v, w, seg = bf16_case(T, nH, nKV, hd, seed=7, mean_len=300, pad=90)
    assert len(np.unique(np.asarray(seg[:512]))) > 1 and seg[511] == seg[512] >= 0
    ring, scale = errors_against_float32(
        lambda q, k, v: ring_flash_attention(q, k, v, seg, mesh=mesh, interpret=True),
        q, k, v, w, seg)
    dense, _ = errors_against_float32(lambda q, k, v: dense_branch(q, k, v, seg), q, k, v, w, seg)
    for name, r, d, s in zip(("out", "dq", "dk", "dv"), ring, dense, scale):
        assert 0 < r <= 2 * d and d < 0.02 * s, (name, r, d, s)
