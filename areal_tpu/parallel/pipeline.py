"""SPMD pipeline parallelism over the "pp" mesh axis — stage-stacked GSPMD.

Parity target: the reference's native pipeline engine —
realhf/impl/model/parallelism/pipeline_parallel/static_schedule.py:159
(instruction schedules) + pipe_runner.py:778 (executors) and Megatron's
forward_backward_func (areal/engine/megatron_engine.py:846). The TPU
re-design replaces instruction lists + p2p send/recv with a single jitted
program over *stage-stacked* arrays:

- the stacked layer parameters [L, ...] are reshaped to [pp, L/pp, ...] and
  sharded over the "pp" mesh axis on dim 0, so each stage holds L/pp layers
  (the memory scaling PP exists for),
- pipeline state is [pp, T, H]: row s is the activation stage s works on.
  One `jax.vmap(stage_fn)` over the leading dim runs every stage in
  parallel — GSPMD partitions the vmapped program over "pp" (and keeps
  handling dp/sp/tp automatically inside each stage),
- activations hop stage→stage with `jnp.roll(y, 1, axis=0)` — a static
  rotation XLA lowers to the same neighbour collective-permute a manual
  ppermute would emit, with dp/sp/tp inside each stage left to the
  partitioner instead of a partial-manual `shard_map`.

Three schedules:

- `pipeline_trunk` — GPipe: all M forwards stream through (M + pp - 1
  steps), outputs collect on the last stage, autodiff runs straight back
  through the scan. Simple and the numerics reference, but the backward
  scan holds residuals for every step, so live activation memory grows
  with M.
- `pipeline_1f1b_grads` — 1F1B: one interleaved loop of M + 2·pp - 2
  rounds where every round runs one forward AND one backward per stage
  (warmup/cooldown rounds masked). The backward is explicit — a per-stage
  `jax.vjp` that recomputes the stage forward from a stashed input — so
  nothing autodiffs through the round scan and the live stash is capped at
  2·pp - 1 stage inputs per stage regardless of M. Microbatch m's loss
  gradient is seeded in the same round its forward reaches the last stage
  (head + loss + vjp run inline on that stage's output), which is what
  lets the stash recycle. Larger M therefore fits in fixed HBM and the
  bubble fraction (pp-1)/(M+pp-1) shrinks at fixed memory — the point of
  1F1B (GPipe stays available via `pipeline_schedule: gpipe`).

- `pipeline_1f1b_interleaved_grads` — interleaved 1F1B (Megatron's
  virtual-pipeline schedule, arXiv:2104.04473): each pp rank holds v
  NON-contiguous chunks of L/(pp·v) layers (chunk c = vc·pp + s lives on
  rank s), so a microbatch hops rank 0→1→...→pp-1 v times. The warmup /
  cooldown bubble shrinks ~1/v because a rank starts chunk vc=0 of the
  next microbatch group while deeper chunks are still in flight, at the
  cost of v× more (but v× smaller) stage hops. The stash stays bounded:
  per-chunk capacities are computed statically from the timetable and sum
  to at most v·(2·pp - 1) live microbatch activations per rank.

Schedule timetable (round r, stage s, microbatch m, P = pp):
    F(m, s) at r = m + s              (forward wavefront, GPipe-like)
    B(m, s) at r = m + 2P - 2 - s     (backward wavefront, mirrored)
so F(m, P-1) and B(m, P-1) land in the SAME round (loss seeds backward
immediately) and stage s holds at most 2(P-1-s)+1 <= 2P-1 stashed inputs.

Interleaved timetable (v chunks per rank, chunk c = vc·P + s, microbatch
m = g·P + u with u = m % P, Δ = v·P - 1):
    F(m, c) at r = g·v·P + vc·P + u + s
    B(m, c) at r = Δ + g·v·P + (v-1-vc)·P + u + (P-1-s)
Both hops stay the uniform neighbour rotation (roll ±1): finishing chunk c
on rank P-1 wraps to chunk c+1 on rank 0 exactly one round later. At v=1
this reduces term-for-term to the plain 1F1B table above. F(m, C-1) and
B(m, C-1) land in the same round, so the loss seeds the backward
immediately and the stash recycles. A round is decoded per rank from
n = r - s (forward) and n = r - Δ - (P-1-s) (backward) as mixed-radix
(g, vc, u) digits — at most one forward and one backward chunk per rank
per round, like plain 1F1B.

The interleaved schedule expects the engine to store the stacked layer
parameters in CHUNK-MAJOR order (see `interleave_layer_indices`): storage
slot p = s·(v·Lc) + vc·Lc + i holds model layer (vc·P + s)·Lc + i, so the
[L, ...] → [P, v, Lc, ...] reshape is a pure metadata operation and the
pp-sharded leading dim stays contiguous — no layer ever moves between
ranks at dispatch time.

Attention inside a stage must not itself shard tokens over (dp, sp) with a
kernel that can't be partitioned (ring attention's shard_map cannot nest
under the stage vmap); the model resolves attention to a pp-compatible impl
while tracing the stage body (see qwen2.forward_pipelined).
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from areal_tpu.parallel import mesh as mesh_lib

# Engine-facing names for the trunk schedules (api/cli_args.py
# JaxEngineConfig.pipeline_schedule).
PIPELINE_SCHEDULES = ("gpipe", "1f1b", "1f1b_interleaved")


def interleave_layer_indices(L: int, pp: int, v: int) -> list[int]:
    """Model-layer index stored at each engine slot under the interleaved
    layout: slot p = s·(v·Lc) + vc·Lc + i holds model layer (vc·pp+s)·Lc + i
    (Lc = L/(pp·v)), so reshaping the engine stack [L] → [pp, v, Lc] lands
    chunk c = vc·pp + s at [s, vc] with the pp-sharded dim contiguous.

    At v=1 this is the identity — plain 1F1B's contiguous split."""
    assert L % (pp * v) == 0, (L, pp, v)
    Lc = L // (pp * v)
    return [
        (vc * pp + s) * Lc + i
        for s in range(pp)
        for vc in range(v)
        for i in range(Lc)
    ]


def inverse_interleave_layer_indices(L: int, pp: int, v: int) -> list[int]:
    """Engine slot holding each model layer (inverse permutation — used to
    restore model order on export/save)."""
    perm = interleave_layer_indices(L, pp, v)
    inv = [0] * L
    for p, model_l in enumerate(perm):
        inv[model_l] = p
    return inv


def _chunk_stack(layers: Any, pp: int, v: int) -> Any:
    """[L, ...] chunk-major layer pytree → [pp, v, L/(pp·v), ...]."""

    def split(leaf):
        L = leaf.shape[0]
        assert L % (pp * v) == 0, (L, pp, v)
        return leaf.reshape(pp, v, L // (pp * v), *leaf.shape[1:])

    return jax.tree.map(split, layers)


def _pick_chunk(tree_rank: Any, vc: jax.Array) -> Any:
    """Select chunk vc out of a rank-local [v, Lc, ...] pytree (vmapped over
    the pp dim by callers, so vc may differ per rank)."""
    return jax.tree.map(
        lambda a: jax.lax.dynamic_index_in_dim(a, vc, 0, keepdims=False),
        tree_rank,
    )


def _interleaved_stash_sizes(pp: int, v: int, M: int) -> list[int]:
    """Per-virtual-chunk stash capacity: the max number of microbatches
    simultaneously live (forward stashed, backward not yet consumed —
    window [r_F, r_B] inclusive) for chunk position vc, maxed over ranks.

    The live set at any round is a consecutive microbatch interval (r_F and
    r_B are both strictly increasing in m), so slot = m % size is
    collision-free. Sizes sum to <= v·(2·pp - 1)."""
    delta = v * pp - 1
    sizes = []
    for vc in range(v):
        best = 1
        for s in range(pp):
            rf, rb = [], []
            for m in range(M):
                g, u = divmod(m, pp)
                rf.append(g * v * pp + vc * pp + u + s)
                rb.append(
                    delta + g * v * pp + (v - 1 - vc) * pp + u + (pp - 1 - s)
                )
            lo = 0
            for m in range(M):
                while rb[lo] < rf[m]:
                    lo += 1
                best = max(best, m - lo + 1)
        sizes.append(best)
    return sizes


def _stage_stack(layers: Any, pp: int) -> Any:
    """[L, ...] stacked layer pytree → [pp, L/pp, ...].

    The reshape splits the pp-sharded leading dim on its sharded factor, so
    GSPMD keeps each stage's L/pp layers on its own shard — no data moves.
    """

    def split(leaf):
        L = leaf.shape[0]
        assert L % pp == 0, (L, pp)
        return leaf.reshape(pp, L // pp, *leaf.shape[1:])

    return jax.tree.map(split, layers)


def _index_mb(tree: Any, m: jax.Array) -> Any:
    """Slice the m-th microbatch out of a pytree of [M, ...] arrays."""
    return jax.tree.map(
        lambda a: jax.lax.dynamic_index_in_dim(a, m, 0, keepdims=False), tree
    )


def _gather_per_stage(tree: Any, m_per_stage: jax.Array) -> Any:
    """Per-stage microbatch selection: tree of [M, ...] → tree of [pp, ...]
    where row s is the m_per_stage[s]-th microbatch."""
    return jax.vmap(lambda m: _index_mb(tree, m))(m_per_stage)


def _masked_row_write(
    buf: jax.Array, val: jax.Array, idx: jax.Array, valid: jax.Array
) -> jax.Array:
    """buf[idx] = val where valid, else keep — the write-or-keep idiom that
    makes clipped (out-of-schedule) indices harmless."""
    prev = jax.lax.dynamic_index_in_dim(buf, idx, 0, keepdims=False)
    return jax.lax.dynamic_update_index_in_dim(
        buf, jnp.where(valid, val, prev).astype(buf.dtype), idx, 0
    )


def _pin_stagewise(
    mesh: Mesh, x: jax.Array, token_dim: int = 1
) -> jax.Array:
    """Pin a stage-stacked pipeline carry: dim 0 over "pp", `token_dim`
    over (dp, sp); remaining dims pinned replicated."""
    axes: list[str | None] = [None] * x.ndim
    axes[0] = "stages"
    axes[token_dim] = "tokens"
    return mesh_lib.constrain(x, *axes, mesh=mesh)


def pipeline_trunk(
    mesh: Mesh,
    stage_fn: Callable[[Any, jax.Array, Any], tuple[jax.Array, jax.Array]],
    layers: Any,
    xs: jax.Array,
    aux_inputs: Any,
    *,
    virtual: int = 1,
) -> tuple[jax.Array, jax.Array]:
    """GPipe schedule: run `stage_fn` over pp stages for M microbatches.

    Args:
      mesh: the engine mesh; must contain a "pp" axis of size >= 2.
      stage_fn: (layers_local, x, aux) -> (y, scalar_aux_loss); sees the
        stage-local [L/pp, ...] layer stack and one microbatch activation.
      layers: stacked [L, ...] pytree (sharded over pp on dim 0 by the
        engine's param shardings). With virtual > 1 the stack must be in
        the chunk-major interleaved layout (`interleave_layer_indices`).
      xs: [M, T, H] stacked microbatch activations.
      aux_inputs: pytree of [M, ...] per-microbatch side inputs (positions,
        segment ids, ...) indexed — not circulated — per step.
      virtual: virtual stages per rank; > 1 runs the interleaved forward
        wavefront (each rank cycles through its v chunks).

    Returns (ys [M, T, H], total_aux_loss). Autodiff runs straight through
    (the backward pipeline falls out of the scan's reverse), which is the
    reference path `pipeline_1f1b_grads` is validated against.
    """
    pp = mesh.shape[mesh_lib.AXIS_PP]
    M = xs.shape[0]
    if virtual > 1:
        return _trunk_interleaved(
            mesh, stage_fn, layers, xs, aux_inputs, virtual=virtual
        )
    steps = M + pp - 1
    stages = jnp.arange(pp)
    layers_s = _stage_stack(layers, pp)

    def step(carry, t):
        state, outbuf, aux_sum = carry
        # stage s works on microbatch m = t - s (valid when 0 <= m < M)
        mf = t - stages
        f_valid = (mf >= 0) & (mf < M)
        mf_c = jnp.clip(mf, 0, M - 1)
        fresh = jax.lax.dynamic_index_in_dim(
            xs, jnp.clip(t, 0, M - 1), 0, keepdims=False
        )
        x_in = jnp.where((stages == 0)[:, None, None], fresh[None], state)
        y, aux = jax.vmap(stage_fn)(
            layers_s, x_in, _gather_per_stage(aux_inputs, mf_c)
        )
        aux_sum = aux_sum + jnp.sum(jnp.where(f_valid, aux, 0.0))
        # the last stage finishes microbatch t - (pp - 1)
        out_m = jnp.clip(t - (pp - 1), 0, M - 1)
        outbuf = _masked_row_write(outbuf, y[pp - 1], out_m, t >= pp - 1)
        state = _pin_stagewise(mesh, jnp.roll(y, 1, axis=0))
        return (state, outbuf, aux_sum), None

    init = (
        _pin_stagewise(mesh, jnp.zeros((pp,) + xs.shape[1:], xs.dtype)),
        jnp.zeros_like(xs),
        jnp.float32(0.0),
    )
    (_, outbuf, aux_sum), _ = jax.lax.scan(step, init, jnp.arange(steps))
    return outbuf, aux_sum


def _fwd_decode(r, stages, pp, v, M):
    """Mixed-radix forward decode: which (chunk, microbatch) each rank runs
    at round r. n = r - s = g·v·pp + vc·pp + u with m = g·pp + u."""
    n = r - stages
    u = n % pp
    vc = (n // pp) % v
    m = (n // (pp * v)) * pp + u
    valid = (n >= 0) & (m < M)
    return vc, m, jnp.clip(m, 0, M - 1), valid


def _trunk_interleaved(mesh, stage_fn, layers, xs, aux_inputs, *, virtual):
    """Forward-only interleaved wavefront (autodiff-through, GPipe-style
    memory): rank s runs chunk vc = (n//pp) % v of microbatch m at round
    r = n + s, n = g·v·pp + vc·pp + u."""
    pp = mesh.shape[mesh_lib.AXIS_PP]
    v = int(virtual)
    M = xs.shape[0]
    steps = ((M - 1) // pp) * v * pp + (v - 1) * pp + (M - 1) % pp + pp
    stages = jnp.arange(pp)
    layers_c = _chunk_stack(layers, pp, v)

    def step(carry, t):
        state, outbuf, aux_sum = carry
        vcf, _, mf_c, f_valid = _fwd_decode(t, stages, pp, v, M)
        fresh = jax.lax.dynamic_index_in_dim(xs, mf_c[0], 0, keepdims=False)
        entry = (stages == 0) & (vcf == 0)
        x_in = jnp.where(entry[:, None, None], fresh[None], state)
        y, aux = jax.vmap(stage_fn)(
            jax.vmap(_pick_chunk)(layers_c, vcf),
            x_in,
            _gather_per_stage(aux_inputs, mf_c),
        )
        aux_sum = aux_sum + jnp.sum(jnp.where(f_valid, aux, 0.0))
        # the last rank finishing its LAST chunk completes microbatch m
        out_valid = f_valid[pp - 1] & (vcf[pp - 1] == v - 1)
        outbuf = _masked_row_write(outbuf, y[pp - 1], mf_c[pp - 1], out_valid)
        state = _pin_stagewise(mesh, jnp.roll(y, 1, axis=0))
        return (state, outbuf, aux_sum), None

    init = (
        _pin_stagewise(mesh, jnp.zeros((pp,) + xs.shape[1:], xs.dtype)),
        jnp.zeros_like(xs),
        jnp.float32(0.0),
    )
    (_, outbuf, aux_sum), _ = jax.lax.scan(step, init, jnp.arange(steps))
    return outbuf, aux_sum


def pipeline_1f1b_grads(
    mesh: Mesh,
    stage_fn: Callable[[Any, jax.Array, Any], tuple[jax.Array, jax.Array]],
    head_loss_fn: Callable[[Any, jax.Array, Any], tuple[jax.Array, Any]],
    layers: Any,
    head_params: Any,
    xs: jax.Array,
    aux_inputs: Any,
    mb_data: Any,
    weights: jax.Array,
    *,
    aux_coef: float = 0.0,
) -> tuple[jax.Array, Any, jax.Array, Any, Any, jax.Array]:
    """1F1B schedule with the backward interleaved into the forward loop.

    This does NOT return a differentiable value — it returns the gradients
    themselves, computed by explicit per-stage `jax.vjp` (recompute from a
    stashed stage input, so the stage body is effectively rematerialised).
    Callers (models/qwen2.forward_pipelined_grads) compose these trunk
    gradients with the embedding / lora-combine / head-selection vjps.

    Args:
      stage_fn / layers / xs / aux_inputs: as `pipeline_trunk`.
      head_loss_fn: (head_params, y [T, H], mb_m) -> (scalar_loss, stats)
        — the final-norm + lm-head + caller loss for ONE microbatch, run on
        the last stage's output in the same round it is produced.
      head_params: pytree the head reads (final norm / lm head / tied
        embeddings ...), replicated over pp.
      mb_data: pytree of [M, ...] per-microbatch loss inputs.
      weights: [M] float32 loss weights; gradients equal
        d(sum_m weights[m]·loss_m + aux_coef·aux_total)/dθ.
      aux_coef: cotangent seeded into each stage's scalar aux output (MoE
        router load-balance coefficient; 0 when unused).

    Returns (losses [M], stats pytree of [M, ...], aux_total,
    g_layers [L, ...], g_head, g_xs [M, T, H]).
    """
    pp = mesh.shape[mesh_lib.AXIS_PP]
    M = xs.shape[0]
    S = 2 * pp - 1  # stash slots: max in-flight microbatches on stage 0
    rounds = M + 2 * pp - 2
    stages = jnp.arange(pp)
    layers_s = _stage_stack(layers, pp)

    # Probe the stats pytree structure so the [M]-buffers can be carried
    # through the scan (eval_shape only — nothing runs here).
    _, stats_shape = jax.eval_shape(
        head_loss_fn, head_params, jax.ShapeDtypeStruct(xs.shape[1:], xs.dtype),
        jax.eval_shape(lambda t: _index_mb(t, 0), mb_data),
    )

    def round_fn(carry, r):
        (fwd_in, bwd_in, stash, g_layers, g_head, dxs, losses, stats,
         aux_sum) = carry

        # ---- one forward per stage: F(m, s) at r = m + s ----------------
        mf = r - stages
        f_valid = (mf >= 0) & (mf < M)
        mf_c = jnp.clip(mf, 0, M - 1)
        fresh = jax.lax.dynamic_index_in_dim(
            xs, jnp.clip(r, 0, M - 1), 0, keepdims=False
        )
        x_in = jnp.where((stages == 0)[:, None, None], fresh[None], fwd_in)
        y, aux_f = jax.vmap(stage_fn)(
            layers_s, x_in, _gather_per_stage(aux_inputs, mf_c)
        )
        aux_sum = aux_sum + jnp.sum(jnp.where(f_valid, aux_f, 0.0))
        # Stash the stage INPUT (not output): the explicit backward re-runs
        # the stage from it. Invalid rounds must keep, not clobber — the
        # clipped slot may still be live.
        stash = jax.vmap(_masked_row_write)(stash, x_in, mf_c % S, f_valid)

        # ---- head + loss + seed on the last stage's fresh output --------
        m_last = r - (pp - 1)
        l_valid = (m_last >= 0) & (m_last < M)
        m_last_c = jnp.clip(m_last, 0, M - 1)
        mb_m = _index_mb(mb_data, m_last_c)
        w_m = jnp.where(
            l_valid,
            jax.lax.dynamic_index_in_dim(weights, m_last_c, 0, keepdims=False),
            0.0,
        )
        loss_m, head_vjp, stats_m = jax.vjp(
            lambda hp, y_: head_loss_fn(hp, y_, mb_m),
            head_params,
            y[pp - 1],
            has_aux=True,
        )
        # vjp is linear in the cotangent: a zero weight on out-of-schedule
        # rounds zeroes both the head grads and the backward seed.
        g_head_m, dy = head_vjp(jnp.zeros_like(loss_m) + w_m)
        g_head = jax.tree.map(jnp.add, g_head, g_head_m)
        losses = _masked_row_write(losses, loss_m, m_last_c, l_valid)
        stats = jax.tree.map(
            lambda b, v: _masked_row_write(b, v, m_last_c, l_valid),
            stats,
            stats_m,
        )

        # ---- one backward per stage: B(m, s) at r = m + 2pp - 2 - s -----
        mb_idx = r - (2 * pp - 2 - stages)
        b_valid = (mb_idx >= 0) & (mb_idx < M)
        mb_c = jnp.clip(mb_idx, 0, M - 1)
        g_in = jnp.where((stages == pp - 1)[:, None, None], dy[None], bwd_in)
        g_in = jnp.where(b_valid[:, None, None], g_in, 0.0)
        g_aux = jnp.where(b_valid, jnp.float32(aux_coef), 0.0)
        x_saved = jax.vmap(
            lambda st, slot: jax.lax.dynamic_index_in_dim(
                st, slot, 0, keepdims=False
            )
        )(stash, mb_c % S)
        aux_b = _gather_per_stage(aux_inputs, mb_c)

        def stage_bwd(layers_local, x, aux_t, gy, ga):
            _, vjp = jax.vjp(
                lambda L_, x_: stage_fn(L_, x_, aux_t), layers_local, x
            )
            return vjp((gy.astype(x.dtype), ga))

        g_layers_m, gx = jax.vmap(stage_bwd)(
            layers_s, x_saved, aux_b, g_in, g_aux
        )
        g_layers = jax.tree.map(jnp.add, g_layers, g_layers_m)
        # stage 0's input gradient feeds the embedding backward
        dxs = _masked_row_write(
            dxs, gx[0], jnp.clip(r - (2 * pp - 2), 0, M - 1), b_valid[0]
        )

        fwd_in = _pin_stagewise(mesh, jnp.roll(y, 1, axis=0))
        bwd_in = _pin_stagewise(mesh, jnp.roll(gx, -1, axis=0))
        return (
            (fwd_in, bwd_in, stash, g_layers, g_head, dxs, losses, stats,
             aux_sum),
            None,
        )

    act_shape = (pp,) + xs.shape[1:]
    init = (
        _pin_stagewise(mesh, jnp.zeros(act_shape, xs.dtype)),
        _pin_stagewise(mesh, jnp.zeros(act_shape, xs.dtype)),
        _pin_stagewise(
            mesh, jnp.zeros((pp, S) + xs.shape[1:], xs.dtype), token_dim=2
        ),
        jax.tree.map(jnp.zeros_like, layers_s),
        jax.tree.map(jnp.zeros_like, head_params),
        jnp.zeros_like(xs),
        jnp.zeros((M,), jnp.float32),
        jax.tree.map(
            lambda s: jnp.zeros((M,) + s.shape, s.dtype), stats_shape
        ),
        jnp.float32(0.0),
    )
    (_, _, _, g_layers, g_head, dxs, losses, stats, aux_sum), _ = jax.lax.scan(
        round_fn, init, jnp.arange(rounds)
    )
    g_layers = jax.tree.map(
        lambda g: g.reshape((g.shape[0] * g.shape[1],) + g.shape[2:]), g_layers
    )
    return losses, stats, aux_sum, g_layers, g_head, dxs


def pipeline_1f1b_interleaved_grads(
    mesh: Mesh,
    stage_fn: Callable[[Any, jax.Array, Any], tuple[jax.Array, jax.Array]],
    head_loss_fn: Callable[[Any, jax.Array, Any], tuple[jax.Array, Any]],
    layers: Any,
    head_params: Any,
    xs: jax.Array,
    aux_inputs: Any,
    mb_data: Any,
    weights: jax.Array,
    *,
    virtual: int,
    aux_coef: float = 0.0,
) -> tuple[jax.Array, Any, jax.Array, Any, Any, jax.Array]:
    """Interleaved-virtual-stage 1F1B (see module docstring timetable).

    Same contract as `pipeline_1f1b_grads` — explicit per-chunk `jax.vjp`
    backwards, gradients returned, nothing autodiffs through the round scan
    — but each rank cycles through its v non-contiguous chunks, shrinking
    the warmup/cooldown bubble ~1/v. `layers` must be in the chunk-major
    interleaved storage layout (`interleave_layer_indices`); the returned
    g_layers is in that same layout.

    At v=1 the timetable, stash occupancy and accumulation order all reduce
    exactly to `pipeline_1f1b_grads` — the bitwise oracle for this path
    (tests/test_pipeline_interleaved.py).
    """
    pp = mesh.shape[mesh_lib.AXIS_PP]
    v = int(virtual)
    M = xs.shape[0]
    delta = v * pp - 1
    sizes = _interleaved_stash_sizes(pp, v, M)
    offs = [0]
    for sz in sizes[:-1]:
        offs.append(offs[-1] + sz)
    S_total = sum(sizes)
    off_arr = jnp.asarray(offs, jnp.int32)
    size_arr = jnp.asarray(sizes, jnp.int32)
    # last backward: B(M-1, chunk 0) on rank 0
    rounds = (
        delta
        + ((M - 1) // pp) * v * pp
        + (v - 1) * pp
        + (M - 1) % pp
        + pp
    )
    stages = jnp.arange(pp)
    layers_c = _chunk_stack(layers, pp, v)

    _, stats_shape = jax.eval_shape(
        head_loss_fn, head_params, jax.ShapeDtypeStruct(xs.shape[1:], xs.dtype),
        jax.eval_shape(lambda t: _index_mb(t, 0), mb_data),
    )

    def round_fn(carry, r):
        (fwd_in, bwd_in, stash, g_layers, g_head, dxs, losses, stats,
         aux_sum) = carry

        # ---- one forward chunk per rank -------------------------------
        vcf, _, mf_c, f_valid = _fwd_decode(r, stages, pp, v, M)
        fresh = jax.lax.dynamic_index_in_dim(xs, mf_c[0], 0, keepdims=False)
        entry = (stages == 0) & (vcf == 0)
        x_in = jnp.where(entry[:, None, None], fresh[None], fwd_in)
        y, aux_f = jax.vmap(stage_fn)(
            jax.vmap(_pick_chunk)(layers_c, vcf),
            x_in,
            _gather_per_stage(aux_inputs, mf_c),
        )
        aux_sum = aux_sum + jnp.sum(jnp.where(f_valid, aux_f, 0.0))
        slot_f = jnp.take(off_arr, vcf) + mf_c % jnp.take(size_arr, vcf)
        stash = jax.vmap(_masked_row_write)(stash, x_in, slot_f, f_valid)

        # ---- head + loss + seed when the LAST chunk's forward lands ----
        l_valid = f_valid[pp - 1] & (vcf[pp - 1] == v - 1)
        m_last_c = mf_c[pp - 1]
        mb_m = _index_mb(mb_data, m_last_c)
        w_m = jnp.where(
            l_valid,
            jax.lax.dynamic_index_in_dim(weights, m_last_c, 0, keepdims=False),
            0.0,
        )
        loss_m, head_vjp, stats_m = jax.vjp(
            lambda hp, y_: head_loss_fn(hp, y_, mb_m),
            head_params,
            y[pp - 1],
            has_aux=True,
        )
        g_head_m, dy = head_vjp(jnp.zeros_like(loss_m) + w_m)
        g_head = jax.tree.map(jnp.add, g_head, g_head_m)
        losses = _masked_row_write(losses, loss_m, m_last_c, l_valid)
        stats = jax.tree.map(
            lambda b, val: _masked_row_write(b, val, m_last_c, l_valid),
            stats,
            stats_m,
        )

        # ---- one backward chunk per rank ------------------------------
        nb = r - delta - (pp - 1 - stages)
        ub = nb % pp
        vcb = v - 1 - ((nb // pp) % v)
        mb_idx = (nb // (pp * v)) * pp + ub
        b_valid = (nb >= 0) & (mb_idx < M)
        mb_c = jnp.clip(mb_idx, 0, M - 1)
        # B(m, C-1) runs the same round as F(m, C-1): seed from this
        # round's head vjp; every other chunk receives the rolled gx.
        seed = (stages == pp - 1) & (vcb == v - 1)
        g_in = jnp.where(seed[:, None, None], dy[None], bwd_in)
        g_in = jnp.where(b_valid[:, None, None], g_in, 0.0)
        g_aux = jnp.where(b_valid, jnp.float32(aux_coef), 0.0)
        slot_b = jnp.take(off_arr, vcb) + mb_c % jnp.take(size_arr, vcb)
        x_saved = jax.vmap(
            lambda st, slot: jax.lax.dynamic_index_in_dim(
                st, slot, 0, keepdims=False
            )
        )(stash, slot_b)
        aux_b = _gather_per_stage(aux_inputs, mb_c)

        def stage_bwd(layers_local, x, aux_t, gy, ga):
            _, vjp = jax.vjp(
                lambda L_, x_: stage_fn(L_, x_, aux_t), layers_local, x
            )
            return vjp((gy.astype(x.dtype), ga))

        g_layers_m, gx = jax.vmap(stage_bwd)(
            jax.vmap(_pick_chunk)(layers_c, vcb), x_saved, aux_b, g_in, g_aux
        )

        # accumulate into the rank's chunk slot vcb (invalid rounds add
        # exact zeros — g_in/g_aux were zeroed, vjp is linear)
        def acc_rank(gl, gm, vc_i):
            prev = jax.lax.dynamic_index_in_dim(gl, vc_i, 0, keepdims=False)
            return jax.lax.dynamic_update_index_in_dim(gl, prev + gm, vc_i, 0)

        g_layers = jax.tree.map(
            lambda gl, gm: jax.vmap(acc_rank)(gl, gm, vcb), g_layers,
            g_layers_m,
        )
        # rank 0 finishing chunk 0's backward yields d/d(xs[m])
        dxs = _masked_row_write(
            dxs, gx[0], mb_c[0], b_valid[0] & (vcb[0] == 0)
        )

        fwd_in = _pin_stagewise(mesh, jnp.roll(y, 1, axis=0))
        bwd_in = _pin_stagewise(mesh, jnp.roll(gx, -1, axis=0))
        return (
            (fwd_in, bwd_in, stash, g_layers, g_head, dxs, losses, stats,
             aux_sum),
            None,
        )

    act_shape = (pp,) + xs.shape[1:]
    init = (
        _pin_stagewise(mesh, jnp.zeros(act_shape, xs.dtype)),
        _pin_stagewise(mesh, jnp.zeros(act_shape, xs.dtype)),
        _pin_stagewise(
            mesh, jnp.zeros((pp, S_total) + xs.shape[1:], xs.dtype),
            token_dim=2,
        ),
        jax.tree.map(jnp.zeros_like, layers_c),
        jax.tree.map(jnp.zeros_like, head_params),
        jnp.zeros_like(xs),
        jnp.zeros((M,), jnp.float32),
        jax.tree.map(
            lambda s: jnp.zeros((M,) + s.shape, s.dtype), stats_shape
        ),
        jnp.float32(0.0),
    )
    (_, _, _, g_layers, g_head, dxs, losses, stats, aux_sum), _ = jax.lax.scan(
        round_fn, init, jnp.arange(rounds)
    )
    # [pp, v, Lc, ...] → [L, ...] in the chunk-major storage layout
    g_layers = jax.tree.map(
        lambda g: g.reshape((g.shape[0] * g.shape[1] * g.shape[2],)
                            + g.shape[3:]),
        g_layers,
    )
    return losses, stats, aux_sum, g_layers, g_head, dxs
