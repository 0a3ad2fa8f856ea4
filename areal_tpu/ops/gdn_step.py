"""Gated DeltaNet / Kimi Delta Attention decode step: one recurrent update
of every live slot's state, IN PLACE.

A linear layer's cache is no rows of keys and values but a state a slot:
`S` [linear layers, 1 + slots, Hv, dk, dv] float32 (row 0 the null slot,
which stays zero). One token step of layer `ci` is, per slot and value head,

    S <- exp(g) S;  d = beta (v - S^T k);  S <- S + k d^T;  o = S^T q

(float32 throughout), with `g` one number a head (Gated DeltaNet: `g`
[R, Hv]) or a vector over the head's key lanes (Kimi Delta Attention: `g`
[R, Hv, dk], `S <- Diag(exp(g)) S`); the shape of `g` chooses. The step's
bytes are the state itself, once in and once out, whatever the context's
length: like the paged pool the op takes the WHOLE array and a layer index,
so that a scan's carry is updated in place and never sliced.

Two implementations behind one signature, selected like `paged_attention`'s:

- `"pallas"` (TPU): `pl.pallas_call(name="gdn_step")`, grid (slot, block of
  value heads). The state aliases its output (`input_output_aliases`) and
  each grid step DMAs one `[heads, dk, dv]` slab HBM->VMEM and back. Which
  row a slot's step names rides as a scalar-prefetch vector: its own
  `1 + slot`, or the null row 0 for a slot that is not active, whose step
  copies the (zero) slab through and computes nothing. The arithmetic is
  on the vector unit, exact float32: `k` and `q` come transposed
  `[slot, head block, dk, heads]` so that a head's key is a column that broadcasts along
  the state's lanes, and `exp(g)`, `beta` and `v` come as rows; a vector
  decay comes as a column block beside `k` and `q`, the call named `kda_step`
  so that a trace tells the two apart.
- `"xla"` (CPU / tests): the same arithmetic in `jax.numpy` over the layer's
  rows, written back with one dynamic-update-slice.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from areal_tpu.ops.paged_attention import _default_interpret, resolve_impl

# value heads a grid step takes: 8 x [128, 128] float32 is a 512 KiB slab,
# 2 MiB of VMEM with both directions double-buffered
HEADS_PER_STEP = 8


def gdn_step_reference(S, q, k, v, g, beta):
    """The update on bare rows: S [R, Hv, dk, dv], q, k [R, Hv, dk],
    v [R, Hv, dv], beta [R, Hv], g [R, Hv] or a lane [R, Hv, dk], float32.
    Returns (o [R, Hv, dv], S)."""
    S = S * (jnp.exp(g)[..., None, None] if g.ndim == beta.ndim
             else jnp.exp(g)[..., None])
    m = jnp.sum(S * k[..., :, None], axis=-2)
    d = beta[..., None] * (v - m)
    S = S + k[..., :, None] * d[..., None, :]
    o = jnp.sum(S * q[..., :, None], axis=-2)
    return o, S


def _xla_step(S, q, k, v, g, beta, layer, active):
    rows = S[layer, 1:]
    o, new = gdn_step_reference(rows, q, k, v, g, beta)
    if active is not None:
        new = jnp.where(active[:, None, None, None], new, rows)
    return o, S.at[layer, 1:].set(new)


def _kernel(row_ref, s_ref, qt_ref, kt_ref, v_ref, decay_ref, beta_ref,
            o_ref, s_out_ref, *, heads: int, decay_lanes: bool):
    r = pl.program_id(0)

    @pl.when(row_ref[r] == 0)
    def _():
        # not active: the null row's slab goes back as it came
        s_out_ref[...] = s_ref[...]
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(row_ref[r] != 0)
    def _():
        for h in range(heads):
            if decay_lanes:  # a factor a key lane: [dk, dv] * [dk, 1]
                S = s_ref[0, 0, h] * decay_ref[0, 0, :, h:h + 1]
            else:
                S = s_ref[0, 0, h] * decay_ref[0, h:h + 1, :]  # [dk, dv] * [1, dv]
            k_col = kt_ref[0, 0, :, h:h + 1]  # [dk, 1]
            m = jnp.sum(S * k_col, axis=0, keepdims=True)  # [1, dv]
            d = beta_ref[0, h:h + 1, :] * (v_ref[0, h:h + 1, :] - m)
            S = S + k_col * d
            s_out_ref[0, 0, h] = S
            o_ref[0, h:h + 1, :] = jnp.sum(
                S * qt_ref[0, 0, :, h:h + 1], axis=0, keepdims=True
            )


def _pallas_step(S, q, k, v, g, beta, layer, active, interpret):
    n_lin, rows, Hv, dk, dv = S.shape
    R = rows - 1
    hb = HEADS_PER_STEP if Hv % HEADS_PER_STEP == 0 else Hv
    slot_row = 1 + jnp.arange(R, dtype=jnp.int32)
    if active is not None:
        slot_row = jnp.where(active, slot_row, 0)
    # a head's key and query as columns, a block of heads at a time:
    # [R, Hv / hb, dk, hb]
    qt, kt = (
        jnp.swapaxes(t.reshape(R, Hv // hb, hb, dk), 2, 3) for t in (q, k)
    )
    # exp(g) and beta as rows over the state's lanes: [R, Hv, dv]; a decay a
    # key lane as columns like q^T and k^T
    decay_lanes = g.ndim == 3
    if decay_lanes:
        decay = jnp.swapaxes(jnp.exp(g).reshape(R, Hv // hb, hb, dk), 2, 3)
    else:
        decay = jnp.broadcast_to(jnp.exp(g)[..., None], (R, Hv, dv))
    beta_b = jnp.broadcast_to(beta[..., None], (R, Hv, dv))

    def per_head(r, j, row):  # q^T / k^T: heads on the lanes
        return (r, j, 0, 0)

    def per_row(r, j, row):  # v, decay, beta, o: heads on the sublanes
        return (r, j, 0)

    def state(r, j, row):
        return (layer, row[r], j, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(R, Hv // hb),
        in_specs=[
            pl.BlockSpec((1, 1, hb, dk, dv), state),
            pl.BlockSpec((1, 1, dk, hb), per_head),
            pl.BlockSpec((1, 1, dk, hb), per_head),
            pl.BlockSpec((1, hb, dv), per_row),
            (pl.BlockSpec((1, 1, dk, hb), per_head) if decay_lanes
             else pl.BlockSpec((1, hb, dv), per_row)),
            pl.BlockSpec((1, hb, dv), per_row),
        ],
        out_specs=[
            pl.BlockSpec((1, hb, dv), per_row),
            pl.BlockSpec((1, 1, hb, dk, dv), state),
        ],
    )
    o, S = pl.pallas_call(
        functools.partial(_kernel, heads=hb, decay_lanes=decay_lanes),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((R, Hv, dv), jnp.float32),
            jax.ShapeDtypeStruct(S.shape, S.dtype),
        ],
        # operand 0 is the scalar-prefetch vector; the state is operand 1
        input_output_aliases={1: 1},
        interpret=interpret,
        name="kda_step" if decay_lanes else "gdn_step",
    )(slot_row, S, qt, kt, v, decay, beta_b)
    return o, S


def gdn_step(S, q, k, v, g, beta, layer: int, active=None, *,
             impl: str = "auto", interpret: bool | None = None):
    """One token step of linear layer `layer` for R slots.

    S [n_lin, 1 + R, Hv, dk, dv] float32 (row 0 the null slot); q, k
    [R, Hv, dk] and v [R, Hv, dv] float32 (q and k normalised, q scaled);
    g [R, Hv] float32 log decay (<= 0), or a key lane [R, Hv, dk]; beta
    [R, Hv] float32; `active` [R] bool: a slot that is not active keeps its state (its output is
    unspecified). Returns (o [R, Hv, dv] float32, S)."""
    q, k, v, g, beta = (t.astype(jnp.float32) for t in (q, k, v, g, beta))
    if resolve_impl(impl) != "pallas":
        return _xla_step(S, q, k, v, g, beta, layer, active)
    if interpret is None:
        interpret = _default_interpret()
    return _pallas_step(S, q, k, v, g, beta, layer, active, interpret)
