"""Mamba-1 selective state-space decode step: one recurrent update of every
live slot's state, IN PLACE.

A state-space layer's cache is a state a slot, `S` [state-space layers,
1 + slots, N, Di] float32 (row 0 the null slot, which stays zero): `N` state
lanes on the sublanes, the `Di` channels on the TPU's lanes (sixteen lanes a
row would leave seven eighths of every vreg empty). One token step of layer
`layer` is, per slot, channel c and state lane n,

    h[n, c] <- exp(dt[c] A[n, c]) h[n, c] + dt[c] B[n] u[c]
    y[c] = sum_n C[n] h[n, c] + D[c] u[c]

float32 throughout, diagonal: an `exp`, two multiply-adds and a sum over the
sublanes an element, no matrix product. The step's bytes are the state
itself, once in and once out, whatever the context's length. Like
`ops/gdn_step.py` the op takes the WHOLE array and a layer index (here
possibly traced: the layer's place inside a scanned run), so that a scan's
carry is updated in place and never sliced.

Two implementations behind one signature, selected like `paged_attention`'s:

- `"pallas"` (TPU): `pl.pallas_call(name="ssm_step")`, a grid over a WORK
  LIST of the live slots (`live_slots`: the live slots' indices first, and
  how many). The state aliases its output (`input_output_aliases`) and grid
  step i DMAs slot `order[i]`'s `[N, Di]` slab HBM->VMEM and back; a step
  past the list's end names the block the step before it named, so nothing
  is fetched, computed or written for a slot that is not live (its state
  stays where it is, its output row is zeroed by the caller's mask). The
  layer index and the list ride as scalar-prefetch operands.
- `"xla"` (CPU / tests): the same arithmetic in `jax.numpy` over the layer's
  rows, written back with one dynamic-update-slice.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from areal_tpu.ops.paged_attention import _default_interpret, resolve_impl


def ssm_step_reference(S, dt, u, B, C, A, D):
    """The update on bare rows: S [R, N, Di], dt, u [R, Di], B, C [R, N],
    A [N, Di], D [Di], float32. Returns (y [R, Di], S)."""
    S = jnp.exp(dt[:, None, :] * A[None]) * S + (dt * u)[:, None, :] * B[:, :, None]
    return jnp.sum(S * C[:, :, None], axis=1) + D * u, S


def live_slots(active, slots: int):
    """The kernel's work list: (`order` int32 [slots], the live slots'
    indices first in slot order, then the others'; how many are live)."""
    if active is None:
        return jnp.arange(slots, dtype=jnp.int32), jnp.int32(slots)
    order = jnp.argsort(~active, stable=True).astype(jnp.int32)
    return order, active.sum(dtype=jnp.int32)


def _xla_step(S, dt, u, B, C, A, D, layer, active):
    rows = S[layer, 1:]
    y, new = ssm_step_reference(rows, dt, u, B, C, A, D)
    if active is not None:
        new = jnp.where(active[:, None, None], new, rows)
    return y, S.at[layer, 1:].set(new)


def _kernel(meta_ref, order_ref, s_ref, dtu_ref, bc_ref, a_ref, d_ref,
            y_ref, s_out_ref):
    i = pl.program_id(0)
    n_live = meta_ref[1]

    @pl.when(i < n_live)
    def _():
        dt = dtu_ref[0, 0:1, :]  # [1, Di]
        u = dtu_ref[0, 1:2, :]
        b = bc_ref[0, :, 0:1]  # [N, 1]: a column that broadcasts along the lanes
        c = bc_ref[0, :, 1:2]
        h = jnp.exp(dt * a_ref[...]) * s_ref[0, 0] + (dt * u) * b
        s_out_ref[0, 0] = h
        y_ref[0] = jnp.sum(h * c, axis=0, keepdims=True) + d_ref[...] * u

    @pl.when((i == 0) & (n_live == 0))
    def _():
        # no slot is live: the one block the grid names goes back as it came
        s_out_ref[...] = s_ref[...]


def _pallas_step(S, dt, u, B, C, A, D, layer, live, interpret):
    n_layers, rows, N, Di = S.shape
    R = rows - 1
    order, n_live = live
    meta = jnp.stack([jnp.asarray(layer, jnp.int32), n_live.astype(jnp.int32)])
    # a slot's rows of dt and u side by side, its B and C as columns
    dtu = jnp.stack([dt, u], axis=1)  # [R, 2, Di]
    bc = jnp.stack([B, C], axis=-1)  # [R, N, 2]

    def slot(i, meta, order):
        # past the list's end: the last live slot's block again (no new DMA)
        return order[jnp.minimum(i, jnp.maximum(meta[1] - 1, 0))]

    def state(i, meta, order):
        return (meta[0], 1 + slot(i, meta, order), 0, 0)

    def per_slot(i, meta, order):
        return (slot(i, meta, order), 0, 0)

    def whole(i, meta, order):
        return (0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(R,),
        in_specs=[
            pl.BlockSpec((1, 1, N, Di), state),
            pl.BlockSpec((1, 2, Di), per_slot),
            pl.BlockSpec((1, N, 2), per_slot),
            pl.BlockSpec((N, Di), whole),
            pl.BlockSpec((1, Di), whole),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, Di), per_slot),
            pl.BlockSpec((1, 1, N, Di), state),
        ],
    )
    y, S = pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((R, 1, Di), jnp.float32),
            jax.ShapeDtypeStruct(S.shape, S.dtype),
        ],
        # operands 0 and 1 are the scalar-prefetch vectors; the state is 2
        input_output_aliases={2: 1},
        interpret=interpret,
        name="ssm_step",
    )(meta, order, S, dtu, bc, A, D[None])
    return y[:, 0], S


def ssm_step(S, dt, u, B, C, A, D, layer, active=None, *, impl: str = "auto",
             live: tuple | None = None, interpret: bool | None = None):
    """One token step of state-space layer `layer` (a Python int, or traced
    inside a scanned run) for R slots.

    S [n_layers, 1 + R, N, Di] float32 (row 0 the null slot); dt (> 0), u
    [R, Di]; B, C [R, N]; A [N, Di] (negative); D [Di]; `active` [R] bool: a
    slot that is not active keeps its state and reads y = 0. `live`:
    `live_slots(active, R)` where the caller has taken it already (once a
    token step, not once a layer). Returns (y [R, Di] float32, S)."""
    dt, u, B, C, A, D = (t.astype(jnp.float32) for t in (dt, u, B, C, A, D))
    if resolve_impl(impl) != "pallas":
        y, S = _xla_step(S, dt, u, B, C, A, D, layer, active)
    else:
        if interpret is None:
            interpret = _default_interpret()
        if live is None:
            live = live_slots(active, S.shape[1] - 1)
        y, S = _pallas_step(S, dt, u, B, C, A, D, layer, live, interpret)
    if active is not None:
        y = jnp.where(active[:, None], y, 0.0)
    return y, S
