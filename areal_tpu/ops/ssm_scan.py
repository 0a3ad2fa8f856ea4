"""Mamba-1 selective scan over ONE sequence's tokens (a prefill): the
recurrence of `ops/ssm_step.py`, token by token, the state never leaving the
chip's vector memory.

    h_t[n, c] = exp(dt_t[c] A[n, c]) h_{t-1}[n, c] + dt_t[c] B_t[n] u_t[c]
    y_t[c] = sum_n C_t[n] h_t[n, c]                      (h_{-1} = 0)

float32 throughout, no matrix product. In plain JAX (`models/qwen2.py:
_ssm_chunk_scan`, which the trainer differentiates through) a chunk's pairs
(decay, input) are `[chunk, N, Di]` float32 arrays that an associative scan
passes through HBM a dozen times: 4 MB a token and layer at AI21-Jamba2-3B's
widths, the largest share of its prefill. Here the state `[N, Di]` is carried
in VMEM across a grid over blocks of tokens, a block's tokens walked by a
loop inside the kernel a tile of channels at a time (the tile's state in
registers); what crosses HBM is `dt`, `u`, `y` (a row of `Di` a token) and
`B`, `C` (a column of `N`).

Two implementations behind one signature, selected like `paged_attention`'s:
`"pallas"` (TPU): `pl.pallas_call(name="ssm_scan")`; `"xla"`: the caller's
own scan (`scan=`), so that the two cannot drift apart. A padding token must
come with dt = 0 (decay 1, input 0) and then leaves the state as it is;
`jax.vmap` over sequences adds a grid axis.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from areal_tpu.ops.paged_attention import _default_interpret, resolve_impl

TOKENS_PER_STEP = 32  # a grid step's block of tokens
LANES_PER_TILE = 1024  # channels whose [N, tile] state a token loop keeps in registers


def _kernel(dt_ref, u_ref, bc_ref, a_ref, y_ref, h_ref, h_scr, *, tokens: int, tile: int):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _():
        h_scr[...] = jnp.zeros_like(h_scr)

    Di = h_scr.shape[1]
    for lo in range(0, Di, tile):
        lanes = slice(lo, min(lo + tile, Di))
        a = a_ref[:, lanes]

        def token(t, h, lanes=lanes, a=a):
            dt = dt_ref[pl.ds(t, 1), lanes]  # [1, tile]
            u = u_ref[pl.ds(t, 1), lanes]
            bc = bc_ref[t]  # [N, 2]: this token's B and C as columns
            h = jnp.exp(dt * a) * h + (dt * u) * bc[:, 0:1]
            y_ref[pl.ds(t, 1), lanes] = jnp.sum(h * bc[:, 1:2], axis=0, keepdims=True)
            return h

        h_scr[:, lanes] = jax.lax.fori_loop(0, tokens, token, h_scr[:, lanes])
    # (the block's index never changes: written back once, after the last step)
    h_ref[...] = h_scr[...]


def _pallas_scan(u, dt, B, C, A, interpret):
    T, Di = u.shape
    N = B.shape[-1]
    tokens = TOKENS_PER_STEP
    pad = (-T) % tokens
    if pad:
        u, dt, B, C = (jnp.pad(a, ((0, pad), (0, 0))) for a in (u, dt, B, C))
    bc = jnp.stack([B, C], axis=-1)  # [T, N, 2]
    rows = pl.BlockSpec((tokens, Di), lambda i: (i, 0))
    y, h = pl.pallas_call(
        functools.partial(_kernel, tokens=tokens, tile=min(LANES_PER_TILE, Di)),
        grid=((T + pad) // tokens,),
        in_specs=[
            rows, rows,
            pl.BlockSpec((tokens, N, 2), lambda i: (i, 0, 0)),
            pl.BlockSpec((N, Di), lambda i: (0, 0)),
        ],
        out_specs=[rows, pl.BlockSpec((N, Di), lambda i: (0, 0))],
        out_shape=[
            jax.ShapeDtypeStruct((T + pad, Di), jnp.float32),
            jax.ShapeDtypeStruct((N, Di), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((N, Di), jnp.float32)],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="ssm_scan",
    )(dt, u, bc, A)
    return y[:T], h


def ssm_scan(u, dt, B, C, A, *, scan, impl: str = "auto", interpret: bool | None = None):
    """The selective scan over one sequence from a zero state.

    u, dt [T, Di], B, C [T, N], A [N, Di] (negative), float32; a padding
    token comes with dt = 0. `scan(u, dt, B, C, A)` is the plain-JAX form the
    XLA implementation runs. Returns (y [T, Di] without the skip term, h [N,
    Di] after the last token)."""
    u, dt, B, C, A = (t.astype(jnp.float32) for t in (u, dt, B, C, A))
    if resolve_impl(impl) != "pallas":
        return scan(u, dt, B, C, A)
    if interpret is None:
        interpret = _default_interpret()
    with jax.named_scope("ssm_scan"):
        return _pallas_scan(u, dt, B, C, A, interpret)
