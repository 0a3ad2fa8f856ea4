"""Fused int8-weight dequant-matmul for the decode path (ISSUE 16).

Weight serving stores transformer matmul kernels as per-output-channel
symmetric absmax int8 (ops/quant.py): int8 data in the kernel's own shape
plus one f32 scale per output channel. Small-batch decode is HBM-bandwidth
bound, so halving the weight bytes read per chunk is a direct speedup —
IF the dequantization never materializes an fp copy of the weights in
HBM. Two implementations behind one signature, selected like
`paged_attn_impl`:

- `"pallas"` (TPU): a tiled matmul whose weight operand is the int8
  tensor. Each grid step DMAs one [K_tile, N_tile] int8 block plus its
  [N_tile] scale strip HBM→VMEM and dequantizes immediately after the
  transfer (the `_paged_attn_kernel_q8` discipline: the fp weights exist
  only tile-at-a-time in VMEM), accumulating in an f32 VMEM scratch
  across the K grid axis.
- `"xla"` (CPU / tests): dequantize-then-matmul with the same f32 op
  sequence, globally instead of tile-at-a-time. Identical math up to
  float reassociation from the K tiling; tests/test_weight_quant.py pins
  the two against each other in interpret mode.

The Pallas kernel never gives way to the XLA one: a [K, N] it cannot tile
raises, naming the shape.

The contraction layout is the one every quantized call site in
models/qwen2.py uses: the weight's CONTRACTION axes lead and the x
contraction axes trail (`"...h,hnd->...nd"`, `"tnd,ndh->th"`,
`"th,hm->tm"`, ...), so both operands collapse to a 2D [T, K] @ [K, N]
with the f32 scale per output column folded in at dequantization.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from areal_tpu.ops.paged_attention import _default_interpret, resolve_impl

# MXU-aligned tile edges. K and N must divide evenly (an int8 weight tile
# is [128, 128]; the lane dimension stays 128); T is padded up because
# decode chunks run a handful of slots, far below one tile.
TILE_T = 128
TILE_K = 128
TILE_N = 128


def quant_matmul_tiles_ok(k: int, n: int) -> bool:
    """True when the Pallas kernel can tile this [K, N] weight."""
    return k % TILE_K == 0 and n % TILE_N == 0


def _quant_matmul_kernel(
    x_ref,  # (TILE_T, TILE_K) activations
    q_ref,  # (TILE_K, TILE_N) int8 — THE weight tile, DMA'd in place
    s_ref,  # (1, TILE_N) f32 — that tile's output-channel scales
    o_ref,  # (TILE_T, TILE_N)
    acc_ref,  # VMEM (TILE_T, TILE_N) f32
):
    k = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    # dequantize right after the DMA: int8 tile x per-column scales. The
    # fp weights never exist outside this VMEM tile.
    w = q_ref[:].astype(jnp.float32) * s_ref[0][None, :]
    acc_ref[:] += jax.lax.dot_general(
        x_ref[:].astype(jnp.float32),
        w,
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(k == nk - 1)
    def _finalize():
        o_ref[:] = acc_ref[:].astype(o_ref.dtype)


def _quant_matmul_pallas(x2, q2, s_row, out_dtype, interpret):
    t, kk = x2.shape
    nn = q2.shape[1]
    tp = math.ceil(t / TILE_T) * TILE_T
    if tp != t:
        x2 = jnp.pad(x2, ((0, tp - t), (0, 0)))
    grid = (tp // TILE_T, nn // TILE_N, kk // TILE_K)
    out = pl.pallas_call(
        _quant_matmul_kernel,
        name="quant_matmul",
        grid=grid,
        in_specs=[
            pl.BlockSpec((TILE_T, TILE_K), lambda i, j, k: (i, k)),
            pl.BlockSpec((TILE_K, TILE_N), lambda i, j, k: (k, j)),
            pl.BlockSpec((1, TILE_N), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((TILE_T, TILE_N), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((tp, nn), out_dtype),
        scratch_shapes=[pltpu.VMEM((TILE_T, TILE_N), jnp.float32)],
        interpret=interpret,
    )(x2, q2, s_row.reshape(1, nn))
    return out[:t] if tp != t else out


def _quant_matmul_xla(x2, q2, s_row, out_dtype):
    # dequantize-then-matmul: same f32 op sequence as the kernel, minus
    # the tiling — the pinned numerics fallback
    w = q2.astype(jnp.float32) * s_row[None, :]
    out = jax.lax.dot_general(
        x2.astype(jnp.float32),
        w,
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    return out.astype(out_dtype)


@functools.partial(jax.jit, static_argnames=("n_contract", "impl", "interpret"))
def quant_einsum(
    x: jax.Array,
    w_q: jax.Array,  # int8, kernel's own shape, contraction axes leading
    w_scale: jax.Array,  # f32, the kernel's output dims
    n_contract: int,
    *,
    impl: str = "auto",
    interpret: bool | None = None,
) -> jax.Array:
    """einsum(x, dequant(w_q, w_scale)) with the contraction over w's
    leading `n_contract` axes and x's trailing `n_contract` axes — the
    shape contract of every quantized call site in models/qwen2.py.
    Returns x's batch dims + w's output dims, in x.dtype.
    """
    if interpret is None:
        interpret = _default_interpret()
    impl = resolve_impl(impl)
    k_dims = w_q.shape[:n_contract]
    out_dims = w_q.shape[n_contract:]
    if x.shape[x.ndim - n_contract :] != k_dims:
        raise ValueError(
            f"x contraction dims {x.shape[x.ndim - n_contract:]} != weight "
            f"contraction dims {k_dims}"
        )
    kk = math.prod(k_dims)
    nn = math.prod(out_dims)
    batch = x.shape[: x.ndim - n_contract]
    x2 = x.reshape(math.prod(batch) if batch else 1, kk)
    q2 = w_q.reshape(kk, nn)
    s_row = w_scale.reshape(nn)
    if impl == "pallas":
        if not quant_matmul_tiles_ok(kk, nn):
            raise ValueError(
                f"quant_einsum impl='pallas' cannot tile the [K={kk}, N={nn}]"
                f" weight {tuple(w_q.shape)}: K and N must be multiples of "
                f"{TILE_K} and {TILE_N}; serve this model with "
                "weight_dtype='fp'"
            )
        out2 = _quant_matmul_pallas(x2, q2, s_row, x.dtype, interpret)
    else:
        out2 = _quant_matmul_xla(x2, q2, s_row, x.dtype)
    return out2.reshape(*batch, *out_dims)
