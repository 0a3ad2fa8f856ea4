"""Int8 KV quantization scheme shared by every producer and consumer.

ONE scheme, defined here so the write side (the O(1) row scatter in
models/qwen2.decode_step_paged / verify_step_paged and the prefill
scatters in engine/jax_decode.py) and the read side (the Pallas split-KV
kernels and the XLA gather fallback in ops/paged_attention.py) cannot
drift: symmetric per-row, per-kv-head absmax int8.

    scale[..., head]    = max(|x[..., head, :]|) / 127   (1.0 when the row
                          is all zero, so dequantization is always finite)
    q[..., head, d]     = round(x / scale) clipped to [-127, 127], int8
    dehat(q, scale)     = q * scale

Storage layout (per K and per V):

    data   [L, n_blocks, block_size, nKV*hd]    int8   (the pool)
    scales [L, n_blocks, nKV, block_size]       f32    (the scale pool)

The scale pool is paged EXACTLY like the data pool — same block ids, same
block tables — so every byte-moving path (host-tier offload, session
export/import, /drain migration) gathers the scale blocks alongside the
data blocks and ships both AS-IS: the int8 payload is quantized once at
the scatter and never requantized on any hop. The kv-head axis sits
before block_size so a Pallas BlockSpec for one (block, head) is
(1, 1, block_size): the lane dimension is the 128-multiple page size, not
a size-1 head column.

Worst-case round-trip error per element is scale/2 = amax/254 (round-to-
nearest on a symmetric grid); tests/test_kv_quant.py pins the bound.

Pool operands travel through the engine's jitted functions as either a
bare array (fp path, unchanged) or a (data, scales) tuple (int8) —
`split_pool` / `join_pool` keep the two forms interchangeable, and jax
treats the tuple as a pytree so scan carries, donation and sharding all
work untouched.
"""

from __future__ import annotations

import jax.numpy as jnp

from areal_tpu.ops.quant import (  # noqa: F401 — INT8_QMAX re-exported
    INT8_QMAX,
    dequantize_absmax,
    quantize_absmax,
)

# JaxDecodeConfig.kv_dtype values: "fp" stores kv_cache_dtype verbatim
# (the pre-quantization behavior and the numerics oracle), "int8" stores
# the paged pool in this module's scheme.
KV_DTYPES = ("fp", "int8")


def quantize_kv(x: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """fp rows [..., hd] -> (int8 values [..., hd], f32 scales [...]).

    The reduction axis is the trailing head_dim: one scale per (token row,
    kv head). All-zero rows get scale 1.0 so the dequantized row is an
    exact zero instead of 0/0. Delegates to the shared axis-generic scheme
    in ops/quant.py (ISSUE 16 hoist) — same op sequence, bit-identical."""
    return quantize_absmax(x, axis=-1)


def dequantize_kv(q: jnp.ndarray, scale: jnp.ndarray, dtype) -> jnp.ndarray:
    """(int8 [..., hd], f32 [...]) -> fp [..., hd] in `dtype`."""
    return dequantize_absmax(q, scale, dtype, axis=-1)


def split_pool(pool):
    """Pool operand -> (data, scales): scales is None on the fp path."""
    if isinstance(pool, tuple):
        return pool
    return pool, None


def join_pool(data, scales):
    """Inverse of split_pool: rebuild the operand form `data` came in."""
    return data if scales is None else (data, scales)


def scales_rowmajor(scales: jnp.ndarray) -> jnp.ndarray:
    """Scale blocks [..., nb, nKV, bsz] -> row-major [..., nb*bsz, nKV],
    aligned with a gathered [..., nb*bsz, nKV, hd] data workspace."""
    *lead, nb, nkv, bsz = scales.shape
    return jnp.swapaxes(scales, -1, -2).reshape(*lead, nb * bsz, nkv)


def scales_blocked(rows: jnp.ndarray, nb: int, bsz: int) -> jnp.ndarray:
    """Inverse of scales_rowmajor: [..., nb*bsz, nKV] -> [..., nb, nKV, bsz]."""
    *lead, _, nkv = rows.shape
    return jnp.swapaxes(rows.reshape(*lead, nb, bsz, nkv), -1, -2)
