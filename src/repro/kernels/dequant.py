"""Blockwise int8 -> bf16 dequantization kernel (the FanStore decode path).

This is the TPU stand-in for the paper's LZSS decompression: fetched sample
records arrive as per-block-scaled int8; this kernel widens them at HBM
bandwidth right after the all_to_all, so "decompression" costs one VPU pass
— the same compute-for-bandwidth trade the paper measures in its Fig 10/11,
but with a dense fixed-rate codec that the VPU likes.

Tiling: grid (N/bn, F/bf); each program dequantizes a (bn, bf) VMEM tile of
payload against its (bn, bf/QBLOCK) scale tile. A TPU block's last dim must
be a multiple of 128 or the whole array dim, so the scale tile is either
whole rows (bf = F, the usual case: F/QBLOCK is small) or 128-lane aligned
(bf a multiple of 128 * QBLOCK). Scales are widened to f32 before the call
(the scale array is 1/QBLOCK of the payload), and each QBLOCK-wide column
slab is multiplied by its scale column broadcast across lanes — no repeat
of a narrow tile inside the kernel. int8 loads pack (32, 128), so bn
defaults to a multiple of 32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

QBLOCK = 256     # elements per quantization scale block


def _dequant_kernel(q_ref, s_ref, o_ref, *, qblock: int):
    s = s_ref[...]                                   # (bn, bf//qblock) f32
    for j in range(q_ref.shape[1] // qblock):        # static, aligned slabs
        cols = slice(j * qblock, (j + 1) * qblock)
        q = q_ref[:, cols].astype(jnp.float32)       # (bn, qblock)
        o_ref[:, cols] = (q * s[:, j:j + 1]).astype(o_ref.dtype)


def _feature_tile(f: int, block_f: int, qblock: int) -> int:
    """Largest legal F tile <= block_f: whole rows, or a divisor of F whose
    scale tile (bf // qblock lanes) is a multiple of 128."""
    step = 128 * qblock
    for bf in range(min(block_f, f) // step * step, 0, -step):
        if f % bf == 0:
            return bf
    return f


@functools.partial(jax.jit,
                   static_argnames=("block_n", "block_f", "qblock",
                                    "out_dtype", "interpret"))
def dequant(q: jnp.ndarray, scales: jnp.ndarray, *, block_n: int = 256,
            block_f: int = 8192, qblock: int = QBLOCK,
            out_dtype=jnp.bfloat16, interpret: bool = False) -> jnp.ndarray:
    """q: (N, F) int8, scales: (N, F//qblock) -> (N, F) out_dtype."""
    n, f = q.shape
    if f % qblock:
        raise ValueError(f"F={f} must divide qblock={qblock}")
    bn = min(block_n, n)
    bf = _feature_tile(f, block_f, qblock)
    if n % bn:
        raise ValueError(f"shape ({n},{f}) must tile by ({bn},{bf})")
    grid = (n // bn, f // bf)
    return pl.pallas_call(
        functools.partial(_dequant_kernel, qblock=qblock),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bn, bf), lambda i, j: (i, j)),
            pl.BlockSpec((bn, bf // qblock), lambda i, j: (i, j)),
        ],
        out_specs=pl.BlockSpec((bn, bf), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((n, f), out_dtype),
        interpret=interpret,
        name="fanstore_dequant",
    )(q, scales.astype(jnp.float32))
