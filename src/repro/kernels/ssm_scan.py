"""Chunked selective-scan (Mamba-1) Pallas kernel.

Grid: (B, D/bd, T/tc) — the time axis is the *last* (sequential on TPU)
grid dimension, so the recurrent state lives in a VMEM scratch buffer that
persists across time-chunk iterations: zeroed at t_idx == 0, carried
forward otherwise, exactly the chunked recurrence of
repro.models.mamba.selective_scan but with explicit tiles.

Layout: the state is held transposed, (S, bd), so d_inner sits on the
128-wide lane dim and the small state dim on sublanes. Per time step the
kernel reads one (1, bd) row of u/dt straight from its VMEM ref
(``pl.ds(i, 1)``) and writes one (1, bd) row of y back the same way; the
step's (S, 1) columns of B and C are picked out of their transposed (S, tc)
tiles with a lane mask and a lane reduction — no traced slicing of values,
which the TPU lowering does not implement. State math is fp32 regardless
of input dtype (bf16-safe); inputs are widened to f32 before the call.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssm_kernel(u_ref, dt_ref, b_ref, c_ref, alog_ref, dskip_ref,
                y_ref, hout_ref, h_scratch, *, tc: int):
    t_idx = pl.program_id(2)
    nt = pl.num_programs(2)

    @pl.when(t_idx == 0)
    def _init():
        h_scratch[...] = jnp.zeros_like(h_scratch)

    a = -jnp.exp(alog_ref[...])                           # (S, bd)
    b_t = b_ref[...]                                      # (S, tc)
    c_t = c_ref[...]
    dskip = dskip_ref[...]                                # (1, bd)
    lane = jax.lax.broadcasted_iota(jnp.int32, b_t.shape, 1)

    def step(i, h):
        u = u_ref[pl.ds(i, 1), :]                         # (1, bd)
        dt = dt_ref[pl.ds(i, 1), :]
        hit = lane == i
        b_col = jnp.sum(jnp.where(hit, b_t, 0.0), axis=1, keepdims=True)
        c_col = jnp.sum(jnp.where(hit, c_t, 0.0), axis=1, keepdims=True)
        h = jnp.exp(dt * a) * h + (dt * u) * b_col        # (S, bd)
        y = jnp.sum(h * c_col, axis=0, keepdims=True)     # (1, bd)
        y_ref[pl.ds(i, 1), :] = y + u * dskip
        return h

    h_fin = jax.lax.fori_loop(0, tc, step, h_scratch[...])
    h_scratch[...] = h_fin

    @pl.when(t_idx == nt - 1)
    def _emit_state():
        hout_ref[...] = h_fin


@functools.partial(jax.jit,
                   static_argnames=("block_d", "time_chunk", "interpret"))
def ssm_scan(u, dt, b_in, c_in, a_log, d_skip, *, block_d: int = 512,
             time_chunk: int = 256, interpret: bool = False):
    """u, dt: (B, T, D); b_in, c_in: (B, T, S); a_log: (D, S); d_skip: (D,).

    Returns (y (B, T, D) fp32, h_final (B, D, S) fp32).
    """
    bsz, t, d = u.shape
    s = b_in.shape[-1]
    bd = min(block_d, d)
    tc = min(time_chunk, t)
    if d % bd or t % tc:
        raise ValueError(f"(T={t}, D={d}) must tile by (tc={tc}, bd={bd})")
    f32 = jnp.float32
    grid = (bsz, d // bd, t // tc)
    sq = pl.Squeezed()
    y, h_fin = pl.pallas_call(
        functools.partial(_ssm_kernel, tc=tc),
        grid=grid,
        in_specs=[
            pl.BlockSpec((sq, tc, bd), lambda b, j, ti: (b, ti, j)),   # u
            pl.BlockSpec((sq, tc, bd), lambda b, j, ti: (b, ti, j)),   # dt
            pl.BlockSpec((sq, s, tc), lambda b, j, ti: (b, 0, ti)),    # B^T
            pl.BlockSpec((sq, s, tc), lambda b, j, ti: (b, 0, ti)),    # C^T
            pl.BlockSpec((s, bd), lambda b, j, ti: (0, j)),            # A_log^T
            pl.BlockSpec((1, bd), lambda b, j, ti: (0, j)),            # d_skip
        ],
        out_specs=[
            pl.BlockSpec((sq, tc, bd), lambda b, j, ti: (b, ti, j)),   # y
            pl.BlockSpec((sq, s, bd), lambda b, j, ti: (b, 0, j)),     # h^T
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bsz, t, d), f32),
            jax.ShapeDtypeStruct((bsz, s, d), f32),
        ],
        scratch_shapes=[pltpu.VMEM((s, bd), f32)],
        interpret=interpret,
        name="ssm_scan",
    )(u.astype(f32), dt.astype(f32),
      b_in.astype(f32).swapaxes(1, 2), c_in.astype(f32).swapaxes(1, 2),
      a_log.astype(f32).T, d_skip.astype(f32).reshape(1, d))
    return y, h_fin.swapaxes(1, 2)
