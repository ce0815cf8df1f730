"""Causal GQA flash-attention (forward) Pallas kernel.

Grid: (B, H, Tq/bq, Tk/bk) — kv blocks are the last (sequential) grid dim;
online-softmax stats (m, l) and the output accumulator persist in VMEM
scratch across kv iterations. Causal skipping: kv blocks strictly above the
diagonal are skipped with pl.when (no MXU work issued), which is the
structural win over the lax reference (repro.models.layers.
flash_attention_lax) that must visit every block.

GQA is handled in the index map: query head h reads kv head h // group.
Sliding-window masking composes with causal in-block masks. The wrapper
moves heads in front of time, (B, H, T, dh), so every block's last two dims
are (block, dh): a TPU block's second-to-last dim must be a multiple of 8
(or whole), and a single head there is neither. Head dim goes to the MXU
lane dim — multiples of 128 are the fast path.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  scale: float, causal: bool, window: Optional[int],
                  bq: int, bk: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = qi * bq
    k_start = ki * bk
    live = jnp.bool_(True)
    if causal:
        live &= k_start <= q_start + bq - 1
    if window is not None:
        # entire kv block older than (q_start - window) is dead
        live &= k_start + bk - 1 >= q_start - window + 1

    @pl.when(live)
    def _compute():
        q = q_ref[...].astype(jnp.float32)                 # (bq, dh)
        k = k_ref[...].astype(jnp.float32)                 # (bk, dh)
        v = v_ref[...].astype(jnp.float32)                 # (bk, dv)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = jnp.ones((bq, bk), jnp.bool_)
        if causal:
            mask &= kpos <= qpos
        if window is not None:
            mask &= (qpos - kpos) < window
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_scr[...]
        l_prev = l_scr[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_new = l_prev * alpha + p.sum(axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new
        l_scr[...] = l_new

    @pl.when(ki == nk - 1)
    def _emit():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[...] = (acc_scr[...] / l).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("causal", "window", "scale",
                                    "block_q", "block_k", "interpret"))
def flash_attention(q, k, v, *, causal: bool = True,
                    window: Optional[int] = None,
                    scale: Optional[float] = None,
                    block_q: int = 128, block_k: int = 128,
                    interpret: bool = False) -> jnp.ndarray:
    """q: (B, T, H, dh); k, v: (B, T, KV, dh/dv), H % KV == 0 -> (B, T, H, dv)."""
    b, t, h, dh = q.shape
    kv = k.shape[2]
    dv = v.shape[-1]
    g = h // kv
    scale = scale if scale is not None else 1.0 / math.sqrt(dh)
    bq = min(block_q, t)
    bk = min(block_k, t)
    if t % bq or t % bk:
        raise ValueError(f"T={t} must tile by block sizes ({bq},{bk})")
    grid = (b, h, t // bq, t // bk)
    kernel = functools.partial(_flash_kernel, scale=scale, causal=causal,
                               window=window, bq=bq, bk=bk)
    sq = pl.Squeezed()
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((sq, sq, bq, dh),
                         lambda b_, h_, qi, ki: (b_, h_, qi, 0)),
            pl.BlockSpec((sq, sq, bk, dh),
                         lambda b_, h_, qi, ki: (b_, h_ // g, ki, 0)),
            pl.BlockSpec((sq, sq, bk, dv),
                         lambda b_, h_, qi, ki: (b_, h_ // g, ki, 0)),
        ],
        out_specs=pl.BlockSpec((sq, sq, bq, dv),
                               lambda b_, h_, qi, ki: (b_, h_, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, t, dv), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, dv), jnp.float32),
        ],
        interpret=interpret,
        name="flash_attention",
    )(q.swapaxes(1, 2), k.swapaxes(1, 2), v.swapaxes(1, 2))
    return out.swapaxes(1, 2)
