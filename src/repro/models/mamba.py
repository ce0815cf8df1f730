"""Mamba-1 block (falcon-mamba / the SSM half of hymba).

x -> in_proj -> (u, z); u -> causal depthwise conv -> silu -> selective scan
-> y; out = out_proj(y * silu(z)).

Selective scan: h_t = exp(dt_t * A) * h_{t-1} + dt_t * B_t * u_t,
y_t = C_t . h_t + D * u_t, with diagonal A (d_inner, d_state), input-dependent
dt/B/C. Training uses a chunked scan: lax.scan over time chunks with an
associative scan inside each chunk, rematerialized per chunk in the
backward pass — O(chunk * d_inner * d_state) peak memory. The Pallas
kernel (repro.kernels.ssm_scan) implements the same chunking with explicit
VMEM tiles; this module is the lowering-friendly path the models train
with, on every backend.

Decode is the O(1) recurrence on a carried (h, conv window) state — this is
why falcon-mamba/hymba run the long_500k shape while full-attention archs
cannot.
"""
from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import ModelConfig
from repro.models.layers import _dense_init


def mamba_params(key, cfg: ModelConfig, d_inner: Optional[int] = None,
                 dtype=jnp.float32) -> Dict:
    d = cfg.d_model
    di = d_inner or cfg.d_inner
    st = cfg.ssm_state
    dtr = cfg.dt_rank or max(1, math.ceil(d / 16))
    ks = jax.random.split(key, 7)
    a_init = jnp.tile(jnp.arange(1, st + 1, dtype=jnp.float32)[None, :], (di, 1))
    return {
        "in_proj": _dense_init(ks[0], (d, 2 * di), dtype=dtype),
        "conv_w": _dense_init(ks[1], (cfg.ssm_conv, di),
                              scale=1.0 / math.sqrt(cfg.ssm_conv), dtype=dtype),
        "conv_b": jnp.zeros((di,), dtype),
        "x_proj": _dense_init(ks[2], (di, dtr + 2 * st), dtype=dtype),
        "dt_proj": _dense_init(ks[3], (dtr, di), scale=dtr ** -0.5, dtype=dtype),
        "dt_bias": (jnp.log(jnp.expm1(
            jnp.clip(jnp.exp(jax.random.uniform(ks[4], (di,)) *
                             (math.log(0.1) - math.log(1e-3)) + math.log(1e-3)),
                     1e-4, None)))).astype(dtype),
        "a_log": jnp.log(a_init).astype(dtype),
        "d_skip": jnp.ones((di,), dtype),
        "out_proj": _dense_init(ks[5], (di, d), dtype=dtype),
    }


def _causal_conv(u: jnp.ndarray, w: jnp.ndarray, b: jnp.ndarray,
                 state: Optional[jnp.ndarray] = None
                 ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Depthwise causal conv1d. u: (B, T, di); w: (K, di).

    ``state``: (B, K-1, di) carried context (decode); returns (out, new_state).
    """
    k = w.shape[0]
    if state is None:
        state = jnp.zeros((u.shape[0], k - 1, u.shape[2]), u.dtype)
    ext = jnp.concatenate([state, u], axis=1)           # (B, K-1+T, di)
    out = jnp.zeros_like(u)
    for i in range(k):
        out = out + ext[:, i: i + u.shape[1]] * w[i].astype(u.dtype)
    new_state = ext[:, -(k - 1):] if k > 1 else state
    return out + b.astype(u.dtype), new_state


def _ssm_chunk(a_bar, bu, h0):
    """Associative scan within a chunk. a_bar/bu: (B, Q, di, st)."""
    def combine(x, y):
        a1, b1 = x
        a2, b2 = y
        return a1 * a2, a2 * b1 + b2
    a_cum, h = lax.associative_scan(combine, (a_bar, bu), axis=1)
    h = h + a_cum * h0[:, None]
    return h


def selective_scan(u, dt, b_in, c_in, a_log, d_skip, h0=None, *,
                   chunk: int = 256, unroll: bool = False):
    """u: (B, T, di); dt: (B, T, di); b_in/c_in: (B, T, st).

    Returns (y (B, T, di), h_final (B, di, st)). fp32 state math.
    """
    bsz, t, di = u.shape
    st = b_in.shape[-1]
    a = -jnp.exp(a_log.astype(jnp.float32))             # (di, st)
    if h0 is None:
        h0 = jnp.zeros((bsz, di, st), jnp.float32)
    if unroll:          # cost-exact mode: single-trip chunk loop
        chunk = t
    chunk = min(chunk, t)
    pad = (-t) % chunk
    if pad:
        u = jnp.pad(u, ((0, 0), (0, pad), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        b_in = jnp.pad(b_in, ((0, 0), (0, pad), (0, 0)))
        c_in = jnp.pad(c_in, ((0, 0), (0, pad), (0, 0)))
    nt = u.shape[1] // chunk

    def to_chunks(x):
        return x.reshape(bsz, nt, chunk, *x.shape[2:]).swapaxes(0, 1)

    uc, dtc, bc, cc = map(to_chunks, (u, dt, b_in, c_in))

    def step(h, xs):
        uq, dtq, bq, cq = xs                            # (B, Q, ...)
        dtf = dtq.astype(jnp.float32)
        a_bar = jnp.exp(dtf[..., None] * a)             # (B,Q,di,st)
        bu = (dtf * uq.astype(jnp.float32))[..., None] * bq.astype(jnp.float32)[:, :, None, :]
        hseq = _ssm_chunk(a_bar, bu, h)                 # (B,Q,di,st)
        y = jnp.einsum("bqds,bqs->bqd", hseq, cq.astype(jnp.float32))
        return hseq[:, -1], y

    # Remat each chunk: the backward pass then keeps only the chunk-boundary
    # states and rebuilds one chunk's (B, Q, di, st) scan at a time, instead
    # of holding every chunk's at once (at T=4096 that alone is several
    # times a 16 GiB chip's HBM).
    h_final, yc = lax.scan(jax.checkpoint(step), h0, (uc, dtc, bc, cc))
    y = yc.swapaxes(0, 1).reshape(bsz, nt * chunk, di)[:, :t]
    y = y + u.astype(jnp.float32)[:, :y.shape[1]][:, :t] * d_skip.astype(jnp.float32)
    return y, h_final


def apply_mamba(p, x, cfg: ModelConfig, *, ssm_impl: str = "lax"
                ) -> jnp.ndarray:
    """Full-sequence mamba block. x: (B, T, d) -> (B, T, d)."""
    di = p["in_proj"].shape[1] // 2
    uz = x @ p["in_proj"].astype(x.dtype)
    u, z = jnp.split(uz, 2, axis=-1)
    u, _ = _causal_conv(u, p["conv_w"], p["conv_b"])
    u = jax.nn.silu(u)
    dtr = p["dt_proj"].shape[0]
    proj = u @ p["x_proj"].astype(u.dtype)
    dt_lowrank, b_in, c_in = jnp.split(proj, [dtr, dtr + cfg.ssm_state], axis=-1)
    dt = jax.nn.softplus(dt_lowrank @ p["dt_proj"].astype(u.dtype)
                         + p["dt_bias"].astype(u.dtype))
    if ssm_impl == "kernel":
        from repro.kernels import ops as kops
        y, _ = kops.ssm_scan(u, dt, b_in, c_in, p["a_log"], p["d_skip"])
    else:
        y, _ = selective_scan(u, dt, b_in, c_in, p["a_log"], p["d_skip"],
                              unroll=cfg.unroll)
    y = y.astype(x.dtype) * jax.nn.silu(z)
    return y @ p["out_proj"].astype(x.dtype)


def mamba_decode_state(cfg: ModelConfig, batch: int, d_inner: Optional[int] = None,
                       dtype=jnp.float32) -> Dict:
    di = d_inner or cfg.d_inner
    return {"h": jnp.zeros((batch, di, cfg.ssm_state), jnp.float32),
            "conv": jnp.zeros((batch, cfg.ssm_conv - 1, di), dtype)}


def apply_mamba_decode(p, x, state: Dict, cfg: ModelConfig
                       ) -> Tuple[jnp.ndarray, Dict]:
    """One-token step. x: (B, 1, d); state: {h, conv}."""
    uz = x @ p["in_proj"].astype(x.dtype)
    u, z = jnp.split(uz, 2, axis=-1)
    u, conv_new = _causal_conv(u, p["conv_w"], p["conv_b"], state["conv"])
    u = jax.nn.silu(u)
    dtr = p["dt_proj"].shape[0]
    proj = u @ p["x_proj"].astype(u.dtype)
    dt_lowrank, b_in, c_in = jnp.split(proj, [dtr, dtr + cfg.ssm_state], axis=-1)
    dt = jax.nn.softplus(dt_lowrank @ p["dt_proj"].astype(u.dtype)
                         + p["dt_bias"].astype(u.dtype))
    a = -jnp.exp(p["a_log"].astype(jnp.float32))
    dtf = dt[:, 0].astype(jnp.float32)                  # (B, di)
    a_bar = jnp.exp(dtf[..., None] * a)                 # (B, di, st)
    bu = (dtf * u[:, 0].astype(jnp.float32))[..., None] * \
        b_in[:, 0].astype(jnp.float32)[:, None, :]
    h = a_bar * state["h"] + bu
    y = jnp.einsum("bds,bs->bd", h, c_in[:, 0].astype(jnp.float32))
    y = y + u[:, 0].astype(jnp.float32) * p["d_skip"].astype(jnp.float32)
    y = y[:, None].astype(x.dtype) * jax.nn.silu(z)
    return y @ p["out_proj"].astype(x.dtype), {"h": h, "conv": conv_new}
