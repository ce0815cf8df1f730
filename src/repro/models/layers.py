"""Shared neural building blocks (pure functions, params as pytrees).

Conventions:
  * activations ``x`` are (batch, seq, d_model) in ``cfg.dtype`` (bf16),
  * params are fp32 leaves in nested dicts; scanned stacks add a leading
    layer axis,
  * attention is computed with a blocked online-softmax scan (flash-style,
    pure lax) so the T x T score matrix is never materialized — the Pallas
    kernel in repro.kernels.flash_attn is the TPU-tiled version of the same
    algorithm and is swapped in by ops.attention when enabled.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import ModelConfig

# ---------------------------------------------------------------------------
# init helpers
# ---------------------------------------------------------------------------

def _dense_init(key, shape, scale: Optional[float] = None, dtype=jnp.float32):
    fan_in = shape[0] if len(shape) <= 2 else math.prod(shape[:-1])
    scale = scale if scale is not None else 1.0 / math.sqrt(max(1, fan_in))
    return (jax.random.normal(key, shape) * scale).astype(dtype)


def embed_init(key, vocab: int, d: int, dtype=jnp.float32):
    return (jax.random.normal(key, (vocab, d)) * 0.02).astype(dtype)


# ---------------------------------------------------------------------------
# normalization
# ---------------------------------------------------------------------------

def norm_init(cfg: ModelConfig, d: Optional[int] = None):
    d = d or cfg.d_model
    p = {"scale": jnp.ones((d,), jnp.float32)}
    if cfg.norm == "ln":
        p["bias"] = jnp.zeros((d,), jnp.float32)
    return p


def apply_norm(p, x, cfg: ModelConfig, eps: float = 1e-5):
    xf = x.astype(jnp.float32)
    if cfg.norm == "ln":
        mu = xf.mean(-1, keepdims=True)
        var = ((xf - mu) ** 2).mean(-1, keepdims=True)
        out = (xf - mu) * lax.rsqrt(var + eps) * p["scale"] + p["bias"]
    else:
        ms = (xf * xf).mean(-1, keepdims=True)
        out = xf * lax.rsqrt(ms + eps) * p["scale"]
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# rotary embeddings
# ---------------------------------------------------------------------------

def rope_freqs(cfg: ModelConfig, rot_dim: int) -> jnp.ndarray:
    exponent = jnp.arange(0, rot_dim, 2, dtype=jnp.float32) / rot_dim
    return 1.0 / (cfg.rope_theta ** exponent)            # (rot_dim/2,)


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, cfg: ModelConfig
               ) -> jnp.ndarray:
    """Rotate the first ``rot_dim`` dims of each head.

    cfg.rope == "full": rot_dim = head_dim (llama/qwen style).
    cfg.rope == "half": rot_dim = head_dim // 2 (chatglm's 2d/partial rotary).
    x: (B, T, H, dh); positions: (B, T) int32.
    """
    if cfg.rope == "none":
        return x
    dh = x.shape[-1]
    rot = dh if cfg.rope == "full" else dh // 2
    inv = rope_freqs(cfg, rot)
    theta = positions[..., None].astype(jnp.float32) * inv   # (B,T,rot/2)
    cos = jnp.cos(theta)[:, :, None, :]
    sin = jnp.sin(theta)[:, :, None, :]
    xr, xp = x[..., :rot], x[..., rot:]
    x1, x2 = xr[..., 0::2], xr[..., 1::2]
    r1 = x1 * cos - x2 * sin
    r2 = x2 * cos + x1 * sin
    out = jnp.stack([r1, r2], axis=-1).reshape(xr.shape)
    return jnp.concatenate([out, xp], axis=-1).astype(x.dtype)


# ---------------------------------------------------------------------------
# attention (blocked online-softmax; GQA; causal + optional sliding window)
# ---------------------------------------------------------------------------

def attention_params(key, cfg: ModelConfig, dtype=jnp.float32):
    d, h, kv, dh = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 4)
    p = {
        "wq": _dense_init(ks[0], (d, h, dh), dtype=dtype),
        "wk": _dense_init(ks[1], (d, kv, dh), dtype=dtype),
        "wv": _dense_init(ks[2], (d, kv, dh), dtype=dtype),
        "wo": _dense_init(ks[3], (h, dh, d), scale=1.0 / math.sqrt(h * dh),
                          dtype=dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((h, dh), dtype)
        p["bk"] = jnp.zeros((kv, dh), dtype)
        p["bv"] = jnp.zeros((kv, dh), dtype)
    return p


def qkv_project(p, x, cfg: ModelConfig, positions):
    q = jnp.einsum("btd,dhk->bthk", x, p["wq"].astype(x.dtype))
    k = jnp.einsum("btd,dhk->bthk", x, p["wk"].astype(x.dtype))
    v = jnp.einsum("btd,dhk->bthk", x, p["wv"].astype(x.dtype))
    if cfg.qkv_bias:
        q = q + p["bq"].astype(x.dtype)
        k = k + p["bk"].astype(x.dtype)
        v = v + p["bv"].astype(x.dtype)
    q = apply_rope(q, positions, cfg)
    k = apply_rope(k, positions, cfg)
    return q, k, v


def flash_attention_lax(q, k, v, *, causal: bool = True,
                        window: Optional[int] = None,
                        q_offset: int = 0,
                        block_q: int = 512, block_k: int = 512,
                        scale: Optional[float] = None,
                        unroll: bool = False,
                        scale_in_q: bool = False,
                        probs_bf16: bool = False) -> jnp.ndarray:
    """Blocked attention with online softmax — O(T) memory, pure lax.

    q: (B, Tq, H, dh); k, v: (B, Tk, KV, dh) with H % KV == 0.
    ``q_offset`` is the absolute position of q[0] (prefill continuation).
    A kv block whose mask is entirely zero is still computed (static grid) —
    the Pallas kernel version skips them; roofline treats this as the
    reference cost.
    """
    b, tq, h, dh = q.shape
    tk, kvh = k.shape[1], k.shape[2]
    dv = v.shape[-1]                    # may differ from dh (MLA)
    g = h // kvh
    scale = scale if scale is not None else 1.0 / math.sqrt(dh)
    if unroll:          # cost-exact mode: single-trip kv loop (counted fully)
        block_k = tk
    block_q = min(block_q, tq)
    block_k = min(block_k, tk)
    nq, nk = -(-tq // block_q), -(-tk // block_k)
    # pad to block multiples
    qp = jnp.pad(q, ((0, 0), (0, nq * block_q - tq), (0, 0), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, nk * block_k - tk), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, nk * block_k - tk), (0, 0), (0, 0)))
    qp = qp.reshape(b, nq, block_q, kvh, g, dh)
    if scale_in_q:
        qp = (qp.astype(jnp.float32) * scale).astype(q.dtype)
    kp = kp.reshape(b, nk, block_k, kvh, dh)
    vp = vp.reshape(b, nk, block_k, kvh, dv)
    q_pos = q_offset + jnp.arange(nq * block_q).reshape(nq, block_q)
    k_pos = jnp.arange(nk * block_k).reshape(nk, block_k)

    def kv_step(carry, kv_idx):
        m, l, acc = carry          # (b,nq,bq,kvh,g), same, (...,dh)
        kb = kp[:, kv_idx]         # (b, bk, kvh, dh)
        vb = vp[:, kv_idx]
        s = jnp.einsum("bnqhgd,bkhd->bnqhgk", qp, kb).astype(jnp.float32)
        if not scale_in_q:
            s = s * scale
        qpos = q_pos[:, :, None]                       # (nq, bq, 1)
        kpos = k_pos[kv_idx][None, None, :]            # (1, 1, bk)
        mask = (kpos <= qpos) if causal else jnp.ones_like(kpos <= qpos)
        if window is not None:
            mask &= (qpos - kpos) < window
        mask &= kpos < tk                              # exclude kv padding
        s = jnp.where(mask[None, :, :, None, None, :], s, -1e30)
        m_new = jnp.maximum(m, s.max(-1))
        alpha = jnp.exp(m - m_new)
        if probs_bf16:       # keep the (.., bk)-sized probs in bf16; f32 stats
            p_ = jnp.exp((s - m_new[..., None]).astype(jnp.bfloat16))
            l_new = l * alpha + p_.sum(-1, dtype=jnp.float32)
            pv = p_.astype(vb.dtype)
        else:
            p_ = jnp.exp(s - m_new[..., None])
            l_new = l * alpha + p_.sum(-1)
            pv = p_.astype(vb.dtype)
        acc_new = acc * alpha[..., None] + jnp.einsum(
            "bnqhgk,bkhd->bnqhgd", pv, vb).astype(jnp.float32)
        return (m_new, l_new, acc_new), None

    init = (jnp.full((b, nq, block_q, kvh, g), -1e30, jnp.float32),
            jnp.zeros((b, nq, block_q, kvh, g), jnp.float32),
            jnp.zeros((b, nq, block_q, kvh, g, dv), jnp.float32))
    # Remat each kv block: the backward pass keeps the (m, l, acc) carries
    # and rebuilds one block's scores at a time, not all nk of them.
    (m, l, acc), _ = lax.scan(jax.checkpoint(kv_step), init, jnp.arange(nk))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    out = out.reshape(b, nq * block_q, kvh * g, dv)[:, :tq]
    return out.astype(q.dtype)


def attention_out(p, attn, x_dtype):
    return jnp.einsum("bthk,hkd->btd", attn,
                      p["wo"].astype(attn.dtype)).astype(x_dtype)


def decode_attention(q, k_cache, v_cache, cache_len, *,
                     window: Optional[int] = None,
                     scale: Optional[float] = None) -> jnp.ndarray:
    """Single-position attention against a (B, S, KV, dh) cache.

    ``cache_len``: number of valid positions (int32 scalar or (B,)).
    """
    b, tq, h, dh = q.shape
    kvh = k_cache.shape[2]
    g = h // kvh
    scale = scale if scale is not None else 1.0 / math.sqrt(dh)
    qg = q.reshape(b, tq, kvh, g, dh)
    s = jnp.einsum("bthgd,bshd->bthgs", qg, k_cache).astype(jnp.float32) * scale
    pos = jnp.arange(k_cache.shape[1])
    valid = pos[None, :] < jnp.reshape(cache_len, (-1, 1))
    if window is not None:
        valid &= pos[None, :] >= (jnp.reshape(cache_len, (-1, 1)) - window)
    s = jnp.where(valid[:, None, None, None, :], s, -1e30)
    p_ = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    out = jnp.einsum("bthgs,bshd->bthgd", p_, v_cache)
    return out.reshape(b, tq, h, dh)


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------

def mlp_params(key, cfg: ModelConfig, d_ff: Optional[int] = None,
               dtype=jnp.float32):
    d = cfg.d_model
    f = d_ff or cfg.d_ff
    ks = jax.random.split(key, 3)
    if cfg.mlp == "swiglu":
        return {"wi": _dense_init(ks[0], (d, f), dtype=dtype),
                "wg": _dense_init(ks[1], (d, f), dtype=dtype),
                "wo": _dense_init(ks[2], (f, d), dtype=dtype)}
    return {"wi": _dense_init(ks[0], (d, f), dtype=dtype),
            "wo": _dense_init(ks[2], (f, d), dtype=dtype)}


def apply_mlp(p, x, cfg: ModelConfig):
    wi = p["wi"].astype(x.dtype)
    wo = p["wo"].astype(x.dtype)
    h = x @ wi
    if cfg.mlp == "swiglu":
        h = jax.nn.silu(h) * (x @ p["wg"].astype(x.dtype))
    elif cfg.mlp == "gelu":
        h = jax.nn.gelu(h)
    elif cfg.mlp == "sqrelu":
        r = jax.nn.relu(h)
        h = r * r
    else:
        raise ValueError(cfg.mlp)
    return h @ wo


# ---------------------------------------------------------------------------
# logits / loss
# ---------------------------------------------------------------------------

def chunked_cross_entropy(hidden: jnp.ndarray, embed: jnp.ndarray,
                          labels: jnp.ndarray, *, chunk: int = 2048,
                          mask: Optional[jnp.ndarray] = None,
                          unroll: bool = False) -> jnp.ndarray:
    """Mean CE without materializing the full (tokens, vocab) logits.

    hidden: (B, T, d); embed: (V, d); labels: (B, T) int32; mask (B, T) or
    None. Scans over token chunks; each chunk's logits are (chunk, V) only.
    """
    b, t, d = hidden.shape
    n = b * t
    hf = hidden.reshape(n, d)
    lf = labels.reshape(n)
    mf = jnp.ones((n,), jnp.float32) if mask is None else \
        mask.reshape(n).astype(jnp.float32)
    if unroll:          # cost-exact mode: single-trip CE loop
        chunk = n
    chunk = min(chunk, n)
    pad = (-n) % chunk
    if pad:
        hf = jnp.pad(hf, ((0, pad), (0, 0)))
        lf = jnp.pad(lf, (0, pad))
        mf = jnp.pad(mf, (0, pad))
    hc = hf.reshape(-1, chunk, d)
    lc = lf.reshape(-1, chunk)
    mc = mf.reshape(-1, chunk)
    et = embed.astype(hidden.dtype).T           # (d, V)

    def step(carry, xs):
        h, l, m = xs
        logits = (h @ et).astype(jnp.float32)
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, l[:, None], axis=-1)[:, 0]
        ce = (logz - gold) * m
        return carry + ce.sum(), None

    # checkpoint: the (chunk, V) logits are recomputed in backward instead of
    # being stored once per chunk (that storage would dominate peak memory).
    step = jax.checkpoint(step, policy=jax.checkpoint_policies.nothing_saveable)
    total, _ = lax.scan(step, jnp.zeros((), jnp.float32), (hc, lc, mc))
    return total / jnp.maximum(mf.sum(), 1.0)
