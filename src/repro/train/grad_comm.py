"""Compressed gradient all-reduce (int8 wire) with error feedback.

The all-reduce is decomposed as reduce-scatter + all-gather, both carried
over the wire in int8 (4x fewer collective bytes than fp32, 2x vs bf16):

  1. flatten grads -> (D, chunk) layout; quantize per-chunk (absmax scale,
     error-feedback residual folded in before rounding),
  2. all_to_all the int8 chunks (this IS the reduce-scatter's data motion),
  3. each device sums its received column in fp32 -> its reduced shard,
  4. re-quantize the shard and all_gather int8 + scales,
  5. dequantize, unflatten, divide by D.

Error feedback keeps the quantization *unbiased over time*: the residual
(what rounding lost this step) is added to next step's gradient, which is
what keeps convergence intact at int8 (1-bit Adam lineage). The residual
pytree is threaded through the train step as part of TrainState.
"""
from __future__ import annotations

from functools import partial
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P


def quantize_ef(x: jnp.ndarray, residual: Optional[jnp.ndarray], *,
                axis: int = -1) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """int8 absmax quantization with error feedback.

    Returns (q int8, scale f32 (per leading slice), new_residual)."""
    xf = x.astype(jnp.float32)
    if residual is not None:
        xf = xf + residual
    absmax = jnp.max(jnp.abs(xf), axis=axis, keepdims=True)
    scale = jnp.where(absmax > 0, absmax / 127.0, 1.0)
    q = jnp.clip(jnp.rint(xf / scale), -127, 127).astype(jnp.int8)
    new_res = xf - q.astype(jnp.float32) * scale
    return q, scale, new_res


def _flatten_grads(grads: Any) -> Tuple[jnp.ndarray, Any, list]:
    leaves, tdef = jax.tree.flatten(grads)
    flat = jnp.concatenate([l.reshape(-1).astype(jnp.float32) for l in leaves])
    shapes = [(l.shape, l.dtype) for l in leaves]
    return flat, tdef, shapes


def _unflatten_grads(flat: jnp.ndarray, tdef, shapes) -> Any:
    out = []
    off = 0
    for shape, dtype in shapes:
        n = 1
        for s in shape:
            n *= s
        out.append(flat[off: off + n].reshape(shape).astype(dtype))
        off += n
    return jax.tree.unflatten(tdef, out)


def make_compressed_psum(mesh: Mesh, axes: Tuple[str, ...]):
    """Build ``cpsum(flat_grads, residual) -> (mean_grads, new_residual)``.

    ``flat_grads``: (N,) fp32, replicated over ``axes`` is WRONG input — it
    must be the *local* (unsummed) gradient, identical shape per device.
    Runs inside shard_map; callers use :func:`compressed_psum` below.
    """
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    world = 1
    for a in axes:
        world *= sizes[a]
    ax = axes[0] if len(axes) == 1 else axes

    def local_fn(flat, res):
        n = flat.shape[0]
        chunk = -(-n // world)
        pad = chunk * world - n
        flat_p = jnp.pad(flat, (0, pad)).reshape(world, chunk)
        res_p = jnp.pad(res, (0, pad)).reshape(world, chunk)
        # 1) quantize my contribution per destination chunk (+EF)
        q, scale, new_res = quantize_ef(flat_p, res_p, axis=-1)
        # 2) reduce-scatter data motion: int8 chunks + f32 scales
        q_rx = lax.all_to_all(q, ax, 0, 0, tiled=False).reshape(world, chunk)
        s_rx = lax.all_to_all(scale, ax, 0, 0, tiled=False).reshape(world, 1)
        # 3) local fp32 reduction of my shard
        shard = jnp.sum(q_rx.astype(jnp.float32) * s_rx, axis=0)   # (chunk,)
        # 4) second quantization + all-gather (no EF: error is transient)
        q2, scale2, _ = quantize_ef(shard[None], None, axis=-1)
        qg = lax.all_gather(q2[0], ax, tiled=False).reshape(world, chunk)
        sg = lax.all_gather(scale2[0], ax, tiled=False).reshape(world, 1)
        total = (qg.astype(jnp.float32) * sg).reshape(-1)[:n]
        return total / world, new_res.reshape(-1)[:n]

    return jax.shard_map(local_fn, mesh=mesh,
                         in_specs=(P(), P()), out_specs=(P(), P()),
                         check_vma=False)


def compressed_psum(grads: Any, residual: Any, mesh: Mesh,
                    axes: Tuple[str, ...]) -> Tuple[Any, Any]:
    """Mean-reduce a gradient pytree over ``axes`` with an int8 wire.

    ``residual``: same-structure fp32 pytree (error feedback), or zeros.
    NOTE: inputs must be unreduced per-device gradients with identical
    pytree structure; use inside jit under the mesh.
    """
    flat, tdef, shapes = _flatten_grads(grads)
    res_flat, _, _ = _flatten_grads(residual)
    cpsum = make_compressed_psum(mesh, axes)
    out, new_res = cpsum(flat, res_flat)
    return _unflatten_grads(out, tdef, shapes), \
        _unflatten_grads(new_res, tdef, [(s, jnp.float32) for s, _ in shapes])
