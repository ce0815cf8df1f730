"""GPipe-style pipeline parallelism over a ``stage`` mesh axis.

``split_stages`` reshapes every scanned-parameter leaf ``(L, ...)`` into
``(S, L/S, ...)`` so stage ``s`` owns layer group ``s``. ``pipeline_apply``
runs the classic microbatch schedule: M microbatches flow through S stages
in M + S - 1 ticks; stage 0 injects a fresh microbatch each tick, every
stage applies its layer group, activations shift one stage forward via
``ppermute``, and the last stage collects results. The bubble fraction is
(S-1)/(M+S-1), as in the paper (Huang et al., 2019).

The stage function must be shape- and dtype-preserving on activations
(hidden-state in, hidden-state out), which is what a layer group is.
"""
from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P


def split_stages(params: Any, num_stages: int) -> Any:
    """(L, ...) leaves -> (num_stages, L/num_stages, ...) leaves."""
    def split(x):
        if x.shape[0] % num_stages:
            raise ValueError(
                f"leading dim {x.shape[0]} not divisible by {num_stages} stages")
        return x.reshape((num_stages, x.shape[0] // num_stages) + x.shape[1:])
    return jax.tree.map(split, params)


def pipeline_apply(fn: Callable[[Any, jnp.ndarray], jnp.ndarray],
                   staged_params: Any, x: jnp.ndarray, *,
                   mesh, microbatches: int,
                   stage_axis: str = "stage") -> jnp.ndarray:
    """Apply ``fn(stage_params, h) -> h`` through all stages of ``mesh``.

    ``staged_params`` leaves carry a leading stage dim (from
    :func:`split_stages`); ``x`` is the full batch, split into
    ``microbatches`` along dim 0 (must divide the batch).
    """
    num_stages = mesh.shape[stage_axis]
    batch = x.shape[0]
    if batch % microbatches:
        raise ValueError(f"batch {batch} not divisible by {microbatches}")
    xs = x.reshape((microbatches, batch // microbatches) + x.shape[1:])
    shift = [(i, i + 1) for i in range(num_stages - 1)]

    def local_fn(params, xs):
        params = jax.tree.map(lambda a: a[0], params)   # drop local stage dim
        stage = lax.axis_index(stage_axis)
        acts0 = jnp.zeros(xs.shape[1:], xs.dtype)
        out0 = jnp.zeros(xs.shape, xs.dtype)

        def tick(carry, t):
            acts, out = carry
            inject = xs[jnp.clip(t, 0, microbatches - 1)]
            h = jnp.where(stage == 0, inject, acts)
            y = fn(params, h)
            idx = t - (num_stages - 1)                  # microbatch draining
            collect = (stage == num_stages - 1) & (idx >= 0)
            out = jnp.where(collect, out.at[jnp.clip(idx, 0)].set(y), out)
            y = lax.ppermute(y, stage_axis, shift)      # hand to next stage
            return (y, out), None

        ticks = jnp.arange(microbatches + num_stages - 1)
        (_, out), _ = lax.scan(tick, (acts0, out0), ticks)
        # only the last stage holds real outputs; replicate them everywhere
        keep = (stage == num_stages - 1).astype(out.dtype)
        return lax.psum(out * keep, stage_axis)

    stage_spec = jax.tree.map(lambda _: P(stage_axis), staged_params)
    result = jax.shard_map(local_fn, mesh=mesh,
                           in_specs=(stage_spec, P()), out_specs=P(),
                           check_vma=False)(staged_params, xs)
    return result.reshape((batch,) + x.shape[1:])
