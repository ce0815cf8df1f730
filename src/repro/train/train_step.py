"""Train-step factory: grad accumulation, remat, and two gradient-sync modes.

  grad_sync="auto"  — GSPMD inserts the (bf16/fp32) gradient all-reduce that
                      falls out of the batch sharding. Paper-faithful
                      baseline: FanStore does not touch gradient traffic.
  grad_sync="int8"  — beyond-paper: the step runs inside shard_map over the
                      data axes (model axis stays GSPMD-auto) and gradients
                      are mean-reduced by repro.train.grad_comm's int8
                      reduce-scatter/all-gather with error feedback. 4x
                      fewer collective bytes than fp32, 2x vs bf16; §Perf
                      quantifies against the roofline collective term.

Microbatching (grad accumulation) runs as a lax.scan over microbatch slices
with fp32 accumulators — compute of microbatch i overlaps XLA's scheduling
of the previous slice's collectives.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.train.grad_comm import make_compressed_psum, _flatten_grads, \
    _unflatten_grads
from repro.train.optimizer import OptimizerConfig, adamw_init, adamw_update


@dataclass
class TrainState:
    params: Any
    opt: Dict
    ef: Optional[jnp.ndarray] = None     # flat error-feedback residual (int8 mode)

    def tree_flatten(self):
        return (self.params, self.opt, self.ef), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


jax.tree_util.register_pytree_node(
    TrainState, TrainState.tree_flatten, TrainState.tree_unflatten)


def init_state(model, key, opt_cfg: OptimizerConfig, *,
               grad_sync: str = "auto") -> TrainState:
    params = model.init(key)
    opt = adamw_init(params)
    ef = None
    if grad_sync == "int8":
        n = sum(int(p.size) for p in jax.tree.leaves(params))
        ef = jnp.zeros((n,), jnp.float32)
    return TrainState(params=params, opt=opt, ef=ef)


def _microbatch(batch: Dict, m: int) -> Dict:
    def split(x):
        g = x.shape[0]
        return x.reshape(m, g // m, *x.shape[1:])
    return {k: split(v) for k, v in batch.items()}


def _accumulate_grads(loss_fn, params, batch: Dict, m: int):
    """lax.scan over microbatches; returns (mean_loss, mean_grads, aux)."""
    if m == 1:
        (loss, metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            params, batch)
        return loss, grads, metrics
    micro = _microbatch(batch, m)
    zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)

    def step(carry, mb):
        acc, loss_acc = carry
        (loss, _), grads = jax.value_and_grad(loss_fn, has_aux=True)(params, mb)
        acc = jax.tree.map(lambda a, g: a + g.astype(jnp.float32), acc, grads)
        return (acc, loss_acc + loss), None

    (grads, loss_sum), _ = lax.scan(step, (zeros, jnp.zeros(())), micro)
    inv = 1.0 / m
    grads = jax.tree.map(lambda g: g * inv, grads)
    return loss_sum * inv, grads, {}


def make_train_step(model, opt_cfg: OptimizerConfig, *,
                    mesh: Optional[Mesh] = None,
                    dp_axes: Tuple[str, ...] = ("data",),
                    grad_sync: str = "auto",
                    microbatches: int = 1,
                    loss_fn: Optional[Callable] = None
                    ) -> Callable:
    """Returns ``step(state, batch) -> (state, metrics)`` (jit-able)."""
    base_loss = loss_fn or (lambda p, b: model.loss(p, b))

    def _loss(p, b):
        loss, metrics = base_loss(p, b)
        return loss, metrics

    if grad_sync == "auto":
        def step(state: TrainState, batch: Dict):
            loss, grads, _ = _accumulate_grads(_loss, state.params, batch,
                                               microbatches)
            params, opt, om = adamw_update(opt_cfg, state.params, grads,
                                           state.opt)
            metrics = {"loss": loss, **om}
            return TrainState(params, opt, state.ef), metrics
        return step

    if grad_sync != "int8":
        raise ValueError(grad_sync)
    if mesh is None:
        raise ValueError("int8 grad sync needs the mesh")
    auto_axes = frozenset(a for a in mesh.axis_names if a not in dp_axes)
    ax = dp_axes[0] if len(dp_axes) == 1 else dp_axes
    cp_inner = None  # built lazily inside (needs shard count only)

    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    world = 1
    for a in dp_axes:
        world *= sizes[a]

    def local_step(state: TrainState, batch: Dict):
        # per-dp-shard gradients: batch is the LOCAL slice in here
        loss, grads, _ = _accumulate_grads(_loss, state.params, batch,
                                           microbatches)
        flat, tdef, shapes = _flatten_grads(grads)
        n = flat.shape[0]
        chunk = -(-n // world)
        pad = chunk * world - n
        flat_p = jnp.pad(flat, (0, pad)).reshape(world, chunk)
        res_p = jnp.pad(state.ef, (0, pad)).reshape(world, chunk)
        from repro.train.grad_comm import quantize_ef
        q, scale, new_res = quantize_ef(flat_p, res_p, axis=-1)
        q_rx = lax.all_to_all(q, ax, 0, 0, tiled=False).reshape(world, chunk)
        s_rx = lax.all_to_all(scale, ax, 0, 0, tiled=False).reshape(world, 1)
        shard = jnp.sum(q_rx.astype(jnp.float32) * s_rx, axis=0)
        q2, scale2, _ = quantize_ef(shard[None], None, axis=-1)
        qg = lax.all_gather(q2[0], ax, tiled=False).reshape(world, chunk)
        sg = lax.all_gather(scale2[0], ax, tiled=False).reshape(world, 1)
        mean = ((qg.astype(jnp.float32) * sg).reshape(-1)[:n]) / world
        grads = _unflatten_grads(mean, tdef, shapes)
        params, opt, om = adamw_update(opt_cfg, state.params, grads, state.opt)
        loss = lax.pmean(loss, ax)
        metrics = {"loss": loss, **om}
        return TrainState(params, opt, new_res.reshape(-1)[:n]), metrics

    def step(state: TrainState, batch: Dict):
        state_specs = TrainState(
            params=jax.tree.map(lambda _: P(), state.params),
            opt=jax.tree.map(lambda _: P(), state.opt),
            ef=P())
        batch_specs = {k: P(dp_axes) for k in batch}
        out = jax.shard_map(
            local_step, mesh=mesh,
            in_specs=(state_specs, batch_specs),
            out_specs=(state_specs, {"loss": P(), "grad_norm": P(), "lr": P()}),
            check_vma=False, axis_names=set(dp_axes))(state, batch)
        return out

    return step
