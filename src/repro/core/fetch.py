"""Batched sample fetch over the mesh — the FanStore data plane on ICI.

Semantics: the dataset is an (S, B) array of fixed-size sample records,
sharded (S over ``data``, B over ``model``); a step's global batch is a
vector of G sample indices sharded over (``pod``, ``data``). ``fetch``
returns the (G, B) payload batch with the same index order, sharded
(G over (pod, data), B over model).

Routing is MoE-style dispatch with storage shards as "experts":

  1. all_gather the request ids within the data axis (tiny: G ints),
  2. every shard gathers the records it owns for every requester and
     scatters them into a (D, capacity, B/M) send buffer,
  3. one all_to_all flips owner->requester,
  4. requesters scatter received records into batch-slot order.

Capacity: with uniform-random requests, each (owner, requester) pair gets
Binomial(G/D, 1/D) records; ``capacity_factor`` pads above the mean. The
overflow flag reports drops (training treats it like the paper treats a
failed read: deterministic, observable). The stratified sampler
(repro.data.sampler.StratifiedSampler) guarantees exactly G/D^2 per pair, so
capacity_factor=1.0 gives a zero-waste, zero-drop exchange — the beyond-paper
configuration measured in EXPERIMENTS.md.

Pods: by default the store is replicated per pod (paper's replication factor
R = n_pods) so the exchange never crosses the pod boundary; set
``shard_over_pods=True`` to split S over (pod, data) and let the all_to_all
span both axes (for datasets too large for one pod's HBM).
"""
from __future__ import annotations

import math
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def required_capacity(local_batch: int, num_shards: int,
                      capacity_factor: float) -> int:
    """Per-(owner,requester) record slots: ceil(cf * G_local / D)."""
    return max(1, math.ceil(capacity_factor * local_batch / num_shards))


def make_fetch_fn(mesh: Mesh, *, num_samples: int, sample_bytes: int,
                  data_axis: str = "data", model_axis: Optional[str] = "model",
                  pod_axis: Optional[str] = None,
                  capacity_factor: float = 2.0,
                  dtype=jnp.uint8):
    """Build a jit-able ``fetch(store, idx) -> (batch, overflow)``.

    store: (S, B) sharded P((pod?, data), model)  [pod only if shard_over_pods]
    idx:   (G,)  int32 sharded P((pod, data))
    batch: (G, B) sharded P((pod, data), model)
    overflow: (num_batch_shards,) bool, one flag per (pod, data) shard.
    """
    axis_sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    fetch_axes: Tuple[str, ...] = (data_axis,) if pod_axis is None \
        else (pod_axis, data_axis)
    D = 1
    for a in fetch_axes:
        D *= axis_sizes[a]
    if num_samples % D:
        raise ValueError(f"num_samples {num_samples} must divide {D} shards")
    s_local = num_samples // D

    batch_axes = tuple(a for a in (pod_axis, data_axis) if a is not None) \
        if pod_axis is not None else (data_axis,)
    # When the store is pod-replicated, requests are still pod-sharded: the
    # exchange happens independently inside each pod's replica.
    store_spec = P(fetch_axes if pod_axis is not None else data_axis, model_axis)
    idx_spec = P(batch_axes)
    out_spec = (P(batch_axes, model_axis), P(batch_axes))

    def local_fn(store_l, idx_l):
        # store_l: (s_local, B_local); idx_l: (g_local,)
        g_local = idx_l.shape[0]
        cap = required_capacity(g_local, D, capacity_factor)
        d = lax.axis_index(fetch_axes)           # linearized shard id
        all_req = lax.all_gather(idx_l, fetch_axes, tiled=False)  # (D, g_local)
        all_req = all_req.reshape(D, g_local)
        owner = all_req // s_local                # (D, g_local)
        mine = owner == d
        local_row = jnp.where(mine, all_req - d * s_local, 0)
        payload = jnp.take(store_l, local_row.reshape(-1), axis=0)
        payload = payload.reshape(D, g_local, -1)             # (D, g, B_l)
        pos = jnp.cumsum(mine.astype(jnp.int32), axis=1) - 1  # (D, g)
        slot = jnp.where(mine & (pos < cap), pos, cap)        # cap = drop
        send = jnp.zeros((D, cap) + payload.shape[2:], dtype=payload.dtype)
        send = jax.vmap(lambda b, s, p: b.at[s].set(p, mode="drop"))(
            send, slot, payload)
        col = jnp.broadcast_to(jnp.arange(g_local, dtype=jnp.int32)[None],
                               (D, g_local))
        send_slots = jnp.full((D, cap), -1, jnp.int32)
        send_slots = jax.vmap(lambda b, s, c: b.at[s].set(c, mode="drop"))(
            send_slots, slot, col)
        axis = fetch_axes[0] if len(fetch_axes) == 1 else fetch_axes
        recv = lax.all_to_all(send, axis, 0, 0, tiled=False)
        recv_slots = lax.all_to_all(send_slots, axis, 0, 0, tiled=False)
        out = jnp.zeros((g_local,) + payload.shape[2:], dtype=payload.dtype)
        tgt = jnp.where(recv_slots >= 0, recv_slots, g_local).reshape(-1)
        out = out.at[tgt].set(recv.reshape((-1,) + payload.shape[2:]),
                              mode="drop")
        overflow = (jnp.sum(mine, axis=1) > cap).any()
        return out, overflow[None]

    shmap = jax.shard_map(local_fn, mesh=mesh,
                          in_specs=(store_spec, idx_spec),
                          out_specs=out_spec, check_vma=False)

    def fetch(store: jax.Array, idx: jax.Array):
        return shmap(store, idx)

    fetch.store_spec = store_spec          # type: ignore[attr-defined]
    fetch.idx_spec = idx_spec              # type: ignore[attr-defined]
    fetch.out_specs = out_spec             # type: ignore[attr-defined]
    fetch.num_shards = D                   # type: ignore[attr-defined]
    fetch.samples_per_shard = s_local      # type: ignore[attr-defined]
    return fetch


def tokens_from_payload(batch_u8: jax.Array, seq_len: int) -> jax.Array:
    """Bitcast fetched uint8 payload records to int32 token sequences."""
    b = batch_u8.shape[0]
    return lax.bitcast_convert_type(
        batch_u8.reshape(b, seq_len, 4), jnp.int32).reshape(b, seq_len)
