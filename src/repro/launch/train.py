"""End-to-end training driver: FanStore data plane + model + checkpoints.

Runs for real on this CPU container with the reduced (smoke) configs and on
TPU with the full ones — the driver code is identical; only --preset and the
mesh change. Demonstrates the whole system:

  dataset -> fanstore partitions -> ClusterSpec topology (simulated
  nodes x co-located workers, pluggable transport backend via --backend:
  modeled / socket / shm) -> one cluster.connect() FanStoreSession per
  (node, worker) sharing each node's cache tier ->
  PrefetchLoader (threads; --prefetch-schedule switches it to the
  clairvoyant schedule-driven mode: the epoch permutation materialized
  from the sampler's peek_epoch() rides ahead of compute in
  window-coalesced round trips, driven by one PrefetchScheduler per
  (node, worker) — every node keeps its own windows in flight; there is
  no node-0 pin) ->
  [optional device-store all_to_all fetch] ->
  train_step (auto or int8 grad sync) -> CheckpointManager -> resume

Checkpoints can additionally stream through the FanStore engine itself
(--ckpt-fanstore): shards chunk through the session's CheckpointWriter on
the concurrent write lane, so the modeled clocks show checkpoint I/O
overlapped with the data plane instead of serialized in front of it.

Usage (CPU example, ~1 minute):
  PYTHONPATH=src python -m repro.launch.train --arch qwen2-72b \
      --preset smoke --steps 30 --global-batch 16 --seq-len 64

``run(args)`` is the whole driver and returns what it observed (per-step
losses and times, the step's compile time); ``chip_smoke.py`` calls it at
a published width with ``--preset full --layers N``.
"""
from __future__ import annotations

import argparse
import time
from typing import Callable, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ARCH_IDS, get_config, get_smoke
from repro.data.pipeline import PrefetchLoader
from repro.data.sampler import GlobalUniformSampler, StratifiedSampler
from repro.data.synthetic import files_to_tokens, token_dataset, tokens_to_files
from repro.fanstore.cluster import FanStoreCluster
from repro.fanstore.metrics import SPANS, JsonlSink, Reduce, fold_spans
from repro.fanstore.prefetch import EpochSchedule, SchedulerGroup
from repro.fanstore.spec import ClusterSpec
from repro.fanstore.prepare import prepare_dataset
from repro.launch.compile_cache import enable_compile_cache
from repro.models import build_model
from repro.train.checkpoint import (CheckpointManager, restore_checkpoint,
                                    save_to_session)
from repro.train.optimizer import OptimizerConfig
from repro.train.train_step import init_state, make_train_step


def parse_args(argv: Optional[Sequence[str]] = None) -> argparse.Namespace:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-72b", choices=ARCH_IDS)
    ap.add_argument("--preset", default="smoke", choices=["smoke", "full"])
    ap.add_argument("--layers", type=int, default=0,
                    help="cut the model to this many layers, widths "
                         "untouched (0 = the preset's depth); global-"
                         "attention layers stay first, evenly spaced and "
                         "last")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--global-batch", type=int, default=16)
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--num-samples", type=int, default=512)
    ap.add_argument("--nodes", type=int, default=4)
    ap.add_argument("--workers", type=int, default=1,
                    help="co-located training workers per node; each gets "
                         "its own cluster.connect() session (and, under "
                         "--prefetch-schedule, its own loader axis) while "
                         "sharing the node's cache tier")
    ap.add_argument("--replication", type=int, default=1)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--grad-sync", default="auto", choices=["auto", "int8"])
    ap.add_argument("--sampler", default="uniform",
                    choices=["uniform", "stratified"])
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=20)
    ap.add_argument("--ckpt-fanstore", action="store_true",
                    help="also stream checkpoint shards through the "
                         "FanStore session write path (concurrent write "
                         "lane, placement-owned outputs)")
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--metrics-jsonl", default=None, metavar="PATH",
                    help="stream per-step training metrics (loss mean, "
                         "step-time p99, items/s rate, per-rank read "
                         "bytes, the read path's and loader's spans) "
                         "plus the full accounting-ledger bridge "
                         "through the cluster's MetricsCollector to this "
                         "JSONL sink (periodic ticks + a final explicit "
                         "flush)")
    ap.add_argument("--metrics-every", type=float, default=1.0,
                    help="minimum seconds between periodic JSONL "
                         "snapshots (0 = snapshot every step)")
    ap.add_argument("--io-threads", type=int, default=4)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--backend", default="modeled",
                    choices=["modeled", "socket", "shm", "rdma"],
                    help="transport backend behind the cluster: the "
                         "modeled interconnect, real TCP serving loops "
                         "(striped/pipelined), the zero-copy shared-"
                         "memory fast path, or one-sided rdma-class "
                         "reads over registered segments")
    ap.add_argument("--prefetch-schedule", action="store_true",
                    help="clairvoyant data plane: materialize the epoch's "
                         "permutation from the sampler's peek_epoch() into "
                         "an EpochSchedule axed per (node, worker) and "
                         "drive PrefetchLoader(schedule=SchedulerGroup) — "
                         "every worker on every node keeps its own "
                         "lookahead windows of remote I/O riding ahead of "
                         "compute (steps past the first epoch fall back "
                         "to demand reads)")
    ap.add_argument("--prefetch-window", type=int, default=8,
                    help="lookahead window in training steps for "
                         "--prefetch-schedule")
    ap.add_argument("--epochs", type=int, default=0,
                    help="with --prefetch-schedule: stitch this many "
                         "consecutive epochs into ONE schedule "
                         "(EpochSchedule.from_sampler(epochs=K)) so "
                         "lookahead windows flow across epoch boundaries "
                         "with no drain-and-refill stall and the Belady "
                         "oracle stays exact at the seam; --steps is then "
                         "derived as epochs * steps_per_epoch "
                         "(0 = single-epoch schedule, --steps drives)")
    args = ap.parse_args(argv)
    if args.epochs:
        if not args.prefetch_schedule:
            raise SystemExit("--epochs requires --prefetch-schedule "
                             "(it parameterizes the stitched schedule)")
        # derive the step budget up front so the optimizer schedule and
        # the stitched EpochSchedule agree on the horizon
        args.steps = args.epochs * (args.num_samples // args.global_batch)
    return args


def run(args: argparse.Namespace,
        on_batch: Optional[Callable[[int, Dict], None]] = None) -> Dict:
    """Train as ``args`` say; ``on_batch(step, batch)`` sees every batch
    the loader hands to the step, before the step runs.

    Returns ``losses`` and ``step_s`` (one per step run, each timed until
    the loss is on the host) and ``compile_s`` of the train step.
    """
    cfg = (get_smoke if args.preset == "smoke" else get_config)(args.arch)
    if args.layers:
        cfg = cfg.with_depth(args.layers)
    if cfg.family in ("audio", "vlm"):
        raise SystemExit("driver demo supports LM-batch families; "
                         "see examples/ for audio/vlm smoke paths")
    model = build_model(cfg)
    ocfg = OptimizerConfig(lr=args.lr, warmup_steps=max(2, args.steps // 20),
                           total_steps=args.steps)

    # ---- FanStore data plane -------------------------------------------------
    tokens = token_dataset(args.num_samples, args.seq_len, cfg.vocab_size,
                           seed=args.seed)
    files = tokens_to_files(tokens)
    blobs, rep = prepare_dataset(files, num_partitions=args.nodes * 2,
                                 compress=False)
    # the schedule-driven loaders stage windows through each node's shared
    # cache tier; budget every node to hold its epoch slice (bounded by the
    # whole dataset — co-located workers SHARE the tier, not split it)
    cache_bytes = 0
    if args.prefetch_schedule:
        cache_bytes = sum(len(b) for b in files.values()) + (1 << 20)
    workers = max(1, args.workers)
    spec = ClusterSpec(num_nodes=args.nodes, workers_per_node=workers,
                       backend=args.backend,
                       replication=args.replication,
                       cache_bytes=cache_bytes,
                       cache_policy="belady" if cache_bytes else "lru")
    num_loaders = spec.total_workers
    if args.prefetch_schedule and args.global_batch % num_loaders:
        raise SystemExit(
            f"--global-batch {args.global_batch} must divide across "
            f"{args.nodes} nodes x {workers} workers for "
            f"--prefetch-schedule")
    cluster = FanStoreCluster.from_spec(spec)
    cluster.load_partitions(blobs)
    paths = sorted(files)
    print(f"fanstore: {rep.num_files} files in {rep.num_partitions} "
          f"partitions on {args.nodes} nodes x {workers} workers "
          f"(R={args.replication}, backend={args.backend})")

    if args.sampler == "stratified":
        sampler = StratifiedSampler(args.num_samples, args.global_batch,
                                    num_shards=args.nodes, seed=args.seed)
    else:
        sampler = GlobalUniformSampler(args.num_samples, args.global_batch,
                                       seed=args.seed)

    # one descriptor-based session per (node, worker) in the declared
    # topology; every read and write below goes through this surface (no
    # raw cluster calls). Co-located sessions share their node's tier.
    order = [ctx.key for ctx in spec.workers()]   # node-major, the
    sessions = {key: cluster.connect(*key) for key in order}  # slice order
    step_counter = {"n": 0}

    # observability: per-step series stream through the cluster's
    # collector to a JSONL sink (periodic ticks in the loop below plus a
    # final explicit flush). Per-rank read bytes are recorded on each
    # issuing session, so the PER_RANK view ties each loader's traffic
    # to its (node, worker) coordinate. The program's spans (read path,
    # loader) are recorded too and folded in: per span name the P50/P99
    # duration and each counter's sum.
    sink = (JsonlSink(args.metrics_jsonl,
                      every_s=args.metrics_every or None)
            if args.metrics_jsonl else None)

    def _read(key, chunk_paths) -> list:
        blobs_out = sessions[key].read_many(chunk_paths)
        if sink is not None:
            sessions[key].record_metric(
                "train.read_bytes", sum(len(b) for b in blobs_out))
        return blobs_out

    def fetch_many(idxs) -> list:
        # under --prefetch-schedule each step's batch is split into one
        # contiguous slice per (node, worker) — the same slicing the
        # materialized schedule uses — and every slice is ONE coalesced
        # read_many on its own session (no node-0 pin: all nodes read);
        # otherwise the whole batch rides the session whose turn it is
        step_counter["n"] += 1
        if not args.prefetch_schedule:
            key = order[(step_counter["n"] - 1) % len(order)]
            return _read(key, [paths[i] for i in idxs])
        per = len(idxs) // len(order)
        out = []
        for r, key in enumerate(order):
            chunk = idxs[r * per:(r + 1) * per]
            out.extend(_read(key, [paths[i] for i in chunk]))
        return out

    def decode(blobs_list):
        return {"tokens": jnp.asarray(files_to_tokens(blobs_list,
                                                      args.seq_len))}

    scheduler = None
    if args.prefetch_schedule:
        # the permutation of every epoch is fully determined by the
        # sampler seed: materialize it WITHOUT advancing the sampler,
        # axed per (node, worker), and run one clairvoyant driver per
        # coordinate so every node keeps its own lookahead windows in
        # flight. --epochs K stitches K epochs into one globally-stepped
        # horizon: windows flow across the epoch boundary instead of
        # draining at epoch end.
        stitch = max(1, args.epochs)
        schedule = EpochSchedule.from_sampler(sampler, paths,
                                              num_requesters=num_loaders,
                                              workers_per_node=workers,
                                              cluster=cluster,
                                              epochs=stitch)
        scheduler = SchedulerGroup.for_schedule(
            cluster, schedule, window_steps=args.prefetch_window)
        print(f"prefetch-schedule: {len(scheduler)} loaders "
              f"({args.nodes} nodes x {workers} workers), "
              f"{scheduler.num_windows} windows of "
              f"{args.prefetch_window} steps over "
              f"{schedule.num_steps} steps"
              + (f" ({stitch} stitched epochs x "
                 f"{schedule.steps_per_epoch} steps)"
                 if stitch > 1 else ""))

    loader = PrefetchLoader(sampler, fetch_many=fetch_many, decode=decode,
                            num_threads=args.io_threads, depth=2,
                            schedule=scheduler)

    # ---- train state / restore ------------------------------------------------
    state = init_state(model, jax.random.key(args.seed), ocfg,
                       grad_sync=args.grad_sync)
    start_step = 0
    mgr = CheckpointManager(args.ckpt_dir) if args.ckpt_dir else None
    if args.resume and mgr is not None and mgr.latest_step() is not None:
        state, manifest = restore_checkpoint(args.ckpt_dir, state)
        start_step = manifest["step"]
        sampler.state.step = manifest["extra"].get("sampler_step", 0)
        sampler.state.epoch = manifest["extra"].get("sampler_epoch", 0)
        print(f"resumed from step {start_step}")

    # the state is donated: each step's new state takes the old one's HBM
    batch_shape = {"tokens": jax.ShapeDtypeStruct(
        (args.global_batch, args.seq_len), jnp.int32)}
    losses: List[float] = []
    step_s: List[float] = []
    n_done = start_step
    spans_forced = SPANS.forced
    if sink is not None:
        SPANS.forced = True
    try:
        t_compile = time.perf_counter()
        step_fn = jax.jit(make_train_step(model, ocfg,
                                          microbatches=args.microbatches),
                          donate_argnums=0).lower(state, batch_shape).compile()
        compile_s = time.perf_counter() - t_compile
        t0 = time.perf_counter()
        for batch in loader.batches(args.steps - start_step):
            if on_batch is not None:
                on_batch(n_done, batch)
            t_step = time.perf_counter()
            state, metrics = step_fn(state, batch)
            losses.append(float(metrics["loss"]))
            step_s.append(time.perf_counter() - t_step)
            n_done += 1
            if sink is not None:
                cm = cluster.metrics
                cm.record_metric("train.loss", losses[-1],
                                 reduce=Reduce.MEAN)
                cm.record_metric("train.step_time_s", step_s[-1],
                                 reduce=Reduce.P99)
                cm.record_metric("train.items", args.global_batch,
                                 rate=True)
                fold_spans(cm, SPANS.drain())
                sink.tick(cm)
            if n_done % 10 == 0 or n_done == args.steps:
                dt = time.perf_counter() - t0
                items = (n_done - start_step) * args.global_batch / dt
                print(f"step {n_done:5d} loss={losses[-1]:.4f} "
                      f"lr={float(metrics['lr']):.2e} "
                      f"throughput={items:.1f} items/s", flush=True)
            if n_done % args.ckpt_every == 0:
                extra = {"sampler_step": sampler.state.step,
                         "sampler_epoch": sampler.state.epoch}
                if mgr is not None:
                    mgr.save(n_done, state, extra=extra)
                if args.ckpt_fanstore:
                    save_to_session(sessions[order[0]], n_done, state,
                                    extra=extra)
        extra = {"sampler_step": sampler.state.step,
                 "sampler_epoch": sampler.state.epoch}
        if mgr is not None:
            mgr.save(n_done, state, blocking=True, extra=extra)
        if args.ckpt_fanstore and n_done % args.ckpt_every != 0:
            save_to_session(sessions[order[0]], n_done, state, extra=extra)
    finally:
        SPANS.forced = spans_forced
        try:
            loader.close()   # may re-raise an in-flight window error
        finally:
            cluster.close()  # join the I/O pool + any serving loops
    print(f"done: {n_done} steps, local-hit-rate="
          f"{cluster.local_hit_rate():.3f}")
    if sink is not None:
        # final explicit flush: the last snapshot carries the complete
        # ledger bridge (the clocks outlive cluster.close())
        fold_spans(cluster.metrics, SPANS.drain())
        snap = sink.flush(cluster.metrics)
        sink.close()
        view = sessions[order[0]].metrics()
        st = snap["metrics"].get("train.step_time_s", {})
        print(f"metrics: jsonl={args.metrics_jsonl} "
              f"records={sink.records_written} "
              f"version={snap['version']} "
              f"series={len(snap['metrics'])} "
              f"step_p50={st.get('p50', 0.0):.4f}s "
              f"step_p99={st.get('p99', 0.0):.4f}s "
              f"rank0_read_bytes="
              f"{view['metrics'].get('train.read_bytes', {}).get('sum', 0):.0f}")
    if scheduler is not None:
        prefetch_s = max(c.prefetch_s for c in cluster.clocks.values())
        busy_s = max(c.busy_s for c in cluster.clocks.values())
        print(f"prefetch-schedule: loaders={len(scheduler)} "
              f"windows_issued={scheduler.windows_issued} "
              f"bytes_scheduled={scheduler.bytes_scheduled} "
              f"cache_hit_rate={cluster.cache_hit_rate():.3f} "
              f"max_prefetch_s={prefetch_s:.6f} "
              f"(prefetch lane overlaps demand; busy={busy_s:.6f})")
    if args.backend != "modeled":
        print(f"measured: makespan={cluster.measured_makespan_s():.6f}s "
              f"bytes={cluster.accounting.measured_bytes()} "
              f"requests={cluster.accounting.measured_requests()}")
    if args.ckpt_fanstore:
        clock = cluster.clocks[order[0][0]]
        print(f"fanstore-ckpt: write_bytes={clock.write_bytes} "
              f"write_s={clock.write_s:.6f} consume_s={clock.consume_s:.6f} "
              f"(write lane overlaps the data plane; busy={clock.busy_s:.6f})")
    return {"losses": losses, "step_s": step_s, "compile_s": compile_s}


def main() -> None:
    args = parse_args()
    enable_compile_cache()
    run(args)


if __name__ == "__main__":
    main()
