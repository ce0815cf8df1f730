"""Benchmark aggregator — one section per paper table/figure.

  fig1    global vs partitioned dataset view (accuracy/loss gap)
  fig3    single-node bw/throughput: FanStore vs SSD vs FUSE vs SFS
  fig5/6  multi-node scaling (GPU-cluster and CPU-cluster arms)
  fig7-9  application throughput + weak scaling (ResNet/SRGAN/FRNN minis)
  fig10/11 + sec6.3  compression ratio / prep cost / relative throughput
  fetch   device-tier fetch collective bytes (uniform vs stratified)

Prints ``name,metric=value,...`` CSV-ish lines.

``--io-json PATH`` additionally (or, with ``--only io-json``, exclusively)
writes the machine-readable BENCH_io.json perf snapshot: epoch makespan,
hit rates, and bytes moved for the seed / batched / prefetched arms at 8
and 64 nodes, the write half (write_many vs per-file loop, checkpoint
flush makespan with/without prefetch-lane overlap), the
LRU-vs-Belady-vs-2Q cache comparison, the guarded ``cache_policy_sweep``
(all seven eviction policies x three byte budgets x permutation / zipf /
scan traces) and ``cross_epoch`` block (stitched multi-epoch prefetch
schedule vs drain-and-refill), the multi-tenant ``workers`` block
(shared node cache tier vs private per-worker caches at the same total
bytes), the ``measured`` block (read+write, scheduled-prefetch, and
checkpoint-overlap traces over the real socket/shm wires), the
``measured.wire`` block (single-connection vs striped/pipelined socket vs
the one-sided rdma backend on a pure-remote trace, with a pinned
throughput floor and wire-codec engagement truth), the
``prefetch_depth`` block (the slow latency-bound fabric where the
scheduled-prefetch ratio is guarded), and the ``failover`` block (kill a
node mid-epoch at R=2: zero failed reads via replica failover, retry
ledger == injected faults, bounded degraded makespan, plus the R=1
classified-NodeLostError control), and the ``serving`` block (64
read-mostly tenants on 8 nodes replaying a zipfian shard trace through
the admission-gated serving plane: hot-shard replication strictly beats
single-owner makespan, per-tenant attribution ties out exactly, peak
inflight respects ``max_inflight_bytes``, and the within-node fairness
ratio stays under 2x). ``--smoke`` shrinks it to the fast-lane CI
variant (scripts/ci.sh fast).

The io-json emission flows through the observability plane: the bench
blocks are attached to a :class:`repro.fanstore.metrics.MetricsCollector`,
streamed to a JSONL sink next to the output path (``BENCH_io.jsonl``),
and the written ``BENCH_io.json`` is the SNAPSHOT-derived copy (asserted
equal to the source blocks, so the schema stays byte-compatible). The
perf-trajectory guards are the declarative ``IO_SLO_GUARDS`` table below,
evaluated over the reloaded JSONL stream — not assert soup.
"""
from __future__ import annotations

import argparse
import json
import os
import pathlib
import sys
import time
import traceback

_ROOT = pathlib.Path(__file__).resolve().parents[1]
for _p in (str(_ROOT), str(_ROOT / "src")):
    if _p not in sys.path:         # `python benchmarks/run.py` from anywhere,
        sys.path.insert(0, _p)     # with or without PYTHONPATH=src

from repro.fanstore.metrics import (JsonlSink, MetricsCollector, Ref,  # noqa: E402
                                    SloGuard, check_slos)
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402

# Every BENCH_io.json perf-trajectory guard, as data. Paths are dotted
# with `*` wildcards; a Ref threshold compares against another path (its
# wildcards bind to the metric path's, leftovers mean "for all", which is
# how "belady >= every policy on the same arm" is spelled). Deterministic
# modeled quantities throughout, except the explicitly measured blocks.
IO_SLO_GUARDS = [
    # fast-fabric arms: direction-only (the GUARDED prefetch ratio lives
    # in prefetch_depth, where the win is structural)
    SloGuard("prefetch_direction", "arms.*.prefetch_speedup_vs_batched",
             ">=", 1.0),
    SloGuard("write_many_beats_loop", "arms.*.write.write_speedup",
             ">", 1.0),
    SloGuard("ckpt_overlap_wins", "arms.*.write.overlapped_makespan_s",
             "<", Ref("arms.*.write.serialized_makespan_s")),
    # cache policies: oracle beats LRU at equal byte budget
    SloGuard("belady_beats_lru", "cache_policies.belady_hit_rate",
             ">", Ref("cache_policies.lru_hit_rate")),
    # online intelligence: adaptive policies never lose to LRU on any
    # (budget, trace) arm; predictor closes >= 40% of the zipf gap;
    # Belady stays the upper bound; 2Q holds the scan trace
    SloGuard("arc_vs_lru_uniform", "cache_policy_sweep.uniform.arms.*.arc",
             ">=", Ref("cache_policy_sweep.uniform.arms.*.lru")),
    SloGuard("arc_vs_lru_zipf", "cache_policy_sweep.zipf.arms.*.arc",
             ">=", Ref("cache_policy_sweep.zipf.arms.*.lru")),
    SloGuard("predictive_vs_lru_uniform",
             "cache_policy_sweep.uniform.arms.*.predictive",
             ">=", Ref("cache_policy_sweep.uniform.arms.*.lru")),
    SloGuard("predictive_vs_lru_zipf",
             "cache_policy_sweep.zipf.arms.*.predictive",
             ">=", Ref("cache_policy_sweep.zipf.arms.*.lru")),
    SloGuard("belady_upper_bound_uniform",
             "cache_policy_sweep.uniform.arms.*.belady",
             ">=", Ref("cache_policy_sweep.uniform.arms.*.*")),
    SloGuard("belady_upper_bound_zipf",
             "cache_policy_sweep.zipf.arms.*.belady",
             ">=", Ref("cache_policy_sweep.zipf.arms.*.*")),
    SloGuard("zipf_gap_closure", "cache_policy_sweep.zipf_gap_closure.*",
             ">=", 0.40),
    SloGuard("twoq_holds_scan", "cache_policy_sweep.scan.2q",
             ">=", Ref("cache_policy_sweep.scan.lru")),
    # cross-epoch stitching: fewer boundary round trips, strictly earlier
    # finish, clean retry ledger
    SloGuard("stitching_beats_drain", "cross_epoch.stitched.makespan_s",
             "<", Ref("cross_epoch.drain_refill.makespan_s")),
    SloGuard("stitching_saves_window",
             "cross_epoch.stitched.prefetch_windows",
             "<", Ref("cross_epoch.drain_refill.prefetch_windows")),
    SloGuard("cross_epoch_clean_retries", "cross_epoch.*.retries",
             "==", 0),
    # multi-tenant workers: shared tier strictly beats private caches of
    # the same total bytes; attribution ledgers tie out
    SloGuard("shared_tier_wins", "workers.shared.makespan_s",
             "<", Ref("workers.private.makespan_s")),
    SloGuard("shared_tier_hit_rate", "workers.shared.cache_hit_rate",
             ">", Ref("workers.private.cache_hit_rate")),
    SloGuard("worker_attribution", "workers.*.attribution_ok", "truthy"),
    # hardware truth: real bytes over real wires, clean teardown, shm
    # beats socket, ledgers == trace bytes exactly
    SloGuard("measured_teardown", "measured.teardown_clean", "truthy"),
    SloGuard("socket_ran", "measured.socket.elapsed_s", ">", 0),
    SloGuard("shm_ran", "measured.shm.elapsed_s", ">", 0),
    SloGuard("socket_makespan", "measured.socket.measured_makespan_s",
             ">", 0),
    SloGuard("shm_makespan", "measured.shm.measured_makespan_s", ">", 0),
    SloGuard("socket_byte_ledger", "measured.socket.measured_bytes",
             "==", Ref("measured.socket.read_bytes")),
    SloGuard("shm_byte_ledger", "measured.shm.measured_bytes",
             "==", Ref("measured.shm.read_bytes")),
    SloGuard("socket_moved_bytes", "measured.socket.read_bytes", ">", 0),
    SloGuard("shm_moved_bytes", "measured.shm.read_bytes", ">", 0),
    SloGuard("shm_beats_socket", "measured.shm_speedup_vs_socket",
             ">", 1.0),
    # measured prefetch arm: nonzero PREFETCH-lane time, ledger == staged
    # bytes, demand reads hit the cache, shm beats socket
    SloGuard("prefetch_teardown", "measured.prefetch.teardown_clean",
             "truthy"),
    SloGuard("prefetch_lane_ran",
             "measured.prefetch.socket.measured_prefetch_s", ">", 0),
    SloGuard("prefetch_lane_ran_shm",
             "measured.prefetch.shm.measured_prefetch_s", ">", 0),
    SloGuard("prefetch_byte_ledger_socket",
             "measured.prefetch.socket.measured_bytes",
             "==", Ref("measured.prefetch.socket.staged_bytes")),
    SloGuard("prefetch_byte_ledger_shm",
             "measured.prefetch.shm.measured_bytes",
             "==", Ref("measured.prefetch.shm.staged_bytes")),
    SloGuard("prefetch_staged_socket",
             "measured.prefetch.socket.staged_bytes", ">", 0),
    SloGuard("prefetch_staged_shm",
             "measured.prefetch.shm.staged_bytes", ">", 0),
    SloGuard("prefetch_cache_hits_socket",
             "measured.prefetch.socket.cache_hits", ">", 0),
    SloGuard("prefetch_cache_hits_shm",
             "measured.prefetch.shm.cache_hits", ">", 0),
    SloGuard("prefetch_shm_beats_socket",
             "measured.prefetch.shm_speedup_vs_socket", ">", 1.0),
    # measured checkpoint arm: BOTH concurrent lanes show time in the
    # same wall window
    SloGuard("ckpt_teardown", "measured.checkpoint.teardown_clean",
             "truthy"),
    SloGuard("ckpt_write_lane", "measured.checkpoint.*.measured_write_s",
             ">", 0),
    SloGuard("ckpt_prefetch_lane",
             "measured.checkpoint.*.measured_prefetch_s", ">", 0),
    SloGuard("ckpt_elapsed", "measured.checkpoint.*.elapsed_s", ">", 0),
    SloGuard("ckpt_makespan", "measured.checkpoint.*.measured_makespan_s",
             ">", 0),
    SloGuard("ckpt_shm_beats_socket",
             "measured.checkpoint.shm_speedup_vs_socket", ">", 1.0),
    # wire gap: the rebuilt socket data plane holds its floor. 300 MB/s
    # is deliberately conservative (>= 4x what the PR-4 wire measured on
    # this trace shape, ~3x under what the striped wire actually does) so
    # CI noise can't flake it while a protocol regression can't hide
    SloGuard("wire_teardown", "measured.wire.teardown_clean", "truthy"),
    SloGuard("striped_floor", "measured.wire.striped.throughput_MBps",
             ">=", 300.0),
    SloGuard("stripe_speedup_multicore", "measured.wire.stripe_speedup",
             ">", 1.0, when=("measured.wire.cpu_count", ">", 1)),
    # one core: stripe threads serialize, so wall-clock parallelism
    # cannot express — bound the overhead instead
    SloGuard("stripe_overhead_unicore", "measured.wire.stripe_speedup",
             ">", 0.4, when=("measured.wire.cpu_count", "<=", 1)),
    SloGuard("striping_on", "measured.wire.striped.stripes_used",
             "min_len", 2),
    SloGuard("single_conn_stripe0", "measured.wire.single.stripes_used",
             "subset", (0,)),
    # codec truth: LZSS engages exactly when the cost model predicts
    SloGuard("codec_engages", "measured.wire.codec.engages_when_predicted",
             "truthy"),
    SloGuard("codec_stays_raw", "measured.wire.codec.raw_when_not_predicted",
             "truthy"),
    # one-sided contract: rdma moves the bytes with ZERO owner serve time
    SloGuard("rdma_one_sided", "measured.wire.rdma.serve_ns", "==", 0),
    SloGuard("rdma_moved_bytes", "measured.wire.rdma.throughput_MBps",
             ">", 0),
    # the guarded prefetch ratio: structural ~1.2x on the slow fabric
    SloGuard("deep_prefetch_win", "prefetch_depth.prefetch_speedup",
             ">", 1.15),
    SloGuard("deep_prefetch_scheduled", "prefetch_depth.prefetch_windows",
             ">", 0),
    # failover: a mid-epoch kill at R=2 is invisible (zero failed reads),
    # fully accounted (retries == injected, exactly), detected, healed,
    # and cheap; the R=1 control fails FAST and CLASSIFIED
    SloGuard("failover_zero_failures", "failover.degraded.reads_failed",
             "==", 0),
    SloGuard("failover_kill_fired", "failover.degraded.injected", ">", 0),
    SloGuard("failover_retry_ledger", "failover.degraded.retries",
             "==", Ref("failover.degraded.injected")),
    SloGuard("failover_detected", "failover.kill_node",
             "in", Ref("failover.degraded.failed_nodes")),
    SloGuard("failover_healed", "failover.degraded.healed_copies", ">", 0),
    SloGuard("failover_bounded", "failover.degraded_ratio", "<=", 1.6),
    SloGuard("r1_classified", "failover.r1.error", "==", "NodeLostError"),
    SloGuard("r1_names_loss", "failover.r1.lost_partitions", "nonempty"),
    # serving plane: stays multi-tenant, replication strictly wins,
    # attribution ties out, admission cap respected, promotion fired,
    # fairness bounded on both arms
    SloGuard("serving_multi_tenant", "serving.tenants", ">=", 64),
    SloGuard("serving_nodes", "serving.nodes", "==", 8),
    SloGuard("replication_wins", "serving.replicated.makespan_s",
             "<", Ref("serving.single.makespan_s")),
    SloGuard("serving_attribution", "serving.*.attribution_ok", "truthy"),
    SloGuard("promotion_fired", "serving.replicated.promoted_partitions",
             "nonempty"),
    SloGuard("inflight_nonzero", "serving.*.peak_inflight_bytes", ">", 0),
    SloGuard("inflight_capped", "serving.*.peak_inflight_bytes",
             "<=", Ref("serving.max_inflight_bytes")),
    SloGuard("no_shedding", "serving.*.admission_shed", "==", 0),
    SloGuard("fairness_bound", "serving.*.fairness_ratio", "<=", 2.0),
]


def write_io_json(path: str, *, smoke: bool = False) -> None:
    from benchmarks.io_scaling import bench_json
    result = bench_json(smoke=smoke)
    # ONE pipeline: attach every bench block to a collector, stream the
    # versioned snapshot to the JSONL sink beside the output path, and
    # write BENCH_io.json from the SNAPSHOT-derived copy (asserted equal
    # to the source blocks under JSON canonicalization, so the emitted
    # schema is unchanged).
    collector = MetricsCollector()
    for block_name, block in result.items():
        collector.record_block(block_name, block)
    jsonl_path = str(pathlib.Path(path).with_suffix(".jsonl"))
    if os.path.exists(jsonl_path):
        os.remove(jsonl_path)  # fresh stream: the CI nonempty check is honest
    with JsonlSink(jsonl_path) as sink:
        snap = sink.flush(collector)
    records = JsonlSink.load(jsonl_path)
    assert records and records[-1]["version"] == snap["version"], (
        "JSONL sink round trip lost the flushed snapshot")
    doc = records[-1]["bench"]
    canonical = json.loads(json.dumps(result, sort_keys=True, default=str))
    assert doc == canonical, (
        "snapshot-derived BENCH blocks diverged from the bench result")
    with open(path, "w") as f:
        json.dump(doc, f, indent=1, sort_keys=True)
    # perf-trajectory guards: the declarative table over the JSONL stream
    violations = check_slos(doc, IO_SLO_GUARDS)
    if violations:
        raise AssertionError(
            "BENCH_io.json SLO guard violations:\n  "
            + "\n  ".join(violations))
    cp = result["cache_policies"]
    cs = result["cache_policy_sweep"]
    ce = result["cross_epoch"]
    wb = result["workers"]
    m = result["measured"]
    mp = m["prefetch"]
    mc = m["checkpoint"]
    mw = m["wire"]
    pd = result["prefetch_depth"]
    fo = result["failover"]
    fd = fo["degraded"]
    r1 = fo["r1"]
    sv = result["serving"]
    rsv = sv["replicated"]
    for entry in result["arms"]:
        w = entry["write"]
        print(f"io_json,nodes={entry['nodes']},"
              f"batched_speedup={entry['batched_speedup']:.3f},"
              f"prefetch_speedup={entry['prefetch_speedup_vs_batched']:.3f},"
              f"write_speedup={w['write_speedup']:.3f},"
              f"ckpt_overlap_speedup={w['overlap_speedup']:.3f}",
              flush=True)
    print(f"io_json,lru_hit={cp['lru_hit_rate']:.3f},"
          f"belady_hit={cp['belady_hit_rate']:.3f},"
          f"twoq_hit={cp['2q_hit_rate']:.3f}", flush=True)
    for kind in ("uniform", "zipf"):
        for bf, arm in sorted(cs[kind]["arms"].items(),
                              key=lambda kv: int(kv[0])):
            print(f"io_json,sweep={kind},budget_files={bf},"
                  + ",".join(f"{p}_hit={arm[p]:.3f}"
                             for p in cs["policies"]), flush=True)
    print("io_json,"
          + ",".join(f"zipf_gap_closure_{bf}={c:.2f}"
                     for bf, c in sorted(cs["zipf_gap_closure"].items(),
                                         key=lambda kv: int(kv[0])))
          + f",scan_lru_hit={cs['scan']['lru']:.3f}"
          f",scan_twoq_hit={cs['scan']['2q']:.3f}", flush=True)
    print(f"io_json,cross_epoch_stitched="
          f"{ce['stitched']['makespan_s']:.4f}s,"
          f"drain_refill={ce['drain_refill']['makespan_s']:.4f}s,"
          f"stall_speedup={ce['stall_speedup']:.3f},"
          f"windows={ce['stitched']['prefetch_windows']}v"
          f"{ce['drain_refill']['prefetch_windows']}", flush=True)
    print(f"io_json,workers={wb['workers']},nodes={wb['nodes']},"
          f"shared_hit={wb['shared']['cache_hit_rate']:.3f},"
          f"private_hit={wb['private']['cache_hit_rate']:.3f},"
          f"shared_tier_speedup={wb['shared_speedup']:.3f}", flush=True)
    print(f"io_json,measured_socket={m['socket']['elapsed_s']:.4f}s,"
          f"measured_shm={m['shm']['elapsed_s']:.4f}s,"
          f"shm_speedup={m['shm_speedup_vs_socket']:.2f}", flush=True)
    print(f"io_json,measured_prefetch_socket="
          f"{mp['socket']['elapsed_s']:.4f}s,"
          f"measured_prefetch_shm={mp['shm']['elapsed_s']:.4f}s,"
          f"prefetch_shm_speedup={mp['shm_speedup_vs_socket']:.2f}",
          flush=True)
    print(f"io_json,measured_ckpt_socket={mc['socket']['elapsed_s']:.4f}s,"
          f"measured_ckpt_shm={mc['shm']['elapsed_s']:.4f}s,"
          f"ckpt_shm_speedup={mc['shm_speedup_vs_socket']:.2f}", flush=True)
    print(f"io_json,wire_single={mw['single']['throughput_MBps']:.0f}MB/s,"
          f"wire_striped={mw['striped']['throughput_MBps']:.0f}MB/s,"
          f"wire_rdma={mw['rdma']['throughput_MBps']:.0f}MB/s,"
          f"stripe_speedup={mw['stripe_speedup']:.2f},"
          f"codec_saved={mw['codec']['forced_saved_bytes']}", flush=True)
    print(f"io_json,prefetch_depth_window={pd['window']},"
          f"batched={pd['batched_makespan_s']:.4f}s,"
          f"prefetched={pd['prefetched_makespan_s']:.4f}s,"
          f"deep_prefetch_speedup={pd['prefetch_speedup']:.3f}", flush=True)
    print(f"io_json,failover_kill_node={fo['kill_node']},"
          f"degraded_ratio={fo['degraded_ratio']:.3f},"
          f"reads_failed={fd['reads_failed']},"
          f"injected={fd['injected']},retries={fd['retries']},"
          f"healed_copies={fd['healed_copies']},"
          f"r1_lost={len(r1['lost_partitions'])}", flush=True)
    print(f"io_json,serving_tenants={sv['tenants']},"
          f"serving_nodes={sv['nodes']},"
          f"replication_speedup={sv['replication_speedup']:.2f},"
          f"promoted={len(rsv['promoted_partitions'])},"
          f"peak_inflight={rsv['peak_inflight_bytes']},"
          f"fairness_ratio={rsv['fairness_ratio']:.3f}", flush=True)
    print(f"io_json,wrote={path},metrics_jsonl={jsonl_path},"
          f"snapshot_version={snap['version']},"
          f"guards={len(IO_SLO_GUARDS)}", flush=True)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma list: fig1,fig3,scaling,apps,compression,"
                         "fetch,io-json")
    ap.add_argument("--skip", default=None)
    ap.add_argument("--io-json", default=None, metavar="PATH",
                    help="also write the BENCH_io.json perf snapshot here")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny io-json variant for the CI fast lane")
    args = ap.parse_args()
    enable_compile_cache()

    sections = {
        "fig3": lambda: __import__("benchmarks.io_single_node",
                                   fromlist=["main"]).main(),
        "scaling": lambda: __import__("benchmarks.io_scaling",
                                      fromlist=["main"]).main(),
        "apps": lambda: __import__("benchmarks.app_throughput",
                                   fromlist=["main"]).main(),
        "compression": lambda: __import__("benchmarks.compression",
                                          fromlist=["main"]).main(),
        "fig1": lambda: __import__("benchmarks.view_ablation",
                                   fromlist=["main"]).main(),
        "fetch": lambda: __import__("benchmarks.fetch_device",
                                    fromlist=["main"]).main(),
    }
    only = set(args.only.split(",")) if args.only else set(sections)
    skip = set(args.skip.split(",")) if args.skip else set()
    failures = 0
    for name, fn in sections.items():
        if name not in only or name in skip:
            continue
        t0 = time.perf_counter()
        try:
            for line in fn():
                print(line, flush=True)
            print(f"section={name},seconds={time.perf_counter()-t0:.1f}",
                  flush=True)
        except Exception:
            failures += 1
            print(f"section={name},FAILED", flush=True)
            traceback.print_exc()
    # io-json runs when named in --only (works inside a comma list) or when
    # an output path is given; --only io-json alone defaults the path
    if (args.io_json or "io-json" in only) and "io-json" not in skip:
        try:
            write_io_json(args.io_json or "BENCH_io.json", smoke=args.smoke)
        except Exception:
            failures += 1
            print("section=io-json,FAILED", flush=True)
            traceback.print_exc()
    sys.exit(1 if failures else 0)


if __name__ == "__main__":
    main()
