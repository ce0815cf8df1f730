"""FanStore's data plane, built only from the program's own calls.

The same chain ``launch/train.py`` ``run()`` builds in demand mode:
``prepare_dataset`` -> ``FanStoreCluster.from_spec(ClusterSpec(...))`` ->
``load_partitions`` -> one ``cluster.connect(node, worker)`` session per
(node, worker) -> ``PrefetchLoader(sampler, fetch_many=..., decode=...)``,
where each batch is one ``read_many`` on the session whose turn it is.
The harness only wraps spans around the calls.
"""
from __future__ import annotations

from typing import Callable, Dict, List

from chipbench.spans import Spans

# batches the producer may fetch: the window, not this, ends a run
UNBOUNDED = 1 << 40


class Plane:
    def __init__(self, topology: Dict, traffic: Dict, paths: List[str],
                 files: Dict[str, bytes], decode: Callable, spans: Spans,
                 seed: int):
        from repro.data.pipeline import PrefetchLoader
        from repro.data.sampler import GlobalUniformSampler
        from repro.fanstore.cluster import FanStoreCluster
        from repro.fanstore.prepare import prepare_dataset
        from repro.fanstore.spec import ClusterSpec

        if traffic["sampler"] != "global_uniform" or traffic["read"] != "demand":
            raise ValueError(f"unsupported traffic {traffic}")
        blobs, self.report = prepare_dataset(
            files, num_partitions=int(topology["partitions"]),
            compress=bool(topology["compress"]))
        spec = ClusterSpec(num_nodes=int(topology["num_nodes"]),
                           workers_per_node=int(topology["workers_per_node"]),
                           backend=topology["backend"],
                           replication=int(topology["replication"]),
                           cache_bytes=int(topology["cache_bytes"]),
                           cache_policy="lru")
        self.cluster = FanStoreCluster.from_spec(spec)
        try:
            self.cluster.load_partitions(blobs)
            del blobs
            order = [ctx.key for ctx in spec.workers()]
            sessions = {key: self.cluster.connect(*key) for key in order}
            turn = [0]

            def fetch_many(idxs) -> list:
                key = order[turn[0] % len(order)]
                turn[0] += 1
                with spans.span("read_many"):
                    return sessions[key].read_many([paths[i] for i in idxs])

            def decode_batch(blobs_list):
                with spans.span("decode"):
                    return decode(blobs_list)

            self.sampler = GlobalUniformSampler(len(paths),
                                                int(traffic["batch"]),
                                                seed=seed)
            self.loader = PrefetchLoader(self.sampler, fetch_many=fetch_many,
                                         decode=decode_batch,
                                         depth=int(traffic["loader_depth"]))
        except BaseException:
            self.cluster.close()
            raise
        self.loader.start(UNBOUNDED)

    def next(self, spans: Spans):
        with spans.span("input_wait"):
            return next(self.loader)

    def close(self) -> None:
        try:
            self.loader.close()
        finally:
            self.cluster.close()
