"""The modeled backend: PR 1's in-process transport, now one wire among
several.

``InterconnectModel`` is the first-order fabric cost model (per-message
latency + per-byte cost) every backend accounts against; it lives here
because the modeled backend is its reference consumer (it is re-exported
from :mod:`repro.fanstore.transport` and :mod:`repro.fanstore.cluster`
for compatibility).

``ModeledBackend`` moves payloads by direct in-process calls against the
owner's ``NodeStore`` — exactly what the pre-seam ``Transport`` did, and
regression-pinned to stay byte-for-byte identical: an owner serves each
request with one ``NodeStore.serve_many`` pass (the same bytes and store
stats as a ``serve_remote`` per file; a caller that takes views gets raw
records as views over the owner's blobs, since both sides share one
address space and the wire's cost is booked on the clocks, not paid in
wall time), outputs ship through
``stage_output``, and the modeled clock accrual lives unchanged in
:class:`TransportBackend`. It records no measured wall time
(``measured = False``): predictions stay the modeled clocks' job,
hardware truth is the socket/shm backends' job.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import List, Sequence, Tuple

from repro.fanstore.backends.base import TransportBackend
from repro.fanstore.wire import FetchItem

__all__ = ["InterconnectModel", "ModeledBackend"]


@dataclass
class InterconnectModel:
    """First-order fabric model: per-message latency + per-byte cost.

    Defaults approximate the paper's CPU cluster (100 Gb/s OPA, ~1.5 us):
    latency_s per round trip, bandwidth_Bps per NIC direction. Local tier
    is modeled with disk_bw_Bps (SSD) and a per-open syscall overhead.
    cache_bw_Bps is the client-side read-cache (RAM) service rate.
    """
    latency_s: float = 1.5e-6
    bandwidth_Bps: float = 100e9 / 8
    disk_bw_Bps: float = 2.0e9
    open_overhead_s: float = 3e-6
    decompress_Bps: float = 1.5e9     # LZSS-class decode rate per core
    cache_bw_Bps: float = 20e9        # DRAM-resident read cache
    # one-sided (RDMA-class) arm: a registered read skips the owner's CPU
    # entirely — the requester pays a registration-table lookup instead of
    # a request/response latency, then line-rate bytes. Only the rdma
    # backend consults these.
    rdma_lookup_s: float = 2e-7       # table lookup + doorbell, no RTT
    rdma_bandwidth_Bps: float = 100e9 / 8

    def remote_cost(self, nbytes: int) -> float:
        return self.latency_s + nbytes / self.bandwidth_Bps

    def local_cost(self, nbytes: int, *, compressed: bool = False) -> float:
        t = self.open_overhead_s + nbytes / self.disk_bw_Bps
        if compressed:
            t += nbytes / self.decompress_Bps
        return t

    def cache_cost(self, nbytes: int) -> float:
        return nbytes / self.cache_bw_Bps


class ModeledBackend(TransportBackend):
    """In-process payload movement + modeled accounting (the default)."""

    name = "modeled"
    measured = False
    gathers = True

    def _move_fetch(self, requester: int, owner: int,
                    items: Sequence[FetchItem], materialize: bool,
                    verb: str, views: bool = False
                    ) -> Tuple[List[bytes], int]:
        if materialize:
            out = self.nodes[owner].serve_many([it.path for it in items],
                                               views=views)
        else:
            out = [b"" for _ in items]
        return out, 0

    def _move_put(self, writer: int, owner: int,
                  pairs: Sequence[Tuple[FetchItem, bytes]]) -> int:
        node = self.nodes[owner]
        for item, data in pairs:
            node.stage_output(writer, item.path, data)
        return 0
