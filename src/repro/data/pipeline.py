"""Host-side prefetching data pipeline (paper §3.4: async I/O / prefetch).

``PrefetchLoader`` runs a pool of I/O threads (Keras uses 4 per process; same
default) that pull sample indices from a sampler, fetch the bytes through a
FanStore read function, decode, and stage finished batches in a bounded
queue — so the I/O of batch t+1..t+depth overlaps the compute of batch t.
The loader is checkpointable: its cursor is the sampler state.

Beyond depth-batches lookahead, the loader can drive a *clairvoyant*
schedule (``schedule=`` a :class:`repro.fanstore.prefetch.PrefetchScheduler`):
before fetching step t it tells the scheduler to keep windows issued through
step t + ``prefetch_window``, so whole-epoch remote I/O rides ahead of
compute in window-coalesced round trips and the per-step ``fetch_many`` is
served from the client cache without blocking on the fabric.
"""
from __future__ import annotations

import queue
import threading
from typing import Callable, Iterator, List, Optional, Sequence

import numpy as np

from repro.fanstore.metrics import SPANS


class EpochShuffler:
    """Deterministic per-epoch permutation utility (shared by samplers/tests)."""

    def __init__(self, n: int, seed: int = 0):
        self.n = n
        self.seed = seed

    def perm(self, epoch: int) -> np.ndarray:
        return np.random.default_rng((self.seed, epoch)).permutation(self.n)


class PrefetchLoader:
    """Bounded-depth async batch loader.

    Args:
      sampler: object with ``next_batch() -> np.ndarray[int32]`` and ``state``.
      fetch: maps one sample index -> bytes (e.g. a FanStore read).
      decode: maps list-of-bytes for a batch -> model-ready arrays.
      fetch_many: optional batched fetch mapping a list of sample indices ->
        list of bytes in order (e.g. ``FanStoreCluster.read_many``). When
        given, each batch is ONE coalesced call — the engine groups requests
        per owner node and pays one round trip per owner instead of one per
        sample — and the per-sample thread fan-out is skipped.
      num_threads: I/O threads *per batch* fetching samples concurrently
        (per-sample path only).
      depth: batches staged ahead of compute.
      schedule: optional clairvoyant prefetch driver (an object with
        ``ensure(step)``/``wait_ready(step)``/``close()``, i.e. a
        ``repro.fanstore.prefetch.PrefetchScheduler``). The producer keeps
        lookahead windows issued ahead of consumption and gates each step
        on its own window, so ``fetch_many`` hits the client cache instead
        of paying per-step round trips.
      prefetch_window: how many steps ahead of the consuming step the
        schedule is kept issued (default: the scheduler's own window size).

    Errors raised inside the producer thread are never swallowed: they
    surface on the next ``__next__`` (in place of further batches) or on
    ``close()`` if the consumer stopped early.

    While :data:`repro.fanstore.metrics.SPANS` records, the producer's
    ``fanstore.loader.fetch``, ``fanstore.loader.decode`` and
    ``fanstore.loader.put_wait`` (time blocked on a full queue) and the
    consumer's ``fanstore.loader.get`` (time waiting in ``__next__``,
    counter ``starved`` when the queue was empty on arrival) share the
    batch's id with the spans the fetch opens (``fanstore.read_many``).
    While nothing records, a batch still takes an id from a counter, sets
    it in a thread-local and goes through the queue as a ``(id, batch)``
    pair; each of its four span sites is one flag check.
    """

    def __init__(self, sampler, fetch: Callable[[int], bytes] = None,
                 decode: Callable[[List[bytes]], object] = None, *,
                 fetch_many: Optional[
                     Callable[[List[int]], List[bytes]]] = None,
                 num_threads: int = 4, depth: int = 2,
                 schedule=None, prefetch_window: Optional[int] = None):
        if fetch is None and fetch_many is None:
            raise ValueError("need fetch or fetch_many")
        if decode is None:
            raise ValueError("decode is required")
        self.sampler = sampler
        self.fetch = fetch
        self.fetch_many = fetch_many
        self.decode = decode
        self.num_threads = num_threads
        self.depth = depth
        self.schedule = schedule
        if prefetch_window is None:
            prefetch_window = getattr(schedule, "window_steps", None) or depth
        self.prefetch_window = prefetch_window
        self._sched_step = getattr(getattr(sampler, "state", None), "step", 0)
        self._q: "queue.Queue" = queue.Queue(maxsize=depth)
        self._stop = threading.Event()
        self._producer: Optional[threading.Thread] = None
        self._err: Optional[BaseException] = None
        self._err_raised = False
        self._done = False

    # -- batch assembly ------------------------------------------------------
    def _fetch_batch(self, indices: np.ndarray) -> object:
        with SPANS.span("fanstore.loader.fetch"):
            blobs = self._fetch_blobs(indices)
        with SPANS.span("fanstore.loader.decode"):
            return self.decode(blobs)

    def _fetch_blobs(self, indices: np.ndarray) -> List[bytes]:
        if self.fetch_many is not None:
            return self.fetch_many([int(i) for i in indices])
        out: List[Optional[bytes]] = [None] * len(indices)
        if self.num_threads <= 1:
            for i, idx in enumerate(indices):
                out[i] = self.fetch(int(idx))
        else:
            cursor = iter(range(len(indices)))
            lock = threading.Lock()
            errors: List[BaseException] = []
            batch_id = SPANS.batch()

            def worker():
                SPANS.set_batch(batch_id)
                while True:
                    with lock:
                        if errors:
                            return
                        i = next(cursor, None)
                    if i is None:
                        return
                    try:
                        out[i] = self.fetch(int(indices[i]))
                    except BaseException as e:
                        with lock:
                            errors.append(e)
                        return

            threads = [threading.Thread(target=worker)
                       for _ in range(self.num_threads)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            if errors:
                raise errors[0]
        return out  # type: ignore[return-value]

    def _produce(self, num_batches: int) -> None:
        try:
            for _ in range(num_batches):
                if self._stop.is_set():
                    return
                if self.schedule is not None:
                    # keep lookahead windows in flight, then gate on the
                    # current step's window so the fetch hits the cache
                    self.schedule.ensure(
                        self._sched_step + self.prefetch_window)
                    self.schedule.wait_ready(self._sched_step)
                batch_id = SPANS.next_batch()
                SPANS.set_batch(batch_id)
                batch = self._fetch_batch(self.sampler.next_batch())
                self._sched_step += 1
                with SPANS.span("fanstore.loader.put_wait") as span:
                    if span:
                        span.add("full", int(self._q.full()))
                    while not self._stop.is_set():
                        try:
                            self._q.put((batch_id, batch), timeout=0.1)
                            break
                        except queue.Full:
                            continue
        except BaseException as e:   # surfaced on the consumer side
            self._err = e
        finally:
            SPANS.set_batch(None)
            self._q.put(None)

    # -- public API ------------------------------------------------------------
    def start(self, num_batches: int) -> "PrefetchLoader":
        """Spawn the producer for ``num_batches``; consume via ``__next__``."""
        if self._producer is not None and self._producer.is_alive():
            raise RuntimeError("loader is already running")
        self._drain()               # stale sentinel from an earlier run
        self._stop.clear()
        self._err = None
        self._err_raised = False
        self._done = False
        self._producer = threading.Thread(
            target=self._produce, args=(num_batches,), daemon=True)
        self._producer.start()
        return self

    def __iter__(self) -> Iterator[object]:
        return self

    def __next__(self) -> object:
        if self._producer is None:
            raise RuntimeError("call start()/batches() before iterating")
        if self._done:
            self._raise_pending()
            raise StopIteration
        with SPANS.span("fanstore.loader.get") as span:
            if span:
                span.add("starved", int(self._q.empty()))
            item = self._q.get()
            if span and item is not None:
                span.batch = item[0]
        if item is None:
            self._done = True
            self._producer.join()
            if self.schedule is not None:
                self.schedule.close()    # surfaces in-flight window errors
            self._raise_pending()
            raise StopIteration
        return item[1]

    def batches(self, num_batches: int) -> Iterator[object]:
        """Yield ``num_batches`` decoded batches with prefetch overlap."""
        self.start(num_batches)
        return iter(self)

    def _raise_pending(self) -> None:
        if self._err is not None and not self._err_raised:
            self._err_raised = True
            raise self._err

    def close(self) -> None:
        """Stop the producer, drain staged batches, and re-raise any
        producer-side error that has not been surfaced yet — an exception
        raised after the consumer walked away must not be swallowed."""
        self._stop.set()
        t = self._producer
        if t is not None:
            while t.is_alive():
                self._drain()
                t.join(timeout=0.05)
            t.join()
        self._drain()
        self._done = True
        if self.schedule is not None:
            self.schedule.close()
        self._raise_pending()

    def _drain(self) -> None:
        try:
            while True:
                self._q.get_nowait()
        except queue.Empty:
            pass

    def stop(self) -> None:
        """Legacy alias for :meth:`close` (same error-surfacing contract)."""
        self.close()

    @property
    def cursor(self):
        return self.sampler.state
