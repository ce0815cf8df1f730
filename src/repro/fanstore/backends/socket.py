"""The socket backend: a real TCP wire path behind the transport seam.

Every node runs one serving loop (paper §5.2's "I/O thread"): a
``_NodeServer`` binds a loopback TCP socket, accepts connections, and
answers framed :mod:`repro.fanstore.wire` requests by scatter-gathering
from its own ``NodeStore`` — ``FETCH_BATCH``/``FETCH_WINDOW`` frames come
back as one ``DATA`` frame carrying every payload in the group (the wire
twin of the modeled one-round-trip-per-owner coalescing), ``PUT_BATCH``
frames land in the owner's per-(writer, path) staging, and handler
exceptions travel back as ERR frames that re-raise client-side as the
same exception class.

The data plane is built for throughput:

* **Connection striping** — up to ``stripes`` persistent connections per
  (requester, owner) pair. A large batch is split into contiguous
  sub-batches balanced by stored bytes (``wire.split_stripes``), each
  sub-batch rides its own connection concurrently (its own server-side
  handler thread, its own TCP stream), and the payload runs are slotted
  back into item order whatever order the stripes finish
  (``wire.reassemble``). Stripe legs are wall-timed individually
  (``WallClock.attribute_stripe``).
* **Request pipelining** — within one connection a sub-batch is cut into
  up to ``pipeline_depth`` request frames sent back-to-back before the
  first response is read, so the server builds response *k+1* while the
  client drains response *k*; TCP FIFO plus the server's strict
  one-response-per-request discipline keeps framing aligned with no
  sequence numbers on the wire.
* **Vectored I/O** — responses are scatter-gathered with ``sendmsg``
  straight from the store's zero-copy ``serve_remote_view`` buffers
  (``wire.write_frame_parts``), and both sides ``recv_into`` reusable
  per-connection receive buffers, so each payload crosses Python exactly
  once per side (kernel->buffer on receive; buffer->kernel on send).
* **Tuned sockets** — TCP_NODELAY plus sized SO_SNDBUF/SO_RCVBUF
  (``sock_buf_bytes``, default 4 MiB) on every connection, both sides.
* **LZSS-on-the-wire** — the per-payload codec flag from
  :class:`~repro.fanstore.wire.WireCodecPolicy`: each DATA/PUT payload is
  compressed only when the cost model predicts the codec CPU beats the
  wire time saved, and ships raw (flag clear) when the attempt does not
  shrink it. The receiver ledgers raw-vs-sent bytes onto its
  ``WallClock``.

Connections are dialed lazily, each serialized by a per-stripe lock, and
closed on backend ``close()`` — teardown joins every stripe's connection
handler deterministically (the PR-4 wake-up dial covers the accept loop;
shutdown+close unblocks each per-connection recv). Serving loops are
named ``fanstore-serve-*`` / ``fanstore-conn-*`` and the stripe fan-out
pool ``fanstore-stripe-*`` so the leak-check fixture sees them all.

Accounting is dual: the modeled clocks accrue exactly as on every other
two-sided backend (so modeled quantities stay backend-independent), while
measured wall time accrues onto the ``WallClock`` lanes — the requester
pays the observed round-trip duration, and the owner's serve lane is
credited with the handling time the server reports inside each response
frame. These are the repo's hardware-truth numbers (``BENCH_io.json``'s
``measured`` block; the ``measured.wire`` block pins striped-vs-single
throughput on the standard trace).
"""
from __future__ import annotations

import socket
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Dict, List, Optional, Sequence, Tuple

from repro.fanstore import wire
from repro.fanstore.backends.base import TransportBackend
from repro.fanstore.metadata import StatRecord
from repro.fanstore.store import NodeStore
from repro.fanstore.wire import FetchItem, MsgType

__all__ = ["SocketBackend"]

_FETCH_TYPES = {"fetch": MsgType.FETCH, "fetch_batch": MsgType.FETCH_BATCH,
                "fetch_window": MsgType.FETCH_WINDOW}

#: default socket buffer size (SO_SNDBUF/SO_RCVBUF), both sides
_SOCK_BUF = 4 << 20

#: a batch smaller than this ships on one stripe: splitting it would pay
#: extra dials and thread hops for bytes a single stream moves instantly
_STRIPE_MIN_BYTES = 128 << 10


def _tune(sock: socket.socket, buf_bytes: int) -> None:
    sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
    try:
        sock.setsockopt(socket.SOL_SOCKET, socket.SO_SNDBUF, buf_bytes)
        sock.setsockopt(socket.SOL_SOCKET, socket.SO_RCVBUF, buf_bytes)
    except OSError:        # pragma: no cover - kernel may clamp, never fatal
        pass


class _NodeServer:
    """One node's serving loop: accept thread + per-connection handlers."""

    def __init__(self, node_id: int, store: NodeStore, host: str,
                 policy: Optional[wire.WireCodecPolicy] = None,
                 buf_bytes: int = _SOCK_BUF, join_timeout_s: float = 5.0):
        self.node_id = node_id
        self.store = store
        self.policy = policy if policy is not None and policy.codec != "none" \
            else None
        self.buf_bytes = buf_bytes
        self.join_timeout_s = join_timeout_s
        self._listener = socket.create_server((host, 0))
        self.address: Tuple[str, int] = self._listener.getsockname()
        self._stop = threading.Event()
        self._conn_lock = threading.Lock()
        self._conns: List[socket.socket] = []
        self._threads: List[threading.Thread] = []
        self._accept_thread = threading.Thread(
            target=self._accept_loop,
            name=f"fanstore-serve-{node_id}", daemon=True)
        self._accept_thread.start()

    # ---- serving loop ------------------------------------------------------
    def _accept_loop(self) -> None:
        while not self._stop.is_set():
            try:
                conn, _ = self._listener.accept()
            except OSError:           # listener closed: clean shutdown
                return
            if self._stop.is_set():   # the wake-up dial from close()
                conn.close()
                return
            _tune(conn, self.buf_bytes)
            t = threading.Thread(
                target=self._handle, args=(conn,),
                name=f"fanstore-conn-{self.node_id}", daemon=True)
            with self._conn_lock:
                self._conns.append(conn)
                self._threads.append(t)
            t.start()

    def _handle(self, conn: socket.socket) -> None:
        # reusable receive buffer: the connection is persistent, so one
        # geometrically-grown buffer serves every request frame with zero
        # per-frame allocation (the decoders copy payloads out before the
        # next read overwrites it)
        rbuf = bytearray(1 << 16)
        try:
            while not self._stop.is_set():
                mtype, body = wire.read_frame(conn, rbuf)
                self._dispatch(conn, mtype, body)
        except (ConnectionError, OSError):
            pass                       # peer hung up / shutdown race
        finally:
            conn.close()

    def _dispatch(self, conn: socket.socket, mtype: MsgType,
                  body) -> None:
        """Answer one request with exactly one response frame — a handler
        exception (FileNotFoundError from a bad path, PermissionError,
        anything the store raises) becomes an ERR frame and the connection
        stays usable; only a failure to WRITE the response (peer gone)
        propagates and closes the connection. The response scatter list is
        built before any byte is sent, so request/response framing can
        never desynchronize — the discipline pipelined clients rely on."""
        rtype, parts = self._answer(mtype, body)
        wire.write_frame_parts(conn, rtype, parts)

    def _answer(self, mtype: MsgType, body) -> Tuple[MsgType, List[bytes]]:
        t0 = time.perf_counter_ns()
        try:
            if mtype in (MsgType.FETCH, MsgType.FETCH_BATCH,
                         MsgType.FETCH_WINDOW):
                paths, materialize = wire.decode_fetch(body)
                if materialize:        # ONE scatter-gather over local blobs:
                    # zero-copy views — sendmsg gathers them straight from
                    # the partition blobs / output tier, payloads are
                    # never joined into a response body
                    payloads = [self.store.serve_remote_view(p)
                                for p in paths]
                else:
                    payloads = [b"" for _ in paths]
                return MsgType.DATA, wire.encode_data_parts(
                    payloads, serve_ns=time.perf_counter_ns() - t0,
                    policy=self.policy)
            if mtype == MsgType.PUT_BATCH:
                writer, entries = wire.decode_put(body)
                for path, data in entries:
                    self.store.stage_output(writer, path, data)
                return MsgType.OK, [wire.encode_ok(
                    serve_ns=time.perf_counter_ns() - t0)]
            if mtype == MsgType.STAT:
                path = wire.decode_stat(body)
                return MsgType.STAT_OK, [wire.encode_stat_ok(
                    self._stat(path), serve_ns=time.perf_counter_ns() - t0)]
            raise wire.WireError(f"unexpected request type {mtype!r}")
        except BaseException as exc:   # noqa: BLE001 — becomes an ERR frame
            return MsgType.ERR, [wire.encode_error(exc)]

    def _stat(self, path: str) -> StatRecord:
        rec = self.store.record_for(path)
        if rec is not None:
            return rec.stat
        size = self.store.output_size(path)   # metadata-only: no read booked
        if size is not None:
            return StatRecord.for_data(size)
        raise FileNotFoundError(path)

    def close(self) -> None:
        self._stop.set()
        # a blocking accept() is not reliably interrupted by closing the
        # listener from another thread; dial it once so it wakes, sees the
        # stop flag, and exits deterministically
        try:
            socket.create_connection(self.address, timeout=1.0).close()
        except OSError:
            pass
        self._listener.close()
        with self._conn_lock:
            conns, threads = list(self._conns), list(self._threads)
            self._conns.clear()
            self._threads.clear()
        for c in conns:
            try:
                c.shutdown(socket.SHUT_RDWR)
            except OSError:
                pass
            c.close()                  # unblocks recv()
        self._accept_thread.join(timeout=self.join_timeout_s)
        for t in threads:
            t.join(timeout=self.join_timeout_s)
        # a join that timed out used to succeed SILENTLY, leaking the
        # thread past this close and into the conftest leak fixture (or a
        # CI hang) with no pointer back here — name the stuck threads now
        stuck = [t.name for t in [self._accept_thread, *threads]
                 if t.is_alive()]
        if stuck:
            raise RuntimeError(
                f"fanstore socket teardown: node {self.node_id} serving "
                f"threads failed to join within {self.join_timeout_s}s: "
                f"{stuck}")


class _Conn:
    """One client-side stripe connection: socket + request lock + reusable
    receive buffer (pipelined responses decode before the next read)."""

    __slots__ = ("sock", "lock", "rbuf")

    def __init__(self, sock: socket.socket):
        self.sock = sock
        self.lock = threading.Lock()
        self.rbuf = bytearray(1 << 16)


class SocketBackend(TransportBackend):
    """Framed TCP transfers between per-node serving loops (loopback)."""

    name = "socket"
    measured = True

    def __init__(self, net, nodes, clocks, *, wall=None, num_threads: int = 8,
                 host: str = "127.0.0.1", sock_buf_bytes: int = _SOCK_BUF,
                 stripe_min_bytes: int = _STRIPE_MIN_BYTES,
                 dial_retries: int = 3, dial_backoff_s: float = 0.05,
                 join_timeout_s: float = 5.0, **wire_opts):
        super().__init__(net, nodes, clocks, wall=wall,
                         num_threads=num_threads, **wire_opts)
        self.host = host
        self.sock_buf_bytes = int(sock_buf_bytes)
        self.stripe_min_bytes = int(stripe_min_bytes)
        self.dial_retries = int(dial_retries)
        self.dial_backoff_s = float(dial_backoff_s)
        self.join_timeout_s = float(join_timeout_s)
        self._servers: Dict[int, _NodeServer] = {}
        # one persistent connection per (requester, owner, stripe) — the
        # single-connection wire of PR 4 is exactly the stripes=1 case
        self._conns: Dict[Tuple[int, int, int], _Conn] = {}
        self._dial_lock = threading.Lock()
        self._stripe_pool: Optional[ThreadPoolExecutor] = None

    # ---- lifecycle ---------------------------------------------------------
    def _start_serving(self) -> None:
        for nid, store in self.nodes.items():
            if nid not in self._servers:
                self._servers[nid] = _NodeServer(
                    nid, store, self.host, policy=self.wire_policy,
                    buf_bytes=self.sock_buf_bytes,
                    join_timeout_s=self.join_timeout_s)
        if self.stripes > 1 and self._stripe_pool is None:
            # fan-out workers for concurrent stripe legs; sized past the
            # stripe count so two overlapping striped batches (demand +
            # prefetch) both make progress. Workers spawn on demand.
            self._stripe_pool = ThreadPoolExecutor(
                max_workers=2 * self.stripes,
                thread_name_prefix="fanstore-stripe")

    def _stop_serving(self) -> None:
        with self._dial_lock:
            conns = list(self._conns.values())
            self._conns.clear()
        for c in conns:                # EVERY stripe's connection, each join
            try:                       # deterministic: shutdown unblocks the
                c.sock.shutdown(socket.SHUT_RDWR)  # server-side recv, close
            except OSError:            # releases the fd
                pass
            c.sock.close()
        pool, self._stripe_pool = self._stripe_pool, None
        if pool is not None:
            pool.shutdown(wait=True)   # joins every fanstore-stripe worker
        # close EVERY server even if one reports stuck threads, then
        # surface the first failure (a partial teardown would strand the
        # remaining serving loops with no further close coming)
        stuck: List[BaseException] = []
        for srv in self._servers.values():
            try:
                srv.close()
            except RuntimeError as exc:
                stuck.append(exc)
        self._servers.clear()
        if stuck:
            raise stuck[0]

    def server_address(self, node_id: int) -> Tuple[str, int]:
        """The (host, port) a node's serving loop listens on."""
        self.start()
        return self._servers[node_id].address

    def _connect(self, owner: int) -> socket.socket:
        """Dial one connection to ``owner``'s serving loop, retrying a
        refused/reset dial with exponential backoff (``dial_retries``
        attempts) — a serving loop still binding during a startup race
        used to fail the first fetch permanently. A dropped or unknown
        owner raises ``ConnectionError`` (the classified failure the
        failover read path retries on another replica), never ``KeyError``.
        Call with the dial lock held."""
        srv = self._servers.get(owner)
        if srv is None:
            raise ConnectionError(
                f"node {owner} has no serving loop (dead or never joined)")
        last: Optional[OSError] = None
        for attempt in range(self.dial_retries + 1):
            if attempt:
                time.sleep(self.dial_backoff_s * (2 ** (attempt - 1)))
            try:
                sock = socket.create_connection(srv.address)
                _tune(sock, self.sock_buf_bytes)
                return sock
            except (ConnectionRefusedError, ConnectionResetError,
                    ConnectionAbortedError) as exc:
                last = exc
        raise ConnectionError(
            f"dial to node {owner} at {srv.address} failed after "
            f"{self.dial_retries + 1} attempts") from last

    def _conn(self, requester: int, owner: int, stripe: int = 0) -> _Conn:
        key = (requester, owner, stripe)
        hit = self._conns.get(key)      # GIL-atomic fast path
        if hit is not None:
            return hit
        # _lazy_start takes the lifecycle lock, so run it BEFORE taking
        # the dial lock (close() holds lifecycle while tearing down); it
        # raises rather than respawning servers on a closed backend
        self._lazy_start()
        with self._dial_lock:
            hit = self._conns.get(key)
            if hit is None:
                hit = _Conn(self._connect(owner))
                self._conns[key] = hit
        return hit

    # ---- membership --------------------------------------------------------
    def drop_node(self, node_id: int) -> None:
        """A peer died: close every stripe dialed to OR from it and tear
        down its serving loop, so stale connections fail fast with a
        ``ConnectionError`` (classified, retried on a replica) instead of
        hanging on a half-open socket."""
        with self._dial_lock:
            doomed = [k for k in self._conns
                      if node_id in (k[0], k[1])]
            conns = [self._conns.pop(k) for k in doomed]
            srv = self._servers.pop(node_id, None)
        for c in conns:
            try:
                c.sock.shutdown(socket.SHUT_RDWR)
            except OSError:
                pass
            c.sock.close()
        if srv is not None:
            srv.close()

    def ensure_node(self, node_id: int) -> None:
        """A peer (re)joined: spawn its serving loop if the wire is up
        (lazy start covers the not-yet-started case)."""
        with self._lifecycle:
            started = self._started
        if started and node_id not in self._servers:
            self._servers[node_id] = _NodeServer(
                node_id, self.nodes[node_id], self.host,
                policy=self.wire_policy, buf_bytes=self.sock_buf_bytes,
                join_timeout_s=self.join_timeout_s)

    # ---- one round trip ----------------------------------------------------
    def _request(self, requester: int, owner: int, mtype: MsgType,
                 body: bytes, *, parts: Optional[List[bytes]] = None
                 ) -> Tuple[MsgType, memoryview]:
        conn = self._conn(requester, owner)
        with conn.lock:                # one frame out, one frame back
            if parts is not None:
                wire.write_frame_parts(conn.sock, mtype, parts)
            else:
                wire.write_frame(conn.sock, mtype, body)
            rtype, rbody = wire.read_frame(conn.sock, conn.rbuf)
            if rtype == MsgType.ERR:
                raise wire.decode_error(rbody)
            # copy before dropping the lock: rbody aliases the reusable
            # receive buffer, which the next request overwrites (OK/STAT
            # responses are tiny; DATA responses decode under the lock in
            # _fetch_on_stripe instead)
            return rtype, memoryview(bytes(rbody))

    # ---- striped + pipelined fetch -----------------------------------------
    def _fetch_on_stripe(self, requester: int, owner: int, stripe: int,
                         items: Sequence[FetchItem], materialize: bool,
                         verb: str) -> Tuple[List[bytes], int, int, int]:
        """One stripe leg: up to ``pipeline_depth`` request frames in
        flight on this stripe's connection. Every request frame goes out
        before the first response is read — the server answers strictly
        in order per connection, so the pipeline can never mismatch.
        Returns (payloads, serve_ns, raw_bytes, wire_bytes)."""
        mtype = _FETCH_TYPES[verb]
        depth = self.pipeline_depth if len(items) > 1 else 1
        chunks = wire.split_stripes(items, depth)
        conn = self._conn(requester, owner, stripe)
        payloads: List[bytes] = []
        serve_ns = raw_b = wire_b = 0
        err: Optional[BaseException] = None
        with conn.lock:
            wire.sendmsg_all(conn.sock, [
                wire.frame(mtype, wire.encode_fetch(
                    [it.path for it in items[s:e]], materialize=materialize))
                for s, e in chunks])
            for _ in chunks:           # drain EVERY response (keep framing
                rtype, rbody = wire.read_frame(conn.sock, conn.rbuf)
                if rtype == MsgType.ERR:   # aligned even past an error)
                    err = err or wire.decode_error(rbody)
                    continue
                p, s_ns, raw, sent = wire.decode_data_ex(rbody)
                payloads.extend(p)
                serve_ns += s_ns
                raw_b += raw
                wire_b += sent
        if err is not None:
            raise err
        return payloads, serve_ns, raw_b, wire_b

    def _timed_stripe(self, requester: int, owner: int, stripe: int,
                      items: Sequence[FetchItem], materialize: bool,
                      verb: str) -> Tuple[List[bytes], int]:
        """Run one stripe leg and book its wall time, bytes, and codec
        ledger under the stripe's id."""
        t0 = time.perf_counter_ns()
        payloads, serve_ns, raw_b, wire_b = self._fetch_on_stripe(
            requester, owner, stripe, items, materialize, verb)
        dt = time.perf_counter_ns() - t0
        with self._lock:
            w = self.wall[requester]
            w.attribute_stripe(stripe, dt, sum(len(p) for p in payloads))
            w.wire_raw_bytes += raw_b
            w.wire_sent_bytes += wire_b
        return payloads, serve_ns

    # ---- movement primitives -----------------------------------------------
    def _move_fetch(self, requester: int, owner: int,
                    items: Sequence[FetchItem], materialize: bool,
                    verb: str, views: bool = False
                    ) -> Tuple[List[bytes], int]:
        pool = self._stripe_pool
        n_stripes = min(self.stripes, len(items)) if materialize else 1
        if (n_stripes > 1 and pool is not None
                and sum(it.stored for it in items) >= self.stripe_min_bytes):
            bounds = wire.split_stripes(items, n_stripes)
            futs = [pool.submit(self._timed_stripe, requester, owner, sid,
                                items[s:e], materialize, verb)
                    for sid, (s, e) in enumerate(bounds)]
            results = [f.result() for f in futs]
            payloads = wire.reassemble(
                len(items),
                [(bounds[i], results[i][0]) for i in range(len(bounds))])
            # serve legs run on concurrent handler threads server-side;
            # lanes are activity totals, so they sum (same convention as
            # every measured lane)
            return payloads, sum(r[1] for r in results)
        return self._timed_stripe(requester, owner, 0, items, materialize,
                                  verb)

    def _move_put(self, writer: int, owner: int,
                  pairs: Sequence[Tuple[FetchItem, bytes]]) -> int:
        policy = self.wire_policy if self.wire_policy.codec != "none" else None
        _, rbody = self._request(
            writer, owner, MsgType.PUT_BATCH, b"",
            parts=wire.encode_put_parts(
                writer, [(it.path, d) for it, d in pairs], policy=policy))
        return wire.decode_ok(rbody)

    # ---- extra wire verb ---------------------------------------------------
    def stat_remote(self, requester: int, owner: int,
                    path: str) -> StatRecord:
        """Ask an owner's serving loop for a file's stat over the wire."""
        _, rbody = self._request(requester, owner, MsgType.STAT,
                                 wire.encode_stat(path))
        st, _ = wire.decode_stat_ok(rbody)
        return st
