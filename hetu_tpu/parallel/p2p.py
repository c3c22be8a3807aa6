"""Host-mediated pipeline boundary transport between worker processes.

Reference parity: PipelineSendOp/PipelineReceiveOp move stage boundaries
over NCCL p2p (reference gpu_ops/PipelineSend.py:8-74,
mpi_nccl_communication.cu:166-230). On TPU pods, in-process stage
boundaries ride ICI via device placement; when stages span *worker
processes* (pods/hosts), the boundary crosses DCN — here a direct TCP
channel carrying numpy buffers between the owning hosts, the same
host-mediated role the reference's vans play for PS traffic.

Addressing: rank k listens on ``HETU_PIPE_HOSTS[k] : HETU_PIPE_BASE_PORT
+ k`` (launcher-exported; defaults cover the single-machine case).
Messages are tagged; ``recv(tag)`` blocks until a matching message
arrives, so the pipeline's data dependencies double as cross-process
synchronization — no separate barrier protocol.

Flow control (round-4 review weak #2): the inbox is bounded at
``HETU_PIPE_MAX_BUF_MB`` (default 256). When a slow consumer lets the
buffer fill, reader threads stop draining their sockets, so TCP's own
window pushes back on the sender — host RSS stays bounded instead of
growing with every in-flight boundary tensor. Large payloads stream
from the array's buffer in 4MB chunks (no whole-message copy on send).
"""
from __future__ import annotations

import os
import socket
import struct
import threading
from collections import deque

import numpy as np

from .. import telemetry as _telemetry

__all__ = ["PipeChannel", "get_channel"]

_MAGIC = 0x48503250  # "HP2P"
_HDR = struct.Struct("<IHHQ")  # magic, taglen, dtypelen, payload bytes
_CHUNK = 4 << 20


class PipeChannel:
    def __init__(self, rank, nprocs):
        self.rank = rank
        self.nprocs = nprocs
        hosts = os.environ.get(
            "HETU_PIPE_HOSTS",
            ",".join(["127.0.0.1"] * nprocs)).split(",")
        base = int(os.environ.get("HETU_PIPE_BASE_PORT", "19500"))
        self.addrs = [(hosts[i % len(hosts)], base + i)
                      for i in range(nprocs)]
        self._inbox = {}          # tag -> deque[np.ndarray]
        self._mu = threading.Lock()
        self._cv = threading.Condition(self._mu)
        self._buffered = 0        # inbox bytes (flow-control accounting)
        self._wanted = set()      # tags an active recv() is blocked on
        self._sending = 0         # sends in flight (see backpressure)
        self.max_buffered = int(os.environ.get(
            "HETU_PIPE_MAX_BUF_MB", "256")) << 20
        self._out = {}            # dst rank -> (socket, send lock)
        self._out_mu = threading.Lock()   # guards the MAP only
        self._closing = False
        self._listener = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        self._listener.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR,
                                  1)
        self._listener.bind(("0.0.0.0", self.addrs[rank][1]))
        self._listener.listen(8)
        self._accept_thread = threading.Thread(target=self._accept_loop,
                                               daemon=True)
        self._accept_thread.start()

    # -- receive side ----------------------------------------------------
    def _accept_loop(self):
        while not self._closing:
            try:
                conn, _ = self._listener.accept()
            except OSError:
                return
            conn.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            threading.Thread(target=self._conn_loop, args=(conn,),
                             daemon=True).start()

    def _read_full(self, conn, n):
        buf = bytearray(n)
        view = memoryview(buf)
        got = 0
        while got < n:
            r = conn.recv_into(view[got:], n - got)
            if r == 0:
                return None
            got += r
        return bytes(buf)

    def _conn_loop(self, conn):
        with conn:
            while True:
                hdr = self._read_full(conn, _HDR.size)
                if hdr is None:
                    return
                magic, taglen, dtlen, nbytes = _HDR.unpack(hdr)
                if magic != _MAGIC:
                    return
                meta = self._read_full(conn, taglen + dtlen + 4)
                if meta is None:
                    return
                tag = meta[:taglen].decode()
                dtype = np.dtype(meta[taglen:taglen + dtlen].decode())
                ndim = struct.unpack_from("<i", meta, taglen + dtlen)[0]
                dims = self._read_full(conn, 8 * ndim)
                if dims is None and ndim:
                    return
                shape = struct.unpack(f"<{ndim}q", dims) if ndim else ()
                body = self._read_full(conn, nbytes) if nbytes else b""
                if body is None:
                    return
                arr = np.frombuffer(body, dtype=dtype).reshape(shape)
                with self._cv:
                    # backpressure: hold THIS reader (and via unread TCP
                    # bytes, its sender) while the consumer lags — i.e.
                    # while it is neither in recv() nor in send(). While
                    # it is, always admit: a blocked recv's message may
                    # be behind any other message on any connection, and
                    # a consumer blocked in send() (peer's inbox full,
                    # TCP window closed) with its own inbox also at cap
                    # would otherwise deadlock both ranks of a
                    # bidirectional pipeline. The cap thus bounds RSS
                    # exactly in the runaway case (producer far ahead,
                    # consumer busy computing), which is the case that
                    # grows RSS.
                    self._cv.wait_for(
                        lambda: self._buffered < self.max_buffered
                        or self._wanted or self._sending
                        or self._closing)
                    if self._closing:
                        return
                    self._inbox.setdefault(tag, deque()).append(arr)
                    self._buffered += arr.nbytes
                    self._cv.notify_all()

    def recv(self, tag, timeout=None):
        """Block until a message tagged ``tag`` arrives; FIFO per tag.
        Default timeout is HETU_PIPE_TIMEOUT_S (600s — the peer may be
        XLA-compiling its stage block on the first step). With telemetry
        on, the wait is recorded as a ``p2p_recv`` span with the payload
        byte count (the cross-rank half of pipeline-bubble accounting;
        pipeline.py attributes the same wait to its stage)."""
        tel = _telemetry.get_telemetry()
        if not tel.enabled:
            return self._recv(tag, timeout)
        # black box: a recv that never completes is the signature of a
        # dead/diverged peer — the pending flight entry names the tag
        # (and the blackbox CLI names the rank it implies)
        frec = tel.flight_start("p2p", "p2p_recv",
                                peer=self._peer_of_tag(tag), tag=tag)
        t0 = tel.clock()
        arr = self._recv(tag, timeout)
        t1 = tel.clock()
        tel.flight_complete(frec)
        tel.complete("p2p_recv", t0, t1,
                     {"tag": tag, "bytes": int(arr.nbytes)})
        tel.inc("p2p_recv_bytes", int(arr.nbytes))
        tel.observe("p2p_recv_wait_ms", (t1 - t0) / 1e6)
        return arr

    def _peer_of_tag(self, tag):
        """Best-effort peer rank for a recv: in a 2-process fleet the
        sender is unambiguous; beyond that the tag itself is the
        diagnostic and the peer stays unknown (None)."""
        if self.nprocs == 2:
            return 1 - self.rank
        return None

    def _recv(self, tag, timeout=None):
        if timeout is None:
            timeout = float(os.environ.get("HETU_PIPE_TIMEOUT_S", "600"))
        with self._cv:
            self._wanted.add(tag)
            self._cv.notify_all()   # readers holding this tag may admit
            try:
                ok = self._cv.wait_for(
                    lambda: self._inbox.get(tag), timeout=timeout)
            finally:
                self._wanted.discard(tag)
            if not ok:
                raise TimeoutError(
                    f"pipeline recv timed out waiting for '{tag}' on "
                    f"rank {self.rank}")
            q = self._inbox[tag]
            arr = q.popleft()
            if not q:
                del self._inbox[tag]   # tags are step-unique: don't leak
            self._buffered -= arr.nbytes
            self._cv.notify_all()      # wake readers held by backpressure
            return arr

    # -- send side -------------------------------------------------------
    def _conn_to(self, dst):
        """(socket, per-destination send lock) for ``dst``."""
        with self._out_mu:
            ent = self._out.get(dst)
        if ent is not None:
            return ent
        # connect OUTSIDE the map lock (HT603 finding): the 60s retry
        # loop against a not-yet-listening peer must not stall sends to
        # every OTHER rank behind _out_mu
        host, port = self.addrs[dst]
        deadline = 60.0
        import time
        t0 = time.time()
        while True:
            try:
                s = socket.create_connection((host, port), timeout=5)
                break
            except OSError:
                if time.time() - t0 > deadline:
                    raise
                time.sleep(0.1)
        s.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        drop = None
        with self._out_mu:
            ent = self._out.get(dst)
            if ent is not None:
                # two senders raced the first connect: keep the socket
                # already in the map (its peer may have received bytes)
                drop = s
            elif self._closing:
                # close() already cleared the map: storing now would
                # leak a socket nothing will ever close
                drop = s
            else:
                ent = self._out[dst] = (s, threading.Lock())
        if drop is not None:
            try:
                drop.close()
            except OSError:
                pass
        if ent is None:
            raise OSError("PipeChannel is closed")
        return ent

    def send(self, dst, tag, arr):
        tel = _telemetry.get_telemetry()
        if not tel.enabled:
            return self._send(dst, tag, arr)
        nbytes = int(getattr(arr, "nbytes", 0))
        frec = tel.flight_start("p2p", "p2p_send", peer=dst, tag=tag,
                                nbytes=nbytes)
        with tel.span("p2p_send", tag=tag, dst=dst, bytes=nbytes):
            self._send(dst, tag, arr)
        tel.flight_complete(frec)
        tel.inc("p2p_send_bytes", nbytes)

    def _send(self, dst, tag, arr):
        arr = np.ascontiguousarray(arr)
        tb = tag.encode()
        db = arr.dtype.str.encode()
        hdr = (_HDR.pack(_MAGIC, len(tb), len(db), arr.nbytes) + tb + db
               + struct.pack("<i", arr.ndim)
               + struct.pack(f"<{arr.ndim}q", *arr.shape))
        view = memoryview(arr).cast("B")
        s, send_lk = self._conn_to(dst)
        with self._cv:
            self._sending += 1
            self._cv.notify_all()   # readers may admit while we send
        try:
            # per-DESTINATION send lock: frames on one socket must not
            # interleave, but a huge boundary tensor to one rank (or
            # its TCP-backpressure stall) must not block sends to every
            # other rank behind a channel-wide lock
            with send_lk:
                s.sendall(hdr)
                # stream the payload from the array's own buffer in
                # chunks: no whole-message copy, and large boundary
                # tensors interleave with TCP flow control instead of
                # one giant blob
                for off in range(0, arr.nbytes, _CHUNK):
                    s.sendall(view[off:off + _CHUNK])
        finally:
            with self._cv:
                self._sending -= 1

    def close(self):
        self._closing = True
        with self._cv:
            self._cv.notify_all()   # release readers held by backpressure
        try:
            self._listener.close()
        except OSError:
            pass
        with self._out_mu:
            for s, _lk in self._out.values():
                try:
                    s.close()
                except OSError:
                    pass
            self._out.clear()


_channel = None
_channel_mu = threading.Lock()


def get_channel():
    """Process-wide channel, built from the launcher env on first use.
    Double-checked: two pipeline runner threads first-touching the
    channel must not both bind the listener (HT605)."""
    global _channel
    if _channel is None:
        with _channel_mu:
            if _channel is None:
                rank = int(os.environ.get("HETU_PROC_ID", "0"))
                nprocs = int(os.environ.get("HETU_NUM_PROCS", "1"))
                _channel = PipeChannel(rank, nprocs)
    return _channel
