"""p2p channel hardening (round-4 review weak #2/#8): bounded inbox with
TCP backpressure, chunked large-message streaming, and loud unmapped-
hostname errors instead of the silent rank-0 fallback."""
import threading
import time

import numpy as np
import pytest

from hetu_tpu.parallel.p2p import PipeChannel
from hetu_tpu.parallel.pipeline import _owner_of
from hetu_tpu.ps.server import pick_free_port


@pytest.fixture()
def channel_pair(monkeypatch):
    monkeypatch.setenv("HETU_PIPE_BASE_PORT", str(pick_free_port()))
    monkeypatch.setenv("HETU_PIPE_HOSTS", "127.0.0.1,127.0.0.1")
    a = PipeChannel(0, 2)
    b = PipeChannel(1, 2)
    yield a, b
    a.close()
    b.close()


def test_roundtrip_large_message_chunked(channel_pair):
    """A 20MB tensor streams through the 4MB-chunk path intact."""
    a, b = channel_pair
    arr = np.arange(5 * 1024 * 1024, dtype=np.float32).reshape(5, -1)
    a.send(1, "big", arr)
    got = b.recv("big", timeout=30)
    np.testing.assert_array_equal(got, arr)
    assert b._buffered == 0


def test_slow_consumer_backpressure(channel_pair):
    """A flooding sender cannot grow the consumer's inbox past the
    configured bound — the reader thread stops draining its socket and
    TCP pushes back on the sender."""
    a, b = channel_pair
    b.max_buffered = 4 << 20          # 4MB cap for the test
    msg = np.ones((1 << 18,), np.float32)   # 1MB each
    n = 40

    def flood():
        for i in range(n):
            a.send(1, f"m{i}", msg)

    t = threading.Thread(target=flood, daemon=True)
    t.start()
    # let the sender run against the cap; the inbox must stay bounded
    # (cap + at most one in-flight message per reader thread)
    time.sleep(1.0)
    assert b._buffered <= b.max_buffered + msg.nbytes, b._buffered
    # drain everything: the held reader resumes and all 40MB arrive
    for i in range(n):
        got = b.recv(f"m{i}", timeout=30)
        assert got.nbytes == msg.nbytes
    t.join(timeout=30)
    assert not t.is_alive()
    assert b._buffered == 0


def test_owner_of_unmapped_host_raises(monkeypatch):
    monkeypatch.delenv("HETU_HOSTS", raising=False)
    assert _owner_of("worker3", 4) == 3
    assert _owner_of("localhost", 4) == 0
    assert _owner_of("anything", 1) == 0      # single-process: fine
    monkeypatch.setenv("HETU_HOSTS", "alpha,beta")
    assert _owner_of("beta", 2) == 1
    with pytest.raises(ValueError, match="does not map"):
        _owner_of("btea", 2)                  # typo'd yaml fails fast


def test_owner_of_rejects_local_nodename_multiproc(monkeypatch):
    """The local nodename is NOT an accepted stage hostname in
    multi-process runs (ADVICE r5 #1): rank k's nodename differs from
    rank j's, so a nodename escape hatch would resolve the same stage
    to different owners on different ranks and silently split the
    pipeline. Only rank-invariant names resolve: worker<k>, HETU_HOSTS
    entries, localhost."""
    import os
    monkeypatch.delenv("HETU_HOSTS", raising=False)
    node = os.uname().nodename
    if node in ("localhost", "127.0.0.1") or (
            node.startswith("worker") and node[6:].isdigit()):
        pytest.skip("host's nodename is itself a mapped name")
    with pytest.raises(ValueError, match="does not map"):
        _owner_of(node, 2)
    # still fine single-process, and when HETU_HOSTS maps it
    assert _owner_of(node, 1) == 0
    monkeypatch.setenv("HETU_HOSTS", f"head,{node}")
    assert _owner_of(node, 2) == 1
