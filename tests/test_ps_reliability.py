"""PS transport reliability (round-4 review #3; reference parity:
ps-lite/src/resender.h retry-on-timeout + customer.h request tracking).

Covers: (a) requests issued while the server is dead block, retry with
backoff, reconnect to a restarted server, and complete; (b) a mutating
request replayed with the same (worker, seq) identity — the wire-level
situation after a lost response — applies exactly once; (c) training
completes across a kill+restart using the worker-driven state-recovery
contract (re-register + upload last-known values)."""
import os
import socket
import struct
import threading
import time

import numpy as np
import pytest

from hetu_tpu.ps import server as ps_server
from hetu_tpu.ps import client as ps_client

HDR = struct.Struct("<IIiiQIIQ")  # magic op tensor_id status len worker res seq
MAGIC = 0x48505332


def _send_raw(port, op, tensor_id, payload, worker=7, seq=1):
    with socket.create_connection(("127.0.0.1", port), timeout=5) as s:
        s.sendall(HDR.pack(MAGIC, op, tensor_id, 0, len(payload),
                           worker, 0, seq) + payload)
        hdr = b""
        while len(hdr) < HDR.size:
            hdr += s.recv(HDR.size - len(hdr))
        magic, _, _, status, plen, _, _, _ = HDR.unpack(hdr)
        assert magic == MAGIC
        body = b""
        while len(body) < plen:
            body += s.recv(plen - len(body))
        return status, body


def _floats_payload(arr):
    a = np.asarray(arr, np.float32).ravel()
    return struct.pack("<q", a.size) + a.tobytes()


@pytest.fixture()
def ps1():
    port = ps_server.pick_free_port()
    os.environ["HETU_PS_PORTS"] = str(port)
    os.environ["HETU_PS_HOSTS"] = "127.0.0.1"
    os.environ["HETU_PS_TIMEOUT_MS"] = "5000"
    os.environ["HETU_PS_RETRY_MS"] = "30000"
    proc = ps_server.ensure_server(port=port, nworkers=1)
    client = ps_client.PSClient(rank=0, nworkers=1)
    yield client, proc, port
    try:
        client.shutdown_servers()
    except Exception:
        pass
    client.close()
    ps_server.shutdown_server()
    for k in ("HETU_PS_TIMEOUT_MS", "HETU_PS_RETRY_MS"):
        os.environ.pop(k, None)


def test_duplicate_push_applies_once(ps1):
    """Same (worker, seq) DensePush twice == the retry-after-lost-response
    wire pattern; the server's dedup must apply it exactly once."""
    client, _, port = ps1
    client.init_tensor(4100, (8,), kind=0, opt="None")
    client.set_param(4100, np.zeros(8, np.float32))
    g = np.ones(8, np.float32)
    payload = _floats_payload(g)
    assert _send_raw(port, 3, 4100, payload, worker=7, seq=42)[0] == 0
    assert _send_raw(port, 3, 4100, payload, worker=7, seq=42)[0] == 0
    np.testing.assert_allclose(client.pull(4100, (8,)), np.ones(8))
    # a NEW seq from the same worker applies again
    assert _send_raw(port, 3, 4100, payload, worker=7, seq=43)[0] == 0
    np.testing.assert_allclose(client.pull(4100, (8,)), 2 * np.ones(8))


def test_duplicate_ddpushpull_still_serves_read(ps1):
    """A retried DDPushPull must skip the push but still answer the pull
    with current values (the response the first attempt lost)."""
    client, _, port = ps1
    client.init_tensor(4101, (4,), kind=0, opt="SGD", lrs=[0.5])
    client.set_param(4101, np.zeros(4, np.float32))
    payload = _floats_payload(np.ones(4, np.float32))
    st, body = _send_raw(port, 4, 4101, payload, worker=7, seq=99)
    assert st == 0
    st, body = _send_raw(port, 4, 4101, payload, worker=7, seq=99)
    assert st == 0
    n = struct.unpack_from("<q", body)[0]
    vals = np.frombuffer(body[8:8 + 4 * n], np.float32)
    np.testing.assert_allclose(vals, -0.5 * np.ones(4))   # applied once
    np.testing.assert_allclose(client.pull(4101, (4,)), -0.5 * np.ones(4))


def test_kill_restart_mid_train_completes(ps1):
    """Kill -9 the server mid-train, restart it on the same port, and
    finish training: the client layer retries/reconnects transparently
    (requests issued during the outage block, not fail), and the worker
    restores server state by re-registering and uploading its last-known
    values (the recovery contract: dense params are mastered worker-side
    between pulls, so a restarted empty server is re-seeded)."""
    client, proc, port = ps1
    client.init_tensor(4102, (16,), kind=0, opt="SGD", lrs=[0.1])
    vals = np.zeros(16, np.float32)
    client.set_param(4102, vals)
    g = np.ones(16, np.float32)
    for _ in range(3):
        out = client.dd_pushpull(4102, g)
        client.wait(4102)
        vals = out.copy()
    np.testing.assert_allclose(vals, -0.3 * np.ones(16), rtol=1e-5)

    # hard-kill the server; restart it ~1.5s later from another thread
    proc.kill()
    proc.wait()

    def restart():
        time.sleep(1.5)
        ps_server.ensure_server(port=port, nworkers=1)

    t = threading.Thread(target=restart)
    t.start()
    # issued while the server is DOWN: must retry+reconnect, not fail
    client.init_tensor(4102, (16,), kind=0, opt="SGD", lrs=[0.1])
    t.join()
    client.set_param(4102, vals)         # re-seed from worker copy
    for _ in range(2):
        out = client.dd_pushpull(4102, g)
        client.wait(4102)
        vals = out.copy()
    np.testing.assert_allclose(vals, -0.5 * np.ones(16), rtol=1e-5)


def test_ensure_server_adopts_startup_race_winner(monkeypatch):
    """Two processes race ensure_server: both see the port closed, both
    try to claim it — the kernel lets exactly one bind. The loser must
    wait for the winner's server and adopt it (return None), not spawn
    a doomed child or raise (ISSUE 13 satellite). Simulated by
    occupying the port with a listener while forcing the fast-path
    check to miss it once (the race window)."""
    sock = socket.socket()
    sock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
    sock.bind(("0.0.0.0", 0))
    sock.listen(1)
    port = sock.getsockname()[1]
    real_port_open = ps_server._port_open
    calls = {"n": 0}

    def racy_port_open(host, p):
        calls["n"] += 1
        if calls["n"] == 1:
            return False        # the race window: check misses the winner
        return real_port_open(host, p)

    monkeypatch.setattr(ps_server, "_port_open", racy_port_open)
    procs_before = list(ps_server._server_procs)
    try:
        # the claim-bind fails (winner holds the port): adopt, never
        # spawn — and never hand back a dead Popen
        assert ps_server.ensure_server(port=port, nworkers=1) is None
        assert ps_server._server_procs == procs_before
        assert calls["n"] >= 2          # fast path missed, adopt re-checked
    finally:
        sock.close()


def test_ensure_server_detects_child_death_during_startup(monkeypatch):
    """With the port pre-listened by the parent's claim, connectability
    no longer proves the child is serving — a child that dies during
    startup must surface as "exited during startup" via the readiness
    pipe, not be handed back as a live server whose backlog swallows
    connections."""
    monkeypatch.setattr(ps_server.sys, "executable", "/bin/false")
    port = ps_server.pick_free_port()
    try:
        with pytest.raises(RuntimeError, match="during startup"):
            ps_server.ensure_server(port=port, nworkers=1, wait_s=5.0)
        # the claim died with the child: the port is free again
        assert not ps_server._port_open("127.0.0.1", port)
    finally:
        ps_server.shutdown_server()
