"""Checkpoint fleet-resize (round-4 review #7): a key-range-partitioned
tensor saved under one server count loads under another — the client
reassembles the saved shards from the manifest and redistributes over
the new ranges (reference parity: ps-lite server dumps are
partition-independent raw binaries, PSFHandle.h:357-395)."""
import os

import numpy as np
import pytest

from hetu_tpu.ps import server as ps_server
from hetu_tpu.ps import client as ps_client

ROWS, WIDTH = 10, 4
TID = 4700


def _fleet(nservers, nworkers=1):
    ports = [ps_server.pick_free_port() for _ in range(nservers)]
    os.environ["HETU_PS_PORTS"] = ",".join(str(p) for p in ports)
    os.environ["HETU_PS_HOSTS"] = ",".join(["127.0.0.1"] * nservers)
    for p in ports:
        ps_server.ensure_server(port=p, nworkers=nworkers)
    client = ps_client.PSClient(rank=0, nworkers=nworkers)
    assert client.nservers == nservers
    return client


def _teardown(client):
    client.shutdown_servers()
    client.close()
    ps_server.shutdown_server()


@pytest.mark.parametrize("new_nservers", [1, 3])
def test_resize_load(tmp_path, new_nservers):
    val = np.arange(ROWS * WIDTH, dtype=np.float32).reshape(ROWS, WIDTH)
    path = str(tmp_path / "emb.bin")

    save_client = _fleet(2)
    try:
        save_client.init_tensor(TID, (ROWS, WIDTH), kind=0, opt="None")
        save_client.set_param(TID, val)
        assert save_client.save_param(TID, path) == 0
    finally:
        _teardown(save_client)

    load_client = _fleet(new_nservers)
    try:
        load_client.init_tensor(TID, (ROWS, WIDTH), kind=0, opt="None")
        assert load_client.load_param(TID, path) == 0
        np.testing.assert_allclose(
            load_client.pull(TID, (ROWS, WIDTH)), val)
    finally:
        _teardown(load_client)


def test_unsplit_checkpoint_loads_into_split_fleet(tmp_path):
    """A checkpoint written by a single server (no manifest) loads into a
    multi-server fleet: treated as one full dump and re-split."""
    val = np.linspace(0, 1, ROWS * WIDTH, dtype=np.float32).reshape(
        ROWS, WIDTH)
    path = str(tmp_path / "single.bin")

    c1 = _fleet(1)
    try:
        c1.init_tensor(TID + 1, (ROWS, WIDTH), kind=0, opt="None")
        c1.set_param(TID + 1, val)
        assert c1.save_param(TID + 1, path) == 0
    finally:
        _teardown(c1)

    c2 = _fleet(2)
    try:
        c2.init_tensor(TID + 1, (ROWS, WIDTH), kind=0, opt="None")
        assert c2.load_param(TID + 1, path) == 0
        np.testing.assert_allclose(c2.pull(TID + 1, (ROWS, WIDTH)), val)
    finally:
        _teardown(c2)
