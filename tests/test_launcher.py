"""heturun launcher: yaml config -> PS servers + worker fleet on
localhost (reference bin/heturun + runner.py:148-270 single-machine path,
launcher.py:18-58)."""
import os
import subprocess
import sys

import numpy as np
import pytest

from hetu_tpu.launcher import ClusterConfig, parse_config

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

WORKER_SCRIPT = """
import os
import numpy as np
import hetu_tpu as ht
from hetu_tpu.executor import Executor

rank = int(os.environ["HETU_PS_RANK"])
rng = np.random.RandomState(0)
emb_val = rng.randn(50, 8).astype("f") * 0.1
w_val = rng.randn(8 * 4 + 5, 1).astype("f") * 0.1
dense = ht.Variable("dense", trainable=False)
sparse = ht.Variable("sparse", trainable=False)
y_ = ht.Variable("y_", trainable=False)
emb = ht.Variable("ctr_embedding", value=emb_val)
w = ht.Variable("ctr_w", value=w_val)
look = ht.embedding_lookup_op(emb, sparse)
flat = ht.array_reshape_op(look, (-1, 8 * 4))
feats = ht.concat_op(flat, dense, axis=1)
y = ht.sigmoid_op(ht.matmul_op(feats, w))
loss = ht.reduce_mean_op(ht.binarycrossentropy_op(y, y_), [0])
train_op = ht.optim.SGDOptimizer(learning_rate=0.3).minimize(loss)
exe = Executor([loss, train_op], ctx=ht.cpu(0), comm_mode="PS")
frng = np.random.RandomState(1 + rank)
losses = []
for _ in range(20):
    d = frng.randn(16, 5).astype("f")
    s = frng.randint(0, 50, (16, 4))
    # planted signal: label = sign of the first dense feature (fast to
    # learn through the dense weight even under async 2-worker pushes)
    yv = (d[:, :1] > 0).astype("f")
    losses.append(exe.run(feed_dict={dense: d, sparse: s, y_: yv}
                          )[0].asnumpy().item())
out = os.path.join(os.environ["HETU_TEST_OUT"], f"loss_{rank}.txt")
with open(out, "w") as f:
    f.write(" ".join(str(x) for x in losses))
"""

CONFIG = """
nodes:
  - host: localhost
    servers: 2
    workers: 2
    chief: true
"""


def test_parse_config(tmp_path):
    cfg_path = tmp_path / "cluster.yml"
    cfg_path.write_text(CONFIG)
    cfg = parse_config(str(cfg_path))
    assert cfg.chief == "localhost"
    assert cfg.num_servers == 2 and cfg.num_workers == 2
    assert cfg.single_host
    eps = cfg.server_endpoints()
    assert len(eps) == 2 and eps[0][1] != eps[1][1]


def test_parse_config_rejects_two_chiefs():
    with pytest.raises(AssertionError):
        ClusterConfig([{"host": "a", "chief": True},
                       {"host": "b", "chief": True}])


def test_heturun_end_to_end(tmp_path):
    """heturun -c cluster.yml python train.py: 2 servers + 2 workers on
    localhost, PS-mode CTR training, losses written per worker."""
    cfg_path = tmp_path / "cluster.yml"
    cfg_path.write_text(CONFIG)
    script = tmp_path / "train.py"
    script.write_text(WORKER_SCRIPT)
    env = {**os.environ,
           "PYTHONPATH": REPO + os.pathsep + os.environ.get(
               "PYTHONPATH", ""),
           "JAX_PLATFORMS": "cpu",
           "HETU_TEST_OUT": str(tmp_path)}
    env.pop("HETU_PS_HOSTS", None)
    env.pop("HETU_PS_PORTS", None)
    proc = subprocess.run(
        [sys.executable, "-m", "hetu_tpu.launcher", "-c", str(cfg_path),
         sys.executable, str(script)],
        env=env, capture_output=True, text=True, timeout=180)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    for rank in range(2):
        path = tmp_path / f"loss_{rank}.txt"
        assert path.exists(), f"worker {rank} wrote no losses"
        losses = [float(x) for x in path.read_text().split()]
        assert len(losses) == 20 and all(np.isfinite(losses))
        # planted-parity signal: the tail must improve on the head
        # (async 2-worker PS is noisy, so compare half-means)
        assert np.mean(losses[10:]) < np.mean(losses[:10]), \
            f"worker {rank}: {losses}"


DEVICE_CACHE_WORKER = """
import os
import numpy as np
import hetu_tpu as ht
from hetu_tpu.executor import Executor

rank = int(os.environ["HETU_PS_RANK"])
rng = np.random.RandomState(0)
emb_val = rng.randn(50, 8).astype("f") * 0.1
w_val = rng.randn(8 * 4 + 5, 1).astype("f") * 0.1
dense = ht.Variable("dense", trainable=False)
sparse = ht.Variable("sparse", trainable=False)
y_ = ht.Variable("y_", trainable=False)
emb = ht.Variable("ctr_embedding", value=emb_val)
w = ht.Variable("ctr_w", value=w_val)
look = ht.embedding_lookup_op(emb, sparse)
flat = ht.array_reshape_op(look, (-1, 8 * 4))
feats = ht.concat_op(flat, dense, axis=1)
y = ht.sigmoid_op(ht.matmul_op(feats, w))
loss = ht.reduce_mean_op(ht.binarycrossentropy_op(y, y_), [0])
train_op = ht.optim.SGDOptimizer(learning_rate=0.3).minimize(loss)
# the HET device-cache path: HBM rows, bounded staleness, 2 workers
exe = Executor([loss, train_op], ctx=ht.cpu(0), comm_mode="PS",
               cstable_policy="Device", cache_bound=3)
frng = np.random.RandomState(1 + rank)
losses = []
for _ in range(25):
    d = frng.randn(16, 5).astype("f")
    s = frng.randint(0, 50, (16, 4))
    yv = (d[:, :1] > 0).astype("f")
    losses.append(exe.run(feed_dict={dense: d, sparse: s, y_: yv}
                          )[0].asnumpy().item())
exe.close()
rt = next(iter(exe.ps_runtime.device_tables.values()))
out = os.path.join(os.environ["HETU_TEST_OUT"], f"dcl_{rank}.txt")
with open(out, "w") as f:
    f.write(" ".join(str(x) for x in losses))
    f.write("\\nperf " + str(rt.perf))
"""


SPMD_CONFIG = """
spmd: true
nodes:
  - host: localhost
    workers: 2
    chief: true
"""

SPMD_DP_WORKER = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
os.environ["JAX_PLATFORMS"] = "cpu"
import numpy as np
from hetu_tpu.executor import Executor, HetuConfig, maybe_init_distributed
maybe_init_distributed()        # joins the 2-process JAX job
import jax
jax.config.update("jax_default_matmul_precision", "highest")
assert jax.process_count() == 2, jax.process_count()
assert len(jax.devices()) == 2, jax.devices()
import hetu_tpu as ht
from jax.sharding import Mesh

rng = np.random.RandomState(0)
x = ht.Variable("x", trainable=False)
y_ = ht.Variable("y_", trainable=False)
w1 = ht.Variable("w1", value=rng.randn(12, 16).astype("f") * 0.3)
w2 = ht.Variable("w2", value=rng.randn(16, 4).astype("f") * 0.3)
h = ht.relu_op(ht.matmul_op(x, w1))
loss = ht.reduce_mean_op(
    ht.softmaxcrossentropy_op(ht.matmul_op(h, w2), y_), [0])
train_op = ht.optim.SGDOptimizer(0.2).minimize(loss)
mesh = Mesh(np.asarray(jax.devices()), ("dp",))
config = HetuConfig(eval_node_list=[loss, train_op], mesh=mesh)
config.nrank = 2
exe = Executor({"default": [loss, train_op]}, config=config)
frng = np.random.RandomState(3)
xs = frng.randn(32, 12).astype("f")
ys = np.eye(4, dtype="f")[frng.randint(0, 4, 32)]
losses = [float(np.asarray(exe.run(feed_dict={x: xs, y_: ys}
                                   )[0].asnumpy()).reshape(()))
          for _ in range(6)]
rank = int(os.environ["HETU_PROC_ID"])
with open(os.path.join(os.environ["HETU_TEST_OUT"],
                       f"spmd_dp_{rank}.txt"), "w") as f:
    f.write(" ".join(str(v) for v in losses))
"""

SPMD_PP_WORKER = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
os.environ["JAX_PLATFORMS"] = "cpu"
import numpy as np
from hetu_tpu.executor import Executor, maybe_init_distributed
maybe_init_distributed()
import jax
jax.config.update("jax_default_matmul_precision", "highest")
import hetu_tpu as ht

rank = int(os.environ["HETU_PROC_ID"])
rng = np.random.RandomState(0)
w1v = rng.randn(12, 16).astype("f") * 0.3
w2v = rng.randn(16, 4).astype("f") * 0.3
# stage 0 on worker process 0, stage 1 (with the loss) on process 1:
# the 'worker<k>' hostnames map stages to ranks (pipeline._owner_of)
with ht.context(ht.rcpu("worker0", 0)):
    x = ht.Variable("x", trainable=False)
    w1 = ht.Variable("w1", value=w1v)
    a = ht.relu_op(ht.matmul_op(x, w1))
with ht.context(ht.rcpu("worker1", 0)):
    w2 = ht.Variable("w2", value=w2v)
    y_ = ht.Variable("y_", trainable=False)
    loss = ht.reduce_mean_op(
        ht.softmaxcrossentropy_op(ht.matmul_op(a, w2), y_), [0])
    train_op = ht.optim.SGDOptimizer(0.2).minimize(loss)
exe = Executor([loss, train_op], gpipe=True, num_microbatches=4)
sub = exe.subexecutors["default"]
assert sub.multiproc, "2-process pipeline must take the cross-host path"
frng = np.random.RandomState(3)
xs = frng.randn(32, 12).astype("f")
ys = np.eye(4, dtype="f")[frng.randint(0, 4, 32)]
losses = []
for _ in range(6):
    out = exe.run(feed_dict={x: xs, y_: ys})
    if out[0] is not None:
        losses.append(float(np.asarray(out[0].asnumpy()).reshape(())))
with open(os.path.join(os.environ["HETU_TEST_OUT"],
                       f"spmd_pp_{rank}.txt"), "w") as f:
    f.write(" ".join(str(v) for v in losses))
"""


SPMD_1F1B_WORKER = SPMD_PP_WORKER.replace(
    "gpipe=True", "pipedream=True").replace(
    'f"spmd_pp_{rank}.txt"', 'f"spmd_1f1b_{rank}.txt"')


def _run_spmd(tmp_path, worker_src, name):
    cfg_path = tmp_path / "spmd.yml"
    cfg_path.write_text(SPMD_CONFIG)
    script = tmp_path / f"{name}.py"
    script.write_text(worker_src)
    from launcher_util import clean_launcher_env
    env = clean_launcher_env(HETU_TEST_OUT=str(tmp_path))
    proc = subprocess.run(
        [sys.executable, "-m", "hetu_tpu.launcher", "-c", str(cfg_path),
         sys.executable, str(script)],
        env=env, capture_output=True, text=True, timeout=240)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    return tmp_path


def _single_process_mlp_reference(steps=6):
    """The same MLP/batch trained in this (single) process — ground truth
    for both 2-process modes."""
    import hetu_tpu as ht
    from hetu_tpu.executor import Executor

    rng = np.random.RandomState(0)
    x = ht.Variable("x", trainable=False)
    y_ = ht.Variable("y_", trainable=False)
    w1 = ht.Variable("w1", value=rng.randn(12, 16).astype("f") * 0.3)
    w2 = ht.Variable("w2", value=rng.randn(16, 4).astype("f") * 0.3)
    h = ht.relu_op(ht.matmul_op(x, w1))
    loss = ht.reduce_mean_op(
        ht.softmaxcrossentropy_op(ht.matmul_op(h, w2), y_), [0])
    train_op = ht.optim.SGDOptimizer(0.2).minimize(loss)
    exe = Executor([loss, train_op], ctx=ht.cpu(0))
    frng = np.random.RandomState(3)
    xs = frng.randn(32, 12).astype("f")
    ys = np.eye(4, dtype="f")[frng.randint(0, 4, 32)]
    return [float(np.asarray(exe.run(feed_dict={x: xs, y_: ys}
                                     )[0].asnumpy()).reshape(()))
            for _ in range(steps)]


def test_two_process_dp_loss_equivalence(tmp_path):
    """round-4 review #2: 2 JAX processes (jax.distributed over
    localhost, gloo CPU collectives) training DP must produce the same
    loss trajectory as the same model in one process."""
    _run_spmd(tmp_path, SPMD_DP_WORKER, "dp_worker")
    base = _single_process_mlp_reference()
    for rank in range(2):
        path = tmp_path / f"spmd_dp_{rank}.txt"
        assert path.exists(), f"worker {rank} wrote no losses"
        got = [float(v) for v in path.read_text().split()]
        np.testing.assert_allclose(got, base, rtol=2e-4, atol=1e-5)


def test_two_process_pipeline_loss_equivalence(tmp_path):
    """round-4 review #2: a 2-stage GPipe pipeline split across 2
    worker PROCESSES (host-mediated boundary transport) matches the
    single-process run of the same model."""
    _run_spmd(tmp_path, SPMD_PP_WORKER, "pp_worker")
    base = _single_process_mlp_reference()
    # rank 1 owns the loss stage
    path = tmp_path / "spmd_pp_1.txt"
    assert path.exists()
    got = [float(v) for v in path.read_text().split()]
    assert len(got) == 6
    np.testing.assert_allclose(got, base, rtol=2e-4, atol=1e-5)
    # rank 0 ran all steps but owns no loss
    assert (tmp_path / "spmd_pp_0.txt").read_text().strip() == ""


def test_two_process_1f1b_loss_equivalence(tmp_path):
    """1F1B (PipeDream weight stashing) across 2 worker PROCESSES: each
    rank executes its projection of the global 1F1B schedule, so the
    loss trajectory is identical to the in-process 1F1B run of the
    same model (per-microbatch updates differ from GPipe's full-batch
    apply — ground truth is an in-process pipedream executor)."""
    import hetu_tpu as ht
    from hetu_tpu.executor import Executor

    _run_spmd(tmp_path, SPMD_1F1B_WORKER, "pd_worker")

    rng = np.random.RandomState(0)
    with ht.context(ht.cpu(0)):
        x = ht.Variable("x", trainable=False)
        w1 = ht.Variable("w1", value=rng.randn(12, 16).astype("f") * 0.3)
        a = ht.relu_op(ht.matmul_op(x, w1))
    with ht.context(ht.cpu(1)):
        w2 = ht.Variable("w2", value=rng.randn(16, 4).astype("f") * 0.3)
        y_ = ht.Variable("y_", trainable=False)
        loss = ht.reduce_mean_op(
            ht.softmaxcrossentropy_op(ht.matmul_op(a, w2), y_), [0])
        train_op = ht.optim.SGDOptimizer(0.2).minimize(loss)
    exe = Executor([loss, train_op], pipedream=True, num_microbatches=4)
    frng = np.random.RandomState(3)
    xs = frng.randn(32, 12).astype("f")
    ys = np.eye(4, dtype="f")[frng.randint(0, 4, 32)]
    base = [float(np.asarray(exe.run(feed_dict={x: xs, y_: ys}
                                     )[0].asnumpy()).reshape(()))
            for _ in range(6)]

    path = tmp_path / "spmd_1f1b_1.txt"
    assert path.exists()
    got = [float(v) for v in path.read_text().split()]
    assert len(got) == 6
    np.testing.assert_allclose(got, base, rtol=2e-4, atol=1e-5)
    assert (tmp_path / "spmd_1f1b_0.txt").read_text().strip() == ""


def test_heturun_device_cache_two_workers(tmp_path):
    """2 servers + 2 workers with the HBM device cache: bounded-staleness
    drains and refreshes run against a live multi-worker fleet; both
    workers' planted-signal losses must fall."""
    cfg_path = tmp_path / "cluster.yml"
    cfg_path.write_text(CONFIG)
    script = tmp_path / "train_dc.py"
    script.write_text(DEVICE_CACHE_WORKER)
    env = {**os.environ,
           "PYTHONPATH": REPO + os.pathsep + os.environ.get(
               "PYTHONPATH", ""),
           "JAX_PLATFORMS": "cpu",
           "HETU_TEST_OUT": str(tmp_path)}
    env.pop("HETU_PS_HOSTS", None)
    env.pop("HETU_PS_PORTS", None)
    proc = subprocess.run(
        [sys.executable, "-m", "hetu_tpu.launcher", "-c", str(cfg_path),
         sys.executable, str(script)],
        env=env, capture_output=True, text=True, timeout=240)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    for rank in range(2):
        path = tmp_path / f"dcl_{rank}.txt"
        assert path.exists(), f"worker {rank} wrote no losses"
        first = path.read_text().splitlines()[0]
        losses = [float(x) for x in first.split()]
        assert losses[-1] < losses[0], (rank, losses[0], losses[-1])


HYBRID_SPMD_CONFIG = """
spmd: true
nodes:
  - host: localhost
    servers: 1
    workers: 2
    chief: true
"""

SPMD_HYBRID_WORKER = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
os.environ["JAX_PLATFORMS"] = "cpu"
import numpy as np
from hetu_tpu.executor import Executor, HetuConfig, maybe_init_distributed
maybe_init_distributed()
import jax
jax.config.update("jax_default_matmul_precision", "highest")
from jax.sharding import Mesh
import hetu_tpu as ht

rank = int(os.environ["HETU_PROC_ID"])
rng = np.random.RandomState(0)
emb_val = rng.randn(50, 8).astype("f") * 0.1
w_val = rng.randn(8 * 4 + 5, 1).astype("f") * 0.1
dense = ht.Variable("dense", trainable=False)
sparse = ht.Variable("sparse", trainable=False)
y_ = ht.Variable("y_", trainable=False)
emb = ht.Variable("hy2_embedding", value=emb_val)
w = ht.Variable("hy2_w", value=w_val)
look = ht.embedding_lookup_op(emb, sparse)
flat = ht.array_reshape_op(look, (-1, 8 * 4))
feats = ht.concat_op(flat, dense, axis=1)
y = ht.sigmoid_op(ht.matmul_op(feats, w))
loss = ht.reduce_mean_op(ht.binarycrossentropy_op(y, y_), [0])
train_op = ht.optim.SGDOptimizer(learning_rate=0.3).minimize(loss)
mesh = Mesh(np.asarray(jax.devices()), ("dp",))
config = HetuConfig(eval_node_list=[loss, train_op], comm_mode="Hybrid",
                    cstable_policy="Device", cache_bound=3, mesh=mesh)
config.nrank = 2
exe = Executor({"default": [loss, train_op]}, config=config)
frng = np.random.RandomState(1)    # SAME batches on both ranks (SPMD)
losses = []
for step in range(25):
    d = frng.randn(16, 5).astype("f")
    s = frng.randint(0, 50, (16, 4))
    yv = (d[:, :1] > 0).astype("f")
    losses.append(float(np.asarray(
        exe.run(feed_dict={dense: d, sparse: s, y_: yv}
                )[0].asnumpy()).reshape(())))
exe.ps_runtime.drain()
client = exe.config.ps_comm
rt = next(iter(exe.ps_runtime.device_tables.values()))
touched = np.nonzero(rt.id_of >= 0)[0]
ids = rt.id_of[touched][:5]
rows = client.sparse_pull(rt.tid, ids, rt.width)
delta = float(np.abs(rows - emb_val[ids]).max())
wfinal = np.asarray(exe.params[str(w.id)]).ravel()
out = os.path.join(os.environ["HETU_TEST_OUT"], f"hy2_{rank}.txt")
with open(out, "w") as f:
    f.write(" ".join(str(x) for x in losses) + chr(10))
    f.write(str(delta) + chr(10))
    f.write(" ".join(str(v) for v in wfinal) + chr(10))
    f.write(str(rt.perf))
exe.close()
"""


def test_two_process_hybrid_asp(tmp_path):
    """Hybrid across REAL process boundaries (round-4 review missing #6):
    2 SPMD worker processes (dense params in-graph, AllReduce over the
    2-process dp mesh) + a live PS server holding the embedding through
    the HBM device cache with ASP bounded staleness. Asserts per rank:
    losses fall; the server's embedding rows moved from their initial
    values (each worker's async pushes crossed its process boundary);
    and both ranks end with IDENTICAL dense weights (the cross-process
    AllReduce really synchronized them)."""
    cfg_path = tmp_path / "hybrid.yml"
    cfg_path.write_text(HYBRID_SPMD_CONFIG)
    script = tmp_path / "hybrid_worker.py"
    script.write_text(SPMD_HYBRID_WORKER)
    from launcher_util import clean_launcher_env
    env = clean_launcher_env(HETU_TEST_OUT=str(tmp_path))
    proc = subprocess.run(
        [sys.executable, "-m", "hetu_tpu.launcher", "-c", str(cfg_path),
         sys.executable, str(script)],
        env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    finals = []
    for rank in range(2):
        path = tmp_path / f"hy2_{rank}.txt"
        assert path.exists(), f"worker {rank} wrote nothing"
        lines = path.read_text().splitlines()
        losses = [float(v) for v in lines[0].split()]
        assert losses[-1] < losses[0], (rank, losses[:3], losses[-3:])
        delta = float(lines[1])
        assert delta > 1e-4, \
            f"rank {rank}: server embedding rows never moved ({delta})"
        finals.append(np.asarray([float(v) for v in lines[2].split()]))
    np.testing.assert_allclose(
        finals[0], finals[1], rtol=1e-5, atol=1e-7,
        err_msg="dense params diverged across ranks (AllReduce broken)")
