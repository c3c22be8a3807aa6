"""The user-facing parallel-config zoo workflow end-to-end: heturun CLI
-> zoo scripts -> validate_results allclose gate (round-3 review missing #5:
the parity workflow existed only as pytest internals; a user must be
able to run the documented flow).  A fast subset of
examples/runner/parallel/all_mlp_tests.sh.
"""
import os
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")
ZOO = os.path.join(ROOT, "examples", "runner", "parallel")
HETURUN = os.path.join(ROOT, "bin", "heturun")


def _run(config, script, *extra):
    env = {**os.environ,
           "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=8"}
    cmd = [HETURUN, "-c", os.path.join(ZOO, config), sys.executable,
           os.path.join(ZOO, script), "--steps", "5"] + list(extra)
    res = subprocess.run(cmd, cwd=ZOO, env=env, capture_output=True,
                         text=True, timeout=420)
    assert res.returncode == 0, res.stdout + res.stderr


@pytest.fixture(scope="module")
def base_losses(tmp_path_factory):
    """Ground truth, computed once for every parametrized case."""
    base = str(tmp_path_factory.mktemp("zoo") / "base.npy")
    _run("config1.yml", "test_mlp_base.py", "--save", "--log", base)
    return np.load(base)


@pytest.mark.parametrize("case", [
    ("test_mlp_mp.py", ["--split", "middle"]),
    ("test_mlp_mp.py", ["--split", "2"]),
    ("test_mlp_pp.py", []),
    ("test_mlp_mp_pp.py", ["--split", "left"]),
])
def test_zoo_config_matches_base(tmp_path, base_losses, case):
    script, extra = case
    res = str(tmp_path / "res0.npy")
    _run("config4.yml", script, *extra, "--log", res)
    np.testing.assert_allclose(base_losses, np.load(res), rtol=1e-4,
                               atol=1e-6)


@pytest.fixture(scope="module")
def cnn_base_losses(tmp_path_factory):
    base = str(tmp_path_factory.mktemp("cnnzoo") / "cnn_base.npy")
    _run("config1.yml", "test_cnn_base.py", "--save", "--log", base)
    return np.load(base)


@pytest.mark.parametrize("split", ["left", "middle", "right"])
def test_cnn_zoo_split_matches_base(tmp_path, cnn_base_losses, split):
    """The CNN zoo (reference all_cnn_tests.sh): every conv dispatch
    split — batch / out-channel / contracted in-channel — reproduces
    the single-device base loss series."""
    res = str(tmp_path / f"cnn_{split}.npy")
    _run("config2.yml", "test_cnn_mp.py", "--split", split, "--log", res)
    np.testing.assert_allclose(cnn_base_losses, np.load(res), rtol=1e-4,
                               atol=1e-6)


MOCK_SSH = """#!/bin/sh
# mock ssh for the two-host zoo test: drop flags and the host, run the
# remote command line locally (the launcher's ssh path stays real)
while [ "$#" -gt 0 ]; do
  case "$1" in
    -i) shift 2;;
    -*) shift;;
    *) break;;
  esac
done
shift   # the host
exec sh -c "$*"
"""


def test_zoo_two_host_ssh(tmp_path, base_losses):
    """dist_config2.yml exercises the launcher's REAL ssh code path for
    its second host (a loopback alias; ssh itself is a PATH shim that
    runs the command locally — reference dist_config8.yml's two-host
    shape): 2-process SPMD data parallelism must reproduce the base
    loss series."""
    bindir = tmp_path / "bin"
    bindir.mkdir()
    ssh = bindir / "ssh"
    ssh.write_text(MOCK_SSH)
    ssh.chmod(0o755)
    from launcher_util import clean_launcher_env
    res = str(tmp_path / "dist.npy")
    env = clean_launcher_env(
        PATH=f"{bindir}{os.pathsep}{os.environ['PATH']}",
        JAX_PLATFORMS="cpu")
    cmd = [HETURUN, "-c", os.path.join(ZOO, "dist_config2.yml"),
           sys.executable, os.path.join(ZOO, "dist_data_mlp.py"),
           "--steps", "5", "--log", res]
    proc = subprocess.run(cmd, cwd=ZOO, env=env, capture_output=True,
                          text=True, timeout=420)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    np.testing.assert_allclose(base_losses, np.load(res), rtol=1e-4,
                               atol=1e-6)
