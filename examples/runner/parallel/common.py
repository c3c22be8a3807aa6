"""Shared pieces of the parallel-config zoo (reference parity:
examples/runner/parallel/ — fixed ``std/`` weights so every config
trains the SAME model, loss series logged to ``results/*.npy``,
``validate_results.py`` asserts allclose against the base run).

TPU-native notes: the reference runs each config as an mpirun fleet;
here a config is ONE SPMD process over a device mesh — ``device(i)``
returns the i-th mesh device (real TPU chips, or the virtual CPU mesh
when ``JAX_PLATFORMS=cpu`` + ``--xla_force_host_platform_device_count``
are set, which ``all_mlp_tests.sh`` exports).
"""
import os
import sys

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", "..", ".."))

# on the virtual CPU mesh the configs are compared with allclose against
# the base run: keep matmuls in real float32 there
if os.environ.get("JAX_PLATFORMS", "").split(",")[0] == "cpu":
    import jax
    jax.config.update("jax_default_matmul_precision", "highest")

import hetu_tpu as ht                                   # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
STD = os.path.join(HERE, "std")
RESULTS = os.path.join(HERE, "results")

DIMS = dict(in_dim=784, hidden1=256, special=512, out_dim=10)


def device(i):
    """i-th mesh device: TPU when available, else the virtual CPU mesh."""
    import jax
    if jax.default_backend() == "tpu":
        return ht.tpu(i)
    return ht.cpu(i)


def ensure_std(force=False):
    """Write the fixed weights every config loads (the reference keeps a
    pre-generated std/ dir; we generate deterministically on first use —
    ``force`` regenerates after a DIMS/init edit)."""
    os.makedirs(STD, exist_ok=True)
    rng = np.random.RandomState(42)
    specs = {
        "mlp_fc1_weight": (DIMS["in_dim"], DIMS["hidden1"]),
        "mlp_fc1_bias": (DIMS["hidden1"],),
        "special_weight": (DIMS["hidden1"], DIMS["special"]),
        "mlp_fc2_weight": (DIMS["special"], DIMS["out_dim"]),
        "mlp_fc2_bias": (DIMS["out_dim"],),
    }
    for name, shape in specs.items():
        path = os.path.join(STD, name + ".npy")
        if force or not os.path.exists(path):
            np.save(path, (rng.randn(*shape) * 0.05).astype(np.float32))


def ensure_cnn_std(force=False):
    """Fixed weights for the CNN zoo variants (reference
    all_cnn_tests.sh trains the same conv model under every split)."""
    os.makedirs(STD, exist_ok=True)
    rng = np.random.RandomState(43)
    specs = {
        "cnn_conv1_weight": (32, 1, 5, 5),
        "special_cnn_weight": (32, 32, 5, 5),
        "cnn_fc_weight": (32 * 7 * 7, 10),
        "cnn_fc_bias": (10,),
    }
    for name, shape in specs.items():
        path = os.path.join(STD, name + ".npy")
        if force or not os.path.exists(path):
            np.save(path, (rng.randn(*shape) * 0.04).astype(np.float32))


def conv_relu(x, name, ctx=None):
    """5x5/pad2 conv + relu from fixed std/ weights (reference
    test_model_cnn_base.py conv_relu)."""
    w = ht.Variable(name, value=load_std(name), ctx=ctx)
    return ht.relu_op(ht.conv2d_op(x, w, padding=2, stride=1))


def load_std(name):
    return np.load(os.path.join(STD, name + ".npy"))


# conv split vocabulary -> (data parts, filter parts) over NCHW x OIHW
# (reference test_model_cnn.py --split): 'left' batch-splits the data,
# 'right' splits the filter's output channels, 'middle' splits the
# contracted input channels on both operands
CNN_SPLITS = {
    "left": ((2, 1, 1, 1), (1, 1, 1, 1)),
    "right": ((1, 1, 1, 1), (2, 1, 1, 1)),
    "middle": ((1, 2, 1, 1), (1, 2, 1, 1)),
}


def fc(x, name, with_relu=True, ctx=None):
    """Linear layer from fixed std/ weights (reference
    test_mlp_mp_pp.py:8-17)."""
    weight = ht.Variable(name + "_weight", value=load_std(name + "_weight"),
                         ctx=ctx)
    bias = ht.Variable(name + "_bias", value=load_std(name + "_bias"),
                       ctx=ctx)
    x = ht.matmul_op(x, weight)
    x = x + ht.broadcastto_op(bias, x)
    if with_relu:
        x = ht.relu_op(x)
    return x


def batches(batch_size=64, batch_num=5, seed=7):
    """Deterministic MNIST-shaped batches (real MNIST files when present,
    ht.data.mnist()'s planted-signal stand-in otherwise — equivalence
    only needs both runs to see identical data)."""
    (tx, ty), _, _ = ht.data.mnist()
    rng = np.random.RandomState(seed)
    idx = rng.permutation(len(tx))[:batch_size * batch_num]
    xs = tx[idx].reshape(batch_num, batch_size, -1)
    ys = ty[idx].reshape(batch_num, batch_size, -1)
    return xs, ys


def train_and_log(executor, x, y_, steps, log_path, batch_size=64):
    """Run ``steps`` steps over the fixed batches; save the loss series
    (the artifact validate_results.py compares)."""
    xs, ys = batches(batch_size=batch_size)
    losses = []
    for i in range(steps):
        out = executor.run(feed_dict={x: xs[i % len(xs)],
                                      y_: ys[i % len(ys)]})
        losses.append(float(np.asarray(out[0].asnumpy()).reshape(())))
    print("losses:", [round(v, 6) for v in losses])
    if log_path:
        os.makedirs(os.path.dirname(os.path.abspath(log_path)),
                    exist_ok=True)
        np.save(log_path, np.asarray(losses))
    return losses


# the reference's split vocabulary -> (activation parts, weight parts)
# for y = a @ w (test_mlp_mp_pp.py:66-89): 'left' row-splits the batch,
# 'right' col-splits the weight, 'middle' splits the contraction dim,
# '0'-'4' are the 4-way composites
SPLITS = {
    "left": ((2, 1), (1, 1)),
    "right": ((1, 1), (1, 2)),
    "middle": ((1, 2), (2, 1)),
    "0": ((4, 1), (1, 1)),
    "1": ((2, 2), (2, 1)),
    "2": ((2, 1), (1, 2)),
    "3": ((1, 2), (2, 2)),
    "4": ((1, 1), (1, 4)),
}
