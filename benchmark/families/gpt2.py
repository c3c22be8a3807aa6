"""GPT-2 family: from a configuration file to what the drivers run.

Training goes through the graph a user builds
(``hetu_tpu.models.GPTLMHeadModel`` under ``ht.Executor``), serving
through ``ContinuousBatchingEngine``. Nothing here times anything.

A family file offers the drivers:

* ``build_train(config, traffic, seed)``        -> ``TrainSession``
* ``build_engine(config, engine_kw, seed)``    -> ``(engine, weights)``
* ``engine_reference_logits(config, weights, tokens, positions,
  pad_to)`` and ``LOGIT_TOLERANCE``
* ``train_flops_per_token(config, seq_len)``
* ``flash_calls_per_step(config, traffic, batch)``: the flash-attention
  kernel calls one training step of this family makes on one chip
"""
import numpy as np

from benchmark.flops import transformer as flops
from benchmark.harness.session import TrainSession, executor_seed
from benchmark.reference import gpt2 as reference

LOGIT_TOLERANCE = reference.LOGIT_TOLERANCE


def model_config(config, seq_len=None):
    from hetu_tpu.models import GPTConfig
    return GPTConfig(
        vocab_size=config["vocab_size"],
        hidden_size=config["hidden_size"],
        num_hidden_layers=config["num_hidden_layers"],
        num_attention_heads=config["num_attention_heads"],
        intermediate_size=config["intermediate_size"],
        hidden_act=config["hidden_act"],
        hidden_dropout_prob=config["hidden_dropout_prob"],
        max_position_embeddings=seq_len
        or config["max_position_embeddings"],
        initializer_range=config["initializer_range"],
        use_flash_attention=True)


def train_flops_per_token(config, seq_len):
    return flops.gpt_train_flops_per_token(
        seq_len, config["hidden_size"], config["num_hidden_layers"],
        config["intermediate_size"], config["vocab_size"])


def flash_calls_per_step(config, traffic, batch):
    """The flash-attention kernel calls one training step on one chip
    can make, one dict per kind (``flops/flash.py`` turns them into
    operations and bytes): every layer calls the causal forward once
    and the fused backward once. Whether the program really runs the
    backward as kernels (today from 512 positions up) is read from the
    trace by ``trace/flash_calls.py``, not assumed here."""
    heads = config["num_attention_heads"]
    shape = dict(b=batch, h=heads, s=traffic["seq_len"],
                 d=config["hidden_size"] // heads, itemsize=2,
                 causal=True, calls=config["num_hidden_layers"])
    return [dict(shape, kind="forward"), dict(shape, kind="backward")]


def lm_batch(rng, batch, seq_len, vocab):
    """Uniform random token ids and their next-token labels (the last
    position has none: the sparse-CE op's ignored index)."""
    ids = rng.randint(0, vocab, (batch, seq_len)).astype(np.int32)
    labels = np.concatenate(
        [ids[:, 1:], np.full((batch, 1), -1, np.int32)], axis=1)
    return ids, labels


def build_train(config, traffic, seed):
    import jax.numpy as jnp
    import hetu_tpu as ht
    from hetu_tpu.executor import Executor
    from hetu_tpu.models import GPTLMHeadModel

    seq_len = traffic["seq_len"]
    cfg = model_config(config, seq_len)
    model = GPTLMHeadModel(cfg)
    ids = ht.Variable("input_ids", trainable=False)
    labels = ht.Variable("labels", trainable=False)
    logits, loss = model(ids, labels)
    lm_loss = ht.reduce_mean_op(loss, [0, 1])
    train_op = ht.optim.AdamOptimizer(
        learning_rate=traffic["learning_rate"]).minimize(lm_loss)
    executor = Executor(
        {"default": [lm_loss, train_op], "validate": [lm_loss, logits]},
        dtype=jnp.dtype(config["train_dtype"]), seed=executor_seed(seed))

    return TrainSession(
        executor=executor, feed_nodes=(ids, labels),
        make_batch=lambda rng, batch: lm_batch(
            rng, batch, seq_len, config["vocab_size"]),
        tokens_per_sequence=seq_len,
        reference=lambda params, feed: reference.lm_outputs(
            params, config, *feed),
        loss_tolerance=reference.LOSS_TOLERANCE,
        output_tolerance=reference.OUTPUT_TOLERANCE)


# ---------------------------------------------------------------------------
# serving
# ---------------------------------------------------------------------------

def param_shapes(config):
    """name -> (shape, kind) for every name of ``gpt_param_names``."""
    from hetu_tpu.models.gpt import gpt_param_names
    h, i, v = (config["hidden_size"], config["intermediate_size"],
               config["vocab_size"])
    by_role = {"ln1": ((h,), (h,)), "ln2": ((h,), (h,)),
               "qkv": ((h, 3 * h), (3 * h,)), "proj": ((h, h), (h,)),
               "fc": ((h, i), (i,)), "mlp_proj": ((i, h), (h,))}
    names = gpt_param_names(model_config(config))
    out = {names["wte"]: ((v, h), "normal"),
           names["wpe"]: ((config["max_position_embeddings"], h), "normal"),
           names["ln_f"][0]: ((h,), "ones"),
           names["ln_f"][1]: ((h,), "zeros"),
           names["lm_head"]: ((h, v), "normal")}
    for blk in names["blocks"]:
        for role, (w_name, b_name) in blk.items():
            w_shape, b_shape = by_role[role]
            out[w_name] = (w_shape, "ones" if role.startswith("ln")
                           else "normal")
            out[b_name] = (b_shape, "zeros")
    return out


def seeded_weights(config, seed):
    """Every serving parameter, made on the device in ONE jitted call
    from the seed, in float32 as the engine holds them: N(0,
    initializer_range) matrices, unit LayerNorm scales, zero biases
    (GPT-2's own initialisation)."""
    import jax
    import jax.numpy as jnp

    shapes = param_shapes(config)
    std = config["initializer_range"]

    def make(key):
        out = {}
        for n, (name, (shape, kind)) in enumerate(sorted(shapes.items())):
            if kind == "normal":
                out[name] = std * jax.random.normal(
                    jax.random.fold_in(key, n), shape, jnp.float32)
            else:
                out[name] = jnp.full(shape, 1.0 if kind == "ones" else 0.0,
                                     jnp.float32)
        return out

    return jax.jit(make)(jax.random.PRNGKey(executor_seed(seed)))


def build_engine(config, engine_kw, seed):
    from hetu_tpu.serving.scheduler import ContinuousBatchingEngine
    weights = seeded_weights(config, seed)
    engine = ContinuousBatchingEngine(
        model_config(config), weights.__getitem__, **engine_kw)
    return engine, weights


def engine_reference_logits(config, weights, tokens, positions,
                            pad_to=None):
    """[len(positions), V] logits of the plain forward over ``tokens``
    at ``positions``; each row predicts the token after its position."""
    return reference.logits_at(weights, config, tokens, positions, pad_to)
