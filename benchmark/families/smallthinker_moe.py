"""``smallthinker_moe`` family (SmallThinker, arXiv:2507.20984: a
sparse-expert decoder whose router sits before attention, sliding-window
layers with rotary positions and global layers with none, grouped-query
heads, ReGLU experts): from a configuration file to what the TRAINING
driver runs. Training only (``not_served`` in the file).

A configuration of this family is ONE CHIP'S SHARE of an
expert-parallel job, as ``families/sarvam_mla.py`` has it for serving:
``moe_num_primary_experts`` experts from ``first_expert`` on are held
here, the router stays ``num_routed_experts`` wide, and ``vocab_size``
is the slice of the vocabulary held. What the absent experts would add
is left out of the program and of the reference alike.

**What ``correct`` holds the step to** (``drivers/train_executor.py``):
the graph's inference-mode loss and its scores at EVERY position of the
checked sequence against ``reference/smallthinker_moe.py``. Routing is
discrete, so the reference runs FORCED onto the program's own picks,
logs how many rows picked differently from its own top-k, and fails the
comparison where a differing pick lies further under its own cut than
the bfloat16 stream explains (``PICK_MARGIN``). The picks are outputs
of the SAME ``validate`` program as the scores (``[loss, scores,
*picks]``; the reference hands the picks it was forced onto back as its
own, so the driver's comparison of them reads 0): a second inference
group is another compiled program, whose fusions round a near-tie the
other way on a row in a thousand, and the reference forced onto THAT
program's pick then misses the scores' program by an expert's whole
contribution on those rows (0.04-0.06 of the scores' spread where
every other row reads under 0.008; my chip run, PR 50).

``flash_calls_per_step`` is asked by the driver AFTER the window and
handed to the readers as ``facts["flash_calls"]``; it is the one entry
of ``facts`` a family fills, so it carries, beside this family's
attention calls (``kv_heads``, ``window``, ``calls``), what the step
counted on the device: the experts' rows and visits (an entry of kind
``moe_counters``).
"""
import json

import numpy as np

from benchmark.families.gpt2 import lm_batch
from benchmark.flops import smallthinker as flops
from benchmark.harness.session import TrainSession, executor_seed
from benchmark.reference import smallthinker_moe as reference

# the executor of this process's session: flash_calls_per_step reads
# its device counters after the window
_SESSION = {}


def model_config(config):
    # what the parent lacks: it fails the cell here, in seconds
    from hetu_tpu.models import SparseDecoderConfig
    return SparseDecoderConfig(
        vocab_size=config["vocab_size"],
        hidden_size=config["hidden_size"],
        num_attention_heads=config["num_attention_heads"],
        num_key_value_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        window_layout=config["sliding_window_layout"],
        rope_layout=config["rope_layout"],
        sliding_window=config["sliding_window_size"],
        moe_ffn_hidden_size=config["moe_ffn_hidden_size"],
        num_experts=config["num_routed_experts"],
        num_experts_per_tok=config["moe_num_active_primary_experts"],
        experts_held=(config["first_expert"],
                      config["moe_num_primary_experts"]),
        activation="relu", rope_theta=config["rope_theta"],
        rms_norm_eps=config["rms_norm_eps"],
        initializer_range=config["assumed"]["initializer_std"],
        embedding_range=config["assumed"].get("embedding_std"))


def param_count(config):
    """Parameters of the share, by hand from the file's sizes."""
    hidden, d = config["hidden_size"], config["head_dim"]
    q = config["num_attention_heads"] * d
    kv = config["num_key_value_heads"] * d
    layer = hidden * (q + 2 * kv) + q * hidden \
        + hidden * config["num_routed_experts"] + 2 * hidden \
        + config["moe_num_primary_experts"] * 3 * hidden \
        * config["moe_ffn_hidden_size"]
    return config["num_hidden_layers"] * layer \
        + 2 * config["vocab_size"] * hidden + hidden


def train_flops_per_token(config, seq_len):
    return flops.train_flops_per_token(config, seq_len)


def attention_calls(config, traffic, batch):
    """The flash calls one step makes on one chip: every layer's
    forward and backward, the window layers' with their band."""
    shape = dict(b=batch, h=config["num_attention_heads"],
                 kv_heads=config["num_key_value_heads"],
                 s=traffic["seq_len"], d=config["head_dim"], itemsize=2,
                 causal=True)
    banded = sum(config["sliding_window_layout"])
    calls = []
    for window, n in ((config["sliding_window_size"], banded),
                      (None, config["num_hidden_layers"] - banded)):
        for kind in ("forward", "backward"):
            if n:
                calls.append(dict(shape, kind=kind, window=window, calls=n))
    return calls


def flash_calls_per_step(config, traffic, batch):
    calls = attention_calls(config, traffic, batch)
    executor = _SESSION.get("executor")
    if executor is not None and hasattr(executor, "moe_counters"):
        layers = executor.moe_counters()
        if layers:
            counted = {
                "kind": "moe_counters", "layers": layers,
                "steps": layers[0]["steps"],
                "moe_routed_rows": sum(c["moe_routed_rows"]
                                       for c in layers),
                "moe_expert_visits": sum(c["moe_expert_visits"]
                                         for c in layers)}
            print(json.dumps({"moe_counters": {
                k: v for k, v in counted.items() if k != "layers"},
                "busiest_over_mean_by_layer": [
                    max(c["moe_rows_by_expert"]) * len(c["moe_rows_by_expert"])
                    / max(c["moe_routed_rows"], 1) for c in layers],
                "rows_a_step_by_layer": [c["moe_routed_rows"] / c["steps"]
                                         for c in layers]}), flush=True)
            calls.append(counted)
    return calls


def build_train(config, traffic, seed):
    import jax.numpy as jnp
    import hetu_tpu as ht
    from hetu_tpu.executor import Executor
    from hetu_tpu.models import SparseDecoderLMHeadModel

    seq_len = traffic["seq_len"]
    model = SparseDecoderLMHeadModel(model_config(config))
    ids = ht.Variable("input_ids", trainable=False)
    labels = ht.Variable("labels", trainable=False)
    logits, loss = model(ids, labels, seq_len=seq_len)
    lm_loss = ht.reduce_mean_op(loss, [0, 1])
    train_op = ht.optim.AdamOptimizer(
        learning_rate=traffic["learning_rate"]).minimize(lm_loss)
    executor = Executor(
        {"default": [lm_loss, train_op],
         "validate": [lm_loss, logits] + list(model.picks)},
        dtype=jnp.dtype(config["train_dtype"]), seed=executor_seed(seed))
    _SESSION["executor"] = executor

    def log(fields):
        print(json.dumps(fields), flush=True)

    def against_reference(params, feed):
        """The reference FORCED onto the picks the validate program's
        own routers made on this feed (the program is run again for
        them: the same executable on the same inputs)."""
        picks = [np.asarray(p.asnumpy()) for p in executor.run(
            "validate", feed_dict=dict(zip((ids, labels), feed)))[2:]]
        loss, scores = reference.loss_and_scores(
            params, config, *feed, forced=picks, log=log)
        return loss, scores + picks

    return TrainSession(
        executor=executor, feed_nodes=(ids, labels),
        make_batch=lambda rng, batch: lm_batch(
            rng, batch, seq_len, config["vocab_size"]),
        tokens_per_sequence=seq_len, reference=against_reference,
        loss_tolerance=reference.LOSS_TOLERANCE,
        output_tolerance=reference.OUTPUT_TOLERANCE)
