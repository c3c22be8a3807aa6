"""``lfm2_moe`` family (LFM2-8B-A1B: gated short-convolution layers
beside grouped-query attention layers with a norm a head, dense SwiGLU
layers before expert layers under a sigmoid router that selects by a
bias, a tied head): from a configuration file to what the TRAINING
driver runs. Training only (``not_served`` in the file).

A configuration of this family is ONE CHIP'S SHARE of an
expert-parallel job, as ``families/smallthinker_moe.py`` has it:
``num_experts`` experts from ``first_expert`` on are held here, the
router stays ``num_routed_experts`` wide, and ``vocab_size`` is the
slice of the vocabulary held. What the absent experts would add is left
out of the program and of the reference alike.

**What ``correct`` holds the step to** is the smallthinker family's
rule (its docstring has the reasons): the ``validate`` program's loss
and its scores at EVERY position of the checked sequence against
``reference/lfm2_moe.py`` FORCED onto the picks that the same program's
routers made (``[loss, scores, *picks]``, an expert layer each), a
differing pick failing where it lies further under the reference's own
cut — in ``score + bias`` — than the bfloat16 stream explains
(``PICK_MARGIN``).

``flash_calls_per_step`` carries this family's attention calls and the
entry of kind ``moe_counters``: what the step counted on the device,
with ``moe_bias_flipped_picks`` and the picks there were
(``moe_picks``) for ``moe.bias_flipped_pct``.
"""
import json

import numpy as np

from benchmark.families.gpt2 import lm_batch
from benchmark.flops import lfm2 as flops
from benchmark.harness.session import TrainSession, executor_seed
from benchmark.reference import lfm2_moe as reference

# the executor of this process's session: flash_calls_per_step reads
# its device counters after the window
_SESSION = {}


def model_config(config):
    # what the parent lacks: it fails the cell here, in seconds
    from hetu_tpu.models import HybridDecoderConfig
    assumed = config["assumed"]["weights"]
    return HybridDecoderConfig(
        vocab_size=config["vocab_size"],
        hidden_size=config["hidden_size"],
        layer_types=config["layer_types"],
        num_dense_layers=config["num_dense_layers"],
        intermediate_size=config["intermediate_size"],
        moe_intermediate_size=config["moe_intermediate_size"],
        num_experts=config["num_routed_experts"],
        num_experts_per_tok=config["num_experts_per_tok"],
        experts_held=(config["first_expert"], config["num_experts"]),
        num_attention_heads=config["num_attention_heads"],
        num_key_value_heads=config["num_key_value_heads"],
        conv_L_cache=config["conv_L_cache"],
        rope_theta=config["rope_theta"], norm_eps=config["norm_eps"],
        routed_scaling_factor=config["routed_scaling_factor"],
        norm_topk_eps=reference.NORM_TOPK_EPS,
        initializer_range=assumed["initializer_std"],
        embedding_range=assumed["embedding_std"],
        expert_bias_range=assumed["expert_bias_std"],
        conv_taps_range=assumed["conv_taps_halfwidth"])


def param_count(config):
    """TRAINED parameters of the share, by hand from the file's sizes
    (the selection bias, ``num_routed_experts`` numbers an expert layer,
    is a buffer and is not among them)."""
    hidden = config["hidden_size"]
    d = hidden // config["num_attention_heads"]
    q = config["num_attention_heads"] * d
    kv = config["num_key_value_heads"] * d
    total = config["vocab_size"] * hidden + hidden      # tied table, norm
    for i, kind in enumerate(config["layer_types"]):
        total += 2 * hidden                             # the two norms
        if kind == "conv":
            total += hidden * 3 * hidden + hidden * hidden \
                + hidden * config["conv_L_cache"]
        else:
            total += hidden * (q + 2 * kv) + q * hidden + 2 * d
        if i < config["num_dense_layers"]:
            total += 3 * hidden * config["intermediate_size"]
        else:
            total += hidden * config["num_routed_experts"] \
                + config["num_experts"] * 3 * hidden \
                * config["moe_intermediate_size"]
    return total


def train_flops_per_token(config, seq_len):
    return flops.train_flops_per_token(config, seq_len)


def attention_calls(config, traffic, batch):
    """The flash calls one step makes on one chip: every attention
    layer's forward and backward, causal, no band."""
    hidden = config["hidden_size"]
    shape = dict(b=batch, h=config["num_attention_heads"],
                 kv_heads=config["num_key_value_heads"],
                 s=traffic["seq_len"],
                 d=hidden // config["num_attention_heads"], itemsize=2,
                 causal=True, window=None)
    n = sum(kind == "full_attention" for kind in config["layer_types"])
    return [dict(shape, kind=kind, calls=n)
            for kind in ("forward", "backward") if n]


def short_conv_calls(config, traffic, batch):
    """What ``kernel.short_conv_train_roofline`` counts from: the rows
    and channels of one step's convolution layers."""
    n = sum(kind == "conv" for kind in config["layer_types"])
    return {"kind": "short_conv", "rows": batch * traffic["seq_len"],
            "channels": config["hidden_size"],
            "taps": config["conv_L_cache"], "itemsize": 2, "calls": n}


def flash_calls_per_step(config, traffic, batch):
    calls = attention_calls(config, traffic, batch)
    calls.append(short_conv_calls(config, traffic, batch))
    executor = _SESSION.get("executor")
    if executor is not None and hasattr(executor, "moe_counters"):
        layers = executor.moe_counters()
        if layers:
            picks = batch * traffic["seq_len"] \
                * config["num_experts_per_tok"]
            counted = {
                "kind": "moe_counters", "layers": layers,
                "steps": layers[0]["steps"],
                "moe_routed_rows": sum(c["moe_routed_rows"]
                                       for c in layers),
                "moe_expert_visits": sum(c["moe_expert_visits"]
                                         for c in layers),
                "moe_bias_flipped_picks": sum(
                    c.get("moe_bias_flipped_picks", 0) for c in layers),
                "moe_picks": sum(picks * c["steps"] for c in layers)}
            print(json.dumps({"moe_counters": {
                k: v for k, v in counted.items() if k != "layers"},
                "busiest_over_mean_by_layer": [
                    max(c["moe_rows_by_expert"]) * len(c["moe_rows_by_expert"])
                    / max(c["moe_routed_rows"], 1) for c in layers],
                "rows_a_step_by_layer": [c["moe_routed_rows"] / c["steps"]
                                         for c in layers],
                "held_share_of_picks_by_layer": [
                    c["moe_routed_rows"] / (picks * c["steps"])
                    for c in layers],
                "bias_flipped_share_by_layer": [
                    c.get("moe_bias_flipped_picks", 0) / (picks * c["steps"])
                    for c in layers]}), flush=True)
            calls.append(counted)
    return calls


def build_train(config, traffic, seed):
    import jax.numpy as jnp
    import hetu_tpu as ht
    from hetu_tpu.executor import Executor
    from hetu_tpu.models import HybridDecoderLMHeadModel

    seq_len = traffic["seq_len"]
    model = HybridDecoderLMHeadModel(model_config(config))
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
