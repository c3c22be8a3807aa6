"""BERT family: BertForPreTraining (MLM + NSP) under ``ht.Executor``,
built as ``examples/nlp/bert/train_hetu_bert.py`` builds it, from a
configuration file. See ``families/gpt2.py`` for what a family offers.
"""
import numpy as np

from benchmark.flops import transformer as flops
from benchmark.harness.session import TrainSession, executor_seed
from benchmark.reference import bert as reference


def model_config(config, seq_len):
    from hetu_tpu.models import BertConfig
    return BertConfig(
        vocab_size=config["vocab_size"],
        hidden_size=config["hidden_size"],
        num_hidden_layers=config["num_hidden_layers"],
        num_attention_heads=config["num_attention_heads"],
        intermediate_size=config["intermediate_size"],
        hidden_act=config["hidden_act"],
        hidden_dropout_prob=config["hidden_dropout_prob"],
        attention_probs_dropout_prob=config[
            "attention_probs_dropout_prob"],
        max_position_embeddings=seq_len,
        type_vocab_size=config["type_vocab_size"],
        initializer_range=config["initializer_range"],
        use_flash_attention=True)


def train_flops_per_token(config, seq_len):
    return flops.bert_train_flops_per_token(
        seq_len, config["hidden_size"], config["num_hidden_layers"],
        config["intermediate_size"], config["vocab_size"])


def flash_calls_per_step(config, traffic, batch):
    """See ``families/gpt2.py``; the encoder's attention is not
    causal."""
    heads = config["num_attention_heads"]
    shape = dict(b=batch, h=heads, s=traffic["seq_len"],
                 d=config["hidden_size"] // heads, itemsize=2,
                 causal=False, calls=config["num_hidden_layers"])
    return [dict(shape, kind="forward"), dict(shape, kind="backward")]


def pretraining_batch(rng, batch, seq_len, vocab, mask_share):
    """The example's synthetic MLM + NSP feed (copied from
    ``examples/nlp/bert/train_hetu_bert.py:synthetic_batch``): uniform
    token ids, the second half of each sequence as segment 1, no
    padding, ``mask_share`` of the positions labelled with their own
    id, a coin for the next-sentence label."""
    input_ids = rng.randint(0, vocab, (batch, seq_len)).astype(np.int32)
    token_type_ids = np.zeros((batch, seq_len), np.int32)
    token_type_ids[:, seq_len // 2:] = 1
    attention_mask = np.ones((batch, seq_len), np.float32)
    mlm_labels = np.where(rng.rand(batch, seq_len) < mask_share,
                          input_ids, -1).astype(np.int32)
    nsp_label = rng.randint(0, 2, (batch,)).astype(np.int32)
    return (input_ids, token_type_ids, attention_mask, mlm_labels,
            nsp_label)


def build_train(config, traffic, seed):
    import jax.numpy as jnp
    import hetu_tpu as ht
    from hetu_tpu.executor import Executor
    from hetu_tpu.models import BertForPreTraining

    seq_len = traffic["seq_len"]
    model = BertForPreTraining(model_config(config, seq_len))
    feed_nodes = tuple(ht.Variable(name, trainable=False) for name in (
        "input_ids", "token_type_ids", "attention_mask",
        "masked_lm_labels", "next_sentence_label"))
    mlm_scores, _, mlm_loss, nsp_loss = model(*feed_nodes)
    loss = ht.reduce_mean_op(mlm_loss, [0, 1]) + \
        ht.reduce_mean_op(nsp_loss, [0])
    train_op = ht.optim.AdamOptimizer(
        learning_rate=traffic["learning_rate"]).minimize(loss)
    executor = Executor(
        {"default": [loss, train_op], "validate": [loss, mlm_scores]},
        dtype=jnp.dtype(config["train_dtype"]), seed=executor_seed(seed))

    return TrainSession(
        executor=executor, feed_nodes=feed_nodes,
        make_batch=lambda rng, batch: pretraining_batch(
            rng, batch, seq_len, config["vocab_size"],
            traffic["mlm_mask_share"]),
        tokens_per_sequence=seq_len,
        reference=lambda params, feed: reference.pretraining_outputs(
            params, config, *feed),
        loss_tolerance=reference.LOSS_TOLERANCE,
        output_tolerance=reference.OUTPUT_TOLERANCE)
