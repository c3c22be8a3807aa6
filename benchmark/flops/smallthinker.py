"""Operations one TOKEN of a ``smallthinker_moe`` configuration costs
in training, for the driver's logged ``mfu`` (the model's operations,
not the program's: nothing recomputed is counted). One chip's share:
the experts held and the sliced vocabulary, as the configuration file
states them."""
from benchmark.flops import gqa_train, moe_train


def train_flops_per_token(config, seq_len):
    hidden, d = config["hidden_size"], config["head_dim"]
    heads, groups = (config["num_attention_heads"],
                     config["num_key_value_heads"])
    layers = config["num_hidden_layers"]
    # a multiply-add is 2 operations, the backward twice the forward
    projections = 2.0 * hidden * d * (2 * heads + 2 * groups)
    router = 2.0 * hidden * config["num_routed_experts"]
    head = 2.0 * hidden * config["vocab_size"]
    dense = 3.0 * (layers * (projections + router) + head)
    # the picks of a token that land here, in expectation
    held = config["moe_num_active_primary_experts"] \
        * config["moe_num_primary_experts"] / config["num_routed_experts"]
    experts = layers * moe_train.flops(held, hidden,
                                       config["moe_ffn_hidden_size"])
    attention = 0.0
    for banded in config["sliding_window_layout"]:
        window = config["sliding_window_size"] if banded else None
        attention += 14.0 * heads * d \
            * gqa_train.pairs(seq_len, window) / seq_len
    return dense + experts + attention
