"""Operations one TOKEN of an ``lfm2_moe`` configuration costs in
training, for the driver's logged ``mfu`` (the model's operations, not
the program's: nothing recomputed is counted). One chip's share: the
experts held and the sliced vocabulary, as the configuration file
states them. The tied table is met twice: as a lookup (no operations)
and as the head."""
from benchmark.flops import gqa_train, moe_train


def train_flops_per_token(config, seq_len):
    hidden = config["hidden_size"]
    heads, groups = (config["num_attention_heads"],
                     config["num_key_value_heads"])
    d = hidden // heads
    matrices = 2.0 * hidden * config["vocab_size"]      # the head
    experts, attention = 0.0, 0.0
    # the picks of a token that land here, in expectation
    held = config["num_experts_per_tok"] * config["num_experts"] \
        / config["num_routed_experts"]
    for i, kind in enumerate(config["layer_types"]):
        if kind == "conv":
            matrices += 2.0 * hidden * 4 * hidden
        else:
            matrices += 2.0 * hidden * d * (2 * heads + 2 * groups)
            attention += 14.0 * heads * d \
                * gqa_train.pairs(seq_len) / seq_len
        if i < config["num_dense_layers"]:
            matrices += 2.0 * 3 * hidden * config["intermediate_size"]
        else:
            matrices += 2.0 * hidden * config["num_routed_experts"]
            experts += moe_train.flops(held, hidden,
                                       config["moe_intermediate_size"])
    # a multiply-add is 2 operations, the backward twice the forward
    return 3.0 * matrices + experts + attention
