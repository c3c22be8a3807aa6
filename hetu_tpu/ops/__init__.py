"""Op library — reference parity with python/hetu/gpu_ops/."""
from .variable import Variable, placeholder_op, PlaceholderOp
from .basic import (
    add_op, addbyconst_op, mul_op, mul_byconst_op, div_op, div_const_op,
    div_handle_zero_op, opposite_op, sqrt_op, rsqrt_op, exp_op, log_op,
    abs_op, power_op, where_op, one_hot_op, matrix_dot_op, cast_op,
    clip_op, clip_mask_op,
)
from .shape import (
    array_reshape_op, array_reshape_gradient_op, broadcastto_op,
    broadcast_shape_op, concat_op, concat_gradient_op, concatenate_op,
    split_op, split_gradient_op, slice_op, slice_gradient_op, transpose_op,
    pad_op, pad_gradient_op, unbroadcast_op, reduce_sum_op, reduce_mean_op,
    reducesumaxiszero_op, oneslike_op, zeroslike_op, flatten_op,
    squeeze_op, unsqueeze_op,
)
from .activations import (
    relu_op, relu_gradient_op, leaky_relu_op, leaky_relu_gradient_op,
    sigmoid_op, tanh_op, gelu_op, swiglu_op, swiglu_gradient_op, sign_op,
    softmax_func, softmax_op,
    softmax_gradient_op, dropout_op, dropout_gradient_op, dropout2d_op,
    dropout2d_gradient_op,
)
from .losses import (
    softmaxcrossentropy_op, softmaxcrossentropy_gradient_op,
    softmaxcrossentropy_sparse_op, softmaxcrossentropy_sparse_gradient_op,
    binarycrossentropy_op, binarycrossentropy_gradient_op, crossentropy_op,
)
from .linalg import matmul_op, batch_matmul_op
from .conv import (
    conv2d_op, conv2d_gradient_of_data_op, conv2d_gradient_of_filter_op,
    max_pool2d_op, max_pool2d_gradient_op, avg_pool2d_op,
    avg_pool2d_gradient_op, conv2d_broadcastto_op, conv2d_reducesum_op,
)
from .norm import (
    batch_normalization_op, batch_normalization_gradient_op,
    batch_normalization_gradient_of_data_op,
    batch_normalization_gradient_of_scale_op,
    batch_normalization_gradient_of_bias_op,
    layer_normalization_op, layer_normalization_gradient_op,
    layer_normalization_gradient_of_data_op,
    layer_normalization_gradient_of_scale_op,
    layer_normalization_gradient_of_bias_op,
    instance_normalization2d_op, instance_normalization2d_gradient_op,
    rms_normalization_op, rms_normalization_gradient_op,
)
from .rotary import rotary_op
from .moe import router_op, router_picks_op, held_experts_op
from .short_conv import short_conv_op
from .embedding import embedding_lookup_op, embedding_lookup_gradient_op
from .sparse import csrmv_op, csrmm_op, distgcn_15d_op
from .attention import (flash_attention_op, ring_attention_op,
                        ulysses_attention_op)
from .comm import (
    allreduceCommunicate_op, groupallreduceCommunicate_op,
    parameterServerCommunicate_op, parameterServerSparsePull_op,
    datah2d_op, datad2h_op, pipeline_send_op, pipeline_receive_op, dispatch,
)
