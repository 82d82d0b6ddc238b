"""Neural-network layers.

Parity: python/paddle/fluid/layers/nn.py — same signatures/semantics
(fc composes mul+add+act like the reference LayerHelper does), but every
op lowers through the jnp kernels in ops/kernels_* and compiles as part
of one XLA module. Shapes may use -1 for batch dims.
"""
import numpy as np

from ..layer_helper import LayerHelper
from ..initializer import (ConstantInitializer, NormalInitializer,
                           NumpyArrayInitializer)
from ..core.dtypes import convert_dtype
from .utils import convert_to_list

__all__ = [
    "fc", "embedding", "conv2d", "conv2d_transpose", "conv3d", "pool2d",
    "adaptive_pool2d", "batch_norm", "layer_norm", "group_norm",
    "instance_norm", "dropout", "softmax", "log_softmax",
    "cross_entropy", "softmax_with_cross_entropy",
    "sigmoid_cross_entropy_with_logits", "square_error_cost", "huber_loss",
    "hinge_loss", "bpr_loss", "margin_rank_loss", "log_loss", "kldiv_loss",
    "mse_loss", "smooth_l1", "label_smooth", "one_hot", "nce",
    "sampled_softmax_with_cross_entropy",
    "dynamic_lstm", "dynamic_lstmp", "dynamic_gru", "lstm_unit", "gru_unit",
    "lstm",
    "matmul", "mul", "bmm", "dot", "transpose", "reshape", "squeeze",
    "unsqueeze", "flatten", "stack", "unstack", "expand", "expand_as",
    "slice", "strided_slice", "gather", "gather_nd", "scatter",
    "scatter_nd_add", "topk", "argsort", "argmax", "argmin", "where",
    "cond_select", "split", "l2_normalize", "mean", "reduce_sum",
    "reduce_mean", "reduce_max", "reduce_min", "reduce_prod", "reduce_all",
    "reduce_any", "cumsum", "clip", "clip_by_norm", "elementwise_add",
    "elementwise_sub", "elementwise_mul", "elementwise_div",
    "elementwise_max", "elementwise_min", "elementwise_pow",
    "elementwise_mod", "scale", "cast", "pad", "pad2d", "prelu",
    "brelu", "leaky_relu", "soft_relu", "relu6", "pow", "hard_sigmoid",
    "swish", "hard_swish", "image_resize", "image_resize_short", "resize_bilinear",
    "resize_nearest", "grid_sampler", "affine_channel", "shuffle_channel",
    "scaled_dot_product_attention", "multi_head_attention",
    "flash_attention", "rms_norm", "rotary_embedding", "short_conv",
    "swiglu", "moe_route", "moe_expert_ffn",
    "l2_norm", "kda_gate", "kda_attention", "selective_scan",
    "add_position_encoding", "lod_reset", "im2sequence",
    "logsumexp", "bilinear_tensor_product", "isfinite", "cos_sim",
    "unique_with_counts_stub", "maxout", "pixel_shuffle",
]


def _dims(shape):
    return [int(s) for s in shape]


def _same_shape_out(helper, x, type, attrs=None, extra_inputs=None, act=None):
    out = helper.create_variable_for_type_inference(x.dtype, x.shape)
    ins = {"X": [x]}
    if extra_inputs:
        ins.update(extra_inputs)
    helper.append_op(type, ins, {"Out": [out]}, attrs or {})
    return helper.append_activation(out, act)


# ---------------------------------------------------------------------------
# dense / embedding
# ---------------------------------------------------------------------------
def _sub_attr(param_attr, suffix):
    """Distinct ParamAttr per weight in multi-weight layers: a NAMED attr
    gets '<name>.<suffix>' so the weights don't silently alias one array
    in the scope (unnamed attrs already auto-unique)."""
    import copy
    from ..param_attr import ParamAttr
    if isinstance(param_attr, str):
        return f"{param_attr}.{suffix}"
    if isinstance(param_attr, ParamAttr) and param_attr.name:
        a = copy.copy(param_attr)
        a.name = f"{param_attr.name}.{suffix}"
        return a
    return param_attr


def fc(input, size, num_flatten_dims=1, param_attr=None, bias_attr=None,
       act=None, is_test=False, name=None):
    """Fully-connected layer (ref layers/nn.py:fc → mul + elementwise_add).

    Like the reference, `input` may be a list of Variables: each gets its
    own weight and the projections are summed before bias/activation."""
    helper = LayerHelper("fc", name=name, act=act, bias_attr=bias_attr)
    inputs = list(input) if isinstance(input, (list, tuple)) else [input]
    if isinstance(param_attr, (list, tuple)):
        if len(param_attr) != len(inputs):
            raise ValueError(
                f"fc got {len(inputs)} inputs but {len(param_attr)} "
                f"param_attrs (the reference raises here too)")
        attrs = list(param_attr)
    elif len(inputs) > 1:
        # one NAMED attr across several inputs would alias one array —
        # derive a distinct name per input (cf. _sub_attr for lstm/gru)
        attrs = [_sub_attr(param_attr, str(i)) for i in range(len(inputs))]
    else:
        attrs = [param_attr]
    dtype = inputs[0].dtype
    projs = []
    for x, pa in zip(inputs, attrs):
        in_dim = int(np.prod(x.shape[num_flatten_dims:]))
        w = helper.create_parameter(pa, shape=[in_dim, size], dtype=dtype)
        out_shape = tuple(x.shape[:num_flatten_dims]) + (size,)
        tmp = helper.create_variable_for_type_inference(dtype, out_shape)
        helper.append_op("mul", {"X": [x], "Y": [w]}, {"Out": [tmp]},
                         {"x_num_col_dims": num_flatten_dims,
                          "y_num_col_dims": 1})
        projs.append(tmp)
    tmp = projs[0]
    for other in projs[1:]:
        summed = helper.create_variable_for_type_inference(
            dtype, tuple(tmp.shape))
        helper.append_op("elementwise_add", {"X": [tmp], "Y": [other]},
                         {"Out": [summed]}, {"axis": -1})
        tmp = summed
    tmp = helper.append_bias_op(tmp, dim_start=num_flatten_dims,
                                bias_attr=bias_attr, size=size)
    return helper.append_activation(tmp, act)


def embedding(input, size, is_sparse=False, is_distributed=False,
              padding_idx=None, param_attr=None, dtype="float32", name=None):
    """ref layers/nn.py:embedding (lookup_table op, lookup_table_op.cc).

    is_sparse=True enables the ROW-SPARSE update path — the XLA-native
    analog of the reference's SelectedRows gradients: the backward
    taps the gathered rows through a zero "delta" input (so the table
    gradient is [..., D] row gradients, never a densified [V, D]
    scatter-add), and the optimizer applies a lazy row-sparse update
    (sparse_adam / sparse_sgd kernels) touching only the rows in Ids.
    Semantics match the reference's lazy_mode (optimizer.py:697):
    untouched rows keep their moments; regularizers/clip are not
    applied to sparse tables. Dense (default) remains the
    MXU-efficient path for small vocabularies."""
    helper = LayerHelper("embedding", name=name)
    w = helper.create_parameter(param_attr, shape=_dims(size), dtype=dtype,
                                default_initializer=NormalInitializer(0.0, 0.02))
    in_shape = input.shape
    if in_shape and in_shape[-1] == 1:
        out_shape = tuple(in_shape[:-1]) + (size[1],)
    else:
        out_shape = tuple(in_shape) + (size[1],)
    out = helper.create_variable_for_type_inference(dtype, out_shape)
    inputs = {"W": [w], "Ids": [input]}
    attrs = {"padding_idx": -1 if padding_idx is None else padding_idx}
    if is_distributed:
        # the pserver-partitioned table analog: DistributeTranspiler
        # row-shards this table (and its optimizer state) over the mesh
        # and XLA SPMD partitions the gather/scatter (ref
        # distribute_lookup_table.py + transpiler pserver split)
        attrs["is_distributed"] = True
    if is_sparse:
        # the row-grad tap: trace seeds it with zeros of the gathered
        # shape inside the diff set; its gradient IS the row gradient
        delta = helper.create_variable_for_type_inference(dtype, out_shape)
        inputs["SparseDelta"] = [delta]
        attrs["is_sparse"] = True
        taps = getattr(w, "_sparse_lookup", None) or []
        taps.append({"ids": input.name, "delta": delta.name})
        w._sparse_lookup = taps
    helper.append_op("lookup_table", inputs, {"Out": [out]}, attrs)
    return out


def nce(input, label, num_total_classes, sample_weight=None, param_attr=None,
        bias_attr=None, num_neg_samples=5, name=None):
    """Sampled softmax stand-in for ref nce_op (noise-contrastive estimation):
    TPU-friendly fixed-size uniform negative sampling."""
    return sampled_softmax_with_cross_entropy(
        input, label, num_total_classes, num_neg_samples + 1,
        param_attr=param_attr, bias_attr=bias_attr, name=name)


def sampled_softmax_with_cross_entropy(input, label, num_classes, num_samples,
                                       param_attr=None, bias_attr=None, name=None):
    helper = LayerHelper("sampled_softmax", name=name)
    dtype = input.dtype
    dim = int(input.shape[-1])
    w = helper.create_parameter(param_attr, shape=[num_classes, dim], dtype=dtype)
    b = helper.create_parameter(bias_attr, shape=[num_classes], dtype=dtype,
                                is_bias=True)
    out = helper.create_variable_for_type_inference(dtype, (input.shape[0], 1))
    helper.append_op("sampled_softmax_ce",
                     {"X": [input], "Label": [label], "W": [w], "B": [b]},
                     {"Loss": [out]},
                     {"num_samples": int(num_samples), "num_classes": int(num_classes)})
    return out


# ---------------------------------------------------------------------------
# conv / pool
# ---------------------------------------------------------------------------
def _conv_out_size(i, k, s, p, d=1):
    if i < 0:
        return -1
    ke = d * (k - 1) + 1
    return (i + 2 * p - ke) // s + 1


def conv2d(input, num_filters, filter_size, stride=1, padding=0, dilation=1,
           groups=1, param_attr=None, bias_attr=None, use_cudnn=True,
           act=None, name=None):
    """ref layers/nn.py:conv2d (NCHW). use_cudnn accepted for parity; XLA
    lowers lax.conv onto the MXU."""
    helper = LayerHelper("conv2d", name=name, act=act)
    dtype = input.dtype
    c_in = int(input.shape[1])
    fh, fw = convert_to_list(filter_size, 2, "filter_size")
    sh, sw = convert_to_list(stride, 2, "stride")
    ph, pw = convert_to_list(padding, 2, "padding")
    dh, dw = convert_to_list(dilation, 2, "dilation")
    g = groups or 1
    std = (2.0 / (fh * fw * c_in)) ** 0.5
    w = helper.create_parameter(param_attr, shape=[num_filters, c_in // g, fh, fw],
                                dtype=dtype,
                                default_initializer=NormalInitializer(0.0, std))
    oh = _conv_out_size(int(input.shape[2]), fh, sh, ph, dh)
    ow = _conv_out_size(int(input.shape[3]), fw, sw, pw, dw)
    out_shape = (input.shape[0], num_filters, oh, ow)
    out = helper.create_variable_for_type_inference(dtype, out_shape)
    ins = {"Input": [input], "Filter": [w]}
    b = helper.create_parameter(bias_attr, shape=[num_filters], dtype=dtype,
                                is_bias=True)
    if b is not None:
        ins["Bias"] = [b]
    helper.append_op("conv2d", ins, {"Output": [out]},
                     {"strides": [sh, sw], "paddings": [ph, pw],
                      "dilations": [dh, dw], "groups": g})
    return helper.append_activation(out, act)


def conv2d_transpose(input, num_filters, output_size=None, filter_size=None,
                     stride=1, padding=0, dilation=1, groups=1,
                     param_attr=None, bias_attr=None, use_cudnn=True,
                     act=None, name=None):
    helper = LayerHelper("conv2d_transpose", name=name, act=act)
    dtype = input.dtype
    c_in = int(input.shape[1])
    fh, fw = convert_to_list(filter_size, 2, "filter_size")
    sh, sw = convert_to_list(stride, 2, "stride")
    ph, pw = convert_to_list(padding, 2, "padding")
    w = helper.create_parameter(param_attr, shape=[c_in, num_filters, fh, fw],
                                dtype=dtype)
    ih, iw = int(input.shape[2]), int(input.shape[3])
    oh = (ih - 1) * sh - 2 * ph + fh if ih > 0 else -1
    ow = (iw - 1) * sw - 2 * pw + fw if iw > 0 else -1
    out = helper.create_variable_for_type_inference(
        dtype, (input.shape[0], num_filters, oh, ow))
    ins = {"Input": [input], "Filter": [w]}
    b = helper.create_parameter(bias_attr, shape=[num_filters], dtype=dtype,
                                is_bias=True)
    if b is not None:
        ins["Bias"] = [b]
    helper.append_op("conv2d_transpose", ins, {"Output": [out]},
                     {"strides": [sh, sw], "paddings": [ph, pw],
                      "dilations": [1, 1]})
    return helper.append_activation(out, act)


def conv3d(input, num_filters, filter_size, stride=1, padding=0, dilation=1,
           groups=1, param_attr=None, bias_attr=None, act=None, name=None):
    helper = LayerHelper("conv3d", name=name, act=act)
    dtype = input.dtype
    c_in = int(input.shape[1])
    fs = convert_to_list(filter_size, 3, "filter_size")
    st = convert_to_list(stride, 3, "stride")
    pd = convert_to_list(padding, 3, "padding")
    w = helper.create_parameter(param_attr,
                                shape=[num_filters, c_in // (groups or 1)] + fs,
                                dtype=dtype)
    od = [_conv_out_size(int(input.shape[2 + i]), fs[i], st[i], pd[i]) for i in range(3)]
    out = helper.create_variable_for_type_inference(
        dtype, (input.shape[0], num_filters) + tuple(od))
    helper.append_op("conv3d", {"Input": [input], "Filter": [w]},
                     {"Output": [out]},
                     {"strides": st, "paddings": pd, "dilations": [1, 1, 1],
                      "groups": groups or 1})
    return helper.append_activation(out, act)


def pool2d(input, pool_size=-1, pool_type="max", pool_stride=1, pool_padding=0,
           global_pooling=False, use_cudnn=True, ceil_mode=False,
           exclusive=True, name=None):
    helper = LayerHelper("pool2d", name=name)
    ks = tuple(convert_to_list(pool_size, 2, "pool_size"))
    st = tuple(convert_to_list(pool_stride, 2, "pool_stride"))
    pd = tuple(convert_to_list(pool_padding, 2, "pool_padding"))
    if global_pooling:
        oh = ow = 1
    else:
        def _out(sz, k, s, p):
            num = sz + 2 * p - k
            return (-(-num // s) if ceil_mode else num // s) + 1
        oh = _out(int(input.shape[2]), ks[0], st[0], pd[0])
        ow = _out(int(input.shape[3]), ks[1], st[1], pd[1])
    out = helper.create_variable_for_type_inference(
        input.dtype, (input.shape[0], input.shape[1], oh, ow))
    helper.append_op("pool2d", {"X": [input]}, {"Out": [out]},
                     {"pooling_type": pool_type, "ksize": list(ks),
                      "strides": list(st), "paddings": list(pd),
                      "global_pooling": global_pooling,
                      "exclusive": exclusive, "ceil_mode": ceil_mode})
    return out


def adaptive_pool2d(input, pool_size, pool_type="max", name=None):
    helper = LayerHelper("adaptive_pool2d", name=name)
    ks = tuple(convert_to_list(pool_size, 2, "pool_size"))
    out = helper.create_variable_for_type_inference(
        input.dtype, (input.shape[0], input.shape[1]) + ks)
    helper.append_op("pool2d", {"X": [input]}, {"Out": [out]},
                     {"pooling_type": pool_type, "ksize": list(ks),
                      "adaptive": True})
    return out


def maxout(x, groups, name=None):
    helper = LayerHelper("maxout", name=name)
    c = int(x.shape[1])
    out = helper.create_variable_for_type_inference(
        x.dtype, (x.shape[0], c // groups) + tuple(x.shape[2:]))
    helper.append_op("maxout", {"X": [x]}, {"Out": [out]}, {"groups": groups})
    return out


def pixel_shuffle(x, upscale_factor, name=None):
    helper = LayerHelper("pixel_shuffle", name=name)
    r = upscale_factor
    n, c, h, w = x.shape
    out = helper.create_variable_for_type_inference(
        x.dtype, (n, c // (r * r), h * r, w * r))
    helper.append_op("pixel_shuffle", {"X": [x]}, {"Out": [out]},
                     {"upscale_factor": r})
    return out


# ---------------------------------------------------------------------------
# normalization / dropout
# ---------------------------------------------------------------------------
def batch_norm(input, act=None, is_test=False, momentum=0.9, epsilon=1e-5,
               param_attr=None, bias_attr=None, data_layout="NCHW",
               in_place=False, name=None, moving_mean_name=None,
               moving_variance_name=None, do_model_average_for_mean_and_var=False,
               use_global_stats=False):
    """ref layers/nn.py:batch_norm. Moving stats live as persistable vars
    updated in-graph each training step."""
    helper = LayerHelper("batch_norm", name=name, act=act)
    dtype = input.dtype
    c = int(input.shape[1] if data_layout == "NCHW" else input.shape[-1])
    scale = helper.create_parameter(param_attr, shape=[c], dtype="float32",
                                    default_initializer=ConstantInitializer(1.0))
    bias = helper.create_parameter(bias_attr, shape=[c], dtype="float32",
                                   is_bias=True)
    mean = helper.create_global_variable([c], "float32", persistable=True,
                                         name=moving_mean_name)
    var = helper.create_global_variable([c], "float32", persistable=True,
                                        name=moving_variance_name)
    helper.set_variable_initializer(mean, ConstantInitializer(0.0))
    helper.set_variable_initializer(var, ConstantInitializer(1.0))
    out = helper.create_variable_for_type_inference(dtype, input.shape)
    saved_mean = helper.create_variable_for_type_inference("float32", (c,), True)
    saved_var = helper.create_variable_for_type_inference("float32", (c,), True)
    helper.append_op(
        "batch_norm",
        {"X": [input], "Scale": [scale], "Bias": [bias],
         "Mean": [mean], "Variance": [var]},
        {"Y": [out], "MeanOut": [mean], "VarianceOut": [var],
         "SavedMean": [saved_mean], "SavedVariance": [saved_var]},
        {"momentum": momentum, "epsilon": epsilon,
         "is_test": is_test or use_global_stats, "data_layout": data_layout})
    return helper.append_activation(out, act)


def layer_norm(input, scale=True, shift=True, begin_norm_axis=1,
               epsilon=1e-5, param_attr=None, bias_attr=None, act=None,
               name=None):
    helper = LayerHelper("layer_norm", name=name, act=act)
    dtype = input.dtype
    norm_shape = [int(np.prod(input.shape[begin_norm_axis:]))]
    ins = {"X": [input]}
    if scale:
        s = helper.create_parameter(param_attr, shape=norm_shape, dtype="float32",
                                    default_initializer=ConstantInitializer(1.0))
        ins["Scale"] = [s]
    if shift:
        b = helper.create_parameter(bias_attr, shape=norm_shape, dtype="float32",
                                    is_bias=True)
        ins["Bias"] = [b]
    out = helper.create_variable_for_type_inference(dtype, input.shape)
    mean = helper.create_variable_for_type_inference("float32", (), True)
    var = helper.create_variable_for_type_inference("float32", (), True)
    helper.append_op("layer_norm", ins,
                     {"Y": [out], "Mean": [mean], "Variance": [var]},
                     {"epsilon": epsilon, "begin_norm_axis": begin_norm_axis})
    return helper.append_activation(out, act)


def group_norm(input, groups, epsilon=1e-5, param_attr=None, bias_attr=None,
               act=None, name=None):
    helper = LayerHelper("group_norm", name=name, act=act)
    c = int(input.shape[1])
    ins = {"X": [input]}
    s = helper.create_parameter(param_attr, shape=[c], dtype="float32",
                                default_initializer=ConstantInitializer(1.0))
    b = helper.create_parameter(bias_attr, shape=[c], dtype="float32", is_bias=True)
    if s is not None:
        ins["Scale"] = [s]
    if b is not None:
        ins["Bias"] = [b]
    out = helper.create_variable_for_type_inference(input.dtype, input.shape)
    mean = helper.create_variable_for_type_inference("float32", (), True)
    var = helper.create_variable_for_type_inference("float32", (), True)
    helper.append_op("group_norm", ins,
                     {"Y": [out], "Mean": [mean], "Variance": [var]},
                     {"groups": groups, "epsilon": epsilon})
    return helper.append_activation(out, act)


def instance_norm(input, epsilon=1e-5, param_attr=None, bias_attr=None,
                  name=None):
    helper = LayerHelper("instance_norm", name=name)
    c = int(input.shape[1])
    s = helper.create_parameter(param_attr, shape=[c], dtype="float32",
                                default_initializer=ConstantInitializer(1.0))
    b = helper.create_parameter(bias_attr, shape=[c], dtype="float32", is_bias=True)
    out = helper.create_variable_for_type_inference(input.dtype, input.shape)
    helper.append_op("instance_norm",
                     {"X": [input], "Scale": [s], "Bias": [b]},
                     {"Y": [out]}, {"epsilon": epsilon})
    return out


def dropout(x, dropout_prob, is_test=False, seed=None, name=None,
            dropout_implementation="downgrade_in_infer"):
    helper = LayerHelper("dropout", name=name)
    out = helper.create_variable_for_type_inference(x.dtype, x.shape)
    mask = helper.create_variable_for_type_inference(x.dtype, x.shape, True)
    helper.append_op("dropout", {"X": [x]}, {"Out": [out], "Mask": [mask]},
                     {"dropout_prob": dropout_prob, "is_test": is_test,
                      "dropout_implementation": dropout_implementation})
    return out


# ---------------------------------------------------------------------------
# softmax & losses
# ---------------------------------------------------------------------------
def softmax(input, axis=-1, use_cudnn=False, name=None):
    helper = LayerHelper("softmax", name=name)
    return _same_shape_out(helper, input, "softmax", {"axis": axis})


def log_softmax(input, axis=-1, name=None):
    helper = LayerHelper("log_softmax", name=name)
    return _same_shape_out(helper, input, "log_softmax", {"axis": axis})


def cross_entropy(input, label, soft_label=False, ignore_index=-100):
    helper = LayerHelper("cross_entropy")
    out_shape = tuple(input.shape[:-1]) + (1,)
    out = helper.create_variable_for_type_inference(input.dtype, out_shape)
    helper.append_op("cross_entropy", {"X": [input], "Label": [label]},
                     {"Y": [out]},
                     {"soft_label": soft_label, "ignore_index": ignore_index})
    return out


def softmax_with_cross_entropy(logits, label, soft_label=False,
                               ignore_index=-100, numeric_stable_mode=True,
                               return_softmax=False, smooth_epsilon=0.0):
    """smooth_epsilon > 0 with integer labels computes label-smoothed CE
    in one fused kernel — same numerics as one_hot→label_smooth→this op
    with soft_label=True, without materializing the [.., K] targets
    (beyond-reference attr; the composed path still works)."""
    helper = LayerHelper("softmax_with_cross_entropy")
    loss_shape = tuple(logits.shape[:-1]) + (1,)
    loss = helper.create_variable_for_type_inference(logits.dtype, loss_shape)
    sm = helper.create_variable_for_type_inference(logits.dtype, logits.shape)
    helper.append_op("softmax_with_cross_entropy",
                     {"Logits": [logits], "Label": [label]},
                     {"Loss": [loss], "Softmax": [sm]},
                     {"soft_label": soft_label, "ignore_index": ignore_index,
                      "smooth_epsilon": smooth_epsilon})
    if return_softmax:
        return loss, sm
    return loss


def sigmoid_cross_entropy_with_logits(x, label, ignore_index=-100, name=None):
    helper = LayerHelper("sigmoid_cross_entropy_with_logits", name=name)
    out = helper.create_variable_for_type_inference(x.dtype, x.shape)
    helper.append_op("sigmoid_cross_entropy_with_logits",
                     {"X": [x], "Label": [label]}, {"Out": [out]},
                     {"ignore_index": ignore_index})
    return out


def square_error_cost(input, label):
    helper = LayerHelper("square_error_cost")
    out = helper.create_variable_for_type_inference(input.dtype, input.shape)
    helper.append_op("square_error_cost", {"X": [input], "Y": [label]},
                     {"Out": [out]}, {})
    return out


def huber_loss(input, label, delta):
    helper = LayerHelper("huber_loss")
    out = helper.create_variable_for_type_inference(input.dtype, input.shape)
    res = helper.create_variable_for_type_inference(input.dtype, input.shape, True)
    helper.append_op("huber_loss", {"X": [input], "Y": [label]},
                     {"Out": [out], "Residual": [res]}, {"delta": delta})
    return out


def hinge_loss(input, label, name=None):
    helper = LayerHelper("hinge_loss", name=name)
    out = helper.create_variable_for_type_inference(input.dtype, input.shape)
    helper.append_op("hinge_loss", {"Logits": [input], "Labels": [label]},
                     {"Loss": [out]}, {})
    return out


def bpr_loss(input, label, name=None):
    helper = LayerHelper("bpr_loss", name=name)
    out = helper.create_variable_for_type_inference(
        input.dtype, (input.shape[0], 1))
    helper.append_op("bpr_loss", {"X": [input], "Label": [label]},
                     {"Y": [out]}, {})
    return out


def margin_rank_loss(label, left, right, margin=0.1, name=None):
    helper = LayerHelper("margin_rank_loss", name=name)
    out = helper.create_variable_for_type_inference(left.dtype, left.shape)
    act = helper.create_variable_for_type_inference(left.dtype, left.shape, True)
    helper.append_op("margin_rank_loss",
                     {"X1": [left], "X2": [right], "Label": [label]},
                     {"Out": [out], "Activated": [act]}, {"margin": margin})
    return out


def log_loss(input, label, epsilon=1e-4, name=None):
    helper = LayerHelper("log_loss", name=name)
    out = helper.create_variable_for_type_inference(input.dtype, input.shape)
    helper.append_op("log_loss", {"Predicted": [input], "Labels": [label]},
                     {"Loss": [out]}, {"epsilon": epsilon})
    return out


def kldiv_loss(x, target, reduction="mean", name=None):
    helper = LayerHelper("kldiv_loss", name=name)
    shape = () if reduction != "none" else x.shape
    out = helper.create_variable_for_type_inference(x.dtype, shape)
    helper.append_op("kldiv_loss", {"X": [x], "Target": [target]},
                     {"Loss": [out]}, {"reduction": reduction})
    return out


def mse_loss(input, label, name=None):
    helper = LayerHelper("mse_loss", name=name)
    out = helper.create_variable_for_type_inference(input.dtype, ())
    helper.append_op("mse_loss", {"X": [input], "Y": [label]},
                     {"Out": [out]}, {})
    return out


def smooth_l1(x, y, inside_weight=None, outside_weight=None, sigma=1.0):
    helper = LayerHelper("smooth_l1")
    out = helper.create_variable_for_type_inference(x.dtype, (x.shape[0], 1))
    diff = helper.create_variable_for_type_inference(x.dtype, x.shape, True)
    helper.append_op("smooth_l1_loss", {"X": [x], "Y": [y]},
                     {"Out": [out], "Diff": [diff]}, {"sigma": sigma})
    return out


def label_smooth(label, prior_dist=None, epsilon=0.1, dtype="float32",
                 name=None):
    helper = LayerHelper("label_smooth", name=name)
    out = helper.create_variable_for_type_inference(dtype, label.shape)
    ins = {"X": [label]}
    if prior_dist is not None:
        ins["PriorDist"] = [prior_dist]
    helper.append_op("label_smooth", ins, {"Out": [out]}, {"epsilon": epsilon})
    return out


def one_hot(input, depth, name=None):
    helper = LayerHelper("one_hot", name=name)
    base = input.shape[:-1] if (input.shape and input.shape[-1] == 1) else input.shape
    out = helper.create_variable_for_type_inference(
        "float32", tuple(base) + (depth,))
    helper.append_op("one_hot", {"X": [input]}, {"Out": [out]},
                     {"depth": depth})
    return out


# ---------------------------------------------------------------------------
# recurrent
# ---------------------------------------------------------------------------
def dynamic_lstm(input, size, h_0=None, c_0=None, param_attr=None,
                 bias_attr=None, use_peepholes=False, is_reverse=False,
                 gate_activation="sigmoid", cell_activation="tanh",
                 candidate_activation="tanh", dtype="float32", name=None,
                 seq_len=None):
    """Padded-batch LSTM (ref layers/nn.py:dynamic_lstm, LoD → mask).

    input: [B, T, D]; size = 4*hidden (gate-packed, matching the ref API).
    Returns (hidden [B,T,H], cell-state last [B,H]).
    """
    helper = LayerHelper("dynamic_lstm", name=name)
    hidden = size // 4
    d_in = int(input.shape[-1])
    w_ih = helper.create_parameter(_sub_attr(param_attr, "ih"),
                                   shape=[d_in, 4 * hidden], dtype=dtype)
    w_hh = helper.create_parameter(_sub_attr(param_attr, "hh"),
                                   shape=[hidden, 4 * hidden], dtype=dtype)
    b = helper.create_parameter(bias_attr, shape=[4 * hidden], dtype=dtype,
                                is_bias=True)
    B, T = input.shape[0], input.shape[1]
    h_seq = helper.create_variable_for_type_inference(dtype, (B, T, hidden))
    last_h = helper.create_variable_for_type_inference(dtype, (B, hidden))
    last_c = helper.create_variable_for_type_inference(dtype, (B, hidden))
    ins = {"Input": [input], "WeightIH": [w_ih], "WeightHH": [w_hh]}
    if b is not None:
        ins["Bias"] = [b]
    if h_0 is not None:
        ins["H0"] = [h_0]
    if c_0 is not None:
        ins["C0"] = [c_0]
    if seq_len is not None:
        ins["SeqLen"] = [seq_len]
    helper.append_op("lstm", ins,
                     {"Hidden": [h_seq], "LastH": [last_h], "LastC": [last_c]},
                     {"is_reverse": is_reverse})
    h_seq._last_h, h_seq._last_c = last_h, last_c
    return h_seq, last_c


def dynamic_lstmp(input, size, proj_size, param_attr=None, bias_attr=None,
                  use_peepholes=False, is_reverse=False,
                  gate_activation="sigmoid", cell_activation="tanh",
                  candidate_activation="tanh", proj_activation="tanh",
                  dtype="float32", name=None, seq_len=None):
    """LSTM with recurrent projection (ref layers/nn.py:dynamic_lstmp,
    lstmp_op). input [B,T,D]; size = 4*hidden. Returns
    (projection [B,T,P], last cell [B,H])."""
    helper = LayerHelper("dynamic_lstmp", name=name)
    hidden = size // 4
    d_in = int(input.shape[-1])
    w_ih = helper.create_parameter(_sub_attr(param_attr, "ih"),
                                   shape=[d_in, 4 * hidden],
                                   dtype=dtype)
    w_hh = helper.create_parameter(_sub_attr(param_attr, "hh"),
                                   shape=[proj_size, 4 * hidden],
                                   dtype=dtype)
    w_proj = helper.create_parameter(_sub_attr(param_attr, "proj"),
                                     shape=[hidden, proj_size],
                                     dtype=dtype)
    b = helper.create_parameter(bias_attr, shape=[4 * hidden], dtype=dtype,
                                is_bias=True)
    B, T = input.shape[0], input.shape[1]
    proj = helper.create_variable_for_type_inference(dtype, (B, T, proj_size))
    last_h = helper.create_variable_for_type_inference(dtype, (B, proj_size))
    last_c = helper.create_variable_for_type_inference(dtype, (B, hidden))
    ins = {"Input": [input], "WeightIH": [w_ih], "WeightHH": [w_hh],
           "Proj": [w_proj]}
    if b is not None:
        ins["Bias"] = [b]
    if seq_len is not None:
        ins["SeqLen"] = [seq_len]
    helper.append_op("lstmp", ins,
                     {"Projection": [proj], "LastH": [last_h],
                      "LastC": [last_c]},
                     {"is_reverse": is_reverse})
    return proj, last_c


def lstm(input, init_h=None, init_c=None, max_len=None, hidden_size=None,
         num_layers=1, dropout_prob=0.0, is_bidirec=False, is_test=False,
         name=None, default_initializer=None, seed=-1, seq_len=None):
    """Multi-layer (optionally bidirectional) LSTM (ref layers/nn.py:lstm,
    cudnn_lstm_op → stacked lax.scan LSTMs; XLA fuses the stack).

    input [B,T,D]. Returns (rnn_out [B,T,H*dirs], last_h [L*dirs,B,H],
    last_c [L*dirs,B,H]).
    """
    if hidden_size is None:
        raise ValueError("lstm requires hidden_size")
    from .tensor import concat as _concat

    def _init_state(packed, idx):
        # packed [L*dirs, B, H] → [B, H] for layer-direction idx
        if packed is None:
            return None
        s = slice(packed, axes=[0], starts=[idx], ends=[idx + 1])
        return squeeze(s, axes=[0])

    x = input
    last_hs, last_cs = [], []
    idx = 0
    for layer in range(num_layers):
        fw, fw_c = dynamic_lstm(
            x, 4 * hidden_size, h_0=_init_state(init_h, idx),
            c_0=_init_state(init_c, idx), seq_len=seq_len,
            name=f"{name or 'lstm'}_l{layer}_fw")
        last_hs.append(fw._last_h)
        idx += 1
        if is_bidirec:
            bw, bw_c = dynamic_lstm(
                x, 4 * hidden_size, is_reverse=True,
                h_0=_init_state(init_h, idx), c_0=_init_state(init_c, idx),
                seq_len=seq_len, name=f"{name or 'lstm'}_l{layer}_bw")
            last_hs.append(bw._last_h)
            idx += 1
            x = _concat([fw, bw], axis=-1)
            last_cs += [fw_c, bw_c]
        else:
            x = fw
            last_cs.append(fw_c)
        if dropout_prob > 0.0 and layer < num_layers - 1:
            x = dropout(x, dropout_prob, is_test=is_test)
    last_h = stack(last_hs, axis=0)  # [L*dirs, B, H]
    last_c = stack(last_cs, axis=0)
    return x, last_h, last_c


def dynamic_gru(input, size, param_attr=None, bias_attr=None,
                is_reverse=False, h_0=None, dtype="float32", name=None,
                seq_len=None):
    """Padded-batch GRU (ref layers/nn.py:dynamic_gru). input [B,T,D]."""
    helper = LayerHelper("dynamic_gru", name=name)
    d_in = int(input.shape[-1])
    w_ih = helper.create_parameter(_sub_attr(param_attr, "ih"),
                                   shape=[d_in, 3 * size], dtype=dtype)
    w_hh = helper.create_parameter(_sub_attr(param_attr, "hh"),
                                   shape=[size, 3 * size], dtype=dtype)
    b = helper.create_parameter(bias_attr, shape=[3 * size], dtype=dtype,
                                is_bias=True)
    B, T = input.shape[0], input.shape[1]
    h_seq = helper.create_variable_for_type_inference(dtype, (B, T, size))
    last_h = helper.create_variable_for_type_inference(dtype, (B, size))
    ins = {"Input": [input], "WeightIH": [w_ih], "WeightHH": [w_hh]}
    if b is not None:
        ins["Bias"] = [b]
    if h_0 is not None:
        ins["H0"] = [h_0]
    if seq_len is not None:
        ins["SeqLen"] = [seq_len]
    helper.append_op("gru", ins, {"Hidden": [h_seq], "LastH": [last_h]},
                     {"is_reverse": is_reverse})
    return h_seq


def lstm_unit(x_t, hidden_t_prev, cell_t_prev, forget_bias=0.0,
              param_attr=None, bias_attr=None, name=None):
    """ref layers/nn.py:lstm_unit — one step; x_t already projected is not
    assumed: does fc([x,h]) like the reference."""
    from . import tensor as _t
    cat = _t.concat([x_t, hidden_t_prev], axis=1)
    hidden = int(hidden_t_prev.shape[-1])
    gates = fc(cat, 4 * hidden, param_attr=param_attr, bias_attr=bias_attr)
    helper = LayerHelper("lstm_unit", name=name)
    c = helper.create_variable_for_type_inference(x_t.dtype, cell_t_prev.shape)
    h = helper.create_variable_for_type_inference(x_t.dtype, hidden_t_prev.shape)
    helper.append_op("lstm_unit", {"X": [gates], "C_prev": [cell_t_prev]},
                     {"C": [c], "H": [h]}, {"forget_bias": forget_bias})
    return h, c


def gru_unit(input, hidden, size, param_attr=None, bias_attr=None,
             activation="tanh", gate_activation="sigmoid", name=None):
    helper = LayerHelper("gru_unit", name=name)
    hidden_dim = size // 3
    w = helper.create_parameter(param_attr, shape=[hidden_dim, 3 * hidden_dim],
                                dtype=input.dtype)
    b = helper.create_parameter(bias_attr, shape=[3 * hidden_dim],
                                dtype=input.dtype, is_bias=True)
    h = helper.create_variable_for_type_inference(input.dtype, hidden.shape)
    gate = helper.create_variable_for_type_inference(
        input.dtype, (hidden.shape[0], 2 * hidden_dim), True)
    rhp = helper.create_variable_for_type_inference(input.dtype, hidden.shape, True)
    ins = {"Input": [input], "HiddenPrev": [hidden], "Weight": [w]}
    if b is not None:
        ins["Bias"] = [b]
    helper.append_op("gru_unit", ins,
                     {"Hidden": [h], "Gate": [gate], "ResetHiddenPrev": [rhp]},
                     {})
    return h, rhp, gate


# ---------------------------------------------------------------------------
# tensor manipulation wrappers (thin; see ops/kernels_*)
# ---------------------------------------------------------------------------
def _simple(helper_name, op_type, x, out_shape=None, attrs=None,
            extra=None, out_slot="Out", dtype=None, stop_gradient=False):
    helper = LayerHelper(helper_name)
    out = helper.create_variable_for_type_inference(
        dtype or x.dtype, out_shape if out_shape is not None else x.shape,
        stop_gradient)
    ins = {"X": [x]}
    if extra:
        ins.update(extra)
    helper.append_op(op_type, ins, {out_slot: [out]}, attrs or {})
    return out


def matmul(x, y, transpose_x=False, transpose_y=False, alpha=1.0, name=None):
    helper = LayerHelper("matmul", name=name)
    xs = list(x.shape)
    ys = list(y.shape)
    if transpose_x and len(xs) >= 2:
        xs[-1], xs[-2] = xs[-2], xs[-1]
    if transpose_y and len(ys) >= 2:
        ys[-1], ys[-2] = ys[-2], ys[-1]
    if len(xs) >= 2 and len(ys) >= 2:
        out_shape = tuple(xs[:-1]) + (ys[-1],)
    else:
        out_shape = ()
    out = helper.create_variable_for_type_inference(x.dtype, out_shape)
    helper.append_op("matmul", {"X": [x], "Y": [y]}, {"Out": [out]},
                     {"transpose_X": transpose_x, "transpose_Y": transpose_y,
                      "alpha": alpha})
    return out


def mul(x, y, x_num_col_dims=1, y_num_col_dims=1, name=None):
    helper = LayerHelper("mul", name=name)
    out_shape = tuple(x.shape[:x_num_col_dims]) + tuple(y.shape[y_num_col_dims:])
    out = helper.create_variable_for_type_inference(x.dtype, out_shape)
    helper.append_op("mul", {"X": [x], "Y": [y]}, {"Out": [out]},
                     {"x_num_col_dims": x_num_col_dims,
                      "y_num_col_dims": y_num_col_dims})
    return out


def bmm(x, y, name=None):
    helper = LayerHelper("bmm", name=name)
    out = helper.create_variable_for_type_inference(
        x.dtype, (x.shape[0], x.shape[1], y.shape[2]))
    helper.append_op("bmm", {"X": [x], "Y": [y]}, {"Out": [out]}, {})
    return out


def dot(x, y, name=None):
    return _simple("dot", "dot", x, tuple(x.shape[:-1]) + (1,),
                   extra={"Y": [y]})


def transpose(x, perm, name=None):
    out_shape = tuple(x.shape[p] for p in perm)
    return _simple("transpose", "transpose", x, out_shape, {"axis": list(perm)})


def reshape(x, shape, actual_shape=None, act=None, inplace=False, name=None):
    shape = list(shape)
    known = 1
    resolved = []
    for i, s in enumerate(shape):
        s = int(s)
        resolved.append(x.shape[i] if s == 0 else s)
    out_shape = tuple(resolved)
    return _simple("reshape", "reshape", x, out_shape, {"shape": shape})


def squeeze(input, axes=None, name=None):
    shape = list(input.shape)
    if axes:
        out_shape = tuple(s for i, s in enumerate(shape)
                          if i not in [a % len(shape) for a in axes])
    else:
        out_shape = tuple(s for s in shape if s != 1)
    return _simple("squeeze", "squeeze", input, out_shape,
                   {"axes": list(axes or [])})


def unsqueeze(input, axes, name=None):
    shape = list(input.shape)
    for a in sorted(axes):
        shape.insert(a, 1)
    return _simple("unsqueeze", "unsqueeze", input, tuple(shape),
                   {"axes": list(axes)})


def flatten(x, axis=1, name=None):
    lead = int(np.prod(x.shape[:axis])) if axis > 0 else 1
    rest = int(np.prod(x.shape[axis:]))
    return _simple("flatten", "flatten", x, (lead, rest), {"axis": axis})


def stack(x, axis=0, name=None):
    xs = x if isinstance(x, (list, tuple)) else [x]
    helper = LayerHelper("stack", name=name)
    shape = list(xs[0].shape)
    shape.insert(axis if axis >= 0 else axis + len(shape) + 1, len(xs))
    out = helper.create_variable_for_type_inference(xs[0].dtype, tuple(shape))
    helper.append_op("stack", {"X": list(xs)}, {"Y": [out]}, {"axis": axis})
    return out


def unstack(x, axis=0, num=None, name=None):
    helper = LayerHelper("unstack", name=name)
    n = num or x.shape[axis]
    shape = tuple(s for i, s in enumerate(x.shape) if i != (axis % len(x.shape)))
    outs = [helper.create_variable_for_type_inference(x.dtype, shape)
            for _ in range(n)]
    helper.append_op("unstack", {"X": [x]}, {"Y": outs}, {"axis": axis})
    return outs


def expand(x, expand_times, name=None):
    out_shape = tuple(-1 if s < 0 else s * t
                      for s, t in zip(x.shape, expand_times))
    return _simple("expand", "expand", x, out_shape,
                   {"expand_times": list(expand_times)})


def expand_as(x, target_tensor, name=None):
    return _simple("expand_as", "expand_as", x, target_tensor.shape,
                   extra={"target_tensor": [target_tensor]})


def slice(input, axes, starts, ends, name=None):
    shape = list(input.shape)
    for a, s, e in zip(axes, starts, ends):
        if shape[a] < 0:
            continue
        dim = shape[a]
        s2 = max(s + dim, 0) if s < 0 else min(s, dim)
        e2 = max(e + dim, 0) if e < 0 else min(e, dim)
        shape[a] = max(e2 - s2, 0)
    helper = LayerHelper("slice", name=name)
    out = helper.create_variable_for_type_inference(input.dtype, tuple(shape))
    helper.append_op("slice", {"Input": [input]}, {"Out": [out]},
                     {"axes": list(axes), "starts": list(starts),
                      "ends": list(ends)})
    return out


def strided_slice(input, axes, starts, ends, strides, name=None):
    helper = LayerHelper("strided_slice", name=name)
    shape = list(input.shape)
    for a, s, e, st in zip(axes, starts, ends, strides):
        if shape[a] >= 0:
            shape[a] = max(0, (e - s + (st - (1 if st > 0 else -1))) // st)
    out = helper.create_variable_for_type_inference(input.dtype, tuple(shape))
    helper.append_op("strided_slice", {"Input": [input]}, {"Out": [out]},
                     {"axes": list(axes), "starts": list(starts),
                      "ends": list(ends), "strides": list(strides)})
    return out


def gather(input, index, axis=0, name=None):
    out_shape = tuple(list(index.shape) + list(input.shape[1:]))
    return _simple("gather", "gather", input, out_shape, {"axis": axis},
                   extra={"Index": [index]})


def gather_nd(input, index, name=None):
    k = index.shape[-1]
    out_shape = tuple(index.shape[:-1]) + tuple(input.shape[k:])
    return _simple("gather_nd", "gather_nd", input, out_shape,
                   extra={"Index": [index]})


def scatter(input, index, updates, overwrite=True, name=None):
    return _simple("scatter", "scatter", input, input.shape,
                   {"overwrite": overwrite},
                   extra={"Ids": [index], "Updates": [updates]})


def scatter_nd_add(ref, index, updates, name=None):
    return _simple("scatter_nd_add", "scatter_nd_add", ref, ref.shape,
                   extra={"Index": [index], "Updates": [updates]})


def topk(input, k, name=None):
    helper = LayerHelper("top_k", name=name)
    out_shape = tuple(input.shape[:-1]) + (k,)
    vals = helper.create_variable_for_type_inference(input.dtype, out_shape)
    idx = helper.create_variable_for_type_inference("int64", out_shape, True)
    helper.append_op("top_k", {"X": [input]},
                     {"Out": [vals], "Indices": [idx]}, {"k": k})
    return vals, idx


def argsort(input, axis=-1, descending=False, name=None):
    helper = LayerHelper("argsort", name=name)
    out = helper.create_variable_for_type_inference(input.dtype, input.shape)
    idx = helper.create_variable_for_type_inference("int64", input.shape, True)
    helper.append_op("argsort", {"X": [input]},
                     {"Out": [out], "Indices": [idx]},
                     {"axis": axis, "descending": descending})
    return out, idx


def argmax(x, axis=-1, keepdims=False, name=None):
    shape = list(x.shape)
    ax = axis % len(shape) if shape else 0
    if keepdims:
        shape[ax] = 1
    else:
        shape.pop(ax)
    return _simple("arg_max", "arg_max", x, tuple(shape),
                   {"axis": axis, "keepdims": keepdims}, dtype="int64",
                   stop_gradient=True)


def argmin(x, axis=-1, name=None):
    shape = list(x.shape)
    shape.pop(axis % len(shape) if shape else 0)
    return _simple("arg_min", "arg_min", x, tuple(shape), {"axis": axis},
                   dtype="int64", stop_gradient=True)


def where(condition, x, y, name=None):
    helper = LayerHelper("where", name=name)
    out = helper.create_variable_for_type_inference(x.dtype, x.shape)
    helper.append_op("where", {"Condition": [condition], "X": [x], "Y": [y]},
                     {"Out": [out]}, {})
    return out


cond_select = where


def split(input, num_or_sections, dim=-1, name=None):
    helper = LayerHelper("split", name=name)
    axis = dim % len(input.shape)
    if isinstance(num_or_sections, int):
        n = num_or_sections
        sections = None
        sizes = [input.shape[axis] // n] * n if input.shape[axis] > 0 else [-1] * n
    else:
        sections = list(num_or_sections)
        sizes = sections
        n = len(sections)
    outs = []
    for s in sizes:
        shape = list(input.shape)
        shape[axis] = s
        outs.append(helper.create_variable_for_type_inference(
            input.dtype, tuple(shape)))
    attrs = {"axis": axis}
    if sections:
        attrs["sections"] = sections
    else:
        attrs["num"] = n
    helper.append_op("split", {"X": [input]}, {"Out": outs}, attrs)
    return outs


def l2_normalize(x, axis, epsilon=1e-12, name=None):
    helper = LayerHelper("l2_normalize", name=name)
    out = helper.create_variable_for_type_inference(x.dtype, x.shape)
    norm = helper.create_variable_for_type_inference(x.dtype, x.shape, True)
    helper.append_op("l2_normalize", {"X": [x]},
                     {"Out": [out], "Norm": [norm]},
                     {"axis": axis, "epsilon": epsilon})
    return out


def mean(x, name=None):
    return _simple("mean", "mean", x, ())


def _reduce_layer(op, input, dim, keep_dim, name):
    shape = list(input.shape)
    if dim is None:
        out_shape = ()
    else:
        dims = [dim] if isinstance(dim, int) else list(dim)
        dims = [d % len(shape) for d in dims]
        if keep_dim:
            out_shape = tuple(1 if i in dims else s for i, s in enumerate(shape))
        else:
            out_shape = tuple(s for i, s in enumerate(shape) if i not in dims)
    return _simple(op, op, input, out_shape,
                   {"dim": [dim] if isinstance(dim, int) else dim,
                    "keep_dim": keep_dim, "reduce_all": dim is None})


def reduce_sum(input, dim=None, keep_dim=False, name=None):
    return _reduce_layer("reduce_sum", input, dim, keep_dim, name)


def reduce_mean(input, dim=None, keep_dim=False, name=None):
    return _reduce_layer("reduce_mean", input, dim, keep_dim, name)


def reduce_max(input, dim=None, keep_dim=False, name=None):
    return _reduce_layer("reduce_max", input, dim, keep_dim, name)


def reduce_min(input, dim=None, keep_dim=False, name=None):
    return _reduce_layer("reduce_min", input, dim, keep_dim, name)


def reduce_prod(input, dim=None, keep_dim=False, name=None):
    return _reduce_layer("reduce_prod", input, dim, keep_dim, name)


def reduce_all(input, dim=None, keep_dim=False, name=None):
    return _reduce_layer("reduce_all", input, dim, keep_dim, name)


def reduce_any(input, dim=None, keep_dim=False, name=None):
    return _reduce_layer("reduce_any", input, dim, keep_dim, name)


def logsumexp(x, dim=None, keep_dim=False, name=None):
    return _reduce_layer("logsumexp", x, dim, keep_dim, name)


def cumsum(x, axis=-1, exclusive=False, reverse=False, name=None):
    return _simple("cumsum", "cumsum", x, x.shape,
                   {"axis": axis, "exclusive": exclusive, "reverse": reverse})


def clip(x, min, max, name=None):
    return _simple("clip", "clip", x, x.shape, {"min": min, "max": max})


def clip_by_norm(x, max_norm, name=None):
    return _simple("clip_by_norm", "clip_by_norm", x, x.shape,
                   {"max_norm": max_norm})


def _elementwise_layer(op, x, y, axis, act, name):
    helper = LayerHelper(op, name=name, act=act)
    out_shape = x.shape if len(x.shape) >= len(y.shape) else y.shape
    out = helper.create_variable_for_type_inference(x.dtype, out_shape)
    helper.append_op(op, {"X": [x], "Y": [y]}, {"Out": [out]}, {"axis": axis})
    return helper.append_activation(out, act)


def elementwise_add(x, y, axis=-1, act=None, name=None):
    return _elementwise_layer("elementwise_add", x, y, axis, act, name)


def elementwise_sub(x, y, axis=-1, act=None, name=None):
    return _elementwise_layer("elementwise_sub", x, y, axis, act, name)


def elementwise_mul(x, y, axis=-1, act=None, name=None):
    return _elementwise_layer("elementwise_mul", x, y, axis, act, name)


def elementwise_div(x, y, axis=-1, act=None, name=None):
    return _elementwise_layer("elementwise_div", x, y, axis, act, name)


def elementwise_max(x, y, axis=-1, act=None, name=None):
    return _elementwise_layer("elementwise_max", x, y, axis, act, name)


def elementwise_min(x, y, axis=-1, act=None, name=None):
    return _elementwise_layer("elementwise_min", x, y, axis, act, name)


def elementwise_pow(x, y, axis=-1, act=None, name=None):
    return _elementwise_layer("elementwise_pow", x, y, axis, act, name)


def elementwise_mod(x, y, axis=-1, act=None, name=None):
    return _elementwise_layer("elementwise_mod", x, y, axis, act, name)


def scale(x, scale=1.0, bias=0.0, bias_after_scale=True, act=None, name=None):
    helper = LayerHelper("scale", name=name, act=act)
    out = helper.create_variable_for_type_inference(x.dtype, x.shape)
    helper.append_op("scale", {"X": [x]}, {"Out": [out]},
                     {"scale": float(scale), "bias": float(bias),
                      "bias_after_scale": bias_after_scale})
    return helper.append_activation(out, act)


def cast(x, dtype):
    dtype = convert_dtype(dtype)
    return _simple("cast", "cast", x, x.shape, {"out_dtype": dtype},
                   dtype=dtype)


def pad(x, paddings, pad_value=0.0, name=None):
    shape = list(x.shape)
    for i in range(len(shape)):
        if shape[i] >= 0:
            shape[i] += paddings[2 * i] + paddings[2 * i + 1]
    return _simple("pad", "pad", x, tuple(shape),
                   {"paddings": list(paddings), "pad_value": pad_value})


def pad2d(input, paddings=(0, 0, 0, 0), mode="constant", pad_value=0.0,
          data_format="NCHW", name=None):
    t, b, l, r = paddings
    shape = list(input.shape)
    if shape[2] >= 0:
        shape[2] += t + b
    if shape[3] >= 0:
        shape[3] += l + r
    return _simple("pad2d", "pad2d", input, tuple(shape),
                   {"paddings": list(paddings), "mode": mode,
                    "pad_value": pad_value})


def prelu(x, mode="all", param_attr=None, name=None):
    helper = LayerHelper("prelu", name=name)
    if mode == "all":
        alpha_shape = [1]
    elif mode == "channel":
        alpha_shape = [int(x.shape[1])]
    else:
        alpha_shape = [int(s) for s in x.shape[1:]]
    alpha = helper.create_parameter(param_attr, shape=alpha_shape,
                                    dtype=x.dtype,
                                    default_initializer=ConstantInitializer(0.25))
    out = helper.create_variable_for_type_inference(x.dtype, x.shape)
    helper.append_op("prelu", {"X": [x], "Alpha": [alpha]}, {"Out": [out]},
                     {"mode": mode})
    return out


def brelu(x, t_min=0.0, t_max=24.0, name=None):
    return _simple("brelu", "clip", x, x.shape, {"min": t_min, "max": t_max})


def leaky_relu(x, alpha=0.02, name=None):
    return _simple("leaky_relu", "leaky_relu", x, x.shape, {"alpha": alpha})


def soft_relu(x, threshold=40.0, name=None):
    return _simple("soft_relu", "soft_relu", x, x.shape,
                   {"threshold": threshold})


def relu6(x, threshold=6.0, name=None):
    return _simple("relu6", "relu6", x, x.shape, {"threshold": threshold})


def pow(x, factor=1.0, name=None):
    return _simple("pow", "pow", x, x.shape, {"factor": factor})


def hard_sigmoid(x, slope=0.2, offset=0.5, name=None):
    return _simple("hard_sigmoid", "hard_sigmoid", x, x.shape,
                   {"slope": slope, "offset": offset})


def swish(x, beta=1.0, name=None):
    return _simple("swish", "swish", x, x.shape, {"beta": beta})


def hard_swish(x, threshold=6.0, scale=6.0, offset=3.0, name=None):
    return _simple("hard_swish", "hard_swish", x, x.shape,
                   {"threshold": threshold, "scale": scale, "offset": offset})


def image_resize(input, out_shape=None, scale=None, resample="BILINEAR",
                 name=None):
    helper = LayerHelper("image_resize", name=name)
    if out_shape:
        oh, ow = out_shape
    else:
        oh = int(input.shape[2] * scale)
        ow = int(input.shape[3] * scale)
    out = helper.create_variable_for_type_inference(
        input.dtype, (input.shape[0], input.shape[1], oh, ow))
    helper.append_op("bilinear_interp" if resample.upper() == "BILINEAR"
                     else "nearest_interp",
                     {"X": [input]}, {"Out": [out]},
                     {"out_h": oh, "out_w": ow,
                      "interp_method": resample.lower()})
    return out


def image_resize_short(input, out_short_len, resample="BILINEAR"):
    """ref nn.py:image_resize_short — resize so the SHORT side equals
    out_short_len, keeping aspect ratio."""
    h, w = int(input.shape[2]), int(input.shape[3])
    short = min(h, w)
    oh = int(round(h * out_short_len / short))
    ow = int(round(w * out_short_len / short))
    return image_resize(input, out_shape=(oh, ow), resample=resample)


def resize_bilinear(input, out_shape=None, scale=None, name=None):
    return image_resize(input, out_shape, scale, "BILINEAR", name)


def resize_nearest(input, out_shape=None, scale=None, name=None):
    return image_resize(input, out_shape, scale, "NEAREST", name)


def grid_sampler(x, grid, name=None):
    helper = LayerHelper("grid_sampler", name=name)
    out = helper.create_variable_for_type_inference(
        x.dtype, (x.shape[0], x.shape[1], grid.shape[1], grid.shape[2]))
    helper.append_op("grid_sampler", {"X": [x], "Grid": [grid]},
                     {"Output": [out]}, {})
    return out


def affine_channel(x, scale=None, bias=None, data_layout="NCHW", name=None):
    helper = LayerHelper("affine_channel", name=name)
    out = helper.create_variable_for_type_inference(x.dtype, x.shape)
    helper.append_op("affine_channel",
                     {"X": [x], "Scale": [scale], "Bias": [bias]},
                     {"Out": [out]}, {"data_layout": data_layout})
    return out


def shuffle_channel(x, group, name=None):
    return _simple("shuffle_channel", "shuffle_channel", x, x.shape,
                   {"group": group})


def bilinear_tensor_product(x, y, size, act=None, param_attr=None,
                            bias_attr=None, name=None):
    helper = LayerHelper("bilinear_tensor_product", name=name, act=act)
    w = helper.create_parameter(param_attr,
                                shape=[size, int(x.shape[-1]), int(y.shape[-1])],
                                dtype=x.dtype)
    out = helper.create_variable_for_type_inference(x.dtype, (x.shape[0], size))
    ins = {"X": [x], "Y": [y], "Weight": [w]}
    b = helper.create_parameter(bias_attr, shape=[size], dtype=x.dtype,
                                is_bias=True)
    if b is not None:
        ins["Bias"] = [b]
    helper.append_op("bilinear_tensor_product", ins, {"Out": [out]}, {})
    return helper.append_activation(out, act)


def isfinite(x, name=None):
    return _simple("isfinite", "isfinite", x, (), dtype="bool",
                   stop_gradient=True)


def cos_sim(X, Y, name=None):
    helper = LayerHelper("cos_sim", name=name)
    out = helper.create_variable_for_type_inference(X.dtype, (X.shape[0], 1))
    xn = helper.create_variable_for_type_inference(X.dtype, (X.shape[0], 1), True)
    yn = helper.create_variable_for_type_inference(X.dtype, (Y.shape[0], 1), True)
    helper.append_op("cos_sim", {"X": [X], "Y": [Y]},
                     {"Out": [out], "XNorm": [xn], "YNorm": [yn]}, {})
    return out


def im2sequence(input, filter_size=1, stride=1, padding=0, name=None):
    helper = LayerHelper("im2sequence", name=name)
    fh, fw = convert_to_list(filter_size, 2, "filter_size")
    sh, sw = convert_to_list(stride, 2, "stride")
    n, c, h, w = input.shape
    oh = (h - fh) // sh + 1 if h > 0 else -1
    ow = (w - fw) // sw + 1 if w > 0 else -1
    out = helper.create_variable_for_type_inference(
        input.dtype, (n, oh * ow if oh > 0 and ow > 0 else -1, c * fh * fw))
    helper.append_op("im2sequence", {"X": [input]}, {"Out": [out]},
                     {"kernels": [fh, fw], "strides": [sh, sw]})
    return out


def lod_reset(x, y=None, target_lod=None):
    """LoD compat no-op: padded arrays carry lengths separately (SURVEY §6)."""
    return x


def unique_with_counts_stub(*a, **k):
    raise NotImplementedError(
        "unique_with_counts has data-dependent output shape; "
        "use fixed-size hashing (layers.hash-style) on TPU")


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------
def scaled_dot_product_attention(queries, keys, values, num_heads=1,
                                 dropout_rate=0.0, mask=None, causal=False,
                                 name=None):
    """ref nets.py:scaled_dot_product_attention. q/k/v: [B, T, D] (heads
    folded in) or [B, H, T, Dh]."""
    helper = LayerHelper("scaled_dot_product_attention", name=name)
    out = helper.create_variable_for_type_inference(queries.dtype, queries.shape)
    wshape = tuple(queries.shape[:-1]) + (keys.shape[-2],)
    w = helper.create_variable_for_type_inference(queries.dtype, wshape, True)
    ins = {"Q": [queries], "K": [keys], "V": [values]}
    if mask is not None:
        ins["Mask"] = [mask]
    helper.append_op("scaled_dot_product_attention", ins,
                     {"Out": [out], "Weights": [w]}, {"causal": causal})
    if dropout_rate:
        out = dropout(out, dropout_rate)
    return out


def flash_attention(q, k, v, attn_bias=None, causal=False, scale=None,
                    use_flash=True, name=None, window=None):
    """The attention itself over heads kept as [B, T, H, Dh] (the
    `bthd` layout of the `flash_attention` op): softmax(q k^T * scale +
    attn_bias) v -> [B, T, H, Dv]. k and v may have fewer heads than q
    (grouped-query attention: H / KVH query heads that follow one another
    share each key-value head). `window` (with `causal`; a sliding
    window, as Mellum 2's `sliding_attention` layers have it): query t
    sees the `window` keys `t - window < s <= t` only, its own among
    them; an attribute of the one op, None by default."""
    if window is not None and (not causal or int(window) < 1):
        raise ValueError("window is a causal band of at least one key: "
                         f"causal={causal}, window={window}")
    helper = LayerHelper("multi_head_attention", name=name)
    out = helper.create_variable_for_type_inference(
        q.dtype, tuple(q.shape[:3]) + (v.shape[3],))
    wshape = (q.shape[0], q.shape[2], q.shape[1], k.shape[1])
    wvar = helper.create_variable_for_type_inference(q.dtype, wshape, True)
    ins = {"Q": [q], "K": [k], "V": [v]}
    if attn_bias is not None:
        ins["Mask"] = [attn_bias]
    helper.append_op("flash_attention" if use_flash else "scaled_dot_product_attention",
                     ins, {"Out": [out], "Weights": [wvar]},
                     {"causal": causal,
                      "scale": scale or int(q.shape[3]) ** -0.5,
                      "layout": "bthd",
                      **({} if window is None else {"window": int(window)})})
    return out


def _packed_flash_attention(q, kv, packed, n_head, attn_bias, causal, name):
    """The flash_attention op over a fused projection as it is: `packed`
    "qkv": q is kv, one [B, T, 3*H*Dh] (q, k, v side by side); "kv": q
    [B, T, H*Dh] and kv [B, S, 2*H*Dh]. -> [B, T, H*Dh]."""
    helper = LayerHelper("multi_head_attention", name=name)
    width = int(kv.shape[2]) // (3 if packed == "qkv" else 2)
    out = helper.create_variable_for_type_inference(
        q.dtype, (q.shape[0], q.shape[1], width))
    wvar = helper.create_variable_for_type_inference(
        q.dtype, (q.shape[0], n_head, q.shape[1], kv.shape[1]), True)
    ins = {"QKV": [q]} if packed == "qkv" else {"Q": [q], "KV": [kv]}
    if attn_bias is not None:
        ins["Mask"] = [attn_bias]
    helper.append_op("flash_attention", ins,
                     {"Out": [out], "Weights": [wvar]},
                     {"causal": causal, "scale": (width // n_head) ** -0.5,
                      "layout": "bthd", "packed": packed, "n_head": n_head})
    return out


def multi_head_attention(queries, keys, values, attn_bias=None, d_key=64,
                         d_value=64, d_model=512, n_head=8, dropout_rate=0.0,
                         causal=False, param_attr=None, name=None,
                         cache=None, use_flash=True, fused_qkv=None):
    """Transformer MHA (ref book machine_translation + nets.py). q/k/v:
    [B, T, d_model]; attn_bias broadcastable to [B, n_head, Tq, Tk].

    fused_qkv: project q/k/v with ONE [d_model, (2*d_key+d_value)*H]
    matmul when queries/keys/values are the same tensor (else a fused
    [d_model, d_key*H + d_value*H] k/v projection when keys is values
    — the cross-attention case): bigger MXU tiles, fewer fusion
    boundaries than three separate [d_model, d_head*H] matmuls.
    With use_flash the projection's output goes to the flash_attention
    op as it is, unsplit (`packed` "qkv": one [B, T, 3*H*Dh] input;
    "kv": q and one [B, S, 2*H*Dh]); the short kernel reads q, k and v
    out of it in place and writes its gradient the same way, so no
    `split` and no pad-and-add of the gradient is left around it.
    Parameter NAMES differ from the unfused layout (one
    `..._qkv`/`..._kv` weight), so checkpoints are not interchangeable
    between the two layouts (models/transformer.py's
    convert_qkv_checkpoint converts) — therefore OPT-IN (default off
    keeps every existing model's names and checkpoints stable); the
    perf paths (bench.py, chip_smoke.py, the benchmark's nmt cell) opt
    in with fused_qkv=True."""
    from . import tensor as _t
    if fused_qkv is None:
        fused_qkv = False
    if fused_qkv and param_attr is not None:
        raise ValueError(
            "fused_qkv shares one weight across q/k/v and cannot honor "
            "an explicit param_attr naming; pass fused_qkv=False")
    packed = None
    if fused_qkv and d_key == d_value and queries is keys \
            and keys is values:
        qkv = fc(queries, 3 * d_key * n_head, num_flatten_dims=2,
                 param_attr=param_attr, bias_attr=False,
                 name=f"{name}_qkv" if name else None)
        if use_flash:
            packed, q, kv = "qkv", qkv, qkv
        else:
            q, k, v = split(qkv, 3, dim=2)
    elif fused_qkv and d_key == d_value and keys is values:
        q = fc(queries, d_key * n_head, num_flatten_dims=2,
               param_attr=param_attr, bias_attr=False,
               name=f"{name}_q" if name else None)
        kv = fc(keys, 2 * d_key * n_head, num_flatten_dims=2,
                param_attr=param_attr, bias_attr=False,
                name=f"{name}_kv" if name else None)
        if use_flash:
            packed = "kv"
        else:
            k, v = split(kv, 2, dim=2)
    else:
        if fused_qkv:
            import warnings
            warnings.warn(
                "fused_qkv=True requested but the fused projection needs "
                "d_key == d_value and q/k/v (or at least k/v) to be the "
                "SAME tensor object"
                f" (got d_key={d_key}, d_value={d_value}, "
                f"queries is keys={queries is keys}, "
                f"keys is values={keys is values}); falling back to the "
                "UNFUSED per-projection weights — parameter names and the "
                "checkpoint layout are the unfused ones",
                stacklevel=2)
        q = fc(queries, d_key * n_head, num_flatten_dims=2,
               param_attr=param_attr, bias_attr=False,
               name=f"{name}_q" if name else None)
        k = fc(keys, d_key * n_head, num_flatten_dims=2,
               param_attr=param_attr, bias_attr=False,
               name=f"{name}_k" if name else None)
        v = fc(values, d_value * n_head, num_flatten_dims=2,
               param_attr=param_attr, bias_attr=False,
               name=f"{name}_v" if name else None)

    if packed is not None:
        out = _packed_flash_attention(q, kv, packed, n_head, attn_bias,
                                      causal, name)
    else:
        # heads stay in [B, T, H, Dh] layout end-to-end: the reshape is
        # free and the attention dots contract with H as a batch dim, so
        # no head split/merge transposes ever materialize (profiled ~1.4
        # ms/step of copies in the bhtd->bhtd layout on the transformer
        # bench)
        q = reshape(q, [0, 0, n_head, d_key])
        k = reshape(k, [0, 0, n_head, d_key])
        v = reshape(v, [0, 0, n_head, d_value])
        out = flash_attention(q, k, v, attn_bias=attn_bias, causal=causal,
                              use_flash=use_flash, name=name)
        out = reshape(out, [0, 0, n_head * d_value])
    if dropout_rate:
        out = dropout(out, dropout_rate,
                      dropout_implementation="upscale_in_train")
    return fc(out, d_model, num_flatten_dims=2, param_attr=param_attr,
              bias_attr=False, name=f"{name}_o" if name else None)


# ---------------------------------------------------------------------------
# decoder-only language-model blocks (models/lfm2_moe.py, solar_open2.py):
# rms_norm, rotary_embedding, short_conv, swiglu, moe_route, moe_expert_ffn,
# and the linear-attention layer's l2_norm, kda_gate, kda_attention
# ---------------------------------------------------------------------------
def rms_norm(input, epsilon=1e-5, param_attr=None, name=None):
    """y = x * rsqrt(mean(x^2, last axis) + epsilon) * w, statistics and
    weight in float32. The last axis is the hidden size, or one head's
    width for the per-head norm of q and k over [B, T, H, Dh]."""
    helper = LayerHelper("rms_norm", name=name)
    w = helper.create_parameter(param_attr, shape=[int(input.shape[-1])],
                                dtype="float32",
                                default_initializer=ConstantInitializer(1.0))
    out = helper.create_variable_for_type_inference(input.dtype, input.shape)
    helper.append_op("rms_norm", {"X": [input], "Scale": [w]}, {"Y": [out]},
                     {"epsilon": epsilon})
    return out


def rotary_embedding(input, theta=10000.0, name=None, rope_type="default",
                     factor=None, original_max_position_embeddings=None,
                     beta_fast=32.0, beta_slow=1.0, attention_factor=None):
    """Rotary positions (rotate-half form) over [B, T, H, Dh]; position t
    is the index along axis 1, pair j turns by t * theta^(-2j/Dh).
    `rope_type="yarn"` (a published `rope_parameters` block's keys, as
    Mellum 2's full-attention layers carry them): the pairs that turn
    more than `beta_fast` times over `original_max_position_embeddings`
    keep that frequency, those that turn less than `beta_slow` times are
    slowed by `factor`, a linear ramp between them (its ends rounded
    outwards), and cos and sin are scaled by `attention_factor`. The
    default call is the plain power law, as it was."""
    attrs = {"theta": float(theta)}
    if rope_type == "yarn":
        if not (factor and original_max_position_embeddings
                and attention_factor):
            raise ValueError("yarn needs factor, attention_factor and "
                             "original_max_position_embeddings")
        attrs.update(
            rope_type="yarn", factor=float(factor),
            original_max_position_embeddings=int(
                original_max_position_embeddings),
            beta_fast=float(beta_fast), beta_slow=float(beta_slow),
            attention_factor=float(attention_factor))
    elif rope_type != "default":
        raise ValueError(f"rope_type is default or yarn, not {rope_type!r}")
    return _same_shape_out(LayerHelper("rotary_embedding", name=name), input,
                           "rotary_embedding", attrs)


def short_conv(input, filter_size=3, param_attr=None, name=None,
               bias_attr=None):
    """Causal depthwise convolution along T of [B, T, C], filter [C,
    filter_size]: out[t] = sum_j w[:, j] * x[t - (K-1) + j], plus a bias
    [C] (`<name>.b_0`, zero to start) where `bias_attr` asks for one
    (a ParamAttr or True); with none, no bias."""
    helper = LayerHelper("short_conv", name=name)
    C = int(input.shape[-1])
    w = helper.create_parameter(param_attr, shape=[C, int(filter_size)],
                                dtype=input.dtype)
    extra = {"Filter": [w]}
    if bias_attr is not None and bias_attr is not False:
        extra["Bias"] = [helper.create_parameter(
            bias_attr if bias_attr is not True else None, [C], input.dtype,
            is_bias=True)]
    return _same_shape_out(helper, input, "short_conv", extra_inputs=extra)


def swiglu(x, y, name=None):
    """silu(x) * y, the gate of a gated linear unit."""
    return _same_shape_out(LayerHelper("swiglu", name=name), x, "swiglu",
                           extra_inputs={"Y": [y]})


def moe_route(input, num_experts, k, use_expert_bias=True,
              norm_topk_prob=True, routed_scaling_factor=1.0,
              param_attr=None, name=None, scoring="sigmoid"):
    """The router of a sparse expert layer over [..., H]: sigmoid scores
    of ALL `num_experts` (`scoring="softmax"`: their softmax over all of
    them, as Mellum 2's router), the k experts chosen by score + bias,
    their weights the chosen scores renormalised and scaled; float32
    throughout. Returns (topk_idx [..., k] int32, topk_w [..., k]
    float32). The bias is a persistable variable that is no Parameter
    (it only selects; no gradient, no optimizer state), `<name>.bias`,
    zero until something sets it."""
    if scoring not in ("sigmoid", "softmax"):
        raise ValueError(f"scoring is sigmoid or softmax, not {scoring!r}")
    helper = LayerHelper("moe_route", name=name)
    w = helper.create_parameter(
        param_attr, shape=[int(input.shape[-1]), int(num_experts)],
        dtype="float32", default_initializer=NormalInitializer(0.0, 0.02))
    ins = {"X": [input], "Weight": [w]}
    if use_expert_bias:
        bias = helper.create_global_variable(
            [int(num_experts)], "float32", persistable=True,
            name=f"{helper.name}.bias")
        helper.set_variable_initializer(bias, ConstantInitializer(0.0))
        ins["Bias"] = [bias]
    lead = tuple(input.shape[:-1])
    idx = helper.create_variable_for_type_inference("int32", lead + (k,),
                                                    True)
    tw = helper.create_variable_for_type_inference("float32", lead + (k,))
    helper.append_op("moe_route", ins, {"TopkIdx": [idx], "TopkW": [tw]},
                     {"k": int(k), "norm_topk_prob": bool(norm_topk_prob),
                      "routed_scaling_factor": float(routed_scaling_factor),
                      **({} if scoring == "sigmoid"
                         else {"scoring": scoring})})
    return idx, tw


def moe_expert_ffn(input, topk_idx, topk_w, experts_held, first_expert,
                   intermediate_size, param_attr=None, name=None):
    """The expert layer's part of THIS chip: experts `first_expert ..
    first_expert + experts_held - 1` of a layer routed over all the
    model's experts. out[n] = sum over the chosen experts of token n that
    are held here of topk_w * (silu(x W1[e]) * (x W3[e])) W2[e]; what the
    absent experts would add is left out, and no token is dropped.
    Returns (out, local_pairs, max_expert_pairs): the (token, expert)
    pairs computed here and the fullest held expert's, int32 scalars."""
    helper = LayerHelper("moe_expert_ffn", name=name)
    H, F, E = int(input.shape[-1]), int(intermediate_size), int(experts_held)
    init = NormalInitializer(0.0, 0.02)
    w1 = helper.create_parameter(_sub_attr(param_attr, "w1"), [E, H, F],
                                 input.dtype, default_initializer=init)
    w3 = helper.create_parameter(_sub_attr(param_attr, "w3"), [E, H, F],
                                 input.dtype, default_initializer=init)
    w2 = helper.create_parameter(_sub_attr(param_attr, "w2"), [E, F, H],
                                 input.dtype, default_initializer=init)
    out = helper.create_variable_for_type_inference(input.dtype, input.shape)
    pairs = helper.create_variable_for_type_inference("int32", (), True)
    fullest = helper.create_variable_for_type_inference("int32", (), True)
    helper.append_op(
        "moe_expert_ffn",
        {"X": [input], "TopkIdx": [topk_idx], "TopkW": [topk_w],
         "W1": [w1], "W3": [w3], "W2": [w2]},
        {"Out": [out], "LocalPairs": [pairs], "MaxExpertPairs": [fullest]},
        {"first_expert": int(first_expert)})
    return out, pairs, fullest


def l2_norm(input, epsilon=1e-6, name=None):
    """x / sqrt(sum(x^2, last axis) + epsilon), statistics in float32: the
    norm a linear-attention layer puts on each head's q and k."""
    return _same_shape_out(LayerHelper("l2_norm", name=name), input,
                           "l2_norm", {"epsilon": float(epsilon)})


def kda_gate(input, a_log_attr=None, dt_bias_attr=None, name=None):
    """The log-decay of a gated delta-rule layer over [B, T, H, D]:
    g = -exp(A_log) * softplus(x + dt_bias), <= 0, one per key channel;
    `A_log` [H] (`<name>.w_0`) and `dt_bias` [H, D] (`<name>.w_1`) are
    float32 parameters and so is g, whatever the program is cast to.
    Defaults: A_log = log 8 (the middle of the public U(1, 16)), dt_bias
    the inverse softplus of a step of 0.01."""
    helper = LayerHelper("kda_gate", name=name)
    H, D = int(input.shape[2]), int(input.shape[3])
    a_log = helper.create_parameter(
        a_log_attr, [H], "float32",
        default_initializer=ConstantInitializer(float(np.log(8.0))))
    dt_bias = helper.create_parameter(
        dt_bias_attr, [H, D], "float32",
        default_initializer=ConstantInitializer(
            float(np.log(np.expm1(0.01)))))
    out = helper.create_variable_for_type_inference("float32", input.shape)
    helper.append_op("kda_gate",
                     {"X": [input], "ALog": [a_log], "DtBias": [dt_bias]},
                     {"Out": [out]})
    return out


def kda_attention(q, k, v, g, beta, scale=None, name=None):
    """Gated delta-rule linear attention with a per-channel decay (Kimi
    Delta Attention) over heads kept as [B, T, H, D]: per head a float32
    state S [Dk, Dv], zero before the sequence,

        S_t = (I - beta_t k_t k_t^T) diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T
        o_t = S_t^T q_t * scale           (scale: Dk ** -0.5)

    g [B, T, H, Dk] is the log-decay (`kda_gate`), beta [B, T, H] the
    step. One op, run in chunks of 64 tokens (ops/kernels_scan.py): on a
    TPU at Dk, Dv multiples of 128 the Mosaic kernels of
    ops/pallas/kda.py with their own backward, elsewhere a jnp
    composition differentiated by the tracer; -> [B, T, H, Dv] in q's
    dtype."""
    helper = LayerHelper("kda_attention", name=name)
    out = helper.create_variable_for_type_inference(
        q.dtype, tuple(q.shape[:3]) + (v.shape[3],))
    helper.append_op("kda_attention",
                     {"Q": [q], "K": [k], "V": [v], "G": [g],
                      "Beta": [beta]}, {"Out": [out]},
                     {"scale": scale or int(q.shape[3]) ** -0.5})
    return out


def selective_scan(x, dt, A_log, B, C, D, name=None):
    """The diagonal selective scan of a Mamba-1 mixer over x, dt [B, T,
    Ch] (dt already the softplus'd step) and B, C [B, T, N]: per channel
    c and state n a float32 state, zero before the sequence,

        h_t[c, n] = exp(dt_t[c] A[c, n]) h_{t-1}[c, n] + dt_t[c] B_t[n] x_t[c]
        y_t[c] = sum_n C_t[n] h_t[c, n] + D[c] x_t[c],  A = -exp(A_log)

    `A_log` [Ch, N] (`<name>.w_0`) and `D` [Ch] (`<name>.w_1`) are the
    ParamAttrs of two float32 parameters, whatever the program is cast to;
    None gives Mamba's initialisation, A_log = log(1 .. N) on every channel
    and D = 1. One op (ops/kernels_scan.py): on a TPU at Ch a multiple of
    128 the Mosaic kernels of ops/pallas/selective_scan.py with their own
    backward, elsewhere a chunked jnp composition differentiated by the
    tracer; -> y [B, T, Ch] in x's dtype."""
    helper = LayerHelper("selective_scan", name=name)
    Ch, N = int(x.shape[2]), int(B.shape[2])
    a_log = helper.create_parameter(
        A_log, [Ch, N], "float32",
        default_initializer=NumpyArrayInitializer(np.log(np.broadcast_to(
            np.arange(1, N + 1, dtype="float32"), (Ch, N)))))
    d = helper.create_parameter(D, [Ch], "float32",
                                default_initializer=ConstantInitializer(1.0))
    out = helper.create_variable_for_type_inference(x.dtype, x.shape)
    helper.append_op("selective_scan",
                     {"X": [x], "Dt": [dt], "ALog": [a_log], "B": [B],
                      "C": [C], "D": [d]}, {"Out": [out]})
    return out


def add_position_encoding(input, alpha=1.0, beta=1.0, name=None):
    return _simple("add_position_encoding", "add_position_encoding", input,
                   input.shape, {"alpha": alpha, "beta": beta})
