"""Symbolic-composition hooks: which tensor args an op exposes under given
attrs, and backward shape inference for parameter variables.

Reference parity: OperatorProperty::ListArguments (e.g. `no_bias` removes
"bias" — src/operator/fully_connected-inl.h) and InferShape's backward
direction (weight shapes derived from data shape), which is what lets
``Symbol.simple_bind`` allocate parameters from just the data shape.
"""
from __future__ import annotations

from .registry import set_arg_select, set_param_shapes


def _prod(xs):
    out = 1
    for x in xs:
        out *= int(x)
    return out


def _pair(v, n):
    if isinstance(v, (tuple, list)):
        return tuple(int(x) for x in v)
    return (int(v),) * n


# -- FullyConnected ---------------------------------------------------------

set_arg_select("FullyConnected", lambda a: (
    ("data", "weight") if a.get("no_bias") else ("data", "weight", "bias")))


def _fc_shapes(shapes, attrs):
    data = shapes[0]
    nh = int(attrs.get("num_hidden", 0))
    if data is None:
        return shapes
    in_dim = _prod(data[1:]) if attrs.get("flatten", True) else data[-1]
    out = list(shapes)
    if len(out) > 1 and out[1] is None:
        out[1] = (nh, in_dim)
    if len(out) > 2 and out[2] is None:
        out[2] = (nh,)
    return out


set_param_shapes("FullyConnected", _fc_shapes)


# -- Convolution / Deconvolution -------------------------------------------

set_arg_select("Convolution", lambda a: (
    ("data", "weight") if a.get("no_bias") else ("data", "weight", "bias")))
set_arg_select("Deconvolution", lambda a: (
    ("data", "weight") if a.get("no_bias", True)
    else ("data", "weight", "bias")))


def _conv_shapes(shapes, attrs):
    data = shapes[0]
    if data is None:
        return shapes
    kernel = tuple(int(k) for k in attrs.get("kernel", ()))
    nf = int(attrs.get("num_filter", 0))
    ng = int(attrs.get("num_group", 1))
    out = list(shapes)
    if len(out) > 1 and out[1] is None:
        out[1] = (nf, data[1] // ng) + kernel
    if len(out) > 2 and out[2] is None:
        out[2] = (nf,)
    return out


set_param_shapes("Convolution", _conv_shapes)


def _deconv_shapes(shapes, attrs):
    data = shapes[0]
    if data is None:
        return shapes
    kernel = tuple(int(k) for k in attrs.get("kernel", ()))
    nf = int(attrs.get("num_filter", 0))
    ng = int(attrs.get("num_group", 1))
    out = list(shapes)
    if len(out) > 1 and out[1] is None:
        # reference layout: (in_channels, num_filter/g, kh, kw)
        out[1] = (data[1], nf // ng) + kernel
    if len(out) > 2 and out[2] is None:
        out[2] = (nf,)
    return out


set_param_shapes("Deconvolution", _deconv_shapes)


# -- Norm layers ------------------------------------------------------------

def _bn_shapes(shapes, attrs):
    data = shapes[0]
    if data is None:
        return shapes
    axis = int(attrs.get("axis", 1)) % len(data)
    c = (data[axis],)
    return [data] + [c if s is None else s for s in shapes[1:]]


set_param_shapes("BatchNorm", _bn_shapes)
set_param_shapes("InstanceNorm", _bn_shapes)


def _ln_shapes(shapes, attrs):
    data = shapes[0]
    if data is None:
        return shapes
    axis = int(attrs.get("axis", -1)) % len(data)
    c = (data[axis],)
    return [data] + [c if s is None else s for s in shapes[1:]]


set_param_shapes("LayerNorm", _ln_shapes)
set_arg_select("LayerNorm", lambda a: (
    ("data", "gamma") if str(a.get("no_bias", False)) in
    ("True", "true", "1") else ("data", "gamma", "beta")))


# -- Embedding --------------------------------------------------------------

def _embedding_shapes(shapes, attrs):
    out = list(shapes)
    if len(out) > 1 and out[1] is None:
        out[1] = (int(attrs.get("input_dim", 0)),
                  int(attrs.get("output_dim", 0)))
    return out


set_param_shapes("Embedding", _embedding_shapes)


# -- LeakyReLU (gamma only for prelu) ---------------------------------------

set_arg_select("LeakyReLU", lambda a: (
    ("data", "gamma") if a.get("act_type") == "prelu" else ("data",)))


def _prelu_shapes(shapes, attrs):
    data = shapes[0]
    out = list(shapes)
    if len(out) > 1 and out[1] is None and data is not None:
        out[1] = (data[1] if len(data) > 1 else 1,)
    return out


set_param_shapes("LeakyReLU", _prelu_shapes)


# -- DeformableConvolution: weight/bias from data like Convolution ----------

set_arg_select("_contrib_DeformableConvolution", lambda a: (
    ("data", "offset", "weight") if a.get("no_bias")
    else ("data", "offset", "weight", "bias")))


def _deform_conv_shapes(shapes, attrs):
    data = shapes[0]
    if data is None:
        return shapes
    kernel = tuple(int(k) for k in attrs.get("kernel", ()))
    nf = int(attrs.get("num_filter", 0))
    ng = int(attrs.get("num_group", 1))
    out = list(shapes)
    if len(out) > 2 and out[2] is None:
        out[2] = (nf, data[1] // ng) + kernel
    if len(out) > 3 and out[3] is None:
        out[3] = (nf,)
    return out


set_param_shapes("_contrib_DeformableConvolution", _deform_conv_shapes)

set_arg_select("_contrib_DeformablePSROIPooling", lambda a: (
    ("data", "rois") if a.get("no_trans")
    else ("data", "rois", "trans")))


# -- RNN (fused): parameters blob + state shapes from data ------------------
# (reference: rnn-inl.h RNNProp::InferShape — param size is a function of
# input size, state size, layers, directions)

set_arg_select("RNN", lambda a: (
    ("data", "parameters", "state", "state_cell")
    if a.get("mode", "lstm") == "lstm"
    else ("data", "parameters", "state")))


def _rnn_shapes(shapes, attrs):
    data = shapes[0]
    if data is None:
        return shapes
    from .rnn_op import rnn_param_size
    mode = attrs.get("mode", "lstm")
    h = int(attrs.get("state_size", 0))
    layers = int(attrs.get("num_layers", 1))
    dirs = 2 if attrs.get("bidirectional") else 1
    out = list(shapes)
    if len(out) > 1 and out[1] is None:
        out[1] = (rnn_param_size(mode, int(data[2]), h, layers,
                                 attrs.get("bidirectional", False)),)
    state_shape = (layers * dirs, int(data[1]), h)
    for i in (2, 3):
        if len(out) > i and out[i] is None:
            out[i] = state_shape
    return out


set_param_shapes("RNN", _rnn_shapes)


# -- Sequence ops: sequence_length only when enabled ------------------------

for _name in ("SequenceMask", "SequenceLast", "SequenceReverse"):
    set_arg_select(_name, lambda a: (
        ("data", "sequence_length") if a.get("use_sequence_length")
        else ("data",)))


# -- output/loss ops: label shape from data shape ---------------------------
# (reference: SoftmaxOutputProp::InferShape — label = data shape minus the
# class axis; regression outputs use label with data's shape)

def _softmax_label_shapes(shapes, attrs):
    data = shapes[0]
    out = list(shapes)
    if data is not None and len(out) > 1 and out[1] is None:
        if attrs.get("multi_output"):
            out[1] = (data[0],) + tuple(data[2:])
        elif attrs.get("preserve_shape"):
            out[1] = tuple(data[:-1])
        else:
            out[1] = (data[0],) if len(data) <= 2 else tuple(data[:-1])
    return out


set_param_shapes("SoftmaxOutput", _softmax_label_shapes)
set_param_shapes("SVMOutput", _softmax_label_shapes)


def _regression_label_shapes(shapes, attrs):
    data = shapes[0]
    out = list(shapes)
    if data is not None and len(out) > 1 and out[1] is None:
        out[1] = tuple(data)
    return out


for _name in ("LinearRegressionOutput", "MAERegressionOutput",
              "LogisticRegressionOutput"):
    set_param_shapes(_name, _regression_label_shapes)


# -- CachedAttention (decode KV caches sized by the max_len attr) -----------

def _cached_attention_shapes(shapes, attrs):
    q = shapes[0]
    k = shapes[1] if len(shapes) > 1 else None
    out = list(shapes)
    tmax = int(attrs.get("max_len", 0))
    if q is not None and tmax:
        # token-contiguous rows (ops/attention.py::cached_attention):
        # (B, max_len, Hkv*hd). The head count follows the KEY
        # projection, not the query — under grouped-query attention
        # Hkv < H and the cache stores only the kv heads
        heads = k[1] if k is not None else q[1]
        cache = (q[0], tmax, heads * q[3])
        if len(out) > 3 and out[3] is None:
            out[3] = cache
        if len(out) > 4 and out[4] is None:
            out[4] = cache
    if len(out) > 5 and out[5] is None:
        out[5] = (1,)
    return out


set_param_shapes("_contrib_CachedAttention", _cached_attention_shapes)


# -- QuantizedFullyConnected ------------------------------------------------

set_arg_select("_contrib_QuantizedFullyConnected", lambda a: (
    ("data", "weight", "scale") if str(a.get("no_bias", False)) in
    ("True", "true", "1") else ("data", "weight", "scale", "bias")))


def _quant_fc_shapes(shapes, attrs):
    # data/weight/bias follow FullyConnected's rule; the extra scale
    # slot (index 2) is (num_hidden,)
    fc = _fc_shapes([shapes[0], shapes[1],
                     shapes[3] if len(shapes) > 3 else None], attrs)
    out = list(shapes)
    if len(out) > 1 and out[1] is None:
        out[1] = fc[1]
    if len(out) > 2 and out[2] is None and int(attrs.get(
            "num_hidden", 0)):
        out[2] = (int(attrs["num_hidden"]),)
    if len(out) > 3 and out[3] is None and len(fc) > 2:
        out[3] = fc[2]
    return out


set_param_shapes("_contrib_QuantizedFullyConnected", _quant_fc_shapes)


def _quant_embedding_shapes(shapes, attrs):
    out = list(shapes)
    vd = (int(attrs.get("input_dim", 0)), int(attrs.get("output_dim",
                                                        0)))
    if len(out) > 1 and out[1] is None:
        out[1] = vd
    if len(out) > 2 and out[2] is None:
        out[2] = (vd[0],)
    return out


set_param_shapes("_contrib_QuantizedEmbedding", _quant_embedding_shapes)


set_param_shapes("_contrib_RollingCachedAttention",
                 _cached_attention_shapes)


def _cached_attention_q8_shapes(shapes, attrs):
    """Int8 variant: slots 3/4 are the int8 caches, 5/6 the per-token
    (B, Tmax, Hkv) scale caches, 7 the pos scalar. NOTE on dtypes:
    infer_type's same-dtype propagation cannot express the int8/f32
    aux split — Generator._fresh_aux (the supported allocator for this
    op) creates them by suffix; Executor-bound users must supply aux
    explicitly."""
    q = shapes[0]
    k = shapes[1] if len(shapes) > 1 else None
    out = list(shapes)
    tmax = int(attrs.get("max_len", 0))
    if q is not None and tmax:
        heads = k[1] if k is not None else q[1]
        for i in (3, 4):
            if len(out) > i and out[i] is None:
                out[i] = (q[0], tmax, heads * q[3])
        for i in (5, 6):
            if len(out) > i and out[i] is None:
                out[i] = (q[0], tmax, heads)
    if len(out) > 7 and out[7] is None:
        out[7] = (1,)
    return out


set_param_shapes("_contrib_CachedAttentionQ8",
                 _cached_attention_q8_shapes)


# -- SSMCached (O(1) decode state — a fixed blob, no length axis) -----------

def _ssm_cached_shapes(shapes, attrs):
    """Slot 4 is the (B, H, hd, hd) recurrent state — sized entirely
    from the query projection; max_len never appears (THE point of the
    op). Slot 5 is the pos scalar, accepted for cached-attention attr
    parity and ignored by the op."""
    q = shapes[0]
    out = list(shapes)
    if q is not None and len(out) > 4 and out[4] is None:
        out[4] = (q[0], q[1], q[3], q[3])
    if len(out) > 5 and out[5] is None:
        out[5] = (1,)
    return out


set_param_shapes("_contrib_SSMCached", _ssm_cached_shapes)


# -- Mamba2Cached (two carried states, neither with a length
# axis), ShortConvCached (one such state) and the RMS norms ------------------

def _mamba2_shapes(shapes, attrs):
    """Everything is sized from xbc (B, T, conv_dim) and the attrs:
    slots 2-6 the per-channel and per-head parameters, slot 7 the
    convolution window, slot 8 the scan state, slot 9 the ignored pos."""
    xbc = shapes[0]
    if xbc is None:
        return shapes
    H, P, N, K = (int(attrs.get(k, 0)) for k in
                  ("num_heads", "head_dim", "d_state", "d_conv"))
    want = [xbc, (xbc[0], xbc[1], H), (xbc[2], K), (xbc[2],), (H,),
            (H,), (H,), (xbc[0], K - 1, xbc[2]), (xbc[0], H, P, N),
            (1,)]
    return [w if s is None else s for s, w in zip(shapes, want)]


set_param_shapes("_contrib_Mamba2Cached", _mamba2_shapes)


def _short_conv_shapes(shapes, attrs):
    """Everything is sized from data (B, T, D) and d_conv: the two
    projections as FullyConnected holds them, the taps, the window of
    d_conv-1 gated rows, the ignored pos."""
    data = shapes[0]
    if data is None:
        return shapes
    D, K = data[2], int(attrs.get("d_conv", 3))
    want = [data, (3 * D, D), (D, K), (D, D), (data[0], K - 1, D), (1,)]
    return [w if s is None else s for s, w in zip(shapes, want)]


set_param_shapes("_contrib_ShortConvCached", _short_conv_shapes)


def _latent_select_shapes(shapes, attrs):
    """Everything is sized from data (B, T, D) and the attrs
    (ops/mla.py): the twelve weights as FullyConnected holds them, the
    latent rows and the index-key rows of max_len positions, pos."""
    data = shapes[0]
    if data is None:
        return shapes
    B, T, D = data
    a = {k: int(attrs.get(k, 0)) for k in (
        "num_heads", "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
        "qk_rope_head_dim", "v_head_dim", "index_heads",
        "index_head_dim", "max_len")}
    H, Lq, L, rd = (a["num_heads"], a["q_lora_rank"], a["kv_lora_rank"],
                    a["qk_rope_head_dim"])
    J, Di, C = a["index_heads"], a["index_head_dim"], a["max_len"]
    want = [data, (T,), (Lq, D), (Lq,),
            (H * (a["qk_nope_head_dim"] + rd), Lq), (L + rd, D), (L,),
            (H * (a["qk_nope_head_dim"] + a["v_head_dim"]), L),
            (D, H * a["v_head_dim"]), (J * Di, Lq), (Di, D), (Di,),
            (Di,), (J, D), (B, C, L + rd), (B, C, Di), (1,)]
    return [w if s is None else s for s, w in zip(shapes, want)]


set_param_shapes("_contrib_LatentSelectAttention",
                 _latent_select_shapes)


def _rms_shapes(shapes, attrs):
    data = shapes[0]
    if data is None:
        return shapes
    out = [data if s is None else s for s in shapes[:-1]]
    return out + [(data[-1],) if shapes[-1] is None else shapes[-1]]


set_param_shapes("RMSNorm", _rms_shapes)
set_param_shapes("_contrib_GatedRMSNorm", _rms_shapes)
