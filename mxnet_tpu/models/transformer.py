"""Decoder-only transformer language model — the long-context flagship.

A NEW model family beyond the 2017 reference (whose sequence stack was
LSTM+bucketing): pre-norm GPT-style decoder built from the symbolic op
catalog, with attention lowered to the Pallas flash kernel
(ops/attention.py) and sequence parallelism available through
parallel.ring for contexts beyond one chip's HBM.

The symbol trains through every framework surface: Module.fit, the
compiled SPMD TrainStep (dp/tp mesh, bf16 compute), and the
predictor/AOT export path. Variable-length corpora bucket over seq_len
exactly like the LSTM toolkit (one jit specialization per bucket).
"""
from __future__ import annotations

from .. import symbol as sym


def _fc(x, num_hidden, name, quantized=False, no_bias=False):
    """FullyConnected or its weight-only-int8 twin. Same "<name>_weight"
    binding; the quantized form adds "<name>_scale" (per-out-channel)
    and keeps the f32 bias. Decode-side only — training always uses the
    float op. no_bias drops "<name>_bias" from the argument list."""
    kw = {"no_bias": True} if no_bias else {}
    if quantized:
        return sym.contrib.QuantizedFullyConnected(
            x, num_hidden=num_hidden, flatten=False, name=name, **kw)
    return sym.FullyConnected(x, num_hidden=num_hidden, flatten=False,
                              name=name, **kw)


def _qkv_heads(x, num_heads, dim, prefix, quantized=False,
               num_kv_heads=None, no_bias=False, head_dim=None,
               qk_norm_eps=None):
    """Shared qkv projection + head split: (B, T, C) -> q (B, H, T, hd)
    and k/v (B, Hkv, T, hd). The training and decode attention blocks
    both use this so their parameter packing can never drift (a repack
    would still bind the same "<prefix>qkv" weights and silently
    corrupt decode otherwise).

    num_kv_heads < num_heads is grouped-query attention (GQA): the
    projection shrinks to (H + 2*Hkv)*hd and the decode KV cache
    stores only Hkv heads — the modern serving memory/bandwidth
    saver. The packing layout [q | k | v] along the output dim equals
    the historical fused-3C layout when Hkv == H, so existing
    checkpoints bind unchanged.

    head_dim: a head size the model states apart from dim / num_heads
    (the q block is then H*hd wide, not dim). qk_norm_eps: when given,
    every head of q and of k is RMS-normalised over its own hd
    channels with one learned gain each ("<prefix>q_norm_gamma",
    "<prefix>k_norm_gamma", (hd,)), before any rotation."""
    Hkv = int(num_kv_heads or num_heads)
    head_dim = int(head_dim or dim // num_heads)
    q_dim = num_heads * head_dim
    kv_dim = Hkv * head_dim
    qkv = _fc(x, q_dim + 2 * kv_dim, prefix + "qkv", quantized, no_bias)

    def cut(begin, end, heads):
        part = sym.slice_axis(qkv, axis=2, begin=begin, end=end)
        part = sym.reshape(part, shape=(0, 0, heads, head_dim))
        return sym.transpose(part, axes=(0, 2, 1, 3))  # (B, H, T, hd)

    q = cut(0, q_dim, num_heads)
    k = cut(q_dim, q_dim + kv_dim, Hkv)
    if qk_norm_eps is not None:
        q = sym.RMSNorm(q, eps=qk_norm_eps, name=prefix + "q_norm")
        k = sym.RMSNorm(k, eps=qk_norm_eps, name=prefix + "k_norm")
    return q, k, cut(q_dim + kv_dim, q_dim + 2 * kv_dim, Hkv)


def _merge_heads_proj(att, dim, prefix, quantized=False,
                      no_bias=False):
    """(B, H, T, hd) attention output -> (B, T, C) through the shared
    output projection."""
    att = sym.transpose(att, axes=(0, 2, 1, 3))       # (B, T, H, hd)
    att = sym.reshape(att, shape=(0, 0, -3))          # (B, T, C)
    return _fc(att, dim, prefix + "proj", quantized, no_bias)


def _attention_block(x, num_heads, dim, prefix, seq_axis=None,
                     rope_positions=None, window=0, num_kv_heads=None):
    """x: (B, T, C) -> (B, T, C); causal flash attention (ring
    attention over ``seq_axis`` when the graph lowers on a mesh
    carrying that axis). rope_positions: (T,) position-id symbol —
    when given, q/k rotate (RoPE) instead of the model using a learned
    position table."""
    q, k, v = _qkv_heads(x, num_heads, dim, prefix,
                         num_kv_heads=num_kv_heads)
    if rope_positions is not None:
        q = sym.contrib.RoPE(q, rope_positions)
        k = sym.contrib.RoPE(k, rope_positions)
    att = sym.contrib.FlashAttention(q, k, v,
                                     causal=True, seq_axis=seq_axis,
                                     window=window,
                                     name=prefix + "attn")
    return _merge_heads_proj(att, dim, prefix)


def _ssm_qkvg(x, num_heads, dim, prefix, quantized=False):
    """Fused q/k/v/gate projection for the SSM block: (B, T, C) ->
    q/k/v (B, H, T, hd) plus a per-head per-token decay-gate logit
    (B, H, T). One FullyConnected of width 3*dim + num_heads named
    "<prefix>qkvg" — shared by the training and decode forms so their
    parameter packing can never drift (the qkv-packing rule of
    _qkv_heads, extended by the H gate columns at the end)."""
    head_dim = dim // num_heads
    qkvg = _fc(x, 3 * dim + num_heads, prefix + "qkvg", quantized)

    def cut(begin, end):
        part = sym.slice_axis(qkvg, axis=2, begin=begin, end=end)
        part = sym.reshape(part, shape=(0, 0, num_heads, head_dim))
        return sym.transpose(part, axes=(0, 2, 1, 3))  # (B, H, T, hd)

    gate = sym.slice_axis(qkvg, axis=2, begin=3 * dim,
                          end=3 * dim + num_heads)      # (B, T, H)
    gate = sym.transpose(gate, axes=(0, 2, 1))          # (B, H, T)
    return (cut(0, dim), cut(dim, 2 * dim), cut(2 * dim, 3 * dim),
            gate)


def _ssm_block(x, num_heads, dim, prefix):
    """x: (B, T, C) -> (B, T, C); gated linear-attention (SSM) block —
    the chunked-scan TRAINING form (ops/ssm.py). No positions enter:
    the recurrence is ordered by construction, so the block composes
    with either pos_encoding (learned adds at the embedding; rope
    rotates only the attention layers of a mixed stack)."""
    q, k, v, g = _ssm_qkvg(x, num_heads, dim, prefix)
    out = sym.contrib.SSMScan(q, k, v, g, name=prefix + "ssm")
    return _merge_heads_proj(out, dim, prefix)


def _ffn_block(x, dim, hidden, prefix, quantized=False, kind="relu",
               no_bias=False):
    """The dense FFN. kind "relu": fc2(relu(fc1 x)); "relu2": the
    ReLU squared. kind "gated_silu": fc1 is twice as wide and holds
    [gate | up]; fc2(silu(gate) * up) — same two parameter names, so
    all kinds bind by one rule."""
    if kind == "gated_silu":
        gu = _fc(x, 2 * hidden, prefix + "fc1", quantized, no_bias)
        g = sym.slice_axis(gu, axis=2, begin=0, end=hidden)
        u = sym.slice_axis(gu, axis=2, begin=hidden, end=2 * hidden)
        h = sym.Activation(g, act_type="silu") * u
    elif kind in ("relu", "relu2"):
        h = _fc(x, hidden, prefix + "fc1", quantized, no_bias)
        h = sym.Activation(h, act_type="relu")
        if kind == "relu2":
            h = sym.square(h)
    else:
        raise ValueError("ffn must be 'relu', 'relu2' or 'gated_silu', "
                         "got %r" % (kind,))
    return _fc(h, dim, prefix + "fc2", quantized, no_bias)


def _norm(x, name, kind="layer", eps=1e-5):
    """The block's norm by kind: "layer" (LayerNorm, "<name>_gamma" and
    "<name>_beta"), "layer_gain" (LayerNorm with a gain and no bias:
    "<name>_gamma" alone) or "rms" (RMSNorm, "<name>_gamma" alone)."""
    if kind == "rms":
        return sym.RMSNorm(x, eps=eps, name=name)
    if kind == "layer_gain":
        return sym.LayerNorm(x, eps=eps, name=name, no_bias=True)
    if kind != "layer":
        raise ValueError("norm must be 'layer', 'layer_gain' or 'rms', "
                         "got %r" % (kind,))
    return sym.LayerNorm(x, eps=eps, name=name)


def _moe_block(x, dim, hidden, num_experts, prefix, expert_axis=None,
               capacity_factor=1.25):
    """Switch-style MoE FFN (the residual around it lives in the layer
    loop, so capacity-dropped tokens pass through unchanged).

    The 3D expert weights carry explicit per-expert Xavier bounds:
    suffix-dispatched Xavier would read (E, D, H) as a conv kernel and
    scale by the D*H "receptive field" — ~sqrt(hidden) too small."""
    from .. import initializer as init_mod

    def xavier(fan_in, fan_out):
        return init_mod.Uniform(scale=(6.0 / (fan_in + fan_out)) ** 0.5)

    gate = sym.Variable(prefix + "gate_weight", shape=(dim, num_experts))
    w1 = sym.Variable(prefix + "experts_w1_weight",
                      shape=(num_experts, dim, hidden),
                      init=xavier(dim, hidden))
    w2 = sym.Variable(prefix + "experts_w2_weight",
                      shape=(num_experts, hidden, dim),
                      init=xavier(hidden, dim))
    return sym.contrib.MoEFFN(x, gate, w1, w2, expert_axis=expert_axis,
                              capacity_factor=capacity_factor,
                              name=prefix + "moe")


def _routed_block(x, dim, hidden, num_experts, prefix, top_k=1,
                  kind="relu", renormalize=False, scoring="softmax",
                  scale=1.0, held=None, latent=0, shared_hidden=0,
                  renorm_eps=None):
    """The expert layer as it is served (_contrib_RoutedExperts): the
    top_k experts by float32 score, every routed (token, expert) pair
    computed and nothing dropped. Binds the parameter names of
    _moe_block, so a Switch checkpoint (top_k 1, "relu") decodes
    through it; kind "gated_silu" makes experts_w1 twice as wide,
    [gate | up], by the rule of _ffn_block's fc1. scoring "sigmoid"
    adds "<prefix>gate_score_bias" (E,), which chooses and does not
    weigh, and renorm_eps is what its renormalisation adds to the
    sum it divides by, where the model states one (route_topk's
    default otherwise); scale multiplies the weights. held=(first,
    count): the
    experts this chip holds of the num_experts routed over (the
    expert arrays have `count` rows). latent=Z: the experts live in Z
    channels between "<prefix>latent_down_weight" (dim, Z) and
    "<prefix>latent_up_weight" (Z, dim). shared_hidden=Hs: a shared
    expert "<prefix>shared_w1_weight" (dim, Hs; (dim, 2 Hs) = [gate |
    up] for "gated_silu"), "<prefix>shared_w2_weight" (Hs, dim), added
    whole (m experts of width h whose outputs are averaged are ONE of
    width m h, their gates, ups and downs side by side, the downs
    times 1 / m: the loader's). Returns (y, stats): the layer's output
    and its int32 counts."""
    if kind not in ("relu", "relu2", "gated_silu"):
        raise ValueError("ffn must be 'relu', 'relu2' or 'gated_silu', "
                         "got %r" % (kind,))
    first, count = held or (0, num_experts)
    inner = int(latent) or dim
    wide = 2 * hidden if kind == "gated_silu" else hidden
    gate = sym.Variable(prefix + "gate_weight", shape=(dim, num_experts))
    w1 = sym.Variable(prefix + "experts_w1_weight",
                      shape=(count, inner, wide))
    w2 = sym.Variable(prefix + "experts_w2_weight",
                      shape=(count, hidden, inner))
    more, attrs = [], {}
    if scoring != "softmax":
        attrs["scoring"] = scoring
        more.append(sym.Variable(prefix + "gate_score_bias",
                                 shape=(num_experts,)))
    if scale != 1.0:
        attrs["scale"] = float(scale)
    if renorm_eps is not None:
        attrs["renorm_eps"] = float(renorm_eps)
    if first:
        attrs["first_expert"] = int(first)
    if latent:
        attrs["latent"] = True
        more += [sym.Variable(prefix + "latent_down_weight",
                              shape=(dim, inner)),
                 sym.Variable(prefix + "latent_up_weight",
                              shape=(inner, dim))]
    if shared_hidden:
        attrs["shared"] = True
        more += [sym.Variable(prefix + "shared_w1_weight",
                              shape=(dim, int(shared_hidden) *
                                     (wide // hidden))),
                 sym.Variable(prefix + "shared_w2_weight",
                              shape=(int(shared_hidden), dim))]
    out = sym.contrib.RoutedExperts(x, gate, w1, w2, *more,
                                    top_k=int(top_k), act=kind,
                                    renormalize=bool(renormalize),
                                    name=prefix + "moe", **attrs)
    return out[0], out[1]


def _check_kv_heads(num_heads, num_kv_heads):
    if num_kv_heads and num_heads % int(num_kv_heads):
        raise ValueError(
            "num_heads (%d) must be a multiple of num_kv_heads (%d) "
            "for grouped-query attention" % (num_heads, num_kv_heads))


def _canon_block_types(block_type, num_layers):
    """Normalize block_type to a per-layer tuple.

    block_type: "attention" | "ssm" | "mamba2" for a uniform stack, or
    a sequence of those naming each layer's kind (mixed stacks — e.g.
    mostly-ssm with a few attention layers, the usual hybrid recipe).
    "mamba2" layers exist in the decode symbol only and take their
    sizes from its `mamba2` argument."""
    if isinstance(block_type, str):
        kinds = (block_type,) * num_layers
    else:
        kinds = tuple(block_type)
        if len(kinds) != num_layers:
            raise ValueError(
                "block_type sequence names each layer: got %d entries "
                "for num_layers=%d" % (len(kinds), num_layers))
    for b in kinds:
        if b not in ("attention", "ssm", "mamba2"):
            raise ValueError(
                "block_type entries must be 'attention', 'ssm' or "
                "'mamba2', got %r" % (b,))
    return kinds


_LAYER_KINDS = ("attention", "ssm", "mamba2", "shortconv", "mla",
                "experts", "mlp")
# the mixers whose decode state has no per-position entries
_RECURRENT = ("ssm", "mamba2", "shortconv")


def _canon_layer_kinds(layer_kinds, num_layers):
    """layer_kinds as a per-layer tuple, or None where the stack is
    spelled the old way (block_type: a mixer and an FFN in every
    layer). Each entry is ONE sublayer: a mixer ("attention" | "ssm" |
    "mamba2" | "shortconv" | "mla"), a routed expert layer ("experts")
    or a dense FFN ("mlp")."""
    if layer_kinds is None:
        return None
    kinds = tuple(layer_kinds)
    if len(kinds) != num_layers:
        raise ValueError(
            "layer_kinds names each layer: got %d entries for "
            "num_layers=%d" % (len(kinds), num_layers))
    for k in kinds:
        if k not in _LAYER_KINDS:
            raise ValueError("layer_kinds entries must be one of %r, "
                             "got %r" % (_LAYER_KINDS, k))
    return kinds


def _mixer_kinds(kinds):
    """The layers of a layer_kinds stack that mix positions (and hold
    decode state), in order."""
    return tuple(k for k in kinds if k not in ("experts", "mlp"))


def _canon_attention_layers(spec, n_attention, pos_encoding, window):
    """attention_layers (get_decode_symbol) as a tuple with one dict
    for each attention layer: ``window`` (0: none), ``rows`` (a
    circular buffer's capacity; 0: a full cache of max_len rows) and
    ``rope`` (whether the layer rotates q and k). None where the stack
    says these once for all layers."""
    if spec is None:
        return None
    spec = tuple(spec)
    if len(spec) != n_attention:
        raise ValueError("attention_layers names each attention layer: "
                         "got %d entries for %d attention layer(s)"
                         % (len(spec), n_attention))
    out = []
    for entry in spec:
        entry = dict(entry)
        w = int(entry.pop("window", window) or 0)
        cache = entry.pop("cache", "full")
        rows = int(entry.pop("rows", 0) or 0)
        pos = entry.pop("pos", "rope" if pos_encoding == "rope"
                        else "none")
        if entry or cache not in ("full", "rolling") or \
                pos not in ("rope", "none") or w < 0:
            raise ValueError(
                "an attention_layers entry is dict(window=, cache='full'"
                " | 'rolling', rows=, pos='rope' | 'none'), got %r"
                % (dict(entry, window=w, cache=cache, rows=rows,
                        pos=pos),))
        if pos == "rope" and pos_encoding != "rope":
            raise ValueError("a layer with pos='rope' needs "
                             "pos_encoding='rope' (the positions are "
                             "an input of the symbol)")
        if cache == "rolling":
            if not w or rows < w:
                raise ValueError(
                    "a rolling layer needs window > 0 and rows >= "
                    "window (the circular capacity covers one window),"
                    " got window=%d rows=%d" % (w, rows))
        elif rows:
            raise ValueError("rows is a rolling layer's capacity; a "
                             "full layer holds max_len rows")
        out.append({"window": w, "rope": pos == "rope",
                    "rows": rows if cache == "rolling" else 0})
    return tuple(out)


def _check_pos_encoding(pos_encoding, dim, num_heads):
    if pos_encoding not in ("learned", "rope"):
        raise ValueError("pos_encoding must be 'learned' or 'rope', "
                         "got %r" % (pos_encoding,))
    if pos_encoding == "rope" and (dim // num_heads) % 2:
        # rope rotates half-split pairs; an odd head_dim would fail
        # deep in lowering with an opaque broadcast error
        raise ValueError("pos_encoding='rope' needs an even head_dim, "
                         "got %d" % (dim // num_heads))


def _layer_block(x, num_heads, dim, ffn_hidden, prefix, seq_axis=None,
                 num_experts=0, expert_axis=None, dropout=0.0,
                 moe_capacity_factor=1.25, rope_positions=None,
                 window=0, num_kv_heads=None, block_type="attention"):
    """One pre-LN transformer block: mixing residual (attention or
    SSM, by block_type) + FFN/MoE residual. Shared by the monolithic
    get_symbol layer loop and the pipeline get_stage_symbol so the two
    can never drift."""
    a = sym.LayerNorm(x, name=prefix + "ln1")
    if block_type == "ssm":
        x = x + _ssm_block(a, num_heads, dim, prefix)
    else:
        x = x + _attention_block(a, num_heads, dim, prefix,
                                 seq_axis=seq_axis,
                                 rope_positions=rope_positions,
                                 window=window,
                                 num_kv_heads=num_kv_heads)
    f = sym.LayerNorm(x, name=prefix + "ln2")
    ff = _moe_block(f, dim, ffn_hidden, num_experts, prefix,
                    expert_axis=expert_axis,
                    capacity_factor=moe_capacity_factor) \
        if num_experts else _ffn_block(f, dim, ffn_hidden, prefix)
    if dropout > 0:
        ff = sym.Dropout(ff, p=dropout)
    out = x + ff
    if seq_axis:
        # keep the (B, T, C) residual stream T-sharded between layers —
        # without the hint GSPMD re-replicates it around the ring
        # shard_map boundary (an all-gather per layer in the compiled
        # step's text). Lenient: inert off-mesh.
        out._set_attr(__shard_hint__="None,%s,None" % seq_axis)
    return out


def get_stage_symbol(num_heads=4, dim=128, ffn_hidden=None,
                     seq_axis=None, pos_encoding="learned",
                     seq_len=None, attention_window=0):
    """One transformer block as a standalone symbol: data (mb, T, C) ->
    (mb, T, C). The pipeline-parallel stage for
    ``parallel.pipeline_from_symbol`` — stack L layers' params on a
    leading stage dim and stream microbatches through a ``pipe`` mesh
    axis. Pre-LN and aux-free by construction, as the GPipe schedule
    requires.

    pos_encoding: "learned" means position information enters BEFORE
    stage 0 (the embedding+table sum, as get_symbol builds it), so the
    stage itself is position-free. "rope" must rotate inside EVERY
    attention layer, so a rope stage needs ``seq_len`` to build its
    positions."""
    ffn_hidden = ffn_hidden or 4 * dim
    if dim % num_heads:
        raise ValueError("dim (%d) must be divisible by num_heads (%d)"
                         % (dim, num_heads))
    _check_pos_encoding(pos_encoding, dim, num_heads)
    rope_positions = None
    if pos_encoding == "rope":
        if not seq_len:
            raise ValueError("pos_encoding='rope' stages need seq_len "
                             "(RoPE applies inside each layer)")
        rope_positions = sym.arange(start=0, stop=seq_len)
    return _layer_block(sym.Variable("data"), num_heads, dim,
                        ffn_hidden, "", seq_axis=seq_axis,
                        rope_positions=rope_positions,
                        window=attention_window)


def _decode_attention_block(x, num_heads, dim, prefix, max_len, pos,
                            quantized=False, rope_positions=None,
                            window=0, rolling=False,
                            num_kv_heads=None, kv_quantize=False,
                            scale=None, no_bias=False, head_dim=None,
                            qk_norm_eps=None, rope_base=None, block=0,
                            scope=None):
    """Incremental variant of _attention_block: identical qkv/proj
    helpers (a training checkpoint binds unchanged), attention routed
    through _contrib_CachedAttention with per-layer k/v cache aux
    states ("<prefix>attn_k_cache"/"_v_cache", created by the op's
    state_inputs registration). kv_quantize routes through the int8
    variant (_contrib_CachedAttentionQ8), which adds per-token scale
    aux states ("_k_scale"/"_v_scale"). scale: the score multiplier
    where the model states one (default head_dim ** -0.5). head_dim,
    qk_norm_eps: see _qkv_heads. rope_base: the rotation's base where
    it is not 10000. block: the block mask of _contrib_CachedAttention
    (position i sees position j iff j's block is not after i's).
    rolling: the circular op, whose cache holds max_len rows HERE (the
    layer's own capacity, not the sequence bound). scope: the plain
    op's device scope, where the stack names its attention kinds."""
    q, k, v = _qkv_heads(x, num_heads, dim, prefix, quantized,
                         num_kv_heads=num_kv_heads, no_bias=no_bias,
                         head_dim=head_dim, qk_norm_eps=qk_norm_eps)
    kw = {} if scale is None else {"scale": float(scale)}
    if block:
        if rolling or kv_quantize:
            raise ValueError("attention_block is built for the plain "
                             "cache only (no rolling, no int8 cache)")
        kw["block"] = int(block)
    if scope and not (rolling or kv_quantize):
        kw["scope"] = scope
    if rope_positions is not None:
        # rotate BEFORE caching: cached keys carry their absolute
        # rotation, so each step only rotates the new tokens
        rkw = {} if rope_base is None else {"base": float(rope_base)}
        q = sym.contrib.RoPE(q, rope_positions, **rkw)
        k = sym.contrib.RoPE(k, rope_positions, **rkw)
    if rolling:
        att = sym.contrib.RollingCachedAttention(
            q, k, v, pos=pos, max_len=max_len, window=window,
            name=prefix + "attn", **kw)
    elif kv_quantize:
        att = sym.contrib.CachedAttentionQ8(
            q, k, v, pos=pos, max_len=max_len, window=window,
            name=prefix + "attn", **kw)
    else:
        att = sym.contrib.CachedAttention(q, k, v,
                                          pos=pos, max_len=max_len,
                                          window=window,
                                          name=prefix + "attn", **kw)
    return _merge_heads_proj(att, dim, prefix, quantized, no_bias)


def _decode_ssm_block(x, num_heads, dim, prefix, max_len, pos,
                      quantized=False):
    """Incremental variant of _ssm_block: identical qkvg/proj helpers
    (a training checkpoint binds unchanged), mixing routed through
    _contrib_SSMCached with one per-layer recurrent-state aux
    ("<prefix>ssm_state", (B, H, hd, hd) f32, created by the op's
    state_inputs registration). The state has NO length axis — a
    decode slot costs the same HBM at any position — and the op
    ignores pos (the recurrence carries its own), so the per-row-
    position serving twin is this same graph."""
    q, k, v, g = _ssm_qkvg(x, num_heads, dim, prefix, quantized)
    out = sym.contrib.SSMCached(q, k, v, g, pos=pos, max_len=max_len,
                                name=prefix + "ssm")
    return _merge_heads_proj(out, dim, prefix, quantized)


MAMBA2_SIZES = ("num_heads", "head_dim", "d_state", "d_conv", "chunk",
                "n_groups")


def _canon_mamba2(mamba2, btypes):
    """The Mamba-2 layers' sizes as a plain dict with every key of
    MAMBA2_SIZES, or None when no layer is of that kind. d_conv,
    chunk and n_groups (the groups B and C come in: heads must divide
    over them) have the family's usual values as defaults; the three
    widths have none."""
    if "mamba2" not in btypes:
        if mamba2:
            raise ValueError("mamba2 sizes given but no block_type "
                             "entry is 'mamba2'")
        return None
    sizes = dict({"d_conv": 4, "chunk": 256, "n_groups": 1},
                 **dict(mamba2 or {}))
    if set(sizes) != set(MAMBA2_SIZES) or \
            any(int(v) < 1 for v in sizes.values()) or \
            int(sizes["d_conv"]) < 2 or \
            int(sizes["num_heads"]) % int(sizes["n_groups"]):
        raise ValueError(
            "'mamba2' layers need mamba2=dict(num_heads=, head_dim=, "
            "d_state=[, d_conv=4, chunk=256, n_groups=1]) with "
            "positive sizes, d_conv >= 2 and num_heads a multiple of "
            "n_groups, got %r" % (mamba2,))
    return {k: int(sizes[k]) for k in MAMBA2_SIZES}


def _decode_mamba2_block(x, dim, prefix, max_len, pos, sizes,
                         quantized=False, no_bias=False, eps=1e-5):
    """A Mamba-2 mixer on the decode path: one input projection
    "<prefix>in_proj" to [z | xBC | dt] (d_inner + conv_dim + heads),
    convolution and selective scan through _contrib_Mamba2Cached with
    two per-layer aux states ("<prefix>mamba_conv_state",
    (B, d_conv-1, conv_dim) in the served dtype, and
    "<prefix>mamba_scan_state", (B, heads, head_dim, d_state) f32 —
    neither has a length axis), the gated RMS norm over d_inner, group
    by group ("<prefix>mnorm_gamma", gate first), and
    "<prefix>out_proj"; conv_dim = d_inner + 2 * n_groups * d_state. The
    op ignores pos, so the per-row-position serving twin is this
    graph."""
    H, P, N = sizes["num_heads"], sizes["head_dim"], sizes["d_state"]
    G = sizes["n_groups"]
    # one group is the ops' default: said only where it is not, so a
    # one-group symbol is the symbol it always was
    by_group = {"n_groups": G} if G > 1 else {}
    sizes = {k: v for k, v in sizes.items() if k != "n_groups"}
    d_inner = H * P
    conv_dim = d_inner + 2 * G * N
    zxd = _fc(x, d_inner + conv_dim + H, prefix + "in_proj", quantized,
              no_bias)
    z = sym.slice_axis(zxd, axis=2, begin=0, end=d_inner)
    xbc = sym.slice_axis(zxd, axis=2, begin=d_inner,
                         end=d_inner + conv_dim)
    dt = sym.slice_axis(zxd, axis=2, begin=d_inner + conv_dim,
                        end=d_inner + conv_dim + H)
    y = sym.contrib.Mamba2Cached(xbc, dt, pos=pos, max_len=max_len,
                                 name=prefix + "mamba", **sizes,
                                 **by_group)
    y = sym.contrib.GatedRMSNorm(y, z, eps=eps, name=prefix + "mnorm",
                                 **({"groups": G} if G > 1 else {}))
    return _fc(y, dim, prefix + "out_proj", quantized, no_bias)


def _decode_shortconv_block(x, prefix, max_len, pos, d_conv):
    """A gated short convolution (ops/shortconv.py) on the decode
    path, the whole operator in one node: "<prefix>in_proj_weight"
    (3*dim, dim) to [b | c | u], the d_conv causal depthwise taps
    "<prefix>shortconv_conv_weight" (dim, d_conv) over b * u, the gate
    c, and "<prefix>out_proj_weight" (dim, dim); no bias, no
    activation. One per-layer aux state, "<prefix>shortconv_conv_state"
    (B, d_conv-1, dim) in the served dtype: the last gated rows, with
    no length axis. The op ignores pos, so the per-row-position
    serving twin is this graph. The projections are the operator's own
    inputs: quantized= passes them by."""
    return sym.contrib.ShortConvCached(
        x, sym.Variable(prefix + "in_proj_weight"),
        out_proj_weight=sym.Variable(prefix + "out_proj_weight"),
        pos=pos, max_len=max_len, d_conv=int(d_conv),
        name=prefix + "shortconv")


def _canon_mla(mla, btypes):
    """The "mla" layers' sizes as a plain dict with every key of
    ops.mla.MLA_SIZES (each a positive int: none has a default), or
    None when no layer is of that kind."""
    from ..ops.mla import MLA_SIZES
    if "mla" not in btypes:
        if mla:
            raise ValueError("mla sizes given but no layer_kinds entry "
                             "is 'mla'")
        return None
    sizes = dict(mla or {})
    if set(sizes) != set(MLA_SIZES) or \
            any(int(v) < 1 for v in sizes.values()) or \
            int(sizes["qk_rope_head_dim"]) % 2 or \
            int(sizes["index_head_dim"]) < int(sizes["qk_rope_head_dim"]):
        raise ValueError(
            "'mla' layers need mla=dict(%s) with positive sizes, an "
            "even qk_rope_head_dim and index_head_dim >= "
            "qk_rope_head_dim, got %r" % ("=, ".join(MLA_SIZES) + "=",
                                          mla))
    return {k: int(sizes[k]) for k in MLA_SIZES}


def _decode_mla_block(x, num_heads, prefix, max_len, pos, positions,
                      sizes, rope_base=None, eps=1e-5):
    """Latent attention over a learned selection of keys
    (ops/mla.py) on the decode path, the whole mixer in one node: its
    twelve weights are the operator's own inputs, "<prefix>mla_
    q_a_weight" (q_lora_rank, dim) ... "<prefix>mla_index_head_weight"
    (index_heads, dim), each (out, in); quantized= passes them by. Two
    per-layer aux states with a length axis and widths of their own:
    "<prefix>mla_latent_cache" (B, max_len, kv_lora_rank +
    qk_rope_head_dim) and "<prefix>mla_index_cache" (B, max_len,
    index_head_dim), in the served dtype. Returns (out, counts): the
    mixer's output and its (3,) int32 [keys visible, keys selected,
    keys computed]."""
    kw = {} if rope_base is None else {"rope_base": float(rope_base)}
    out = sym.contrib.LatentSelectAttention(
        x, positions, pos=pos, max_len=max_len,
        num_heads=int(num_heads), eps=float(eps),
        name=prefix + "mla", **sizes, **kw)
    return out[0], out[1]


def get_decode_symbol(vocab_size, max_len, num_layers=2, num_heads=4,
                      dim=128, ffn_hidden=None, num_experts=0,
                      quantized=False, compute_dtype=None,
                      pos_encoding="learned", attention_window=0,
                      rolling_cache=False, num_kv_heads=None,
                      kv_quantize=False, per_row_pos=False,
                      block_type="attention", norm="layer",
                      norm_eps=1e-5, ffn="relu", use_bias=True,
                      tie_embeddings=False, embedding_multiplier=1.0,
                      residual_multiplier=1.0, logits_scaling=1.0,
                      attention_scale=None, mamba2=None,
                      experts_per_token=1, expert_hidden=None,
                      norm_topk_prob=False, head_dim=None,
                      qk_norm=False, rope_base=None, attention_block=0,
                      moe_stats=False, head_rows=0, layer_kinds=None,
                      expert_scoring="softmax",
                      routed_scaling_factor=1.0, expert_latent=0,
                      shared_expert_hidden=0, experts_held=None,
                      shortconv_kernel=3, norm_topk_eps=None,
                      attention_layers=None, parallel_block=False,
                      mla=None):
    """Autoregressive-decode twin of get_symbol.

    Inputs: data (B, Tnew) token ids for the tokens being appended
    (the whole prompt at prefill, one per step after), positions
    (Tnew,) absolute position ids, cache_pos (1,) = tokens already in
    the caches. Output: logits (B, Tnew, vocab) — no loss head.
    Parameter names match get_symbol exactly; the KV caches are
    auxiliary states shaped (B, max_len, Hkv*head_dim) — a token's kv
    heads side by side in one row — where Hkv = num_kv_heads or
    num_heads (grouped-query attention stores only the kv heads — the
    cache memory/bandwidth win).

    per_row_pos=True builds the CONTINUOUS-BATCHING variant: positions
    becomes (B, Tnew) and cache_pos (B,) — every batch row decodes at
    its own depth, which is what lets a serving slot pool
    (mxnet_tpu/serve/decode.py) retire a finished sequence and admit a
    queued prompt without draining the whole batch. Parameter names
    are unchanged, so the same checkpoint binds both variants.
    Composes with kv_quantize (the int8-cache op has a per-row scatter
    for both the int8 rows and their f32 scale rows); rolling_cache
    remains shared-position only.

    block_type: "attention" (default), "ssm", or a per-layer sequence
    (mixed stacks). SSM layers replace the (B, max_len, H*hd) KV-row
    caches with one (B, H, hd, hd) f32 recurrent-state aux per layer
    ("layerN_ssm_state") — O(1) decode memory in sequence length.
    Knob composition: kv_quantize and attention_window apply to the
    attention LAYERS of a mixed stack and refuse on a pure-SSM stack
    (nothing to quantize/window); rolling_cache refuses with any SSM
    layer (the state is already O(1) — there is no window to roll);
    per_row_pos composes freely (the SSM op ignores pos).

    "mamba2" entries of block_type are Mamba-2 mixers sized by
    `mamba2` (a dict of MAMBA2_SIZES; see _decode_mamba2_block): two
    aux states a layer, a convolution window and a float32 scan state,
    with the composition rules of "ssm" layers; n_groups > 1 gives B,
    C and the gated norm by group. They exist on this decode path only
    (serving); get_symbol does not build them.

    layer_kinds: a per-layer sequence that spells the stack ONE
    SUBLAYER A LAYER, h <- h + f(norm(h)) with the layer's one norm
    "layerN_ln1": "attention" | "ssm" | "mamba2" (the mixers above),
    "shortconv" (a gated short convolution of shortconv_kernel taps,
    ops/shortconv.py: one aux state a layer, a window of
    shortconv_kernel - 1 gated rows in the served dtype, no length
    axis; it exists under this spelling only), "experts" (a routed
    expert layer sized by the expert arguments below) or "mlp" (the
    dense FFN of kind `ffn` and width ffn_hidden). A layer of the last
    two kinds holds no decode state at all. A pre-norm block of two
    sublayers is two entries, so a stack whose FFN differs by layer
    (dense of ffn_hidden in the leading layers, experts of
    expert_hidden after) is spelled as it is: ("shortconv", "mlp",
    "attention", "experts", ...). "mla" (under this spelling only;
    pos_encoding "rope") is latent attention whose keys a learned
    indexer selects, sized by `mla`, a dict with every key of
    ops.mla.MLA_SIZES (q_lora_rank, kv_lora_rank, qk_nope_head_dim,
    qk_rope_head_dim, v_head_dim, index_heads, index_head_dim,
    index_topk) beside num_heads: two aux states a layer with a length
    axis and widths of their own, latent rows (B, max_len,
    kv_lora_rank + qk_rope_head_dim) and index-key rows (B, max_len,
    index_head_dim); the rotation (half-split pairs, rope_base) turns
    qk_rope_head_dim channels only: the last of a head, the first of
    an index head. block_type then stays at its
    default: it is the other spelling, a mixer AND an FFN in every
    layer, and builds the symbol it always built.

    The remaining arguments are what the hybrid families' published
    equations need beyond the block above, each with the default that
    leaves the symbol as it was: norm "layer" | "layer_gain" | "rms"
    (the last two have a gamma and no beta) with norm_eps; ffn "relu" | "gated_silu" (fc1
    twice as wide: [gate | up]); pos_encoding "none" (no position
    enters anywhere, and `positions` is no input of the symbol);
    use_bias=False drops every projection's bias; tie_embeddings
    makes the head the token table itself (one array: no
    lm_head_weight, no lm_head_bias); embedding_multiplier scales the
    embedding, residual_multiplier each block's contribution before it
    is added, logits_scaling DIVIDES the logits; attention_scale
    replaces head_dim ** -0.5.

    num_experts > 0 makes every layer's FFN (under layer_kinds: every
    "experts" layer) a routed expert layer (_contrib_RoutedExperts,
    parallel/moe.py::routed_experts): the experts_per_token largest
    float32 softmax scores (divided by their sum under
    norm_topk_prob), every routed pair computed, nothing dropped;
    experts of width expert_hidden (default ffn_hidden) and of kind
    `ffn` ("relu" | "relu2" | "gated_silu"). The defaults (top-1,
    "relu", the score itself as the weight) serve a Switch checkpoint
    trained through get_symbol. expert_scoring="sigmoid": sigmoid
    scores and a score-correction bias "layerN_gate_score_bias" that
    chooses the experts and does not weigh them (norm_topk_eps: what
    norm_topk_prob adds to the sum it divides by, where the model
    states one: 1e-6 in the lfm2_moe block); routed_scaling_factor
    multiplies the weights. expert_latent=Z: the
    experts map Z -> expert_hidden -> Z between one down-projection a
    token and one up-projection of the weighted sum.
    shared_expert_hidden=Hs: a shared expert of kind `ffn` over the
    full width, added whole. experts_held=(first, count): THE CHIP'S
    SHARE: the layer routes over all num_experts, holds experts first
    .. first + count - 1 and computes the pairs routed to those; what
    the other experts would add is left to their chips. moe_stats=True
    adds a second output, (expert layers, 3) int32: each layer's pairs
    routed, distinct held experts hit and largest expert batch, and
    with experts_held a fourth column, the pairs computed here; a
    stack with "mla" layers adds an output after it (alone where no
    layer routes), (mla layers, 3) int32: each layer's keys visible,
    keys selected and keys computed (columns run x queries), summed
    over rows and positions.
    head_dim: a head size other than dim /
    num_heads (the q block and the out-projection's input are then
    num_heads * head_dim wide; the cache rows Hkv * head_dim).
    qk_norm: each head of q and k RMS-normalised with a learned gain,
    before the rotation. rope_base: the rotary base where it is not
    10000. attention_block=L (> 0): the BLOCK mask in place of the
    causal one — position i sees position j iff floor(j / L) <=
    floor(i / L): causal across blocks of L, both ways inside one —
    for prefill and step alike. head_rows=R (> 0; per_row_pos): one
    more input, head_pos (B,), and the final norm and the head read
    only positions head_pos[b] .. head_pos[b] + R - 1 of row b, so the
    logits are (B, R, vocab) however many positions the layers ran
    (every position still writes its cache rows).

    New TPU-native capability (the 2017 reference's decode story was
    rnn.RNNCell step-wise unrolling); mxnet_tpu.generation.Generator
    drives this symbol."""
    ffn_hidden = ffn_hidden or 4 * dim
    if not head_dim and dim % num_heads:
        raise ValueError("dim (%d) must be divisible by num_heads (%d)"
                         % (dim, num_heads))
    if moe_stats and not num_experts and \
            "mla" not in (layer_kinds or ()):
        raise ValueError("moe_stats needs num_experts > 0 or an 'mla' "
                         "layer")
    if head_rows and not per_row_pos:
        raise ValueError("head_rows needs per_row_pos (head_pos is "
                         "one offset a row)")
    _check_kv_heads(num_heads, num_kv_heads)
    kinds = _canon_layer_kinds(layer_kinds, num_layers)
    if kinds is None:
        btypes = _canon_block_types(block_type, num_layers)
    else:
        if block_type != "attention":
            raise ValueError("layer_kinds and block_type are two "
                             "spellings of the stack: give one")
        if bool(num_experts) != ("experts" in kinds):
            raise ValueError("'experts' layers and num_experts > 0 go "
                             "together: got num_experts=%d for %r"
                             % (num_experts, kinds))
        btypes = _mixer_kinds(kinds)
    if experts_held is not None:
        first, count = (int(v) for v in experts_held)
        if first < 0 or count < 1 or first + count > num_experts:
            raise ValueError("experts_held=(first, count) must lie "
                             "inside num_experts=%d, got %r"
                             % (num_experts, experts_held))
        experts_held = (first, count)
    mamba2 = _canon_mamba2(mamba2, btypes)
    mla = _canon_mla(mla, btypes)
    if mla and pos_encoding != "rope":
        raise ValueError("'mla' layers rotate a slice of each head: "
                         "pos_encoding must be 'rope', got %r"
                         % (pos_encoding,))
    has_ssm = bool(set(_RECURRENT) & set(btypes))
    has_attn = "attention" in btypes
    no_bias = not use_bias
    if int(shortconv_kernel) < 2:
        raise ValueError("shortconv_kernel must be at least 2 (the "
                         "window holds shortconv_kernel - 1 rows), "
                         "got %r" % (shortconv_kernel,))
    if rolling_cache and not attention_window:
        raise ValueError("rolling_cache needs attention_window > 0 "
                         "(the circular capacity covers one window)")
    if kv_quantize and rolling_cache:
        raise ValueError("kv_quantize is not supported with "
                         "rolling_cache (no int8 variant of the "
                         "circular-buffer op)")
    if rolling_cache and has_ssm:
        raise ValueError(
            "rolling_cache is not supported with ssm blocks: the SSM "
            "state is already O(1) in sequence length — there is no "
            "KV window to roll (use block_type='attention' for "
            "rolling caches, or drop rolling_cache)")
    if kv_quantize and not has_attn:
        raise ValueError(
            "kv_quantize needs at least one attention layer: a pure-"
            "SSM stack has no KV cache to quantize (its (H, hd, hd) "
            "f32 state is already O(1); mixed attention/ssm stacks "
            "compose — the attention layers quantize)")
    if attention_window and not has_attn:
        raise ValueError(
            "attention_window needs at least one attention layer: "
            "SSM layers have no attention window (their state decays "
            "continuously; mixed stacks compose — the window applies "
            "to the attention layers)")
    by_layer = _canon_attention_layers(
        attention_layers, btypes.count("attention"), pos_encoding,
        attention_window)
    if by_layer is not None:
        if rolling_cache or attention_block:
            raise ValueError("attention_layers says each layer's cache "
                             "and mask: no rolling_cache or "
                             "attention_block beside it")
        if kv_quantize and any(a["rows"] for a in by_layer):
            raise ValueError("kv_quantize is not supported with a "
                             "rolling layer (no int8 variant of the "
                             "circular-buffer op)")
    if parallel_block and kinds is not None:
        raise ValueError("parallel_block is a mixer and an FFN on one "
                         "norm: spell the stack by block_type, not "
                         "layer_kinds")
    data = sym.Variable("data")
    positions = sym.Variable("positions")
    cache_pos = sym.Variable("cache_pos") if per_row_pos \
        else sym.Variable("cache_pos", shape=(1,))

    tied = {}
    if tie_embeddings:
        # ONE array on the device: the table is named here and handed
        # to both the lookup and the head (int8: with its per-row
        # scales, which are the head's per-output-channel scales)
        tied["weight"] = sym.Variable("tok_embed_weight")
        if quantized:
            tied["scale"] = sym.Variable("tok_embed_scale")
    if quantized:
        # per-row int8 token table (the largest parameter at serving)
        x = sym.contrib.QuantizedEmbedding(
            data, input_dim=vocab_size, output_dim=dim,
            dtype=compute_dtype or "float32",
            name="tok_embed", **tied)
    else:
        x = sym.Embedding(data, input_dim=vocab_size, output_dim=dim,
                          name="tok_embed", **tied)
    if embedding_multiplier != 1.0:
        # in float32, rounded once: a Python scalar times a bf16 array
        # would round the SCALAR to bf16 first (_contrib_ScaleF32)
        x = sym.contrib.ScaleF32(x, scalar=float(embedding_multiplier))
    rope_positions = None
    if pos_encoding == "rope":
        rope_positions = positions
    elif pos_encoding == "learned":
        pos_table = sym.Variable("pos_embed_weight",
                                 shape=(max_len, dim))
        if per_row_pos:
            # (B, Tnew) ids -> (B, Tnew, dim): each row looks up its
            # own depth's rows of the table
            x = sym.broadcast_add(x, sym.take(pos_table, positions))
        else:
            pos_vec = sym.take(pos_table, positions)  # (Tnew, dim)
            x = sym.broadcast_add(x,
                                  sym.expand_dims(pos_vec, axis=0))
    elif pos_encoding != "none":
        raise ValueError("pos_encoding must be 'learned', 'rope' or "
                         "'none', got %r" % (pos_encoding,))

    def residual(x, branch):
        if residual_multiplier == 1.0:
            return x + branch
        return sym.contrib.AddScaledF32(
            x, branch, scalar=float(residual_multiplier))

    layer_specs = iter(by_layer or ())

    def attention(a, prefix):
        # the stack's one window, cache and rotation, or this layer's
        how = dict(window=attention_window, rolling=rolling_cache,
                   rope_positions=rope_positions, block=attention_block)
        rows = max_len
        if by_layer is not None:
            spec = next(layer_specs)
            rows = spec["rows"] or max_len
            how = dict(window=spec["window"], rolling=bool(spec["rows"]),
                       rope_positions=rope_positions if spec["rope"]
                       else None, scope="attn.full")
        return _decode_attention_block(
            a, num_heads, dim, prefix, rows, cache_pos,
            num_kv_heads=num_kv_heads, quantized=quantized,
            kv_quantize=kv_quantize, scale=attention_scale,
            no_bias=no_bias, head_dim=head_dim,
            qk_norm_eps=norm_eps if qk_norm else None,
            rope_base=rope_base, **how)

    def mixer(kind, a, prefix):
        if kind == "ssm":
            return _decode_ssm_block(a, num_heads, dim, prefix,
                                     max_len, cache_pos,
                                     quantized=quantized)
        if kind == "mamba2":
            return _decode_mamba2_block(a, dim, prefix, max_len,
                                        cache_pos, mamba2,
                                        quantized=quantized,
                                        no_bias=no_bias, eps=norm_eps)
        if kind == "shortconv":
            return _decode_shortconv_block(a, prefix, max_len,
                                           cache_pos, shortconv_kernel)
        if kind == "mla":
            out, counts = _decode_mla_block(
                a, num_heads, prefix, max_len, cache_pos,
                rope_positions, mla, rope_base=rope_base, eps=norm_eps)
            key_stats.append(counts)
            return out
        return attention(a, prefix)

    def experts(f, prefix):
        # inference never capacity-drops: every token is served, and
        # only the routed pairs are computed. Training-time drops mean
        # a dropping checkpoint's decode can differ exactly where
        # training zeroed a token's FFN. (Expert weights stay float —
        # quantized= covers the dense projections.)
        ff, stats = _routed_block(
            f, dim, expert_hidden or ffn_hidden, num_experts, prefix,
            top_k=experts_per_token, kind=ffn,
            renormalize=norm_topk_prob, scoring=expert_scoring,
            scale=routed_scaling_factor, held=experts_held,
            latent=expert_latent, shared_hidden=shared_expert_hidden,
            renorm_eps=norm_topk_eps)
        layer_stats.append(stats)
        return ff

    def dense(f, prefix):
        return _ffn_block(f, dim, ffn_hidden, prefix,
                          quantized=quantized, kind=ffn, no_bias=no_bias)

    layer_stats, key_stats = [], []
    for i in range(num_layers):
        prefix = "layer%d_" % i
        a = _norm(x, prefix + "ln1", norm, norm_eps)
        if kinds is not None:
            # one sublayer a layer: a mixer, an expert layer or an FFN
            f = {"experts": experts, "mlp": dense}.get(kinds[i])
            x = residual(x, f(a, prefix) if f else
                         mixer(kinds[i], a, prefix))
            continue
        x = residual(x, mixer(btypes[i], a, prefix))
        # the parallel block's FFN reads the layer's one norm too
        f = a if parallel_block else \
            _norm(x, prefix + "ln2", norm, norm_eps)
        x = residual(x, (experts if num_experts else dense)(f, prefix))

    if head_rows:
        x = sym.contrib.RowsAt(x, sym.Variable("head_pos"),
                               rows=int(head_rows))
    x = _norm(x, "ln_f", norm, norm_eps)
    if tie_embeddings:
        head = sym.contrib.QuantizedFullyConnected if quantized \
            else sym.FullyConnected
        logits = head(x, num_hidden=vocab_size, flatten=False,
                      no_bias=True, name="lm_head", **tied)
    else:
        logits = _fc(x, vocab_size, "lm_head", quantized, no_bias)
    if logits_scaling != 1.0:
        logits = sym.contrib.ScaleF32(
            logits, scalar=1.0 / float(logits_scaling))
    if moe_stats:
        return sym.Group([logits] + [
            sym.stack(*stats, axis=0, num_args=len(stats))
            for stats in (layer_stats, key_stats) if stats])
    return logits


def get_symbol(vocab_size, seq_len, num_layers=2, num_heads=4, dim=128,
               ffn_hidden=None, dropout=0.0, max_len=None,
               num_experts=0, seq_axis=None, expert_axis=None,
               moe_capacity_factor=1.25, pos_encoding="learned",
               attention_window=0, num_kv_heads=None, loss_chunk=0,
               block_type="attention"):
    """GPT-style causal LM symbol.

    data: (B, T) token ids; softmax_label: (B, T) next-token targets
    (ignore index -1). Output: softmax over vocab per position.

    max_len: position-table capacity (>= seq_len). For BucketingModule,
    pass the same max_len (e.g. the largest bucket) to every bucket's
    get_symbol so the shared pos_embed parameter keeps one shape; each
    bucket slices the first seq_len rows.

    num_experts > 0 swaps each FFN for a Switch-style top-1 MoE
    (_contrib_MoEFFN); under a mesh the expert dimension shards like
    any parameter, and the shard_map expert-parallel form lives in
    parallel.moe_ffn.

    seq_axis: mesh-axis name for sequence/context parallelism. When the
    symbol is bound/trained over a mesh with that axis, every attention
    layer runs ring attention (K/V blocks rotating on ppermute, T/n of
    the sequence per device) — the long-context training path through
    the ordinary symbol API. Without a mesh the flag is inert.

    expert_axis: same contract for the MoE FFNs (num_experts > 0):
    experts shard over the axis and tokens exchange via all_to_all.

    pos_encoding: "learned" (the pos_embed table, max_len-capped) or
    "rope" — rotary embeddings applied to q/k inside every attention
    layer (no position parameters, graceful length extrapolation; the
    modern long-context choice).

    block_type: "attention" (default), "ssm", or a per-layer sequence
    — SSM layers are gated linear attention (ops/ssm.py) trained in
    the chunked-scan form; their decode twin carries O(1) state
    instead of KV rows (see get_decode_symbol). Incompatible with
    seq_axis (the scan is sequential over the sequence).

    loss_chunk: 0 (default) keeps the reference head — FullyConnected
    logits + SoftmaxOutput, output = softmax probabilities per
    position. A positive value swaps in the fused chunked-CE head
    (`_contrib_ChunkedSoftmaxCE`): the OUTPUT CONTRACT CHANGES to the
    per-token loss (B, T) in SoftmaxOutput's gradient scaling (no
    probabilities are ever materialized — that (B*T, vocab) f32
    buffer is what OOMs 64k-token training, not attention). Parameter
    names/shapes are identical, so checkpoints interchange; parameter
    gradients are bit-equal to the dense head's
    (tests/test_transformer.py::test_chunked_loss_head_matches_dense).
    """
    ffn_hidden = ffn_hidden or 4 * dim
    max_len = max_len or seq_len
    assert max_len >= seq_len
    if dim % num_heads:
        raise ValueError("dim (%d) must be divisible by num_heads (%d)"
                         % (dim, num_heads))
    _check_kv_heads(num_heads, num_kv_heads)
    _check_pos_encoding(pos_encoding, dim, num_heads)
    btypes = _canon_block_types(block_type, num_layers)
    if "mamba2" in btypes:
        raise ValueError(
            "block_type 'mamba2' exists on the decode path only "
            "(get_decode_symbol / Generator): the training symbol "
            "does not build it")
    if seq_axis and "ssm" in btypes:
        raise ValueError(
            "seq_axis (ring sequence parallelism) is not supported "
            "with ssm blocks — the chunked scan is sequential over "
            "the sequence; shard batch/tensor axes instead")
    if attention_window and "attention" not in btypes:
        raise ValueError(
            "attention_window needs at least one attention layer "
            "(SSM layers have no attention window)")
    data = sym.Variable("data")
    label = sym.Variable("softmax_label")

    x = sym.Embedding(data, input_dim=vocab_size, output_dim=dim,
                      name="tok_embed")
    rope_positions = None
    if pos_encoding == "rope":
        rope_positions = sym.arange(start=0, stop=seq_len)
    else:
        pos_table = sym.Variable("pos_embed_weight",
                                 shape=(max_len, dim))
        pos = sym.slice_axis(pos_table, axis=0, begin=0, end=seq_len)
        x = sym.broadcast_add(x, sym.expand_dims(pos, axis=0))

    for i in range(num_layers):
        x = _layer_block(x, num_heads, dim, ffn_hidden,
                         "layer%d_" % i, seq_axis=seq_axis,
                         num_experts=num_experts,
                         expert_axis=expert_axis, dropout=dropout,
                         moe_capacity_factor=moe_capacity_factor,
                         num_kv_heads=num_kv_heads,
                         rope_positions=rope_positions,
                         window=attention_window,
                         block_type=btypes[i])

    x = sym.LayerNorm(x, name="ln_f")
    if loss_chunk:
        # chunked fused head: never materializes the (B*T, V) logits
        # (8.6 GB in f32 at 64k tokens x 32k vocab — THE long-context
        # OOM, not attention). Same parameter names as the
        # FullyConnected head, so checkpoints interchange; output is
        # the per-token loss (B, T) in SoftmaxOutput's gradient
        # scaling, not the softmax probabilities.
        w_head = sym.Variable("lm_head_weight",
                              shape=(vocab_size, dim))
        b_head = sym.Variable("lm_head_bias", shape=(vocab_size,))
        x2 = sym.reshape(x, shape=(-3, -2))           # (B*T, D)
        label_r = sym.reshape(label, shape=(-1,))
        loss = sym._contrib_ChunkedSoftmaxCE(
            x2, w_head, b_head, label_r, chunk=int(loss_chunk),
            use_ignore=True, ignore_label=-1.0,
            normalization="valid", name="softmax")
        return sym.reshape(loss, shape=(-1, seq_len))
    logits = sym.FullyConnected(x, num_hidden=vocab_size, flatten=False,
                                name="lm_head")
    logits = sym.reshape(logits, shape=(-3, -2))      # (B*T, V)
    label_r = sym.reshape(label, shape=(-1,))
    return sym.SoftmaxOutput(logits, label_r, use_ignore=True,
                             ignore_label=-1.0, normalization="valid",
                             name="softmax")
