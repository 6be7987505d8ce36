"""Operations and bytes for the LFM2-MoE family, from shapes and from
what the run counted: what one decode step (one token for each of
`slots` rows) must move and compute, whatever the program does to get
there. Bytes are counted in the types the configuration serves in
(bf16 weights, activations, convolution window and key/value rows;
the router's float32 bias); a weight, a state or an activation is
counted once for each time the algorithm has to read or write it, and
temporaries not at all.

An expert's weights are counted only where the step routed a token to
it: by the distinct experts hit a layer and step that the program's own
counter reports (`traffic["measured"]`, filled by the drive), never by
all 64, so a share of the roofline cannot read over 100% for a step
that hit few experts."""
import math

from cellbench.ops.granite import mean_depth
from cellbench.reference.lfm2_moe import _KINDS, _TOP, _shape, sizes

_BF16, _F32 = 2, 4
_count = math.prod
_ROUTED = ("experts_w1_weight", "experts_w2_weight")


def _bytes(name, s):
    return _count(_shape(name, s)) * (
        _F32 if name == "gate_score_bias" else _BF16)


def _layers(s, kind):
    return sum(k == kind for k in s["kinds"])


def expert_params(cfg):
    """One expert: gate, up and down."""
    s = sizes(cfg)
    return 3 * s["dim"] * s["expert_ffn"]


def weight_bytes(cfg):
    """Every parameter once; the tied table is one array."""
    s = sizes(cfg)
    return sum(_bytes(n, s) for n in _TOP) + sum(
        _bytes(n, s) for kind in s["kinds"] for n in _KINDS[kind])


def state_bytes_per_slot(cfg, traffic):
    """By kind, as the deployment holds them: the convolution layers'
    windows of `conv_L_cache` - 1 gated rows, and the attention layers'
    key/value rows at `max_len` positions. Nothing else: no scan
    state, and an FFN of either kind holds none."""
    s = sizes(cfg)
    return {"conv_window": _layers(s, "conv") * (s["taps"] - 1) *
            s["dim"] * _BF16,
            "kv_rows": _layers(s, "attention") * 2 * s["kv_heads"] *
            s["head"] * int(traffic["max_len"]) * _BF16}


def pairs_per_layer(cfg, traffic):
    """(token, expert) pairs one step routes in one layer."""
    return int(traffic["slots"]) * sizes(cfg)["top_k"]


def experts_hit(cfg, traffic):
    """Distinct experts with a token, a layer and step: measured where
    the drive has filled it in, else the most the pairs allow."""
    got = (traffic.get("measured") or {}).get(
        "experts_hit_per_layer_step")
    return float(got) if got else float(
        min(sizes(cfg)["experts"], pairs_per_layer(cfg, traffic)))


def moe_experts_need(cfg, traffic):
    """(operations, bytes) of the routed experts' two products in ONE
    decode step, all expert layers: the weights of the experts hit,
    read once; for each pair the input row in, gate and up out and
    back in, the product in to the down projection, the output row
    out; 2 operations a weight and pair."""
    s = sizes(cfg)
    layers = _layers(s, "experts")
    pairs = pairs_per_layer(cfg, traffic)
    acts = pairs * (2 * s["dim"] + 5 * s["expert_ffn"])
    nbytes = _BF16 * (experts_hit(cfg, traffic) * expert_params(cfg) +
                      acts)
    return layers * 2 * pairs * expert_params(cfg), layers * nbytes


def shortconv_step_need(cfg, traffic):
    """(operations, bytes) of the gated short convolutions of ONE
    decode step, all `conv` layers, all slots, the whole operator: both
    projections' weights and the taps read once a layer; for each slot
    the normed row in, the window read and written, the output row
    out; 2 operations a projection weight and slot, and for each
    channel the two gates and a multiply-add a tap. What share of a
    step's bytes the operator is; no metric sets it against the
    scope's device seconds: the compiler prefetches a step's small
    weights under other operations, so those seconds leave the bytes'
    time out (PERF.md, PR 39: it read 233%)."""
    s = sizes(cfg)
    layers, slots, d = _layers(s, "conv"), int(traffic["slots"]), \
        s["dim"]
    weights = sum(_bytes(n, s) for n in _KINDS["conv"]
                  if n != "ln1_gamma")
    per_slot = 2 * (s["taps"] - 1) * d * _BF16 + 2 * d * _BF16
    flops = slots * (2 * 4 * d * d + (2 * s["taps"] + 2) * d)
    return layers * flops, layers * (weights + slots * per_slot)


def shortconv_conv_need(cfg, traffic, prompt, rows=None):
    """(operations, bytes) of the gated short convolutions of ONE
    prefill of `rows` rows, `slots` where the program does not say
    (`_admit_batch` runs the pool's full width whatever the number of
    real rows), of `prompt` tokens, all `conv` layers, the whole
    operator: the weights once a layer, each
    position's normed row in and output row out, each row's window
    read and written. With 512 positions and more the two projections'
    operations are the bound (2 x 4 x dim^2 a position against 33.5 MB
    of weights a layer), and those run inside the scope's own
    operations whatever the compiler prefetches."""
    s = sizes(cfg)
    layers, d = _layers(s, "conv"), s["dim"]
    rows = int(traffic["slots"] if rows is None else rows)
    tokens = rows * int(prompt)
    weights = sum(_bytes(n, s) for n in _KINDS["conv"]
                  if n != "ln1_gamma")
    nbytes = weights + tokens * 2 * d * _BF16 + \
        rows * 2 * (s["taps"] - 1) * d * _BF16
    flops = tokens * (2 * 4 * d * d + (2 * s["taps"] + 2) * d)
    return layers * flops, layers * nbytes


def decode_step_need(cfg, traffic):
    """(operations, bytes) of ONE decode step of the whole model with
    every slot busy: every weight outside the routed experts read once
    (the tied table once as the head, and a row a slot as the lookup),
    the routed experts hit, the windows read and written, the
    attention layers' key/value rows read up to the mean depth and one
    written; 2 operations a weight and token, and the two attention
    products over that depth."""
    s = sizes(cfg)
    slots = int(traffic["slots"])
    outside = sum(_bytes(n, s) for kind in s["kinds"]
                  for n in _KINDS[kind] if n not in _ROUTED)
    top = sum(_bytes(n, s) for n in _TOP) + slots * s["dim"] * _BF16
    per_slot = state_bytes_per_slot(cfg, traffic)
    depth = mean_depth(traffic)
    rows = per_slot["kv_rows"] * (depth + 1) / float(traffic["max_len"])
    moe_flops, moe_bytes = moe_experts_need(cfg, traffic)
    attn_flops = _layers(s, "attention") * slots * 2 * 2 * \
        s["heads"] * s["head"] * depth
    nbytes = outside + top + moe_bytes + slots * (
        2 * per_slot["conv_window"] + rows)
    return (slots * 2 * (outside + top) // _BF16 + moe_flops +
            attn_flops, nbytes)
