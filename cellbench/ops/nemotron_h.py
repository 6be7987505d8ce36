"""Operations and bytes for the Nemotron-H family, from shapes and from
what the run counted: what one decode step (one token for each of
`slots` rows) must move and compute on THIS chip, whatever the program
does to get there. Bytes are counted in the types the configuration
serves in (bf16 weights, activations, convolution window and key/value
rows; float32 scan state and router bias); a weight, a state or an
activation is counted once for each time the algorithm has to read or
write it, and temporaries not at all.

A routed expert's weights are counted only where the step routed a
token to it: by the distinct held experts hit a layer and step that the
program's own counter reports (`traffic["measured"]`, filled by the
drive), never by all the experts held, and its activations by the
pairs computed here — so a share of the roofline cannot read over 100%
for a step that hit few experts."""
import math

from cellbench.ops.granite import mean_depth
from cellbench.reference.nemotron_h import _KINDS, _TOP, _shape, sizes

_BF16, _F32 = 2, 4
_count = math.prod
_ROUTED = ("experts_w1_weight", "experts_w2_weight")


def _bytes(name, s):
    return _count(_shape(name, s)) * (
        _F32 if name == "gate_score_bias" else _BF16)


def expert_params(cfg):
    """One routed expert: into the hidden width and back."""
    s = sizes(cfg)
    return 2 * s["latent"] * s["expert_ffn"]


def weight_bytes(cfg):
    """Every parameter this chip holds, once."""
    s = sizes(cfg)
    return sum(_bytes(n, s) for n in _TOP) + sum(
        _bytes(n, s) for kind in s["kinds"] for n in _KINDS[kind])


def _layers(s, kind):
    return sum(k == kind for k in s["kinds"])


def _mamba(s):
    d_inner = s["m_heads"] * s["m_head"]
    return (_layers(s, "mamba"), d_inner,
            d_inner + 2 * s["m_groups"] * s["m_state"])


def state_bytes_per_slot(cfg, traffic):
    """By kind, as the deployment holds them: scan state, convolution
    window, key/value rows at `max_len` positions. An expert layer or
    an MLP holds none."""
    s = sizes(cfg)
    layers, d_inner, conv = _mamba(s)
    return {"scan_state": layers * d_inner * s["m_state"] * _F32,
            "conv_window": layers * (s["m_conv"] - 1) * conv * _BF16,
            "kv_rows": _layers(s, "attention") * 2 * s["kv_heads"] *
            s["head"] * int(traffic["max_len"]) * _BF16}


def pairs_per_layer(cfg, traffic):
    """(token, expert) pairs one step routes in one layer, over all
    the router's outputs."""
    return int(traffic["slots"]) * sizes(cfg)["top_k"]


def _measured(traffic, key):
    return (traffic.get("measured") or {}).get(key)


def pairs_here(cfg, traffic):
    """Pairs a layer and step whose expert this chip holds: measured
    where the drive has filled it in, else the share's mean."""
    s = sizes(cfg)
    got = _measured(traffic, "pairs_here_per_layer_step")
    return float(got) if got else \
        pairs_per_layer(cfg, traffic) * s["held"] / s["experts"]


def experts_hit(cfg, traffic):
    """Distinct held experts with a token, a layer and step: measured
    where the drive has filled it in, else the most the pairs allow."""
    got = _measured(traffic, "experts_hit_per_layer_step")
    return float(got) if got else float(
        min(sizes(cfg)["held"], pairs_here(cfg, traffic)))


def moe_experts_need(cfg, traffic):
    """(operations, bytes) of the routed experts' two products in ONE
    decode step, all expert layers: the weights of the experts hit,
    read once; for each pair computed here its latent row in, the
    hidden row out and back in, the latent row out; 2 operations a
    weight and pair."""
    s = sizes(cfg)
    layers = _layers(s, "experts")
    pairs = pairs_here(cfg, traffic)
    acts = pairs * 2 * (s["latent"] + s["expert_ffn"])
    nbytes = _BF16 * (experts_hit(cfg, traffic) * expert_params(cfg) +
                      acts)
    return layers * 2 * pairs * expert_params(cfg), layers * nbytes


def mamba2_step_need(cfg, traffic):
    """(operations, bytes) of the scan and convolution updates of ONE
    decode step, all Mamba-2 layers, all slots: the scan state read and
    written, the convolution window read and written, xBC and dt in, y
    out; 2 operations for each multiply-add of the state update, the
    read-out over the state size and the convolution. B and C come in
    `n_groups` groups: that widens xBC and the window, not the state."""
    s = sizes(cfg)
    layers, d_inner, conv = _mamba(s)
    slots = int(traffic["slots"])
    state = d_inner * s["m_state"]
    nbytes = 2 * state * _F32 + 2 * (s["m_conv"] - 1) * conv * _BF16 + \
        (conv + s["m_heads"] + d_inner) * _BF16
    flops = 2 * 2 * state + 2 * state + 2 * s["m_conv"] * conv
    return layers * slots * flops, layers * slots * nbytes


def decode_step_need(cfg, traffic):
    """(operations, bytes) of ONE decode step of the whole model with
    every slot busy: every weight outside the routed experts read once
    (the token table a row a slot), the routed experts hit, the
    Mamba-2 states and windows read and written, the attention layers'
    key/value rows read up to the mean depth, the head over the
    vocabulary's slice; 2 operations a weight and token."""
    s = sizes(cfg)
    slots = int(traffic["slots"])
    outside = sum(_bytes(n, s) for kind in s["kinds"]
                  for n in _KINDS[kind] if n not in _ROUTED)
    top = _bytes("lm_head_weight", s) + _bytes("ln_f_gamma", s) + \
        slots * s["dim"] * _BF16
    per_slot = state_bytes_per_slot(cfg, traffic)
    rows = per_slot["kv_rows"] * mean_depth(traffic) / \
        float(traffic["max_len"])
    moe_flops, moe_bytes = moe_experts_need(cfg, traffic)
    nbytes = outside + top + moe_bytes + slots * (
        2 * per_slot["scan_state"] + 2 * per_slot["conv_window"] + rows)
    return slots * 2 * (outside + top) // _BF16 + moe_flops, nbytes
