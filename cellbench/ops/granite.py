"""Operations and bytes for the Granite 4.0-H family, from shapes
alone: what one decode step and one prefill's Mamba-2 scans must move
and compute, whatever the program does to get there. Bytes are counted
in the types the configuration serves in (bf16 weights, activations,
convolution window and key/value rows; float32 scan state); a weight,
a state or an activation is counted once for each time the algorithm
has to read or write it, and temporaries not at all."""
import math
import statistics

from cellbench.reference.granite import _KINDS, _TOP, _shape, sizes

_BF16, _F32 = 2, 4
_count = math.prod


def weight_bytes(cfg):
    """Every parameter once, in bf16; the tied table is one array."""
    s = sizes(cfg)
    n = sum(_count(_shape(name, s)) for name in _TOP)
    for kind in s["kinds"]:
        n += sum(_count(_shape(name, s)) for name in _KINDS[kind])
    return _BF16 * n


def _mamba(s):
    d_inner = s["m_heads"] * s["m_head"]
    return (sum(k == "mamba" for k in s["kinds"]), d_inner,
            d_inner + 2 * s["m_state"])


def state_bytes_per_slot(cfg, traffic):
    """By kind, as the deployment holds them: scan state, convolution
    window, key/value rows at `max_len` positions."""
    s = sizes(cfg)
    layers, d_inner, conv = _mamba(s)
    hd = s["dim"] // s["heads"]
    return {"scan_state": layers * d_inner * s["m_state"] * _F32,
            "conv_window": layers * (s["m_conv"] - 1) * conv * _BF16,
            "kv_rows": (len(s["kinds"]) - layers) * 2 * s["kv_heads"] *
            hd * int(traffic["max_len"]) * _BF16}


def mamba2_step_need(cfg, traffic):
    """(operations, bytes) of the scan and convolution updates of ONE
    decode step, all Mamba-2 layers, all slots: the scan state read and
    written, the convolution window read and written, xBC and dt in, y
    out; 2 operations for each multiply-add of the state update, the
    read-out over d_state and the convolution."""
    s = sizes(cfg)
    layers, d_inner, conv = _mamba(s)
    slots = int(traffic["slots"])
    state = d_inner * s["m_state"]
    nbytes = 2 * state * _F32 + 2 * (s["m_conv"] - 1) * conv * _BF16 + \
        (conv + s["m_heads"] + d_inner) * _BF16
    flops = 2 * 2 * state + 2 * state + 2 * s["m_conv"] * conv
    return layers * slots * flops, layers * slots * nbytes


def mamba2_scan_need(cfg, traffic, prompt, rows=None):
    """(operations, bytes) of the chunked scans of ONE prefill of
    `rows` rows, `slots` where the program does not say, of `prompt`
    tokens, all Mamba-2 layers. Per chunk of W
    tokens: the causal half of C.B^T and of the (W, W) scores times x
    (W (W + 1) / 2 pairs), the read of the entry state by every
    position and the chunk's own state (each W * heads * head_dim *
    d_state multiply-adds). Bytes: activated xBC and dt in, y out,
    the state read and written once."""
    s = sizes(cfg)
    layers, d_inner, conv = _mamba(s)
    rows = int(traffic["slots"] if rows is None else rows)
    t = int(prompt)
    w = min(int(cfg["mamba_chunk_size"]), t)
    chunks = -(-t // w)
    pairs = w * (w + 1) // 2
    per_chunk = 2 * pairs * s["m_state"] + 2 * pairs * d_inner + \
        2 * 2 * w * d_inner * s["m_state"]
    nbytes = t * (conv + s["m_heads"] + d_inner) * _BF16 + \
        2 * d_inner * s["m_state"] * _F32
    return layers * rows * chunks * per_chunk, layers * rows * nbytes


def mean_depth(traffic):
    """Positions a slot holds on average while it decodes: its prompt
    and half of its answer (the deck's lengths are equally likely)."""
    return statistics.mean(traffic["prompt_lengths"]) + \
        0.5 * statistics.mean(traffic["output_lengths"])


def decode_step_need(cfg, traffic):
    """(operations, bytes) of ONE decode step of the whole model with
    every slot busy: each weight read once, the Mamba-2 states read
    and written, the attention layers' key/value rows read up to the
    mean depth; 2 operations a weight and token."""
    slots = int(traffic["slots"])
    per_slot = state_bytes_per_slot(cfg, traffic)
    rows = per_slot["kv_rows"] * mean_depth(traffic) / \
        float(traffic["max_len"])
    nbytes = weight_bytes(cfg) + slots * (
        2 * per_slot["scan_state"] + 2 * per_slot["conv_window"] + rows)
    return slots * weight_bytes(cfg) // _BF16 * 2, nbytes

