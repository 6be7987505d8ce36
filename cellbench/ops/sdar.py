"""Operations and bytes for the SDAR family, from shapes and from what
the run counted: what one block step (a forward of `slots` rows x
`block_length` positions) must move and compute, whatever the program
does to get there. Bytes are counted in the types the configuration
serves in (bf16 weights, activations and key/value rows); a weight or
an activation is counted once for each time the algorithm has to read
or write it, and temporaries not at all.

An expert's weights are counted only where the forward routed a token
to it: by the distinct experts hit a layer and forward that the
program's own counter reports (`traffic["measured"]`, filled by the
drive), never by all of `num_experts` — a forward with idle slots or
skewed routing needs fewer bytes, and its share of the roofline must
not read over 100% for it."""
import math

from cellbench.ops.granite import mean_depth
from cellbench.reference.sdar import _LAYER, _TOP, _shape, sizes

_BF16 = 2
_count = math.prod


def expert_params(cfg):
    """One expert: gate, up and down."""
    s = sizes(cfg)
    return 3 * s["dim"] * s["expert_ffn"]


def weight_bytes(cfg):
    """Every parameter once, in bf16."""
    s = sizes(cfg)
    return _BF16 * (sum(_count(_shape(n, s)) for n in _TOP) +
                    s["layers"] * sum(_count(_shape(n, s))
                                      for n in _LAYER))


def pairs_per_layer(cfg, traffic):
    """(token, expert) pairs of one forward in one layer."""
    s = sizes(cfg)
    return int(traffic["slots"]) * s["block"] * s["top_k"]


def experts_hit(cfg, traffic):
    """Distinct experts with a token, a layer and forward: measured
    where the drive has filled it in, else the most the pairs allow."""
    s = sizes(cfg)
    got = (traffic.get("measured") or {}).get(
        "experts_hit_per_layer_forward")
    return float(got) if got else float(
        min(s["experts"], pairs_per_layer(cfg, traffic)))


def moe_experts_need(cfg, traffic):
    """(operations, bytes) of the expert products of ONE block step,
    all layers: the weights of the experts hit, read once; for each
    pair the input row in, gate and up out and back in, the product in
    to the down projection, the output row out; 2 operations a weight
    and pair."""
    s = sizes(cfg)
    pairs = pairs_per_layer(cfg, traffic)
    acts = pairs * (2 * s["dim"] + 5 * s["expert_ffn"])
    nbytes = _BF16 * (experts_hit(cfg, traffic) * expert_params(cfg) +
                      acts)
    return (s["layers"] * 2 * pairs * expert_params(cfg),
            s["layers"] * nbytes)


def block_step_need(cfg, traffic):
    """(operations, bytes) of ONE block step of the whole model with
    every slot busy: attention, router, norm and head weights read
    once (the token table is read a row a position), the experts hit,
    the key/value rows of every layer read up to the mean depth; 2
    operations a weight and position, and the two attention products
    over that depth."""
    s = sizes(cfg)
    tokens = int(traffic["slots"]) * s["block"]
    shared = sum(_count(_shape(n, s)) for n in _LAYER
                 if not n.startswith("experts"))
    top = _count(_shape("lm_head_weight", s)) + s["dim"] * (1 + tokens)
    row = 2 * s["kv_heads"] * s["head"]            # k and v, one layer
    depth = mean_depth(traffic)
    cache = int(traffic["slots"]) * s["layers"] * row * depth
    moe_flops, moe_bytes = moe_experts_need(cfg, traffic)
    attn_flops = s["layers"] * tokens * 2 * 2 * s["heads"] * \
        s["head"] * depth
    return (2 * tokens * (s["layers"] * shared + top) + moe_flops +
            attn_flops,
            _BF16 * (s["layers"] * shared + top + cache) + moe_bytes)
