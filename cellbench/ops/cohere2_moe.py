"""Operations and bytes for the Cohere2-MoE family, from shapes and
from what the run counted: what one decode step (one token for each of
`slots` rows) and one chunk forward of a chunked prefill (`rows` rows
of `tokens` new positions at one shared offset) must move and compute.
Bytes are counted in the types the configuration serves in (bf16
weights, activations and key/value rows; the router's float32 bias); a
weight, a cache row or an activation is counted once for each time the
algorithm has to read or write it, and temporaries not at all.

Two conventions, each chosen so that a share of the roofline cannot
read over 100%. A decode step counts what a sound program must READ:
in a sliding layer the `window` newest rows a slot (every context of
the cell is past the window), though the program's masked product
reads the whole circular buffer; an expert's weights only where the
step routed a token to it, by the program's own counters
(`traffic["measured"]`, filled by the drive). A chunk forward is bound
by its operations, and those are counted AS RUN: both attention
products over the whole masked buffer (`ring_rows` columns in a sliding
layer, `max_len` in the full one: the program's `_attend` computes
every column and masks), the head at every position, the rows the
forward ran (`rows`: the pool's width where the program does not say).
"""
import math

from cellbench.ops.granite import mean_depth
from cellbench.reference.cohere2_moe import (_LAYER, _TOP, _shape,
                                             sizes)

_BF16, _F32 = 2, 4
_count = math.prod


def ring_rows(cfg, traffic):
    """Rows of a sliding layer's circular buffer, by the program's own
    rule (`Generator._size_rings`): the window and the chunk a prompt
    is fed by, less one, rounded up to 8; never more than `max_len`."""
    s = sizes(cfg)
    chunk = int(traffic["prefill_chunk"])
    return min(int(traffic["max_len"]),
               -(-(s["window"] + chunk - 1) // 8) * 8)


def expert_params(cfg):
    """One gated expert, routed or shared: gate, up and down."""
    s = sizes(cfg)
    return 3 * s["dim"] * s["expert_ffn"]


def _outside_params(s):
    """A layer's parameters outside its routed experts: norm,
    attention, router, and the shared experts."""
    return sum(_count(_shape(n, s)) for n in _LAYER) + \
        s["shared"] * 3 * s["dim"] * s["expert_ffn"]


def param_count(cfg):
    """Every parameter held here, the tied table once."""
    s = sizes(cfg)
    return sum(_count(_shape(n, s)) for n in _TOP) + s["layers"] * (
        _outside_params(s) + s["held"] * expert_params(cfg))


def weight_bytes(cfg):
    """The parameters in bf16, and each layer's float32 choosing bias
    (zeros: the program's sigmoid router takes one)."""
    s = sizes(cfg)
    return _BF16 * param_count(cfg) + s["layers"] * s["experts"] * _F32


def _row_bytes(s):
    """One position's key and value rows in one layer."""
    return 2 * s["kv_heads"] * s["head"] * _BF16


def state_bytes_per_slot(cfg, traffic):
    """By kind, as the pool holds them: the full layers' key/value rows
    at `max_len` positions, the sliding layers' circular rows."""
    s = sizes(cfg)
    return {"kv_rows": s["types"].count("full") * _row_bytes(s) *
            int(traffic["max_len"]),
            "kv_window": s["types"].count("sliding") * _row_bytes(s) *
            ring_rows(cfg, traffic)}


def _measured(traffic, key, default):
    got = (traffic.get("measured") or {}).get(key)
    return float(got) if got else float(default)


def pairs_here(cfg, traffic):
    """(token, expert) pairs one step computes in one layer: measured
    where the drive has filled it in, else the held experts' share of
    every routed pair."""
    s = sizes(cfg)
    return _measured(traffic, "pairs_here_per_layer_step",
                     int(traffic["slots"]) * s["top_k"] * s["held"] /
                     s["experts"])


def experts_hit(cfg, traffic):
    """Distinct held experts with a token, a layer and step."""
    s = sizes(cfg)
    return _measured(traffic, "experts_hit_per_layer_step",
                     min(s["held"], pairs_here(cfg, traffic)))


def moe_experts_need(cfg, traffic):
    """(operations, bytes) of the routed experts' two products in ONE
    decode step, all layers: the weights of the held experts hit, read
    once; for each pair computed here the input row in, gate and up
    out and back in, the product in to the down projection, the output
    row out; 2 operations a weight and pair."""
    s = sizes(cfg)
    pairs = pairs_here(cfg, traffic)
    acts = pairs * (2 * s["dim"] + 5 * s["expert_ffn"])
    nbytes = _BF16 * (experts_hit(cfg, traffic) * expert_params(cfg) +
                      acts)
    return (s["layers"] * 2 * pairs * expert_params(cfg),
            s["layers"] * nbytes)


def _attend(s, rows, tokens, columns):
    """(operations, bytes) of one layer's write and both products for
    `rows` x `tokens` queries over `columns` cached positions: the
    cache rows read once a row, the new rows written, q in and the
    output out."""
    width = s["heads"] * s["head"]
    flops = rows * tokens * 2 * 2 * width * columns
    nbytes = rows * (columns + tokens) * _row_bytes(s) + \
        rows * tokens * 2 * width * _BF16
    return flops, nbytes


def _sum(*needs):
    return tuple(sum(part) for part in zip(*needs))


def _times(n, need):
    return n * need[0], n * need[1]


def attn_window_step_need(cfg, traffic):
    """The sliding layers of ONE decode step, every slot busy past the
    window: a slot's `window` newest rows read, one written."""
    s = sizes(cfg)
    return _times(s["types"].count("sliding"),
                  _attend(s, int(traffic["slots"]), 1, s["window"]))


def attn_full_step_need(cfg, traffic):
    """The full layers of ONE decode step: a slot's rows read up to
    the mean depth, one written."""
    s = sizes(cfg)
    return _times(s["types"].count("full"),
                  _attend(s, int(traffic["slots"]), 1,
                          mean_depth(traffic)))


def attn_window_chunk_need(cfg, traffic, tokens, rows=None):
    """The sliding layers of ONE chunk forward, as run: every column
    of the circular buffer."""
    s = sizes(cfg)
    rows = int(traffic["slots"] if rows is None else rows)
    return _times(s["types"].count("sliding"),
                  _attend(s, rows, int(tokens), ring_rows(cfg, traffic)))


def attn_full_chunk_need(cfg, traffic, tokens, rows=None):
    """The full layers of ONE chunk forward, as run: every column of
    the `max_len` buffer."""
    s = sizes(cfg)
    rows = int(traffic["slots"] if rows is None else rows)
    return _times(s["types"].count("full"),
                  _attend(s, rows, int(tokens), int(traffic["max_len"])))


def _top_bytes(s, tokens):
    """The tied table once as the head and a row a token as the
    lookup, the final norm."""
    return _BF16 * (sum(_count(_shape(n, s)) for n in _TOP) +
                    tokens * s["dim"])


def decode_step_need(cfg, traffic):
    """(operations, bytes) of ONE decode step of the whole model with
    every slot busy: every weight outside the routed experts read once
    (the tied table once as the head), the held experts hit, the
    key/value rows as the two functions above count them; 2 operations
    a weight and token."""
    s = sizes(cfg)
    slots = int(traffic["slots"])
    outside = s["layers"] * _outside_params(s) + \
        sum(_count(_shape(n, s)) for n in _TOP)
    products = (slots * 2 * outside,
                s["layers"] * (_BF16 * _outside_params(s) +
                               s["experts"] * _F32) + _top_bytes(s, slots))
    return _sum(products, moe_experts_need(cfg, traffic),
                attn_window_step_need(cfg, traffic),
                attn_full_step_need(cfg, traffic))


def chunk_products_need(cfg, traffic, tokens, rows=None):
    """Everything of ONE chunk forward but attention: each weight read
    once (with 1 024 tokens every held expert is hit), 2 operations a
    weight and token for the projections, the shared experts, the
    router and the head at every position, and for the routed experts
    the pairs the held share expects (`top_k * held / experts` a
    token: a chunk forward returns no counts)."""
    s = sizes(cfg)
    rows = int(traffic["slots"] if rows is None else rows)
    n = rows * int(tokens)
    per_token = s["layers"] * (
        _outside_params(s) +
        s["top_k"] * s["held"] / s["experts"] * expert_params(cfg)) + \
        s["vocab"] * s["dim"]
    acts = n * s["layers"] * 8 * s["dim"] * _BF16
    return 2 * n * per_token, weight_bytes(cfg) + \
        _top_bytes(s, n) - _BF16 * s["vocab"] * s["dim"] + acts


def chunk_forward_need(cfg, traffic, tokens, rows=None):
    """(operations, bytes) of ONE whole chunk forward: its products
    and both kinds of attention."""
    return _sum(chunk_products_need(cfg, traffic, tokens, rows),
                attn_window_chunk_need(cfg, traffic, tokens, rows),
                attn_full_chunk_need(cfg, traffic, tokens, rows))
