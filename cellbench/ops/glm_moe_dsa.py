"""Operations and bytes for the GLM-MoE-DSA family, from shapes and
from what the run counted: what one decode step (one token for each of
`slots` rows) and one chunk forward of a chunked prefill (`rows` rows
of `hi - lo` new positions that start at depth `lo`) must move and
compute. Bytes are counted in the types the configuration serves in
(bf16 weights, activations, latent rows and index-key rows; float32
index scores and the router's float32 bias); a weight, a cache row or
an activation is counted once for each time the algorithm has to read
or write it, and temporaries not at all.

Every need counts what a SOUND program must read or compute and no
more, so that no share of a roofline can read over 100%: the indexer's
scores over the rows visible to each query (t + 1 of them, not the
quarter of `max_len` the program's chunk program scores and masks
them within), the attention over min(t + 1, index_topk) rows a query
with each cached latent row read once (the program runs both products
over every column of that quarter under the selection's mask), the
selection as the bytes of its scores in and its indices out (a
selection computes nothing a roofline counts), an
expert's weights only where the step routed a token to it, by the
program's own counters (`traffic["measured"]`, filled by the drive).
A chunk's needs take its span `(lo, hi)` from the program's own
`prefill_chunk` span (`cellbench/readers/chunk_depth.py`).
"""
import math

from cellbench.ops.cohere2_moe import _measured, _sum, _times
from cellbench.ops.granite import mean_depth
from cellbench.reference.glm_moe_dsa import _KINDS, _TOP, _shape, sizes

_BF16, _F32 = 2, 4
_count = math.prod
# the mixer's weights by the scope their products run under
_INDEXER = ("mla_index_q_weight", "mla_index_k_weight",
            "mla_index_k_norm_gamma", "mla_index_k_norm_beta",
            "mla_index_head_weight")
_ATTEND = ("mla_kv_b_weight",)


def _params(names, s):
    return sum(_count(_shape(n, s)) for n in names)


def expert_params(cfg):
    """One gated expert, routed or shared: gate, up and down."""
    s = sizes(cfg)
    return 3 * s["dim"] * s["expert_ffn"]


def _sublayer_params(kind, s):
    """A sublayer's parameters outside its routed experts (the shared
    expert among them)."""
    n = _params(_KINDS[kind], s)
    if kind == "experts":
        n += 3 * s["dim"] * s["expert_ffn"]
    return n


def _outside_params(s):
    return sum(_sublayer_params(k, s) for k in s["kinds"])


def _layers(s, kind):
    return s["kinds"].count(kind)


def param_count(cfg):
    """Every parameter held here: the table and the head each once."""
    s = sizes(cfg)
    return _params(_TOP, s) + _outside_params(s) + \
        _layers(s, "experts") * s["held"] * expert_params(cfg)


def weight_bytes(cfg):
    """The parameters in bf16, but each expert layer's choosing bias,
    which stays float32."""
    s = sizes(cfg)
    return _BF16 * param_count(cfg) + \
        _layers(s, "experts") * s["experts"] * (_F32 - _BF16)


def _row_widths(s):
    """(latent row, index-key row): what one position leaves behind in
    one layer, in numbers."""
    return s["kv_rank"] + s["rope"], s["index_head"]


def state_bytes_per_slot(cfg, traffic):
    """By kind, as the pool holds them: the latent rows and the
    index-key rows of every mixer at `max_len` positions."""
    s = sizes(cfg)
    f, di = _row_widths(s)
    n = _layers(s, "mla") * int(traffic["max_len"]) * _BF16
    return {"latent_rows": n * f, "index_rows": n * di}


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
    decode step, all expert layers: the weights of the held experts
    hit, read once; for each pair computed here the input row in, gate
    and up out and back in, the product in to the down projection, the
    output row out; 2 operations a weight and pair."""
    s = sizes(cfg)
    pairs = pairs_here(cfg, traffic)
    acts = pairs * (2 * s["dim"] + 5 * s["expert_ffn"])
    nbytes = _BF16 * (experts_hit(cfg, traffic) * expert_params(cfg) +
                      acts)
    return _times(_layers(s, "experts"),
                  (2 * pairs * expert_params(cfg), nbytes))


def _visible(lo, hi):
    """Sum over the queries t of [lo, hi) of the t + 1 rows each sees."""
    return (hi * (hi + 1) - lo * (lo + 1)) // 2


def _selected(lo, hi, k):
    """Sum over the queries t of [lo, hi) of min(t + 1, k)."""
    full = max(lo, min(hi, k - 1))         # queries from here on see k
    return _visible(lo, full) + (hi - full) * k


def _index(s, rows, lo, hi):
    """(operations, bytes) of one layer's indexer for `rows` x [lo, hi)
    queries: its three projections, its key rows written and every
    cached one read once, the scores of the rows each query sees (2
    operations a channel, and the rectifier's weighted sum) written in
    float32 for the selection."""
    n, j, di = rows * (hi - lo), s["index_heads"], s["index_head"]
    weights = _params(_INDEXER, s)
    seen = rows * _visible(lo, hi)
    flops = 2 * n * weights + seen * j * (2 * di + 2)
    nbytes = _BF16 * (weights + n * (s["dim"] + s["q_rank"]) +
                      rows * hi * di + n * di) + _F32 * seen
    return flops, nbytes


def _select(s, rows, lo, hi):
    """One layer's selection: the float32 scores each query sees in,
    an int32 index a selected row out; no operation a roofline
    counts."""
    return 0, _F32 * rows * (_visible(lo, hi) +
                             _selected(lo, hi, s["index_topk"]))


def _attend(s, rows, lo, hi):
    """(operations, bytes) of one layer's attention over the selected
    rows, in the latent space: the query carried through Wkb's key
    half and the result through its value half (2 operations a weight
    and token), both products over min(t + 1, index_topk) rows a
    query; the new latent rows written and every cached one read once,
    q in and the output out."""
    n, h = rows * (hi - lo), s["heads"]
    f, _ = _row_widths(s)
    weights = _params(_ATTEND, s)
    picked = rows * _selected(lo, hi, s["index_topk"])
    flops = 2 * n * weights + picked * h * 2 * (f + s["kv_rank"])
    nbytes = _BF16 * (weights + rows * hi * f + n * f +
                      n * h * (s["nope"] + s["rope"] + s["v_head"]))
    return flops, nbytes


def _step_span(traffic):
    depth = int(round(mean_depth(traffic)))
    return depth, depth + 1


def mla_attend_step_need(cfg, traffic):
    """The indexer, the selection and the attention of ONE decode step
    (what runs under "mla.keys"), all mixers, every slot busy at the
    mean depth: every index-key row to the row's depth, min(depth,
    index_topk) latent rows."""
    s = sizes(cfg)
    span = (int(traffic["slots"]),) + _step_span(traffic)
    return _times(_layers(s, "mla"), _sum(
        _index(s, *span), _select(s, *span), _attend(s, *span)))


def _chunk(need):
    """A chunk forward's need of one part, all mixers: `span` = (lo,
    hi) of the program's chunk span, `rows` the rows the forward ran
    (the pool's width where the program does not say)."""
    def fn(cfg, traffic, span, rows=None):
        s = sizes(cfg)
        rows = int(traffic["slots"] if rows is None else rows)
        return _times(_layers(s, "mla"),
                      need(s, rows, int(span[0]), int(span[1])))
    return fn


dsa_index_chunk_need = _chunk(_index)
dsa_select_chunk_need = _chunk(_select)
mla_attend_chunk_need = _chunk(_attend)


def _top_bytes(s, tokens):
    """The head once and a row of the table a token, the final norm."""
    return _BF16 * (_params(_TOP[1:], s) + tokens * s["dim"])


def _dense_params(s):
    """What every token's products read outside the indexer, Wkb and
    the routed experts: the mixers' four projections and norms, the
    dense FFN, the routers, the shared experts."""
    return _outside_params(s) - _layers(s, "mla") * (
        _params(_INDEXER, s) + _params(_ATTEND, s))


def decode_step_need(cfg, traffic):
    """(operations, bytes) of ONE decode step of the whole model with
    every slot busy: every weight outside the routed experts read once
    (the head once, a table row a slot), the held experts hit, the
    cached rows as `mla_attend_step_need` counts them; 2 operations a
    weight and token."""
    s = sizes(cfg)
    slots = int(traffic["slots"])
    dense = _dense_params(s)
    products = (slots * 2 * (dense + s["vocab"] * s["dim"]),
                _BF16 * dense + _layers(s, "experts") * s["experts"] *
                (_F32 - _BF16) + _top_bytes(s, slots))
    return _sum(products, moe_experts_need(cfg, traffic),
                mla_attend_step_need(cfg, traffic))


def chunk_products_need(cfg, traffic, span, rows=None):
    """Everything of ONE chunk forward outside "mla.keys": each weight
    read once (with 512 tokens every held expert is hit), 2 operations
    a weight and token for the projections, the dense FFN, the shared
    experts, the router and the head at every position, and for the
    routed experts the pairs the held share expects (`top_k * held /
    experts` a token: a chunk forward returns no counts)."""
    s = sizes(cfg)
    rows = int(traffic["slots"] if rows is None else rows)
    n = rows * (int(span[1]) - int(span[0]))
    dense = _dense_params(s)
    routed = _layers(s, "experts") * s["held"] * expert_params(cfg)
    per_token = dense + s["vocab"] * s["dim"] + \
        routed * s["top_k"] / s["experts"]
    acts = n * len(s["kinds"]) * 4 * s["dim"] * _BF16
    nbytes = _BF16 * (dense + routed) + _layers(s, "experts") * \
        s["experts"] * _F32 + _top_bytes(s, n) + acts
    return 2 * n * per_token, nbytes


def chunk_forward_need(cfg, traffic, span, rows=None):
    """(operations, bytes) of ONE whole chunk forward: its products,
    the indexer, the selection and the attention."""
    return _sum(chunk_products_need(cfg, traffic, span, rows),
                dsa_index_chunk_need(cfg, traffic, span, rows),
                dsa_select_chunk_need(cfg, traffic, span, rows),
                mla_attend_chunk_need(cfg, traffic, span, rows))
