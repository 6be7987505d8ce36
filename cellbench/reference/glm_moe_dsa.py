"""Plain reference for the GLM-MoE-DSA family (`glm_moe_dsa`): a
pre-norm stack whose mixer is multi-head latent attention over keys
that a learned indexer selects, and whose FFN is a dense SwiGLU in the
leading `first_k_dense_replace` layers and sigmoid-routed SwiGLU
experts beside a shared expert after. Straightforward `jax.numpy` in
float32 at `highest` matmul precision, no cache, no kernel; it imports
nothing of the program. (One economy: a held expert runs over the
tokens routed to it, `_experts`, not over every token.) For a
position's hidden x (RMSNorm eps `rms_norm_eps`, no bias but the index
key's LayerNorm; t a query position, s <= t a key position):

    n         = RMSNorm(x; g1)
    cq        = RMSNorm(n Wqa; gq)
    [qn | qr]_h = split(cq Wqb)_h                   H heads x (nope | rope)
    [c | kr]  = split(n Wkva);  c <- RMSNorm(c; gkv)   kr shared by all heads
    qr_h, kr <- rope_interleaved(.; theta, own position)
    [kn_h | v_h]_s = split(c_s Wkb)_h               keys and values EXPANDED a head
    qi_j      = split(cq Wiq)_j, its first `rope` channels rotated      J index heads of Di
    ki        = LayerNorm(n Wik; gain, bias, eps 1e-6), its first `rope` channels rotated
    w_j       = (n Wiw)_j * J^-1/2 * Di^-1/2
    I[t, s]   = sum_j w_j[t] relu(qi_j[t] . ki[s])
    S_t       = the min(t + 1, index_topk) positions s <= t of largest I[t, s]   (a tie: the lower s)
    a_h[t]    = sum_{s in S_t} softmax_{S_t}((qn_h[t] . kn_h[s] + qr_h[t] . kr[s]) / sqrt(nope + rope)) v_h[s]
    x        <- x + concat_h(a_h) Wo
    m         = RMSNorm(x; g2)
    dense:    x <- x + Wdown(silu(Wgate m) * (Wup m))
    experts:  p = sigmoid(float32(m) Wr);  E = top_k(p + b)   (a tie: the lower index)
              w_e = scale * p_e / sum_{e' in E} p_e'
              x <- x + sum_{e in E, e held here} w_e Wdown_e(silu(Wgate_e m) * (Wup_e m))
                     + Wdown'(silu(Wgate' m) * (Wup' m))                the shared expert
    logits    = RMSNorm_f(x) H^T                     H the (sliced) head, its own array

**The selection**: the k-th largest visible score of a query is read
off `jax.lax.top_k` over the masked float32 scores of a block of
queries (exact; a sort), and the keys over it are selected, of those
equal to it the lowest positions: the set whose indices `top_k` itself
returns, as a (queries, positions) mask that all H heads of the block
share. Each head's softmax runs over the masked score matrix of the
keys expanded for that head. (A literal gather of the expanded rows,
2 048 x H x (nope + rope + v) float32 numbers a query, moves 4.4 TB a
layer at 16 512 positions; the mask holds the same set.) Nothing here
is computed in the latent space: the program's form, the query carried
through Wkb, shares no algebra with this one. A block of queries is
computed against the keys up to its own end, at the nearest of four
key counts (`_key_counts`): the layer is causal.

**The share.** The configuration states how many of the router's
outputs are held here (`n_routed_experts` of `router_outputs`, from
`routed_experts_first`) and a slice of the vocabulary; the reference is
given the same share and, like the program, leaves out what the absent
experts would add: that partial result goes on to the next layer.

The program spells the stack one sublayer a layer, so the weights are
named `layer{2l}_*` for layer l's mixer and `layer{2l+1}_*` for its
FFN, each with its one norm `ln1_gamma`; `sizes(cfg)["kinds"]` is that
list of 2 x `num_hidden_layers` sublayers.

The weights belong to the benchmark: `make_params` draws every tensor
from the seed in the served type, under the program's parameter names
and layouts; the reference draws them again, a sublayer and an expert
at a time. One layout differs, exactly: the program's rotation pairs
channel i with channel i + rope/2 of the rotary slice, the published
`rope_interleave` pairs 2i with 2i + 1, so `make_params` hands the
program the rotary rows of Wqb (the last `rope` of each head), Wkva
(its last `rope`), Wiq (the first `rope` of each index head) and Wik
with its LayerNorm's gain and bias (their first `rope`) in the order
(0, 2, 4, ... | 1, 3, 5, ...): a permutation of a query's and a key's
channels alike leaves every product as it was. Departures and
assumptions are listed in the configuration's file.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np

from cellbench.reference._seeded import base_key, uniform
from cellbench.reference.opt import (_as_int8_holds, logit_errors,
                                     served_gaps)

__all__ = ["sizes", "make_params", "logits_at", "served_logits",
           "served_gaps", "logit_errors"]

_TOP = ("tok_embed_weight", "ln_f_gamma", "lm_head_weight")
_KINDS = {
    "mla": ("ln1_gamma", "mla_q_a_weight", "mla_q_a_norm_gamma",
            "mla_q_b_weight", "mla_kv_a_weight", "mla_kv_a_norm_gamma",
            "mla_kv_b_weight", "mla_o_weight", "mla_index_q_weight",
            "mla_index_k_weight", "mla_index_k_norm_gamma",
            "mla_index_k_norm_beta", "mla_index_head_weight"),
    "mlp": ("ln1_gamma", "fc1_weight", "fc2_weight"),
    # the routed and the shared experts are drawn an expert at a time
    "experts": ("ln1_gamma", "gate_weight", "gate_score_bias"),
}
_ROUTED, _SHARED = 1, 2
# what a weight-only int8 path would hold in int8, one scale an output
# channel: every (out, in) projection, the table and the head, and an
# expert's (in, out) pair (whose input axis a scale spans either way:
# `_twin`). Gains, the index key's bias, the router and its choosing
# bias stay as drawn.
_INT8 = ("tok_embed_weight", "lm_head_weight", "mla_q_a_weight",
         "mla_q_b_weight", "mla_kv_a_weight", "mla_kv_b_weight",
         "mla_o_weight", "mla_index_q_weight", "mla_index_k_weight",
         "mla_index_head_weight", "fc1_weight", "fc2_weight")
# (mean, deviation) of the uniform draw, for what is not a projection:
# the choosing bias wide enough to change which experts are chosen
_BIAS_RANGE = (0.0, 0.1)
_INDEX_EPS = 1e-6


def sizes(cfg):
    if cfg["attention_bias"] or cfg["hidden_act"] != "silu" or \
            cfg["scoring_func"] != "sigmoid" or \
            cfg["topk_method"] != "noaux_tc" or \
            int(cfg["n_group"]) != 1 or int(cfg["topk_group"]) != 1 or \
            int(cfg["moe_layer_freq"]) != 1 or \
            int(cfg["n_shared_experts"]) != 1 or \
            not cfg["rope_interleave"] or \
            not cfg["indexer_rope_interleave"] or \
            cfg["rope_parameters"]["rope_type"] != "default" or \
            cfg["tie_word_embeddings"] or \
            int(cfg["qk_head_dim"]) != int(cfg["qk_nope_head_dim"]) + \
            int(cfg["qk_rope_head_dim"]):
        raise ValueError(
            "glm_moe_dsa reference: bias-free latent attention with "
            "interleaved rotary pairs and no rotary scaling, SwiGLU "
            "experts chosen by sigmoid scores with a choosing bias and "
            "no routing in groups, an expert layer after every dense "
            "one, one shared expert and an untied head are assumed")
    layers, dense = int(cfg["num_hidden_layers"]), \
        int(cfg["first_k_dense_replace"])
    if not 0 <= dense <= layers:
        raise ValueError("glm_moe_dsa reference: first_k_dense_replace "
                         "must lie within num_hidden_layers")
    return dict(
        dim=int(cfg["hidden_size"]), heads=int(cfg["num_attention_heads"]),
        vocab=int(cfg["vocab_size"]), layers=layers,
        # one entry a sublayer, as the program spells the stack
        kinds=tuple(k for l in range(layers) for k in
                    ("mla", "mlp" if l < dense else "experts")),
        q_rank=int(cfg["q_lora_rank"]), kv_rank=int(cfg["kv_lora_rank"]),
        nope=int(cfg["qk_nope_head_dim"]), rope=int(cfg["qk_rope_head_dim"]),
        v_head=int(cfg["v_head_dim"]),
        index_heads=int(cfg["index_n_heads"]),
        index_head=int(cfg["index_head_dim"]),
        index_topk=int(cfg["index_topk"]),
        theta=float(cfg["rope_parameters"]["rope_theta"]),
        positions=int(cfg["max_position_embeddings"]),
        ffn=int(cfg["intermediate_size"]),
        experts=int(cfg["router_outputs"]),
        held=int(cfg["n_routed_experts"]),
        first=int(cfg["routed_experts_first"]),
        top_k=int(cfg["num_experts_per_tok"]),
        expert_ffn=int(cfg["moe_intermediate_size"]),
        renorm=bool(cfg["norm_topk_prob"]),
        scale=float(cfg["routed_scaling_factor"]),
        eps=float(cfg["rms_norm_eps"]),
        std=float(cfg["initializer_range"]))


def _shape(name, s):
    d, h, lq, l = s["dim"], s["heads"], s["q_rank"], s["kv_rank"]
    rd, j, di = s["rope"], s["index_heads"], s["index_head"]
    return {
        "tok_embed_weight": (s["vocab"], d), "ln_f_gamma": (d,),
        "lm_head_weight": (s["vocab"], d), "ln1_gamma": (d,),
        "mla_q_a_weight": (lq, d), "mla_q_a_norm_gamma": (lq,),
        "mla_q_b_weight": (h * (s["nope"] + rd), lq),
        "mla_kv_a_weight": (l + rd, d), "mla_kv_a_norm_gamma": (l,),
        "mla_kv_b_weight": (h * (s["nope"] + s["v_head"]), l),
        "mla_o_weight": (d, h * s["v_head"]),
        "mla_index_q_weight": (j * di, lq),
        "mla_index_k_weight": (di, d),
        "mla_index_k_norm_gamma": (di,), "mla_index_k_norm_beta": (di,),
        "mla_index_head_weight": (j, d),
        "fc1_weight": (2 * s["ffn"], d),         # [gate | up]
        "fc2_weight": (d, s["ffn"]),
        "gate_weight": (d, s["experts"]),
        "gate_score_bias": (s["experts"],)}[name]


def _draw(key, name, s, dtype):
    """One tensor in the served type: projections, the table and the
    head uniform with deviation `initializer_range`, gains around 1,
    the index key's bias around 0; the router's choosing bias in its
    own range and in float32 whatever the served type."""
    mean, dev = (1.0 if name.endswith("gamma") else 0.0), s["std"]
    if name == "gate_score_bias":
        (mean, dev), dtype = _BIAS_RANGE, jnp.float32
    return uniform(key, _shape(name, s), dev, mean).astype(dtype)


def _top_tensors(key, s, dtype):
    tkey = jax.random.fold_in(key, 0)
    return {n: _draw(jax.random.fold_in(tkey, i), n, s, dtype)
            for i, n in enumerate(_TOP)}


def _layer_tensors(key, layer, kind, s, dtype):
    """What a sublayer holds outside its experts, in the published
    layouts. `layer` (a sublayer's index) may be traced: sublayers of
    one kind share a program."""
    lkey = jax.random.fold_in(key, layer + 1)
    return {n: _draw(jax.random.fold_in(lkey, i), n, s, dtype)
            for i, n in enumerate(_KINDS[kind])}


def _expert_tensors(key, layer, stream, index, s, dtype):
    """One gated expert of sublayer `layer`, routed (`stream` _ROUTED,
    `index` counted over the HELD experts) or shared (_SHARED): w1
    (D, 2F) = [gate | up] and w2 (F, D). `layer` and `index` may be
    traced."""
    ekey = jax.random.fold_in(jax.random.fold_in(jax.random.fold_in(
        key, layer + 1), 100 + stream), index)
    d, f = s["dim"], s["expert_ffn"]
    return (uniform(jax.random.fold_in(ekey, 0), (d, 2 * f),
                    s["std"]).astype(dtype),
            uniform(jax.random.fold_in(ekey, 1), (f, d),
                    s["std"]).astype(dtype))


def _rotary_order(width, at, rope):
    """The order the program wants `width` rows in, for its half-split
    rotation of the `rope` rows from `at` on to be the published
    interleaved one: of those, the even ones, then the odd ones."""
    order = np.arange(width)
    order[at:at + rope] = at + np.concatenate(
        [np.arange(0, rope, 2), np.arange(1, rope, 2)])
    return order


def _by_head(heads, order):
    """`order` over one head's rows, for the rows of `heads` heads."""
    n = len(order)
    return (np.arange(heads)[:, None] * n + order[None, :]).reshape(-1)


def _program_layer(key, layer, kind, s, dtype):
    """One sublayer under the program's names and layouts (see the
    module docstring: the rotary rows regrouped, the experts stacked)."""
    out = dict(_layer_tensors(key, layer, kind, s, dtype))
    if kind == "mla":
        rd = s["rope"]
        head = _rotary_order(s["nope"] + rd, s["nope"], rd)
        index = _rotary_order(s["index_head"], 0, rd)
        for name, order in (
                ("mla_q_b_weight", _by_head(s["heads"], head)),
                ("mla_kv_a_weight",
                 _rotary_order(s["kv_rank"] + rd, s["kv_rank"], rd)),
                ("mla_index_q_weight",
                 _by_head(s["index_heads"], index)),
                ("mla_index_k_weight", index),
                ("mla_index_k_norm_gamma", index),
                ("mla_index_k_norm_beta", index)):
            out[name] = out[name][order]
    if kind == "experts":
        w1, w2 = jax.lax.map(
            lambda e: _expert_tensors(key, layer, _ROUTED, e, s, dtype),
            jnp.arange(s["held"]))
        out["experts_w1_weight"], out["experts_w2_weight"] = w1, w2
        out["shared_w1_weight"], out["shared_w2_weight"] = \
            _expert_tensors(key, layer, _SHARED, 0, s, dtype)
    return out


def make_params(cfg, seed, dtype="bfloat16"):
    """Every tensor of the model under the program's parameter names,
    made on the device: one small program for the top and one for each
    kind of sublayer (its index is an argument), called sublayer by
    sublayer."""
    s = sizes(cfg)
    dtype = jnp.dtype(dtype)
    key = base_key(seed)
    draw = {kind: jax.jit(functools.partial(
        _program_layer, kind=kind, s=s, dtype=dtype))
        for kind in set(s["kinds"])}
    out = dict(jax.jit(lambda k: _top_tensors(k, s, dtype))(key))
    for layer, kind in enumerate(s["kinds"]):
        for n, v in draw[kind](key, jnp.int32(layer)).items():
            out["layer%d_%s" % (layer, n)] = v
    return out


def _rms(x, g, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * g


def _rope_interleaved(x, theta):
    """(n, t, heads, r): channels 2i and 2i + 1 rotate together by
    position * theta ** (-2i / r)."""
    t, r = x.shape[1], x.shape[3]
    freqs = theta ** (-jnp.arange(0, r, 2, dtype=jnp.float32) / r)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * freqs
    cos, sin = (f(ang)[None, :, None, :] for f in (jnp.cos, jnp.sin))
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin,
                      even * sin + odd * cos], axis=-1).reshape(x.shape)


def _rotated(x, lo, hi, theta):
    """x (n, t, heads, w) with its channels lo .. hi - 1 rotated."""
    return jnp.concatenate([x[..., :lo],
                            _rope_interleaved(x[..., lo:hi], theta),
                            x[..., hi:]], axis=-1)


# queries of one block of a score matrix (the indexer's, a head's)
_QUERY_BLOCK = 1024


def _key_counts(blocks, qb):
    """The key counts a block of queries is computed against, ascending,
    the last all of them: whole blocks nearest to quarters of the
    sequence. Block b runs the smallest that holds its own keys
    (`_over`): every layer is causal, so a block early in a sequence
    need not score, rank and mask the keys behind it."""
    return sorted({-(-blocks * i // 4) * qb for i in range(1, 5)})


def _over(b, qb, counts, fn):
    """`fn(keys)` for block b of qb queries at the smallest of
    `counts` that holds positions 0 .. (b + 1) qb - 1."""
    return jax.lax.switch(
        sum(((b + 1) * qb > c).astype(jnp.int32) for c in counts[:-1]),
        [functools.partial(fn, c) for c in counts])


def selected(qi, ki, w, topk):
    """(blocks, n, qb, t) bool: which positions each query attends.
    qi (n, t, J, Di), ki (n, t, Di), w (n, t, J), t a whole number of
    blocks of qb queries (the caller pads; a padded query selects
    something and is dropped). The k-th largest visible score of each
    query by `jax.lax.top_k`; the keys over it, and of those equal to
    it the lowest positions, as many as are still owed (the order
    `top_k` itself gives its indices in)."""
    n, t, _ = ki.shape
    qb = min(t, _QUERY_BLOCK)
    counts = _key_counts(t // qb, qb)

    def block(b):
        rows = b * qb + jnp.arange(qb)
        q, wj = (jax.lax.dynamic_slice_in_dim(a, b * qb, qb, axis=1)
                 for a in (qi, w))

        def over(keys):
            def head(acc, j):
                dots = jnp.einsum("nqd,nkd->nqk", q[:, :, j],
                                  ki[:, :keys])
                return acc + wj[:, :, j, None] * jax.nn.relu(dots), None

            scores, _ = jax.lax.scan(head, jnp.zeros((n, qb, keys)),
                                     jnp.arange(qi.shape[2]))
            seen = jnp.arange(keys)[None, :] <= rows[:, None]
            scores = jnp.where(seen, scores, -jnp.inf)
            k = min(int(topk), keys)
            kth = jax.lax.top_k(scores, k)[0][..., -1:]
            above, at = scores > kth, scores == kth
            owed = k - above.sum(-1, keepdims=True)
            sel = (above | (at & (jnp.cumsum(at, -1) <= owed))) & seen
            return jnp.pad(sel, ((0, 0), (0, 0), (0, t - keys)))

        return _over(b, qb, counts, over)

    return jax.lax.map(block, jnp.arange(t // qb))


def _mixer(x, p, s):
    """Latent attention over the selected keys on (n, t, D) float32:
    keys and values expanded a head, a head and a block of queries at
    a time."""
    n, t, _ = x.shape
    h, nope, rd, vd = s["heads"], s["nope"], s["rope"], s["v_head"]
    lat, j, di = s["kv_rank"], s["index_heads"], s["index_head"]
    a = _rms(x, p["ln1_gamma"], s["eps"])
    cq = _rms(a @ p["mla_q_a_weight"].T, p["mla_q_a_norm_gamma"],
              s["eps"])
    q = _rotated((cq @ p["mla_q_b_weight"].T).reshape(n, t, h, nope + rd),
                 nope, nope + rd, s["theta"])
    ckr = a @ p["mla_kv_a_weight"].T
    c = _rms(ckr[..., :lat], p["mla_kv_a_norm_gamma"], s["eps"])
    kr = _rope_interleaved(ckr[:, :, None, lat:], s["theta"])[:, :, 0]
    qi = _rotated((cq @ p["mla_index_q_weight"].T).reshape(n, t, j, di),
                  0, rd, s["theta"])
    ki = a @ p["mla_index_k_weight"].T
    mu = ki.mean(-1, keepdims=True)
    ki = (ki - mu) / jnp.sqrt(((ki - mu) ** 2).mean(-1, keepdims=True)
                              + _INDEX_EPS)
    ki = ki * p["mla_index_k_norm_gamma"] + p["mla_index_k_norm_beta"]
    ki = _rotated(ki[:, :, None], 0, rd, s["theta"])[:, :, 0]
    w = (a @ p["mla_index_head_weight"].T) * (j ** -0.5 * di ** -0.5)

    # whole blocks of queries: a padded position is seen by no real one
    qb = min(t, _QUERY_BLOCK)
    blocks = -(-t // qb)
    q, qi, w, c, kr, ki = (
        jnp.pad(v, ((0, 0), (0, blocks * qb - t)) + ((0, 0),) *
                (v.ndim - 2)) for v in (q, qi, w, c, kr, ki))
    sel = selected(qi, ki, w, s["index_topk"])
    counts = _key_counts(blocks, qb)
    wkb = p["mla_kv_b_weight"].reshape(h, nope + vd, lat)

    def head(g):
        kv = c @ wkb[g].T                               # (n, t, nope + vd)
        every = jnp.concatenate([kv[..., :nope], kr], axis=-1)

        def block(b):
            qh = jax.lax.dynamic_slice_in_dim(q[:, :, g], b * qb, qb,
                                              axis=1)

            def over(keys):
                scores = jnp.einsum("nqd,nkd->nqk", qh, every[:, :keys]) \
                    / np.sqrt(nope + rd)
                scores = jnp.where(sel[b][:, :, :keys], scores, -jnp.inf)
                return jnp.einsum("nqk,nkd->nqd",
                                  jax.nn.softmax(scores, -1),
                                  kv[:, :keys, nope:])

            return _over(b, qb, counts, over)

        return jax.lax.map(block, jnp.arange(blocks))   # (blocks, n, qb, vd)

    att = jax.lax.map(head, jnp.arange(h))              # (h, blocks, n, qb, vd)
    att = att.transpose(2, 1, 3, 0, 4).reshape(n, blocks * qb, h * vd)
    return x + att[:, :t] @ p["mla_o_weight"].T


def _gated(a, w1, w2):
    f = w2.shape[0]
    return (jax.nn.silu(a @ w1[:, :f]) * (a @ w1[:, f:])) @ w2


def _dense(x, p, s):
    a = _rms(x, p["ln1_gamma"], s["eps"])
    return x + _gated(a, p["fc1_weight"].T, p["fc2_weight"].T)


def _chosen(a, p, s):
    """(tokens, router outputs) weights, zero outside each token's
    chosen experts: float32 sigmoid scores, the top_k largest of score
    + bias (a tie: the lower index), the weights from the scores
    alone, divided by their sum, times the scaling factor."""
    score = jax.nn.sigmoid(a @ p["gate_weight"])
    rows = jnp.arange(a.shape[0])
    left = score + p["gate_score_bias"]
    chosen = jnp.zeros_like(score)
    for _ in range(s["top_k"]):
        best = jnp.argmax(left, axis=-1)
        chosen = chosen.at[rows, best].set(score[rows, best])
        left = left.at[rows, best].set(-jnp.inf)
    if s["renorm"]:
        chosen = chosen / chosen.sum(-1, keepdims=True)
    return s["scale"] * chosen


# token counts one held expert may be computed over (a sequence
# shorter than one runs whole): 16 512 tokens send a router output 516
# of them on average, and the choosing bias sends a favoured one
# several times that
_EXPERT_TOKENS = (1024, 4096)


def _experts(x, p, expert, s):
    """The routed experts held here, one after the other, each over
    the tokens routed to it and weighted by each token's weight for it,
    then the shared expert over every token. `expert(stream, index)`
    hands each one's float32 pair as it is needed. An expert's tokens
    are those of largest weight for it, at the smallest count of
    `_EXPERT_TOKENS` (or all) that holds every token routed to it: the
    rest of them weigh zero and add nothing. Every expert over every
    token would be 32 times the routed pairs at the published sizes."""
    a = _rms(x, p["ln1_gamma"], s["eps"])
    a = a.reshape(-1, a.shape[-1])
    weights = _chosen(a, p, s)[:, s["first"]:s["first"] + s["held"]].T
    tokens = a.shape[0]
    counts = sorted({min(tokens, c) for c in _EXPERT_TOKENS} | {tokens})
    weight, token = jax.lax.top_k(weights, tokens)      # (held, tokens)

    def routed(r, at):
        e, w_e, t_e, n_e = at

        def over(count):
            return r.at[t_e[:count]].add(w_e[:count, None] * _gated(
                a[t_e[:count]], *expert(_ROUTED, e)))

        return jax.lax.switch(
            sum((n_e > c).astype(jnp.int32) for c in counts[:-1]),
            [functools.partial(over, c) for c in counts]), None

    r, _ = jax.lax.scan(routed, jnp.zeros_like(a),
                        (jnp.arange(s["held"]), weight, token,
                         (weights > 0).sum(-1)))
    return x + (r + _gated(a, *expert(_SHARED, 0))).reshape(x.shape)


def _twin(w, int8):
    """A float32 weight as drawn, or as a weight-only int8 path holds
    it (one scale an output channel), selected on the device. (out,
    in) matrices are scaled over axis 1; an expert's (in, out) pair
    over axis 0."""
    return jnp.where(int8, _as_int8_holds(w), w)


@functools.lru_cache(maxsize=None)
def _programs(frozen, dtype_name):
    """The jitted pieces, compiled once per (sizes, served type):
    embed, ONE program a kind of sublayer (its index is an argument),
    head. Each draws its own weights, the experts' one expert at a
    time, and frees them when it returns. `int8` is an argument of
    each and not a second set of programs: the weights as drawn, or as
    a weight-only int8 path holds them, selected on the device."""
    s = dict(frozen)
    dtype = jnp.dtype(dtype_name)

    def up(tree, int8):
        out = {n: v.astype(jnp.float32) for n, v in tree.items()}
        return {n: _twin(v, int8) if n in _INT8 else v
                for n, v in out.items()}

    @jax.jit
    def embed(key, tokens, int8):
        return up(_top_tensors(key, s, dtype),
                  int8)["tok_embed_weight"][tokens]

    def sublayer(kind):
        @jax.jit
        def run(key, index, x, int8):
            def expert(stream, e):
                w1, w2 = (w.astype(jnp.float32) for w in _expert_tensors(
                    key, index, stream, e, s, dtype))
                return _twin(w1.T, int8).T, _twin(w2.T, int8).T

            p = up(_layer_tensors(key, index, kind, s, dtype), int8)
            with jax.default_matmul_precision("highest"):
                if kind == "mla":
                    return _mixer(x, p, s)
                if kind == "mlp":
                    return _dense(x, p, s)
                return _experts(x, p, expert, s)

        return run

    @jax.jit
    def head(key, x, rows, int8):
        """Logits at the positions `rows` (N, R) of each sequence: the
        final norm, then the head."""
        p = up(_top_tensors(key, s, dtype), int8)
        picked = jnp.take_along_axis(x, rows[:, :, None], axis=1)
        with jax.default_matmul_precision("highest"):
            return _rms(picked, p["ln_f_gamma"], s["eps"]) \
                @ p["lm_head_weight"].T

    return embed, {k: sublayer(k) for k in _KINDS}, head


def logits_at(cfg, seed, tokens, rows, dtype="bfloat16", int8=False):
    """Full forward over `tokens` (N, T) int32, sublayer by sublayer,
    and the logits (N, R, V) at positions `rows` (N, R). `int8` rounds
    every projection's weight, the experts, the table and the head
    among them, to what a weight-only int8 path holds."""
    s = sizes(cfg)
    embed, sublayers, head = _programs(
        tuple(sorted(s.items())), str(jnp.dtype(dtype)))
    key = base_key(seed)
    int8 = jnp.bool_(int8)
    x = embed(key, jnp.asarray(tokens, jnp.int32), int8)
    for i, kind in enumerate(s["kinds"]):
        x = sublayers[kind](key, jnp.int32(i), x, int8)
    return head(key, x, jnp.asarray(rows, jnp.int32), int8)


def served_logits(cfg, seed, rows, dtype="bfloat16", pad_to=None,
                  served_to=None, int8=False, group=1):
    """For each served row (prompt_len, ids of prompt + served tokens),
    in order: the reference's logits (n, V) at the n positions that
    each predict one served token. `pad_to` and `served_to` fix the
    compiled shapes (longest row, most served tokens) from run to run.
    Rows are padded on the right: every layer is causal, so a real
    position never reads the padding. `group` rows go through one
    forward."""
    pad_to = pad_to or max(len(ids) for _, ids in rows)
    served_to = served_to or max(len(ids) - p for p, ids in rows)
    for lo in range(0, len(rows), group):
        part = rows[lo:lo + group]
        toks = np.zeros((group, pad_to), np.int32)
        where = np.zeros((group, served_to), np.int32)
        for i, (p, ids) in enumerate(part):
            toks[i, :len(ids)] = ids
            n = len(ids) - p
            # position p-1+j predicts the served token ids[p+j]
            where[i, :n] = np.arange(p - 1, p - 1 + n)
        out = np.asarray(logits_at(cfg, seed, toks, where, dtype, int8))
        for i, (p, ids) in enumerate(part):
            yield out[i, :len(ids) - p]
