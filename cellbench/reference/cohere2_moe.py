"""Plain reference for the Cohere2-MoE family (`cohere2_moe`): a
parallel block (ONE bias-free LayerNorm a layer, read by the attention
and by the FFN, both added to the stream), sliding-window rotary
attention in three layers of four and position-free full attention in
the fourth, sigmoid-routed gated-SiLU experts beside averaged shared
experts, a head tied to the token table. Straightforward `jax.numpy`
in float32 at `highest` matmul precision: attention is the masked
score matrix (a block of query heads and of queries at a time, so that
a row of 8 320 positions fits: blocking the rows of a softmax changes
no number of it), the expert layer a loop over the held experts
weighted by a dense (tokens, experts) matrix that is zero outside each
token's chosen 8. No cache, no kernel, no sorting; it imports nothing
of the program.

    n      = LayerNorm(x)                  mean-subtracted, gain only, eps 1e-5
    q,k,v  = n Wq, n Wk, n Wv              H x hd | Hkv x hd | Hkv x hd, no bias
    sliding layer: q,k <- rope_interleaved(q,k; theta);  j visible to i  iff  0 <= i - j < window
    full layer:    no rotation;                          j visible to i  iff  j <= i
    a      = softmax(q k^T / sqrt(hd) + mask) v  Wo      kv head g serves query heads (H/Hkv) g ..
    s      = sigmoid(float32(n) Wr)                      (router outputs,)
    E      = top_k(s);  w_e = s_e / sum_{e' in E} s_e'   (a tie: the lower index)
    r      = sum_{e in E, e held here} w_e Wdown_e( silu(Wgate_e n) * (Wup_e n) )
    h      = (1/m) sum_{j=1..m} Wdown'_j( silu(Wgate'_j n) * (Wup'_j n) )    the shared experts, averaged
    x     <- x + a + r + h
    logits = LayerNorm_f(x) T^T * logit_scale            T the (sliced) token table

**The share.** The configuration states how many of the router's
outputs are held here (`num_experts` of `router_outputs`, from
`routed_experts_first`) and a slice of the vocabulary; the reference is
given the same share and, like the program, leaves out what the absent
experts would add: that partial result goes on to the next layer.

The weights belong to the benchmark: `make_params` draws every tensor
from the seed in the served type, under the program's parameter names
and layouts; the reference draws them again, a layer and an expert at
a time (one layer is 4.6 GB in float32). Two of the program's layouts
differ from the published ones, exactly: the program's rotation pairs
channel i with channel i + hd/2, the published `rope_gptj` pairs 2i
with 2i + 1, so `make_params` hands the program Wq's and Wk's head
rows in the order (0, 2, 4, ... | 1, 3, 5, ...) in the sliding layers
(a permutation of q's and k's channels alike leaves every q.k as it
was); and the m averaged shared experts are ONE gated expert of m
times the width, gates, ups and downs side by side, the downs times
1/m (m is a power of two, 4 as published, so the product is exact in
any float type; another m is refused). Departures and assumptions are
listed in the configuration's file.
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

_TOP = ("tok_embed_weight", "ln_f_gamma")
_LAYER = ("ln1_gamma", "qkv_weight", "proj_weight", "gate_weight")
# the tensors drawn an expert at a time, and their stream's number
_ROUTED, _SHARED = 1, 2
# what a weight-only int8 path would hold in int8, one scale an output
# channel: (out, in) matrices and an expert's (in, out) pair (whose
# input axis a scale spans either way: `_twin`). Gains and the router
# stay as drawn.
_INT8 = ("tok_embed_weight", "qkv_weight", "proj_weight")
_TYPES = {"sliding_attention": "sliding", "full_attention": "full"}


def sizes(cfg):
    types = tuple(cfg["layer_types"])
    if len(types) != int(cfg["num_hidden_layers"]) or \
            set(types) - set(_TYPES):
        raise ValueError("cohere2_moe reference: layer_types must name "
                         "each of the %s layers by one of %r"
                         % (cfg["num_hidden_layers"], sorted(_TYPES)))
    if cfg["attention_bias"] or cfg["use_qk_norm"] or \
            not cfg["use_parallel_block"] or \
            not cfg["use_gated_activation"] or \
            cfg["hidden_act"] != "silu" or \
            cfg["expert_selection_fn"] != "sigmoid" or \
            cfg["position_embedding_type"] != "rope_gptj" or \
            float(cfg["rotary_pct"]) != 1 or \
            int(cfg["first_k_dense_replace"]) != 0 or \
            cfg["shared_expert_combination_strategy"] != "average" or \
            bin(int(cfg["num_shared_experts"])).count("1") != 1 or \
            not cfg["tie_word_embeddings"]:
        raise ValueError(
            "cohere2_moe reference: a parallel block without bias or "
            "QK-norm, gated-SiLU experts chosen by sigmoid scores, "
            "a power of two of averaged shared experts (the loader folds "
            "1/m into their downs), interleaved rotary pairs over the "
            "whole head, no leading dense layer and a tied head are "
            "assumed")
    return dict(dim=int(cfg["hidden_size"]),
                heads=int(cfg["num_attention_heads"]),
                kv_heads=int(cfg["num_key_value_heads"]),
                head=int(cfg["head_dim"]),
                vocab=int(cfg["vocab_size"]),
                layers=len(types),
                types=tuple(_TYPES[t] for t in types),
                # one entry a layer that routes (every one does): the
                # serve_stream drive counts a step's expert layers by it
                kinds=("experts",) * len(types),
                window=int(cfg["sliding_window"]),
                theta=float(cfg["rope_theta"]),
                positions=int(cfg["max_position_embeddings"]),
                experts=int(cfg["router_outputs"]),
                held=int(cfg["num_experts"]),
                first=int(cfg["routed_experts_first"]),
                top_k=int(cfg["num_experts_per_tok"]),
                expert_ffn=int(cfg["intermediate_size"]),
                shared=int(cfg["num_shared_experts"]),
                renorm=bool(cfg["norm_topk_prob"]),
                eps=float(cfg["layer_norm_eps"]),
                logit_scale=float(cfg["logit_scale"]),
                std=float(cfg["initializer_range"]))


def _shape(name, s):
    d, hd = s["dim"], s["head"]
    q, kv = s["heads"] * hd, s["kv_heads"] * hd
    return {"tok_embed_weight": (s["vocab"], d), "ln_f_gamma": (d,),
            "ln1_gamma": (d,), "qkv_weight": (q + 2 * kv, d),
            "proj_weight": (d, q),
            "gate_weight": (d, s["experts"])}[name]


def _draw(key, name, s, dtype):
    """One tensor in the served type: projections uniform with
    deviation `initializer_range`, gains 1 (the family's)."""
    if name.endswith("gamma"):
        return jnp.ones(_shape(name, s), dtype)
    return uniform(key, _shape(name, s), s["std"]).astype(dtype)


def _top_tensors(key, s, dtype):
    tkey = jax.random.fold_in(key, 0)
    return {n: _draw(jax.random.fold_in(tkey, i), n, s, dtype)
            for i, n in enumerate(_TOP)}


def _layer_tensors(key, layer, s, dtype):
    """What a layer holds outside its experts, in the published
    layouts. `layer` may be traced: all layers share a program."""
    lkey = jax.random.fold_in(key, layer + 1)
    return {n: _draw(jax.random.fold_in(lkey, i), n, s, dtype)
            for i, n in enumerate(_LAYER)}


def _expert_tensors(key, layer, stream, index, s, dtype):
    """One gated expert of `layer`, routed (`stream` _ROUTED, `index`
    counted over the HELD experts) or shared (_SHARED): w1 (D, 2F) =
    [gate | up] and w2 (F, D). `layer` and `index` may be traced."""
    ekey = jax.random.fold_in(jax.random.fold_in(jax.random.fold_in(
        key, layer + 1), 100 + stream), index)
    d, f = s["dim"], s["expert_ffn"]
    return (uniform(jax.random.fold_in(ekey, 0), (d, 2 * f),
                    s["std"]).astype(dtype),
            uniform(jax.random.fold_in(ekey, 1), (f, d),
                    s["std"]).astype(dtype))


def _rotary_rows(s):
    """The order the program wants a head's hd rows of Wq and Wk in,
    for its half-split rotation to be the published interleaved one:
    the even channels, then the odd ones."""
    return np.concatenate([np.arange(0, s["head"], 2),
                           np.arange(1, s["head"], 2)])


def _program_layer(key, layer, kind, s, dtype):
    """One layer under the program's names and layouts (see the module
    docstring: Wq's and Wk's rows regrouped in a sliding layer, the
    shared experts side by side, the choosing bias zero)."""
    out = dict(_layer_tensors(key, layer, s, dtype))
    if kind == "sliding":
        hd = s["head"]
        rot = (s["heads"] + s["kv_heads"]) * hd
        order = (np.arange(0, rot, hd)[:, None] +
                 _rotary_rows(s)[None, :]).reshape(-1)
        out["qkv_weight"] = out["qkv_weight"].at[:rot].set(
            out["qkv_weight"][order])
    w1, w2 = jax.lax.map(
        lambda e: _expert_tensors(key, layer, _ROUTED, e, s, dtype),
        jnp.arange(s["held"]))
    out["experts_w1_weight"], out["experts_w2_weight"] = w1, w2
    g1, g2 = jax.lax.map(
        lambda j: _expert_tensors(key, layer, _SHARED, j, s, dtype),
        jnp.arange(s["shared"]))
    f = s["expert_ffn"]
    # m gated experts as one: [gate_1 .. gate_m | up_1 .. up_m], and
    # the downs stacked, each times 1/m (exact: m is a power of two)
    out["shared_w1_weight"] = jnp.concatenate(
        [g1[j][:, lo:lo + f] for lo in (0, f)
         for j in range(s["shared"])], axis=1)
    out["shared_w2_weight"] = (g2 / s["shared"]).astype(dtype).reshape(
        s["shared"] * f, s["dim"])
    out["gate_score_bias"] = jnp.zeros((s["experts"],), jnp.float32)
    return out


def make_params(cfg, seed, dtype="bfloat16"):
    """Every tensor of the model under the program's parameter names,
    made on the device: one small program for the top and one for each
    kind of layer (its index is an argument), called layer by layer."""
    s = sizes(cfg)
    dtype = jnp.dtype(dtype)
    key = base_key(seed)
    draw = {kind: jax.jit(functools.partial(
        _program_layer, kind=kind, s=s, dtype=dtype))
        for kind in set(s["types"])}
    out = dict(jax.jit(lambda k: _top_tensors(k, s, dtype))(key))
    for layer, kind in enumerate(s["types"]):
        for n, v in draw[kind](key, jnp.int32(layer)).items():
            out["layer%d_%s" % (layer, n)] = v
    return out


def _ln(x, g, eps):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * g


def _rope_interleaved(x, theta):
    """(n, t, heads, hd): channels 2i and 2i + 1 rotate together by
    position * theta ** (-2i / hd)."""
    t, hd = x.shape[1], x.shape[3]
    freqs = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * freqs
    cos, sin = (f(ang)[None, :, None, :] for f in (jnp.cos, jnp.sin))
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin,
                      even * sin + odd * cos], axis=-1).reshape(x.shape)


# queries of one block of the masked score matrix
_QUERY_BLOCK = 1024


def _attention(x, p, sliding, s):
    """`sliding` (a bool, which may be traced: the two kinds of layer
    share one compiled program): the rotation and the window, or
    neither."""
    n, t, _ = x.shape
    h, kv, hd = s["heads"], s["kv_heads"], s["head"]
    qkv = x @ p["qkv_weight"].T
    q = qkv[..., :h * hd].reshape(n, t, h, hd)
    k = qkv[..., h * hd:(h + kv) * hd].reshape(n, t, kv, hd)
    v = qkv[..., (h + kv) * hd:].reshape(n, t, kv, hd)
    q, k = (jnp.where(sliding, _rope_interleaved(a, s["theta"]), a)
            for a in (q, k))
    # a key/value head and the h / kv query heads it serves, a block
    # of queries at a time
    qb = min(t, _QUERY_BLOCK)
    blocks = -(-t // qb)
    q = jnp.pad(q, ((0, 0), (0, blocks * qb - t), (0, 0), (0, 0)))
    q = q.reshape(n, blocks, qb, kv, h // kv, hd)
    cols = jnp.arange(t)

    def one(at):
        g, b = at
        rows = b * qb + jnp.arange(qb)
        back = rows[:, None] - cols[None, :]
        seen = (back >= 0) & (jnp.logical_not(sliding) |
                              (back < s["window"]))
        scores = jnp.einsum("nqmd,nkd->nmqk", q[:, b, :, g], k[:, :, g]) \
            / np.sqrt(hd)
        scores = jnp.where(seen, scores, -jnp.inf)
        return jnp.einsum("nmqk,nkd->nqmd", jax.nn.softmax(scores, -1),
                          v[:, :, g])               # (n, qb, h/kv, hd)

    gs, bs = jnp.meshgrid(jnp.arange(kv), jnp.arange(blocks),
                          indexing="ij")
    att = jax.lax.map(one, (gs.reshape(-1), bs.reshape(-1)))
    att = att.reshape(kv, blocks, n, qb, h // kv, hd)
    att = att.transpose(2, 1, 3, 0, 4, 5).reshape(n, blocks * qb, h * hd)
    return att[:, :t] @ p["proj_weight"].T


def _chosen(a, p, s):
    """(tokens, router outputs) weights, zero outside each token's
    chosen experts: float32 sigmoid scores, the top_k largest (a tie:
    the lower index), divided by their sum."""
    score = jax.nn.sigmoid(a @ p["gate_weight"])
    rows = jnp.arange(a.shape[0])
    left = score
    chosen = jnp.zeros_like(score)
    for _ in range(s["top_k"]):
        best = jnp.argmax(left, axis=-1)
        chosen = chosen.at[rows, best].set(score[rows, best])
        left = left.at[rows, best].set(-jnp.inf)
    if s["renorm"]:
        chosen = chosen / chosen.sum(-1, keepdims=True)
    return chosen


def _gated(a, w1, w2):
    f = w2.shape[0]
    return (jax.nn.silu(a @ w1[:, :f]) * (a @ w1[:, f:])) @ w2


def _ffn(x, p, expert, s):
    """The routed experts held here, one after the other, each over
    every token and weighted by that token's weight for it (zero where
    it was not chosen), then the mean of the shared experts.
    `expert(stream, index)` hands each one's float32 pair as it is
    needed."""
    a = x.reshape(-1, x.shape[-1])
    weights = _chosen(a, p, s)[:, s["first"]:s["first"] + s["held"]]

    def routed(r, at):
        e, weight = at
        return r + weight[:, None] * _gated(a, *expert(_ROUTED, e)), None

    def shared(r, j):
        return r + _gated(a, *expert(_SHARED, j)), None

    r, _ = jax.lax.scan(routed, jnp.zeros_like(a),
                        (jnp.arange(s["held"]), weights.T))
    h, _ = jax.lax.scan(shared, jnp.zeros_like(a),
                        jnp.arange(s["shared"]))
    return (r + h / s["shared"]).reshape(x.shape)


def _layer(x, p, expert, sliding, s):
    """One parallel block on (N, T, D) float32; `sliding`: which kind
    of attention layer it is (see _attention)."""
    a = _ln(x, p["ln1_gamma"], s["eps"])
    return x + _attention(a, p, sliding, s) + _ffn(a, p, expert, s)


def _twin(w, int8):
    """A float32 weight as drawn, or as a weight-only int8 path holds
    it (one scale an output channel), selected on the device. (out,
    in) matrices are scaled over axis 1; an expert's (in, out) pair
    over axis 0."""
    return jnp.where(int8, _as_int8_holds(w), w)


@functools.lru_cache(maxsize=None)
def _programs(frozen, dtype_name):
    """The jitted pieces, compiled once per (sizes, served type):
    embed, ONE layer (its index and its kind are arguments, so every
    layer shares one program: a layer of this size compiles for most
    of a minute on an empty cache), head. Each draws its own weights,
    the experts' one expert at a time, and frees them when it returns.
    `int8` is an argument of each and not a second set of programs:
    the weights as drawn, or as a weight-only int8 path holds them,
    selected on the device."""
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

    @jax.jit
    def layer(key, index, sliding, x, int8):
        def expert(stream, e):
            w1, w2 = (w.astype(jnp.float32) for w in _expert_tensors(
                key, index, stream, e, s, dtype))
            return _twin(w1.T, int8).T, _twin(w2.T, int8).T

        with jax.default_matmul_precision("highest"):
            return _layer(x, up(_layer_tensors(key, index, s, dtype),
                                int8), expert, sliding, s)

    @jax.jit
    def head(key, x, rows, int8):
        """Logits at the positions `rows` (N, R) of each sequence: the
        final norm, then the token table as the head."""
        p = up(_top_tensors(key, s, dtype), int8)
        picked = jnp.take_along_axis(x, rows[:, :, None], axis=1)
        with jax.default_matmul_precision("highest"):
            return _ln(picked, p["ln_f_gamma"], s["eps"]) \
                @ p["tok_embed_weight"].T * s["logit_scale"]

    return embed, layer, head


def logits_at(cfg, seed, tokens, rows, dtype="bfloat16", int8=False):
    """Full forward over `tokens` (N, T) int32, layer by layer, and the
    logits (N, R, V) at positions `rows` (N, R). `int8` rounds every
    projection's weight, the experts and the tied table among them, to
    what a weight-only int8 path holds."""
    s = sizes(cfg)
    embed, layer, head = _programs(
        tuple(sorted(s.items())), str(jnp.dtype(dtype)))
    key = base_key(seed)
    int8 = jnp.bool_(int8)
    x = embed(key, jnp.asarray(tokens, jnp.int32), int8)
    for i, kind in enumerate(s["types"]):
        x = layer(key, jnp.int32(i), jnp.bool_(kind == "sliding"), x,
                  int8)
    return head(key, x, jnp.asarray(rows, jnp.int32), int8)


def served_logits(cfg, seed, rows, dtype="bfloat16", pad_to=None,
                  served_to=None, int8=False, group=2):
    """For each served row (prompt_len, ids of prompt + served tokens),
    in order: the reference's logits (n, V) at the n positions that
    each predict one served token. `pad_to` and `served_to` fix the
    compiled shapes (longest row, most served tokens) from run to run.
    Rows are padded on the right: every layer is causal, so a real
    position never reads the padding. `group` rows go through one
    forward: `group` x `pad_to` positions through one expert at a
    time."""
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
