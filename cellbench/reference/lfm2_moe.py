"""Plain reference for the LFM2-MoE family (`lfm2_moe`): a pre-norm
stack whose operator is a gated short convolution (`conv`) in three
layers of four and QK-normed rotary GQA (`full_attention`) in the
fourth, and whose FFN is a dense SwiGLU in the leading
`num_dense_layers` and sigmoid-routed SwiGLU experts after.
Straightforward `jax.numpy` in float32 at `highest` matmul precision:
the convolution is a sum of three shifted copies, attention the full
masked matrix, the expert layer a loop over all experts weighted by a
dense (tokens, experts) matrix that is zero outside each token's chosen
4. No cache, no window, no kernel, no sorting, no batching of requests;
it imports nothing of the program.

For a position's hidden h (RMSNorm eps `norm_eps` throughout, no bias
anywhere):

    x = RMSNorm(h; g_op)
    conv:  [b | c | u] = x W_in;  g_t = b_t * u_t
           v_t = sum_{j=0..2} w[:, j] * g_{t-2+j}   (rows before 0 are zero;
                                                     no activation)
           op = (c_t * v_t) W_out
    attn:  q = x Wq, k = x Wk, v = x Wv;  each head of q and of k:
           RMSNorm over its 64 channels (q_norm, k_norm), THEN RoPE,
           half-split pairs, base rope_theta, over the whole head
           op = causal softmax(q k^T / sqrt(head_dim)) v Wo   (GQA 32 : 8)
    h' = h + op;  z = RMSNorm(h'; g_ffn)
    dense (l < num_dense_layers):  h'' = h' + (silu(z W1) * z W3) W2
    experts: s = sigmoid(z Wg), float32
           S = the 4 largest of s + e_bias (the bias chooses and does
               not weigh; a tie: the lower index)
           w_e = routed_scaling_factor * s_e / (sum_S s + 1e-6)
           h'' = h' + sum_{e in S} w_e (silu(z W1_e) * z W3_e) W2_e
    logits = RMSNorm(h_last; g_f) E^T          (the head is the table)

The program spells this stack one sublayer a layer, so the weights are
named `layer{2l}_*` for layer l's operator and `layer{2l+1}_*` for its
FFN, each with its one norm `ln1_gamma`; `sizes(cfg)["kinds"]` is that
list of 2 x `num_hidden_layers` sublayers.

The weights belong to the benchmark (`make_params` draws every tensor
from the seed in the served type, under the program's parameter names
and layouts; the reference draws them again, a sublayer at a time: one
expert layer is 2.4 GB in float32). Departures from the published
model, also in the configuration file: every weight is random, uniform
with deviation `initializer_range` (gains around 1, the three taps over
+-0.5, the router's choosing bias over +-0.17 so that it changes which
experts are chosen, kept in float32); the head is tied to the table,
`head_dim` is hidden_size / heads, q and k are RMS-normalised by head,
`W_in`'s chunks are `b | c | u`, and the renormalisation adds 1e-6:
the published `lfm2_moe` block's, which the config does not name.
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
_KINDS = {
    "conv": ("ln1_gamma", "in_proj_weight", "shortconv_conv_weight",
             "out_proj_weight"),
    "attention": ("ln1_gamma", "qkv_weight", "q_norm_gamma",
                  "k_norm_gamma", "proj_weight"),
    "mlp": ("ln1_gamma", "fc1_weight", "fc2_weight"),
    "experts": ("ln1_gamma", "gate_weight", "gate_score_bias",
                "experts_w1_weight", "experts_w2_weight"),
}
_OPERATOR = {"conv": "conv", "full_attention": "attention"}
# what a weight-only int8 path would hold in int8: one scale an output
# channel (for the experts: an output channel of each expert). In every
# one of these layouts the input's axis, which a scale spans, is axis
# 1: (out, in), (E, in, out). Gains, taps, the router and its bias stay
# as drawn.
_INT8 = ("in_proj_weight", "out_proj_weight", "qkv_weight",
         "proj_weight", "fc1_weight", "fc2_weight", "tok_embed_weight",
         "experts_w1_weight", "experts_w2_weight")
# (mean, deviation) of the uniform draw, for what is not a projection
# (those: 0, initializer_range) or a gain (1, initializer_range): the
# three taps over +-0.5, the router's choosing bias over +-0.17
_RANGES = {"shortconv_conv_weight": (0.0, 0.2887),
           "gate_score_bias": (0.0, 0.1)}
_RENORM_EPS = 1e-6


def sizes(cfg):
    a = cfg["assumed"]
    types = list(cfg["layer_types"])
    layers, dense = int(cfg["num_hidden_layers"]), \
        int(cfg["num_dense_layers"])
    heads, dim = int(cfg["num_attention_heads"]), int(cfg["hidden_size"])
    if len(types) != layers or set(types) - set(_OPERATOR) or \
            not 0 <= dense <= layers:
        raise ValueError("lfm2_moe reference: layer_types must name "
                         "each of the %d layers %r, %d of them dense"
                         % (layers, sorted(_OPERATOR), dense))
    if cfg["conv_bias"] or not cfg["use_expert_bias"] or \
            int(a["head_dim"]) * heads != dim or \
            not a["tie_word_embeddings"] or \
            cfg["rope_parameters"]["rope_type"] != "default":
        raise ValueError(
            "lfm2_moe reference: a bias-free convolution, a choosing "
            "expert bias, head_dim = hidden_size / heads, a tied head "
            "and the default rotation are assumed")
    kinds = []
    for l, t in enumerate(types):
        kinds += [_OPERATOR[t], "mlp" if l < dense else "experts"]
    return dict(dim=dim, heads=heads,
                kv_heads=int(cfg["num_key_value_heads"]),
                head=int(a["head_dim"]),
                ffn=int(cfg["intermediate_size"]),
                vocab=int(cfg["vocab_size"]),
                layers=layers, kinds=tuple(kinds),
                positions=int(cfg["max_position_embeddings"]),
                taps=int(cfg["conv_L_cache"]),
                experts=int(cfg["num_experts"]),
                top_k=int(cfg["num_experts_per_tok"]),
                expert_ffn=int(cfg["moe_intermediate_size"]),
                renorm=bool(cfg["norm_topk_prob"]),
                scale=float(cfg["routed_scaling_factor"]),
                eps=float(cfg["norm_eps"]),
                theta=float(cfg["rope_parameters"]["rope_theta"]),
                std=float(cfg["initializer_range"]))


def _shape(name, s):
    d, hd, f, e = s["dim"], s["head"], s["expert_ffn"], s["experts"]
    q, kv = s["heads"] * hd, s["kv_heads"] * hd
    return {"tok_embed_weight": (s["vocab"], d), "ln_f_gamma": (d,),
            "ln1_gamma": (d,),
            "in_proj_weight": (3 * d, d),            # [b | c | u]
            "shortconv_conv_weight": (d, s["taps"]),
            "out_proj_weight": (d, d),
            "qkv_weight": (q + 2 * kv, d),
            "q_norm_gamma": (hd,), "k_norm_gamma": (hd,),
            "proj_weight": (d, q),
            "fc1_weight": (2 * s["ffn"], d),         # [gate | up]
            "fc2_weight": (d, s["ffn"]),
            "gate_weight": (d, e), "gate_score_bias": (e,),
            "experts_w1_weight": (e, d, 2 * f),      # [gate | up]
            "experts_w2_weight": (e, f, d)}[name]


def _draw(key, name, s, dtype):
    """One tensor in the served type, in its own range; the router's
    choosing bias stays float32 whatever the served type."""
    if name in _RANGES:
        mean, dev = _RANGES[name]
    else:
        mean, dev = (1.0 if name.endswith("gamma") else 0.0), s["std"]
    if name == "gate_score_bias":
        dtype = jnp.float32
    return uniform(key, _shape(name, s), dev, mean).astype(dtype)


def _layer_tensors(key, layer, kind, s, dtype):
    """`layer` (a sublayer's index) may be traced: sublayers of one
    kind share a program."""
    lkey = jax.random.fold_in(key, layer + 1)
    return {n: _draw(jax.random.fold_in(lkey, i), n, s, dtype)
            for i, n in enumerate(_KINDS[kind])}


def _top_tensors(key, s, dtype):
    tkey = jax.random.fold_in(key, 0)
    return {n: _draw(jax.random.fold_in(tkey, i), n, s, dtype)
            for i, n in enumerate(_TOP)}


def make_params(cfg, seed, dtype="bfloat16"):
    """Every tensor of the model under the program's parameter names,
    made on the device: one small program for the top and one for each
    kind of sublayer (its index is an argument), called one by one."""
    s = sizes(cfg)
    dtype = jnp.dtype(dtype)
    key = base_key(seed)
    draw = {kind: jax.jit(functools.partial(
        _layer_tensors, kind=kind, s=s, dtype=dtype))
        for kind in set(s["kinds"])}
    out = dict(jax.jit(lambda k: _top_tensors(k, s, dtype))(key))
    for layer, kind in enumerate(s["kinds"]):
        for n, v in draw[kind](key, jnp.int32(layer)).items():
            out["layer%d_%s" % (layer, n)] = v
    return out


def _rms(x, g, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * g


def _swiglu(x, w1, w2):
    """(silu(x Wg) * x Wu) Wd with w1 = [Wg | Wu] (in, 2f), w2 (f, in)."""
    gu = x @ w1
    f = w2.shape[0]
    return (jax.nn.silu(gu[..., :f]) * gu[..., f:]) @ w2


def _conv(x, p, s):
    """The gated short convolution on (N, T, D)."""
    t, d = x.shape[1], s["dim"]
    bcu = x @ p["in_proj_weight"].T
    b, c, u = (bcu[..., i * d:(i + 1) * d] for i in range(3))
    g = jnp.pad(b * u, ((0, 0), (s["taps"] - 1, 0), (0, 0)))
    v = sum(g[:, j:j + t] * p["shortconv_conv_weight"][:, j]
            for j in range(s["taps"]))
    return (c * v) @ p["out_proj_weight"].T


def _rope(x, theta):
    """(N, T, heads, hd), half-split pairs: (x[i], x[i + hd/2]) turn by
    position * theta^(-2i/hd)."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None, None] * freqs
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x1 * jnp.sin(ang) + x2 * jnp.cos(ang)], -1)


def _attention(x, p, s):
    n, t, _ = x.shape
    h, kv, hd = s["heads"], s["kv_heads"], s["head"]
    qkv = x @ p["qkv_weight"].T
    q = qkv[..., :h * hd].reshape(n, t, h, hd)
    k = qkv[..., h * hd:(h + kv) * hd].reshape(n, t, kv, hd)
    v = qkv[..., (h + kv) * hd:].reshape(n, t, kv, hd)
    q = _rope(_rms(q, p["q_norm_gamma"], s["eps"]), s["theta"])
    k = _rope(_rms(k, p["k_norm_gamma"], s["eps"]), s["theta"])
    k, v = (jnp.repeat(a, h // kv, axis=2) for a in (k, v))
    scores = jnp.einsum("nqhd,nkhd->nhqk", q, k) / np.sqrt(hd)
    scores = jnp.where(jnp.tril(jnp.ones((t, t), bool)), scores,
                       -jnp.inf)
    att = jnp.einsum("nhqk,nkhd->nqhd", jax.nn.softmax(scores, -1), v)
    return att.reshape(n, t, h * hd) @ p["proj_weight"].T


def _mlp(x, p, s):
    return _swiglu(x, p["fc1_weight"].T, p["fc2_weight"].T)


def _chosen(z, p, s):
    """(tokens, experts) weights, zero outside each token's chosen
    experts: sigmoid scores, the top_k largest of score + bias (a tie:
    the lower index), the weights from the scores alone."""
    score = jax.nn.sigmoid(z @ p["gate_weight"])
    rows = jnp.arange(z.shape[0])
    left = score + p["gate_score_bias"]
    chosen = jnp.zeros_like(score)
    for _ in range(s["top_k"]):
        best = jnp.argmax(left, axis=-1)
        chosen = chosen.at[rows, best].set(score[rows, best])
        left = left.at[rows, best].set(-jnp.inf)
    if s["renorm"]:
        chosen = chosen / (chosen.sum(-1, keepdims=True) + _RENORM_EPS)
    return s["scale"] * chosen


def _experts(x, p, s):
    """The expert layer on (N, T, D): every expert, one after the
    other, over every token and weighted by that token's weight for it
    (zero where it was not chosen)."""
    z = x.reshape(-1, x.shape[-1])
    weights = _chosen(z, p, s)

    def one(y, at):
        w1, w2, weight = at              # (D, 2f), (f, D), (tokens,)
        return y + weight[:, None] * _swiglu(z, w1, w2), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(z),
                        (p["experts_w1_weight"], p["experts_w2_weight"],
                         weights.T))
    return y.reshape(x.shape)


_FORWARD = {"conv": _conv, "attention": _attention, "mlp": _mlp,
            "experts": _experts}


def _layer(x, p, kind, s):
    """One sublayer on (N, T, D) float32: its norm, itself, the
    residual add."""
    return x + _FORWARD[kind](_rms(x, p["ln1_gamma"], s["eps"]), p, s)


@functools.lru_cache(maxsize=None)
def _programs(frozen, dtype_name):
    """The jitted pieces, compiled once per (sizes, served type):
    embed, one sublayer of each kind (its index is an argument, so all
    sublayers of a kind share one program), head. Each draws its own
    weights and frees them when it returns. `int8` is an argument of
    each and not a second set of programs: the weights as drawn, or as
    a weight-only int8 path holds them, selected on the device."""
    s = dict(frozen)
    dtype = jnp.dtype(dtype_name)

    def up(tree, int8):
        out = {n: v.astype(jnp.float32) for n, v in tree.items()}
        return {n: jnp.where(int8, _as_int8_holds(v), v)
                if n in _INT8 else v for n, v in out.items()}

    @jax.jit
    def embed(key, tokens, int8):
        return up(_top_tensors(key, s, dtype),
                  int8)["tok_embed_weight"][tokens]

    def layer_of(kind):
        @jax.jit
        def layer(key, index, x, int8):
            with jax.default_matmul_precision("highest"):
                return _layer(x, up(_layer_tensors(key, index, kind, s,
                                                   dtype), int8),
                              kind, s)
        return layer

    @jax.jit
    def head(key, x, rows, int8):
        """Logits at the positions `rows` (N, R) of each sequence:
        the final norm, then the token table as the head."""
        p = up(_top_tensors(key, s, dtype), int8)
        picked = jnp.take_along_axis(x, rows[:, :, None], axis=1)
        with jax.default_matmul_precision("highest"):
            return _rms(picked, p["ln_f_gamma"], s["eps"]) \
                @ p["tok_embed_weight"].T

    return embed, {k: layer_of(k) for k in _KINDS}, head


def logits_at(cfg, seed, tokens, rows, dtype="bfloat16", int8=False):
    """Full forward over `tokens` (N, T) int32, sublayer by sublayer,
    and the logits (N, R, V) at positions `rows` (N, R). `int8` rounds
    every projection's weight, the experts and the tied table among
    them, to what a weight-only int8 path holds."""
    s = sizes(cfg)
    embed, layers, head = _programs(
        tuple(sorted(s.items())), str(jnp.dtype(dtype)))
    key = base_key(seed)
    int8 = jnp.bool_(int8)
    x = embed(key, jnp.asarray(tokens, jnp.int32), int8)
    for i, kind in enumerate(s["kinds"]):
        x = layers[kind](key, jnp.int32(i), x, int8)
    return head(key, x, jnp.asarray(rows, jnp.int32), int8)


def served_logits(cfg, seed, rows, dtype="bfloat16", pad_to=None,
                  served_to=None, int8=False, group=4):
    """For each served row (prompt_len, ids of prompt + served tokens),
    in order: the reference's logits (n, V) at the n positions that
    each predict one served token. `pad_to` and `served_to` fix the
    compiled shapes (longest row, most served tokens) from run to run.
    Rows are padded on the right: every sublayer is causal, so a real
    position never reads the padding. `group` rows go through one
    forward: a block of `group` x `pad_to` positions through all 64
    experts at a time, which is what has to fit beside one expert
    layer's float32 weights."""
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
