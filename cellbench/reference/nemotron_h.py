"""Plain reference for the Nemotron-H family (`nemotron_h`): a stack in
which every layer is ONE sublayer, a Mamba-2 mixer (`M`), a position-
free GQA attention (`*`), a LatentMoE expert layer (`E`) or a dense
squared-ReLU MLP (`-`), as `hybrid_override_pattern` spells it.
Straightforward `jax.numpy` in float32 at `highest` matmul precision:
the selective scan is a sequential loop over time, the convolution a
sum of four shifted copies, attention the full masked matrix, the
expert layer a loop over the held experts weighted by a dense (tokens,
experts) matrix that is zero outside each token's chosen 22. No cache,
no chunks, no kernel, no sorting; it imports nothing of the program.

    h = E[ids];  h <- h + f_kind(RMSNorm(h; g))      one norm, one add a layer
    logits = RMSNorm(h; g_f) W_head^T                 (untied head)

    M:  [z | xBC | dt] = a W_in
        xBC_t = silu(b + sum_j w_j * xBC_{t-3+j});  [x, B, C] = xBC
        B, C of shape (groups, state): head h reads group floor(h / (heads/groups))
        D_t = softplus(dt_t + dt_bias);  A = -exp(A_log)
        S_t = exp(D_t A) S_{t-1} + D_t x_t (x) B_t;  y_t = S_t.C_t + D x_t
        y <- RMSNorm_g(y * silu(z)), the mean square over each group's
             channels (gate first);  out = y W_out
    *:  q = a Wq, k = a Wk, v = a Wv; causal softmax(q k^T / sqrt(head_dim)) v Wo
        (no position enters: the published block applies no rotation)
    E:  s = sigmoid(a Wr) over all router outputs, float32
        S = the k largest of s + b (the bias chooses and does not weigh;
            a tie: the lower index);  w_e = scale * s_e / (sum_S s + 1e-20)
        u = a W_dn;  r = sum_{e in S, e held here} w_e relu(u U_e)^2 V_e
        out = r W_up + relu(a P)^2 Q
    -:  relu(a W1)^2 W2

**The share.** The configuration states how many of the router's
outputs are held here (`n_routed_experts` of `router_outputs`, from
`routed_experts_first`) and a slice of the vocabulary; the reference
is given the same share and, like the program, leaves out what the
absent experts would add: that partial result goes on to the next
layer.

The weights belong to the benchmark (`make_params` draws every tensor
from the seed in the served type, under the program's parameter names
and layouts; the reference draws them again, a layer at a time: one
expert layer is 3.0 GB in float32). Departures from the published
model, also in the configuration file: every weight is random, drawn
in the ranges of `_RANGES` below (the published initialisation's for
A_log and dt_bias); `initializer_range` 0.02 with no rescaling of the
output projections by depth; the router's score-correction bias is
drawn with a deviation of 0.1, wide enough to change which experts
are chosen (a zero bias would leave the choosing-not-weighing rule
untested) and kept in float32; the multi-token-prediction module is
not built.
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
    "mamba": ("ln1_gamma", "in_proj_weight", "mamba_conv_weight",
              "mamba_conv_bias", "mamba_dt_bias", "mamba_a_log",
              "mamba_d_skip", "mnorm_gamma", "out_proj_weight"),
    "attention": ("ln1_gamma", "qkv_weight", "proj_weight"),
    "experts": ("ln1_gamma", "gate_weight", "gate_score_bias",
                "latent_down_weight", "latent_up_weight",
                "experts_w1_weight", "experts_w2_weight",
                "shared_w1_weight", "shared_w2_weight"),
    "mlp": ("ln1_gamma", "fc1_weight", "fc2_weight"),
}
_PATTERN = {"M": "mamba", "*": "attention", "E": "experts", "-": "mlp"}
# what a weight-only int8 path would hold in int8, one scale an output
# channel, by where the input's axis (which a scale spans) lies: axis 1
# in (out, in) and (experts, in, out), axis 0 in (in, out). Gains, the
# router and its bias stay as drawn.
_INT8_IN_AXIS_1 = ("in_proj_weight", "out_proj_weight", "qkv_weight",
                   "proj_weight", "fc1_weight", "fc2_weight",
                   "tok_embed_weight", "lm_head_weight",
                   "experts_w1_weight", "experts_w2_weight")
_INT8_IN_AXIS_0 = ("latent_down_weight", "latent_up_weight",
                   "shared_w1_weight", "shared_w2_weight")
# (mean, deviation) of the uniform draw, for what is not a projection
# (those: 0, initializer_range). A_log over log 1 .. log 16 and dt_bias
# over softplus^-1 of time_step_min .. time_step_max (0.001 .. 0.1), as
# the published initialisation draws them; the depthwise convolution
# and its bias over +-0.5; the router's choosing bias over +-0.17; D
# and every gain around 1.
_RANGES = {"mamba_a_log": (1.3863, 0.8004),
           "mamba_dt_bias": (-4.58, 1.34),
           "mamba_conv_weight": (0.0, 0.2887),
           "mamba_conv_bias": (0.0, 0.2887),
           "gate_score_bias": (0.0, 0.1)}


def sizes(cfg):
    pattern = cfg["hybrid_override_pattern"]
    heads, hd = int(cfg["mamba_num_heads"]), int(cfg["mamba_head_dim"])
    if len(pattern) != int(cfg["num_hidden_layers"]) or \
            set(pattern) - set(_PATTERN):
        raise ValueError("nemotron_h reference: the pattern %r must name "
                         "each of the %s layers by one of %r"
                         % (pattern, cfg["num_hidden_layers"],
                            sorted(_PATTERN)))
    if heads * hd != int(cfg["expand"]) * int(cfg["hidden_size"]) or \
            int(cfg["n_group"]) != 1 or int(cfg["topk_group"]) != 1 or \
            cfg["mlp_hidden_act"] != "relu2" or \
            cfg["mamba_hidden_act"] != "silu" or cfg["use_bias"] or \
            cfg["attention_bias"] or not cfg["use_conv_bias"] or \
            cfg["tie_word_embeddings"]:
        raise ValueError(
            "nemotron_h reference: d_inner = expand * hidden_size, one "
            "routing group, relu2 experts, a SiLU mixer, bias-free "
            "projections, a convolution bias and an untied head are "
            "assumed")
    return dict(dim=int(cfg["hidden_size"]),
                heads=int(cfg["num_attention_heads"]),
                kv_heads=int(cfg["num_key_value_heads"]),
                head=int(cfg["head_dim"]),
                ffn=int(cfg["intermediate_size"]),
                vocab=int(cfg["vocab_size"]),
                layers=len(pattern),
                kinds=tuple(_PATTERN[c] for c in pattern),
                positions=int(cfg["max_position_embeddings"]),
                m_heads=heads, m_head=hd,
                m_state=int(cfg["ssm_state_size"]),
                m_groups=int(cfg["n_groups"]),
                m_conv=int(cfg["conv_kernel"]),
                m_chunk=int(cfg["chunk_size"]),
                experts=int(cfg["router_outputs"]),
                held=int(cfg["n_routed_experts"]),
                first=int(cfg["routed_experts_first"]),
                top_k=int(cfg["num_experts_per_tok"]),
                expert_ffn=int(cfg["moe_intermediate_size"]),
                latent=int(cfg["moe_latent_size"]),
                shared=int(cfg["n_shared_experts"]) *
                int(cfg["moe_shared_expert_intermediate_size"]),
                renorm=bool(cfg["norm_topk_prob"]),
                scale=float(cfg["routed_scaling_factor"]),
                eps=float(cfg["layer_norm_epsilon"]),
                std=float(cfg["initializer_range"]))


def _shape(name, s):
    d, v, hd = s["dim"], s["vocab"], s["head"]
    d_inner = s["m_heads"] * s["m_head"]
    conv = d_inner + 2 * s["m_groups"] * s["m_state"]
    q, kv = s["heads"] * hd, s["kv_heads"] * hd
    z, f = s["latent"], s["expert_ffn"]
    return {"tok_embed_weight": (v, d), "ln_f_gamma": (d,),
            "lm_head_weight": (v, d), "ln1_gamma": (d,),
            "qkv_weight": (q + 2 * kv, d), "proj_weight": (d, q),
            "in_proj_weight": (d_inner + conv + s["m_heads"], d),
            "mamba_conv_weight": (conv, s["m_conv"]),
            "mamba_conv_bias": (conv,),
            "mamba_dt_bias": (s["m_heads"],),
            "mamba_a_log": (s["m_heads"],),
            "mamba_d_skip": (s["m_heads"],),
            "mnorm_gamma": (d_inner,),
            "out_proj_weight": (d, d_inner),
            "gate_weight": (d, s["experts"]),
            "gate_score_bias": (s["experts"],),
            "latent_down_weight": (d, z), "latent_up_weight": (z, d),
            "experts_w1_weight": (s["held"], z, f),
            "experts_w2_weight": (s["held"], f, z),
            "shared_w1_weight": (d, s["shared"]),
            "shared_w2_weight": (s["shared"], d),
            "fc1_weight": (s["ffn"], d), "fc2_weight": (d, s["ffn"])}[name]


def _draw(key, name, s, dtype):
    """One tensor in the served type, in its own range; the router's
    choosing bias stays float32 whatever the served type."""
    if name in _RANGES:
        mean, dev = _RANGES[name]
    else:
        mean = 1.0 if name.endswith(("gamma", "d_skip")) else 0.0
        dev = s["std"]
    if name == "gate_score_bias":
        dtype = jnp.float32
    return uniform(key, _shape(name, s), dev, mean).astype(dtype)


def _layer_tensors(key, layer, kind, s, dtype):
    """`layer` may be traced: layers of one kind share a program."""
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
    kind of layer (its index is an argument), called layer by layer."""
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


def _relu2(x):
    return jnp.square(jax.nn.relu(x))


def _mlp(x, p, s):
    return _relu2(x @ p["fc1_weight"].T) @ p["fc2_weight"].T


def _attention(x, p, s):
    n, t, _ = x.shape
    h, kv, hd = s["heads"], s["kv_heads"], s["head"]
    qkv = x @ p["qkv_weight"].T
    q = qkv[..., :h * hd].reshape(n, t, h, hd)
    k = qkv[..., h * hd:(h + kv) * hd].reshape(n, t, kv, hd)
    v = qkv[..., (h + kv) * hd:].reshape(n, t, kv, hd)
    k, v = (jnp.repeat(a, h // kv, axis=2) for a in (k, v))
    scores = jnp.einsum("nqhd,nkhd->nhqk", q, k) / np.sqrt(hd)
    scores = jnp.where(jnp.tril(jnp.ones((t, t), bool)), scores,
                       -jnp.inf)
    att = jnp.einsum("nhqk,nkhd->nqhd", jax.nn.softmax(scores, -1), v)
    return att.reshape(n, t, h * hd) @ p["proj_weight"].T


def _mamba(x, p, s):
    n, t, _ = x.shape
    H, P, N, K, G = (s["m_heads"], s["m_head"], s["m_state"],
                     s["m_conv"], s["m_groups"])
    d_inner = H * P
    conv = d_inner + 2 * G * N
    zxd = x @ p["in_proj_weight"].T
    z, xbc, dt = (zxd[..., :d_inner], zxd[..., d_inner:d_inner + conv],
                  zxd[..., d_inner + conv:])
    padded = jnp.pad(xbc, ((0, 0), (K - 1, 0), (0, 0)))
    xbc = p["mamba_conv_bias"] + sum(
        padded[:, j:j + t] * p["mamba_conv_weight"][:, j]
        for j in range(K))
    xbc = jax.nn.silu(xbc)
    xs = xbc[..., :d_inner].reshape(n, t, H, P)
    # a head reads the B and C of its own group
    Bm, Cm = (jnp.repeat(xbc[..., lo:lo + G * N].reshape(n, t, G, N),
                         H // G, axis=2)
              for lo in (d_inner, d_inner + G * N))       # (n, t, H, N)
    step = jax.nn.softplus(dt + p["mamba_dt_bias"])          # (n, t, H)
    A = -jnp.exp(p["mamba_a_log"])

    def one(S, at):
        x_t, d_t, b_t, c_t = at
        S = jnp.exp(d_t * A)[..., None, None] * S + \
            (d_t[..., None] * x_t)[..., None] * b_t[:, :, None, :]
        return S, (S * c_t[:, :, None, :]).sum(-1)

    _, ys = jax.lax.scan(
        one, jnp.zeros((n, H, P, N), jnp.float32),
        tuple(jnp.moveaxis(a, 1, 0) for a in (xs, step, Bm, Cm)))
    y = jnp.moveaxis(ys, 0, 1) + p["mamba_d_skip"][:, None] * xs
    y = y.reshape(n, t, d_inner) * jax.nn.silu(z)
    # the gated norm, group by group
    y = _rms(y.reshape(n, t, G, d_inner // G),
             p["mnorm_gamma"].reshape(G, d_inner // G), s["eps"])
    return y.reshape(n, t, d_inner) @ p["out_proj_weight"].T


def _chosen(a, p, s):
    """(tokens, router outputs) weights, zero outside each token's
    chosen experts: sigmoid scores, the top_k largest of score + bias
    (a tie: the lower index), the weights from the scores alone."""
    score = jax.nn.sigmoid(a @ p["gate_weight"])
    rows = jnp.arange(a.shape[0])
    left = score + p["gate_score_bias"]
    chosen = jnp.zeros_like(score)
    for _ in range(s["top_k"]):
        best = jnp.argmax(left, axis=-1)
        chosen = chosen.at[rows, best].set(score[rows, best])
        left = left.at[rows, best].set(-jnp.inf)
    if s["renorm"]:
        chosen = chosen / (chosen.sum(-1, keepdims=True) + 1e-20)
    return s["scale"] * chosen


def _experts(x, p, s):
    """The LatentMoE layer on (N, T, D): the held experts, one after
    the other, each over every token and weighted by that token's
    weight for it (zero where it was not chosen); what the experts
    held elsewhere would add is left out."""
    a = x.reshape(-1, x.shape[-1])
    weights = _chosen(a, p, s)[:, s["first"]:s["first"] + s["held"]]
    u = a @ p["latent_down_weight"]

    def one(r, at):
        w1, w2, weight = at              # (Z, f), (f, Z), (tokens,)
        return r + weight[:, None] * (_relu2(u @ w1) @ w2), None

    r, _ = jax.lax.scan(one, jnp.zeros_like(u),
                        (p["experts_w1_weight"], p["experts_w2_weight"],
                         weights.T))
    out = r @ p["latent_up_weight"]
    if s["shared"]:
        out = out + _relu2(a @ p["shared_w1_weight"]) \
            @ p["shared_w2_weight"]
    return out.reshape(x.shape)


_FORWARD = {"mamba": _mamba, "attention": _attention,
            "experts": _experts, "mlp": _mlp}


def _layer(x, p, kind, s):
    """One layer on (N, T, D) float32: one norm, one sublayer, one
    residual add."""
    return x + _FORWARD[kind](_rms(x, p["ln1_gamma"], s["eps"]), p, s)


def _int8_twin(tree):
    """Every projection as a weight-only int8 path would hold it."""
    out = dict(tree)
    out.update({n: _as_int8_holds(v) for n, v in tree.items()
                if n in _INT8_IN_AXIS_1})
    out.update({n: _as_int8_holds(v.T).T for n, v in tree.items()
                if n in _INT8_IN_AXIS_0})
    return out


@functools.lru_cache(maxsize=None)
def _programs(frozen, dtype_name):
    """The jitted pieces, compiled once per (sizes, served type):
    embed, one layer of each kind (its index is an argument, so all
    layers of a kind share one program), head. Each draws its own
    weights and frees them when it returns. `int8` is an argument of
    each and not a second set of programs (which would compile for a
    minute more on a cold machine): the weights as drawn, or as a
    weight-only int8 path holds them, selected on the device."""
    s = dict(frozen)
    dtype = jnp.dtype(dtype_name)

    def up(tree, int8):
        out = {n: v.astype(jnp.float32) for n, v in tree.items()}
        return {n: jnp.where(int8, t, out[n])
                for n, t in _int8_twin(out).items()}

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
        """Logits at the positions `rows` (N, R) of each sequence."""
        p = up(_top_tensors(key, s, dtype), int8)
        picked = jnp.take_along_axis(x, rows[:, :, None], axis=1)
        with jax.default_matmul_precision("highest"):
            return _rms(picked, p["ln_f_gamma"], s["eps"]) \
                @ p["lm_head_weight"].T

    return embed, {k: layer_of(k) for k in _KINDS}, head


def logits_at(cfg, seed, tokens, rows, dtype="bfloat16", int8=False):
    """Full forward over `tokens` (N, T) int32, layer by layer, and the
    logits (N, R, V) at positions `rows` (N, R). `int8` rounds every
    projection's weight, the experts, the table and the head among
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
    Rows are padded on the right: every layer is causal, so a real
    position never reads the padding."""
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
