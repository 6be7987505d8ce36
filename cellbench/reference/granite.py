"""Plain reference for the Granite 4.0-H family (`granitemoehybrid`
with no routed experts): Mamba-2 mixers beside a few position-free GQA
attention layers, RMSNorm, a gated-SiLU MLP in every layer, Granite's
four multipliers, a tied head. Straightforward `jax.numpy` in float32
at `highest` matmul precision: the selective scan is a sequential loop
over time, the convolution a sum of four shifted copies, attention the
full masked matrix. No cache, no chunks, no kernels; it imports
nothing of the program.

    h = embedding_multiplier * E[ids]
    h = h + residual_multiplier * mixer(RMSNorm(h))        per layer
    h = h + residual_multiplier * mlp(RMSNorm(h))
    logits = RMSNorm(h) . E^T / logits_scaling

    mlp:    [g, u] = x W_in;  (silu(g) * u) W_out
    attention (no position enters, no rotary): softmax over the
        causal scores q.k * attention_multiplier, 8 key/value heads
        shared by 32 query heads
    mamba:  [z, xBC, dt] = x W_in
            xBC_t = silu(b + sum_j w_j * xBC_{t-3+j});  [x, B, C] = xBC
            D_t = softplus(dt_t + dt_bias);  A = -exp(A_log)
            S_t = exp(D_t A) S_{t-1} + D_t x_t (x) B_t;  y_t = S_t.C_t + D x_t
            out = RMSNorm_w(y * silu(z)) W_out        (gate first)

The weights belong to the benchmark (`make_params` draws every tensor
from the seed in the served type, under the program's parameter names;
the reference draws them again, a layer at a time). Departures from
the published model, also in the configuration file: every weight is
random, drawn in the ranges of `_RANGES` below (the published
initialisation's, which keep the step size and the decay where
training leaves them); `initializer_range` is assumed 0.02.
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
_MLP = ("ln2_gamma", "fc1_weight", "fc2_weight")
_KINDS = {
    "mamba": ("ln1_gamma", "in_proj_weight", "mamba_conv_weight",
              "mamba_conv_bias", "mamba_dt_bias", "mamba_a_log",
              "mamba_d_skip", "mnorm_gamma", "out_proj_weight") + _MLP,
    "attention": ("ln1_gamma", "qkv_weight", "proj_weight") + _MLP,
}
_PROJECTIONS = ("in_proj_weight", "out_proj_weight", "qkv_weight",
                "proj_weight", "fc1_weight", "fc2_weight",
                "tok_embed_weight")
# (mean, deviation) of the uniform draw, for what is not a projection
# (those: 0, init_std). A_log over log 1 .. log 16 and dt_bias over
# softplus^-1 of 0.001 .. 0.1, as the published initialisation draws
# them; the depthwise convolution over +-1/sqrt(d_conv), the default of
# the layer it is; D and every gamma around 1.
_RANGES = {"mamba_a_log": (1.3863, 0.8004),
           "mamba_dt_bias": (-4.58, 1.34),
           "mamba_conv_weight": (0.0, 0.2887),
           "mamba_conv_bias": (0.0, 0.2887)}


def sizes(cfg):
    heads, hd = int(cfg["mamba_n_heads"]), int(cfg["mamba_d_head"])
    if int(cfg["mamba_n_groups"]) != 1 or \
            heads * hd != int(cfg["mamba_expand"]) * int(cfg["hidden_size"]):
        raise ValueError("granite reference: one group and d_inner = "
                         "mamba_expand * hidden_size are assumed")
    return dict(dim=int(cfg["hidden_size"]),
                heads=int(cfg["num_attention_heads"]),
                kv_heads=int(cfg["num_key_value_heads"]),
                ffn=int(cfg["shared_intermediate_size"]),
                vocab=int(cfg["vocab_size"]),
                layers=int(cfg["num_hidden_layers"]),
                kinds=tuple(cfg["layer_types"])[
                    :int(cfg["num_hidden_layers"])],
                positions=int(cfg["max_position_embeddings"]),
                m_heads=heads, m_head=hd, m_state=int(cfg["mamba_d_state"]),
                m_conv=int(cfg["mamba_d_conv"]),
                eps=float(cfg["rms_norm_eps"]),
                emb_mult=float(cfg["embedding_multiplier"]),
                res_mult=float(cfg["residual_multiplier"]),
                att_mult=float(cfg["attention_multiplier"]),
                logit_div=float(cfg["logits_scaling"]),
                std=float(cfg.get("initializer_range", 0.02)))


def _shape(name, s):
    d, f, v = s["dim"], s["ffn"], s["vocab"]
    d_inner = s["m_heads"] * s["m_head"]
    conv = d_inner + 2 * s["m_state"]
    hd = d // s["heads"]
    return {"tok_embed_weight": (v, d), "ln_f_gamma": (d,),
            "ln1_gamma": (d,), "ln2_gamma": (d,),
            "fc1_weight": (2 * f, d), "fc2_weight": (d, f),
            "qkv_weight": (d + 2 * s["kv_heads"] * hd, d),
            "proj_weight": (d, d),
            "in_proj_weight": (d_inner + conv + s["m_heads"], d),
            "mamba_conv_weight": (conv, s["m_conv"]),
            "mamba_conv_bias": (conv,),
            "mamba_dt_bias": (s["m_heads"],),
            "mamba_a_log": (s["m_heads"],),
            "mamba_d_skip": (s["m_heads"],),
            "mnorm_gamma": (d_inner,),
            "out_proj_weight": (d, d_inner)}[name]


def _draw(key, name, s, dtype):
    """One tensor in the served type, in its own range."""
    if name in _RANGES:
        mean, dev = _RANGES[name]
    else:
        mean = 1.0 if name.endswith(("gamma", "d_skip")) else 0.0
        dev = s["std"]
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
    kind of layer (its index is an argument), called layer by layer —
    a single program over all layers draws the same numbers and takes
    minutes to compile."""
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


def _mlp(x, p):
    gu = x @ p["fc1_weight"].T
    f = gu.shape[-1] // 2
    return (jax.nn.silu(gu[..., :f]) * gu[..., f:]) @ p["fc2_weight"].T


def _attention(x, p, s):
    n, t, d = x.shape
    h, kv = s["heads"], s["kv_heads"]
    hd = d // h
    qkv = x @ p["qkv_weight"].T
    q = qkv[..., :d].reshape(n, t, h, hd)
    k = qkv[..., d:d + kv * hd].reshape(n, t, kv, hd)
    v = qkv[..., d + kv * hd:].reshape(n, t, kv, hd)
    k, v = (jnp.repeat(a, h // kv, axis=2) for a in (k, v))
    scores = jnp.einsum("nqhd,nkhd->nhqk", q, k) * s["att_mult"]
    scores = jnp.where(jnp.tril(jnp.ones((t, t), bool)), scores,
                       -jnp.inf)
    att = jnp.einsum("nhqk,nkhd->nqhd", jax.nn.softmax(scores, -1), v)
    return att.reshape(n, t, d) @ p["proj_weight"].T


def _mamba(x, p, s):
    n, t, _ = x.shape
    H, P, N, K = s["m_heads"], s["m_head"], s["m_state"], s["m_conv"]
    d_inner = H * P
    conv = d_inner + 2 * N
    zxd = x @ p["in_proj_weight"].T
    z, xbc, dt = (zxd[..., :d_inner], zxd[..., d_inner:d_inner + conv],
                  zxd[..., d_inner + conv:])
    padded = jnp.pad(xbc, ((0, 0), (K - 1, 0), (0, 0)))
    xbc = p["mamba_conv_bias"] + sum(
        padded[:, j:j + t] * p["mamba_conv_weight"][:, j]
        for j in range(K))
    xbc = jax.nn.silu(xbc)
    xs = xbc[..., :d_inner].reshape(n, t, H, P)
    Bm, Cm = xbc[..., d_inner:d_inner + N], xbc[..., d_inner + N:]
    step = jax.nn.softplus(dt + p["mamba_dt_bias"])          # (n, t, H)
    A = -jnp.exp(p["mamba_a_log"])

    def one(S, at):
        x_t, d_t, b_t, c_t = at
        S = jnp.exp(d_t * A)[..., None, None] * S + \
            (d_t[..., None] * x_t)[..., None] * b_t[:, None, None, :]
        return S, (S * c_t[:, None, None, :]).sum(-1)

    _, ys = jax.lax.scan(
        one, jnp.zeros((n, H, P, N), jnp.float32),
        tuple(jnp.moveaxis(a, 1, 0) for a in (xs, step, Bm, Cm)))
    y = jnp.moveaxis(ys, 0, 1) + p["mamba_d_skip"][:, None] * xs
    y = y.reshape(n, t, d_inner) * jax.nn.silu(z)
    return _rms(y, p["mnorm_gamma"], s["eps"]) @ p["out_proj_weight"].T


def _layer(x, p, kind, s):
    """One block on (N, T, D) float32."""
    mixer = _mamba if kind == "mamba" else _attention
    x = x + s["res_mult"] * mixer(
        _rms(x, p["ln1_gamma"], s["eps"]), p, s)
    return x + s["res_mult"] * _mlp(
        _rms(x, p["ln2_gamma"], s["eps"]), p)


@functools.lru_cache(maxsize=None)
def _programs(frozen, dtype_name, int8):
    """The jitted pieces, compiled once per (sizes, served type,
    weights as drawn or as int8 holds them): embed, one layer of each
    kind (its index is an argument, so all layers of a kind share one
    program), head."""
    s = dict(frozen)
    dtype = jnp.dtype(dtype_name)

    def up(tree):
        out = {n: v.astype(jnp.float32) for n, v in tree.items()}
        if int8:
            out.update({n: _as_int8_holds(out[n]) for n in out
                        if n in _PROJECTIONS})
        return out

    @jax.jit
    def embed(key, tokens):
        p = up(_top_tensors(key, s, dtype))
        return s["emb_mult"] * p["tok_embed_weight"][tokens]

    def layer_of(kind):
        @jax.jit
        def layer(key, index, x):
            with jax.default_matmul_precision("highest"):
                return _layer(x, up(_layer_tensors(key, index, kind, s,
                                                   dtype)), kind, s)
        return layer

    @jax.jit
    def head(key, x, rows):
        """Logits at the positions `rows` (N, R) of each sequence."""
        p = up(_top_tensors(key, s, dtype))
        picked = jnp.take_along_axis(x, rows[:, :, None], axis=1)
        with jax.default_matmul_precision("highest"):
            h = _rms(picked, p["ln_f_gamma"], s["eps"])
            return h @ p["tok_embed_weight"].T / s["logit_div"]

    return embed, {k: layer_of(k) for k in _KINDS}, head


def logits_at(cfg, seed, tokens, rows, dtype="bfloat16", int8=False):
    """Full forward over `tokens` (N, T) int32, layer by layer, and the
    logits (N, R, V) at positions `rows` (N, R). `int8` rounds every
    projection's weight, the token table among them, to what a
    weight-only int8 path holds."""
    s = sizes(cfg)
    embed, layers, head = _programs(tuple(sorted(s.items())),
                                    str(jnp.dtype(dtype)), bool(int8))
    key = base_key(seed)
    x = embed(key, jnp.asarray(tokens, jnp.int32))
    for i, kind in enumerate(s["kinds"]):
        x = layers[kind](key, jnp.int32(i), x)
    return head(key, x, jnp.asarray(rows, jnp.int32))


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
