"""Plain reference for the OPT family (Zhang et al. 2022): pre-LN
decoder, LayerNorm, ReLU FFN, learned positions. Straightforward
`jax.numpy` in float32 at `highest` matmul precision: no kernels, no
cache, no batching tricks. It imports nothing of the program.

The weights belong to the benchmark, not to the program: `make_params`
draws every tensor from the seed, in the type it is served in, and the
program is handed the result. The reference draws the same tensors
again, a layer at a time, from the same seed, so it takes nothing that
the program has made. Each tensor is a uniform integer times a constant
(`_seeded.uniform`), which any compilation reproduces bit for bit.

Departures from the published model, also listed in the configuration
file: the output head is not tied to the embedding and has a bias, the
position offset of 2 is not modelled, every weight is random.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np

from cellbench.reference._seeded import base_key, uniform

LN_EPS = 1e-5
_LAYER_PARAMS = ("ln1_gamma", "ln1_beta", "qkv_weight", "qkv_bias",
                 "proj_weight", "proj_bias", "ln2_gamma", "ln2_beta",
                 "fc1_weight", "fc1_bias", "fc2_weight", "fc2_bias")
_TOP_PARAMS = ("tok_embed_weight", "pos_embed_weight", "ln_f_gamma",
               "ln_f_beta", "lm_head_weight", "lm_head_bias")
_PROJECTIONS = ("qkv_weight", "proj_weight", "fc1_weight", "fc2_weight",
                "lm_head_weight")


def sizes(cfg):
    return dict(dim=int(cfg["hidden_size"]),
                heads=int(cfg["num_attention_heads"]),
                ffn=int(cfg["ffn_dim"]), vocab=int(cfg["vocab_size"]),
                layers=int(cfg["num_hidden_layers"]),
                positions=int(cfg["max_position_embeddings"]))


def _shape(name, s):
    d, f, v = s["dim"], s["ffn"], s["vocab"]
    return {"ln1_gamma": (d,), "ln1_beta": (d,), "ln2_gamma": (d,),
            "ln2_beta": (d,), "ln_f_gamma": (d,), "ln_f_beta": (d,),
            "qkv_weight": (3 * d, d), "qkv_bias": (3 * d,),
            "proj_weight": (d, d), "proj_bias": (d,),
            "fc1_weight": (f, d), "fc1_bias": (f,),
            "fc2_weight": (d, f), "fc2_bias": (d,),
            "tok_embed_weight": (v, d),
            "pos_embed_weight": (s["positions"], d),
            "lm_head_weight": (v, d), "lm_head_bias": (v,)}[name]


def _draw(key, name, shape, std, dtype):
    """One tensor in the served type; gammas sit around 1."""
    mean = 1.0 if name.endswith("gamma") else 0.0
    return uniform(key, shape, std, mean).astype(dtype)


def _layer_tensors(key, layer, s, std, dtype):
    lkey = jax.random.fold_in(key, layer + 1)
    return {n: _draw(jax.random.fold_in(lkey, i), n, _shape(n, s), std,
                     dtype)
            for i, n in enumerate(_LAYER_PARAMS)}


def _top_tensors(key, s, std, dtype, names=_TOP_PARAMS):
    tkey = jax.random.fold_in(key, 0)
    return {n: _draw(jax.random.fold_in(tkey, _TOP_PARAMS.index(n)), n,
                     _shape(n, s), std, dtype) for n in names}


def make_params(cfg, seed, dtype="bfloat16"):
    """Every tensor of the model under the program's parameter names,
    made on the device in one jitted call."""
    s = sizes(cfg)
    std = float(cfg.get("init_std", 0.02))
    dtype = jnp.dtype(dtype)

    @jax.jit
    def build(key):
        out = dict(_top_tensors(key, s, std, dtype))
        for layer in range(s["layers"]):
            for n, v in _layer_tensors(key, layer, s, std, dtype).items():
                out["layer%d_%s" % (layer, n)] = v
        return out

    return build(base_key(seed))


def _ln(x, g, b):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + LN_EPS) * g + b


def _as_int8_holds(w):
    """A projection's weight as a weight-only int8 path holds it: per
    output channel, symmetric, 127 levels a side, and back to float."""
    scale = jnp.max(jnp.abs(w), axis=1, keepdims=True) / 127.0
    scale = jnp.where(scale == 0, 1.0, scale)
    return jnp.clip(jnp.round(w / scale), -127, 127) * scale


def _linear(x, w, b):
    return x @ w.T + b


def _layer(x, p, heads):
    """One block on (N, T, D) float32."""
    n, t, d = x.shape
    hd = d // heads
    a = _ln(x, p["ln1_gamma"], p["ln1_beta"])
    qkv = _linear(a, p["qkv_weight"], p["qkv_bias"])
    q, k, v = (qkv[..., i * d:(i + 1) * d].reshape(n, t, heads, hd)
               .transpose(0, 2, 1, 3) for i in range(3))
    scores = jnp.einsum("nhqd,nhkd->nhqk", q, k) * hd ** -0.5
    mask = jnp.tril(jnp.ones((t, t), bool))
    scores = jnp.where(mask, scores, -jnp.inf)
    att = jnp.einsum("nhqk,nhkd->nhqd", jax.nn.softmax(scores, -1), v)
    att = att.transpose(0, 2, 1, 3).reshape(n, t, d)
    x = x + _linear(att, p["proj_weight"], p["proj_bias"])
    f = _ln(x, p["ln2_gamma"], p["ln2_beta"])
    h = jax.nn.relu(_linear(f, p["fc1_weight"], p["fc1_bias"]))
    return x + _linear(h, p["fc2_weight"], p["fc2_bias"])


@functools.lru_cache(maxsize=None)
def _programs(cfg_key, dtype_name, int8):
    """The three jitted pieces, compiled once per (sizes, served type,
    weights as drawn or as int8 holds them): embed, one layer (its
    index is an argument, so all layers share one program), head."""
    s = dict(cfg_key)
    std = s.pop("std")
    dtype = jnp.dtype(dtype_name)
    f32 = jnp.float32

    def up(tree):
        out = {n: v.astype(f32) for n, v in tree.items()}
        if int8:
            out.update({n: _as_int8_holds(out[n]) for n in out
                        if n in _PROJECTIONS})
        return out

    @jax.jit
    def embed(key, tokens):
        p = up(_top_tensors(key, s, std, dtype,
                            ("tok_embed_weight", "pos_embed_weight")))
        t = tokens.shape[1]
        return p["tok_embed_weight"][tokens] + p["pos_embed_weight"][:t]

    @jax.jit
    def layer(key, index, x):
        with jax.default_matmul_precision("highest"):
            return _layer(x, up(_layer_tensors(key, index, s, std, dtype)),
                          s["heads"])

    @jax.jit
    def head(key, x, rows):
        """Logits at the positions `rows` (N, R) of each sequence."""
        p = up(_top_tensors(key, s, std, dtype,
                            ("ln_f_gamma", "ln_f_beta", "lm_head_weight",
                             "lm_head_bias")))
        picked = jnp.take_along_axis(x, rows[:, :, None], axis=1)
        with jax.default_matmul_precision("highest"):
            h = _ln(picked, p["ln_f_gamma"], p["ln_f_beta"])
            return _linear(h, p["lm_head_weight"], p["lm_head_bias"])

    return embed, layer, head


def logits_at(cfg, seed, tokens, rows, dtype="bfloat16", int8=False):
    """Full forward over `tokens` (N, T) int32, layer by layer, and the
    logits (N, R, V) at positions `rows` (N, R). `int8` rounds every
    projection's weight to what a weight-only int8 path holds."""
    s = sizes(cfg)
    cfg_key = tuple(sorted({**s, "std": float(cfg.get("init_std",
                                                      0.02))}.items()))
    embed, layer, head = _programs(cfg_key, str(jnp.dtype(dtype)),
                                   bool(int8))
    key = base_key(seed)
    x = embed(key, jnp.asarray(tokens, jnp.int32))
    for i in range(s["layers"]):
        x = layer(key, jnp.int32(i), x)
    return head(key, x, jnp.asarray(rows, jnp.int32))


def served_logits(cfg, seed, rows, dtype="bfloat16", pad_to=None,
                  served_to=None, int8=False, group=4):
    """For each served row (prompt_len, ids of prompt + served tokens),
    in order: the reference's logits (n, V) at the n positions that
    each predict one served token. `pad_to` and `served_to` fix the
    compiled shapes (longest row, most served tokens) from run to
    run."""
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


def served_gaps(rows, logits):
    """At every served position, how far the served token's reference
    logit lies below the reference's best: one flat list over `rows`
    and their reference `logits` (as `served_logits` yields them)."""
    gaps = []
    for (p, ids), ref in zip(rows, logits):
        served = np.asarray(ids[p:], np.int64)
        gaps.extend((ref.max(-1) -
                     ref[np.arange(len(served)), served]).tolist())
    return gaps


def logit_errors(got, ref, ref_int8):
    """(size, int8 share) of the program's error over the same logits:
    the root-mean-square of `got - ref` against the spread of `ref`
    across the vocabulary, and how much of the step from the reference
    to its weight-only-int8 twin the program's error holds (its
    projection on that step: about 0 for rounding noise of any size,
    about 1 for a program that serves from int8 weights)."""
    got, ref, ref_int8 = (np.concatenate(a).astype(np.float64)
                          for a in (got, ref, ref_int8))
    err, step = got - ref, ref_int8 - ref
    spread = np.sqrt(np.mean((ref - ref.mean(-1, keepdims=True)) ** 2))
    return (float(np.sqrt(np.mean(err ** 2)) / spread),
            float(np.sum(err * step) / np.sum(step ** 2)))
