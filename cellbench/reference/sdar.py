"""Plain reference for the SDAR family (`sdar_moe`: the Qwen3-MoE
block under a block-diffusion sampler). Straightforward `jax.numpy` in
float32 at `highest` matmul precision: attention is the full masked
matrix, the expert layer a loop over all experts weighted by a dense
(tokens, experts) matrix that is zero outside each token's top 8. No
cache, no kernel, no batching of requests, no sorting; it imports
nothing of the program.

For a position's hidden h (RMSNorm eps 1e-6 throughout):

    a = RMSNorm(h; g1);  q = a Wq, k = a Wk, v = a Wv   (no bias)
    each head of q and of k: RMSNorm over its 128 channels (q_norm, k_norm)
    RoPE, half-split pairs, base rope_theta, at absolute positions
    h' = h + softmax(q k^T / sqrt(head_dim) + M) v Wo   (GQA: 8 query heads a kv head)
    b = RMSNorm(h'; g2);  p = softmax(b Wr) over all experts, float32
    S = the 8 largest (a tie: the lower index);  w_e = p_e / sum_S p
    h'' = h' + sum_{e in S} w_e (SiLU(b Wg_e) * (b Wu_e)) Wd_e
    logits = RMSNorm(h_last; g_f) W_head^T              (untied head)

M is the BLOCK mask with block length L: position i sees position j
iff floor(j / L) <= floor(i / L).

Generation (the family's published sampler, greedy): the prompt's whole
blocks are clean context; then block by block, the block's L ids are
the prompt's remainder and `mask_id` elsewhere; a denoising forward
over the block (against the clean blocks before it, and itself, both
ways) predicts each masked position's OWN token (no shift) and L / T of
the masked positions are unmasked; when none is left the clean block
joins the context. A served row is replayed here in ONE forward, which
is that definition written without a cache: the clean sequence, and
beside it every noisy state of every block (`plan_row`); a noisy state
at block k sees the clean blocks before k and itself, and sits at block
k's own positions. With the `sequential` rule (leftmost first) every
state follows from the row's tokens alone.

The weights belong to the benchmark (`make_params` draws every tensor
from the seed in the served type, under the program's parameter names
and layouts; the reference draws them again, a layer at a time: the six
layers are 17.4 GB in float32). Departures from the published model,
also in the configuration file: every weight is random, uniform with
deviation `initializer_range` (gains around 1); `q_norm`/`k_norm` are
the Qwen3 block's, which the config does not name; block length, mask
id and "no logit shift" are the family's sampler's, not the config's;
6 of the 48 layers.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np

from cellbench.reference._seeded import base_key, uniform
from cellbench.reference.opt import (_as_int8_holds, logit_errors,
                                     served_gaps)

__all__ = ["sizes", "make_params", "plan_row", "logits_at",
           "served_logits", "served_gaps", "logit_errors"]

_TOP = ("tok_embed_weight", "ln_f_gamma", "lm_head_weight")
_LAYER = ("ln1_gamma", "qkv_weight", "q_norm_gamma", "k_norm_gamma",
          "proj_weight", "ln2_gamma", "gate_weight",
          "experts_w1_weight", "experts_w2_weight")
# what a weight-only int8 path would hold in int8: one scale an output
# channel (for the experts: an output channel of each expert). In
# every one of these layouts the input's axis, which a scale spans, is
# axis 1: (out, in), (E, in, out).
_INT8 = ("qkv_weight", "proj_weight", "lm_head_weight",
         "tok_embed_weight", "experts_w1_weight", "experts_w2_weight")


def sizes(cfg):
    if int(cfg["decoder_sparse_step"]) != 1 or cfg["mlp_only_layers"]:
        raise ValueError("sdar reference: every layer is an expert "
                         "layer (decoder_sparse_step 1, no "
                         "mlp_only_layers)")
    a = cfg["assumed"]
    return dict(dim=int(cfg["hidden_size"]),
                heads=int(cfg["num_attention_heads"]),
                kv_heads=int(cfg["num_key_value_heads"]),
                head=int(cfg["head_dim"]),
                experts=int(cfg["num_experts"]),
                top_k=int(cfg["num_experts_per_tok"]),
                expert_ffn=int(cfg["moe_intermediate_size"]),
                renorm=bool(cfg["norm_topk_prob"]),
                vocab=int(cfg["vocab_size"]),
                layers=int(cfg["num_hidden_layers"]),
                positions=int(cfg["max_position_embeddings"]),
                eps=float(cfg["rms_norm_eps"]),
                theta=float(cfg["rope_theta"]),
                std=float(cfg["initializer_range"]),
                block=int(a["block_length"]),
                mask_id=int(a["mask_token_id"]))


def _shape(name, s):
    d, hd, f, e = s["dim"], s["head"], s["expert_ffn"], s["experts"]
    q, kv = s["heads"] * hd, s["kv_heads"] * hd
    return {"tok_embed_weight": (s["vocab"], d), "ln_f_gamma": (d,),
            "lm_head_weight": (s["vocab"], d),
            "ln1_gamma": (d,), "ln2_gamma": (d,),
            "qkv_weight": (q + 2 * kv, d),
            "q_norm_gamma": (hd,), "k_norm_gamma": (hd,),
            "proj_weight": (d, q), "gate_weight": (d, e),
            "experts_w1_weight": (e, d, 2 * f),     # [gate | up]
            "experts_w2_weight": (e, f, d)}[name]


def _draw(key, name, s, dtype):
    """One tensor in the served type: projections around 0, gains
    around 1, deviation `initializer_range`."""
    mean = 1.0 if name.endswith("gamma") else 0.0
    return uniform(key, _shape(name, s), s["std"], mean).astype(dtype)


def _layer_tensors(key, layer, s, dtype):
    """`layer` may be traced: all layers share one program."""
    lkey = jax.random.fold_in(key, layer + 1)
    return {n: _draw(jax.random.fold_in(lkey, i), n, s, dtype)
            for i, n in enumerate(_LAYER)}


def _top_tensors(key, s, dtype):
    tkey = jax.random.fold_in(key, 0)
    return {n: _draw(jax.random.fold_in(tkey, i), n, s, dtype)
            for i, n in enumerate(_TOP)}


def make_params(cfg, seed, dtype="bfloat16"):
    """Every tensor of the model under the program's parameter names,
    made on the device: one program for the top, one for a layer (its
    index is an argument), called layer by layer."""
    s = sizes(cfg)
    dtype = jnp.dtype(dtype)
    key = base_key(seed)
    draw = jax.jit(functools.partial(_layer_tensors, s=s, dtype=dtype))
    out = dict(jax.jit(lambda k: _top_tensors(k, s, dtype))(key))
    for layer in range(s["layers"]):
        for n, v in draw(key, jnp.int32(layer)).items():
            out["layer%d_%s" % (layer, n)] = v
    return out


def _rms(x, g, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * g


def _rope(x, positions, theta):
    """(T, heads, hd), half-split pairs: (x[i], x[i + hd/2]) turn by
    positions * theta^(-2i/hd)."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[:, None, None] * freqs
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x1 * jnp.sin(ang) + x2 * jnp.cos(ang)], -1)


def _attention(x, p, s, positions, sees):
    """x (T, D); sees (T, T) bool: row i attends column j."""
    t = x.shape[0]
    h, kv, hd = s["heads"], s["kv_heads"], s["head"]
    qkv = x @ p["qkv_weight"].T
    q = qkv[:, :h * hd].reshape(t, h, hd)
    k = qkv[:, h * hd:(h + kv) * hd].reshape(t, kv, hd)
    v = qkv[:, (h + kv) * hd:].reshape(t, kv, hd)
    q = _rope(_rms(q, p["q_norm_gamma"], s["eps"]), positions, s["theta"])
    k = _rope(_rms(k, p["k_norm_gamma"], s["eps"]), positions, s["theta"])
    k, v = (jnp.repeat(a, h // kv, axis=1) for a in (k, v))
    scores = jnp.einsum("qhd,khd->hqk", q, k) / np.sqrt(hd)
    scores = jnp.where(sees[None], scores, -jnp.inf)
    att = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, -1), v)
    return att.reshape(t, h * hd) @ p["proj_weight"].T


def _experts(b, p, s):
    """sum over each token's top-k experts of w_e * expert_e(b): the
    (T, E) weights are zero outside the top k, and the loop runs every
    expert over every token."""
    probs = jax.nn.softmax(b @ p["gate_weight"], axis=-1)   # (T, E)
    rows = jnp.arange(b.shape[0])
    left, chosen = probs, jnp.zeros_like(probs)
    for _ in range(s["top_k"]):
        best = jnp.argmax(left, axis=-1)         # a tie: the lower index
        chosen = chosen.at[rows, best].set(probs[rows, best])
        left = left.at[rows, best].set(-1.0)
    if s["renorm"]:
        chosen = chosen / chosen.sum(-1, keepdims=True)
    f = s["expert_ffn"]

    def one(y, at):
        w1, w2, weight = at                      # (D, 2f), (f, D), (T,)
        gu = b @ w1
        return y + weight[:, None] * (
            (jax.nn.silu(gu[:, :f]) * gu[:, f:]) @ w2), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(b),
                        (p["experts_w1_weight"], p["experts_w2_weight"],
                         chosen.T))
    return y


def _layer(x, p, s, positions, sees):
    """One block on (T, D) float32."""
    x = x + _attention(_rms(x, p["ln1_gamma"], s["eps"]), p, s,
                       positions, sees)
    return x + _experts(_rms(x, p["ln2_gamma"], s["eps"]), p, s)


def _sees(block, state):
    """The mask of the replay: `block` (T,) is each position's block
    index, `state` (T,) is 0 for a clean position, n > 0 for a position
    of the n-th noisy state and -1 for padding. A clean position sees
    the clean positions of its block and of those before; a noisy one
    the clean positions of the blocks before its own, and its own
    state; padding sees padding."""
    bi, bj = block[:, None], block[None, :]
    si, sj = state[:, None], state[None, :]
    return jnp.where(sj == 0,
                     jnp.where(si == 0, bj <= bi, (si > 0) & (bj < bi)),
                     (si != 0) & (sj == si))


@functools.lru_cache(maxsize=None)
def _programs(frozen, dtype_name, int8):
    """The jitted pieces, compiled once per (sizes, served type,
    weights as drawn or as int8 holds them): embed, one layer (its
    index is an argument), head. Each draws its own weights and frees
    them when it returns."""
    s = dict(frozen)
    dtype = jnp.dtype(dtype_name)

    def up(tree):
        out = {n: v.astype(jnp.float32) for n, v in tree.items()}
        if int8:
            out.update({n: _as_int8_holds(out[n])
                        for n in out if n in _INT8})
        return out

    @jax.jit
    def embed(key, tokens):
        return up(_top_tensors(key, s, dtype))["tok_embed_weight"][tokens]

    @jax.jit
    def layer(key, index, x, positions, block, state):
        with jax.default_matmul_precision("highest"):
            return _layer(x, up(_layer_tensors(key, index, s, dtype)), s,
                          positions, _sees(block, state))

    @jax.jit
    def head(key, x, rows):
        """Logits at the positions `rows` (R,)."""
        p = up(_top_tensors(key, s, dtype))
        with jax.default_matmul_precision("highest"):
            return _rms(x[rows], p["ln_f_gamma"], s["eps"]) \
                @ p["lm_head_weight"].T

    return embed, layer, head


def logits_at(cfg, seed, tokens, positions, block, state, rows,
              dtype="bfloat16", int8=False):
    """One forward over `tokens` (T,) laid out as `plan_row` lays a
    replay out, layer by layer, and the logits (R, V) at `rows` (R,).
    `int8` rounds every projection, the experts, the table and the
    head to what a weight-only int8 path holds (gains and the router
    stay as drawn)."""
    s = sizes(cfg)
    embed, layer, head = _programs(tuple(sorted(s.items())),
                                   str(jnp.dtype(dtype)), bool(int8))
    key = base_key(seed)
    i32 = lambda a: jnp.asarray(a, jnp.int32)
    x = embed(key, i32(tokens))
    for i in range(s["layers"]):
        x = layer(key, jnp.int32(i), x, i32(positions), i32(block),
                  i32(state))
    return head(key, x, i32(rows))


def plan_row(prompt_len, ids, block, mask_id, steps):
    """The replay of one served row under the `sequential` rule: `ids`
    is the row (prompt then the served tokens), generated in blocks of
    `block` with `steps` denoising forwards a block. Returns (tokens,
    positions, block index, state, where): the clean sequence followed
    by every noisy state the sampler went through, and for each served
    token the index of the noisy position that predicted it. The last
    block may end past the row: it is laid out only as far as the row
    needs (states after the row's last token was unmasked never ran)."""
    ids = np.asarray(ids, np.int64)
    P, L, per = int(prompt_len), int(block), int(block) // int(steps)
    n = len(ids)
    toks, pos = list(ids), list(range(n))
    state = [0] * n
    where = np.zeros(n - P, np.int64)
    nth = 0
    for start in range(P // L * L, n, L):
        known = max(0, min(P, start + L) - start)    # prompt's remainder
        while known < L and start + known < n:
            nth += 1
            base = len(toks)
            for r in range(L):
                at = start + r
                toks.append(int(ids[at]) if r < known else mask_id)
                pos.append(at)
                state.append(nth)
            for r in range(known, min(known + per, L)):
                if start + r < n:
                    where[start + r - P] = base + r
            known = min(known + per, L)
    pos = np.asarray(pos, np.int64)
    return (np.asarray(toks, np.int64), pos, pos // L,
            np.asarray(state, np.int64), where)


def served_logits(cfg, seed, rows, steps, dtype="bfloat16", pad_to=None,
                  served_to=None, int8=False):
    """For each served row (prompt_len, ids of prompt + served tokens),
    in order: the reference's logits (n, V), for each of the n served
    tokens at the noisy position that predicted it. `pad_to` and
    `served_to` fix the compiled shapes (longest replay, most served
    tokens) from run to run; padding positions see only each other."""
    s = sizes(cfg)
    plans = [plan_row(p, ids, s["block"], s["mask_id"], steps)
             for p, ids in rows]
    pad_to = pad_to or max(len(pl[0]) for pl in plans)
    served_to = served_to or max(len(pl[4]) for pl in plans)
    for toks, pos, blk, state, where in plans:
        t, n = len(toks), len(where)
        if t > pad_to or n > served_to:
            raise ValueError("replay of %d positions, %d served, does "
                             "not fit %d, %d" % (t, n, pad_to, served_to))
        pad = lambda a, fill: np.concatenate(
            [a, np.full(pad_to - t, fill, np.int64)])
        at = np.concatenate([where, np.zeros(served_to - n, np.int64)])
        out = logits_at(cfg, seed, pad(toks, 0), pad(pos, 0),
                        pad(blk, 0), pad(state, -1), at, dtype, int8)
        yield np.asarray(out)[:n]


def replay_length(prompt_len, new, block, steps):
    """Positions of the replay of a row of `new` served tokens: what
    `pad_to` has to hold."""
    ids = np.zeros(prompt_len + new, np.int64)
    return len(plan_row(prompt_len, ids, block, 0, steps)[0])
