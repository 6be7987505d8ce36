"""Multi-head latent attention whose keys a learned indexer selects:
the mixer of the `glm_moe_dsa` block (and of the latent-attention
blocks it descends from), whole, over TWO carried states a layer.

For a position's normed hidden n (D channels; no bias anywhere but the
index key's LayerNorm), H heads, t a query position, s <= t a key
position:

    cq        = RMSNorm(n Wqa)                      the query latent, Lq
    [qn | qr] = split(cq Wqb) a head                H x (nope | rope)
    [c | kr]  = split(n Wkva);  c <- RMSNorm(c)     Lkv | rope, kr shared by the heads
    qr, kr   <- rotate(., own position)
    qi        = split(cq Wiq) a head                J index heads of Di, the first `rope` channels rotated
    ki        = LayerNorm(n Wik), the first `rope` channels rotated
    w         = (n Wiw) * J^-1/2 * Di^-1/2          (J,)
    I[t, s]   = sum_j w_j[t] relu(qi_j[t] . ki[s])  float32
    S_t       = the min(t + 1, K) positions s <= t of largest I[t, s] (a tie: the lower s)
    a_h[t]    = sum_{s in S_t} softmax_{S_t}((qn_h[t] . kn_h[s] + qr_h[t] . kr[s]) / sqrt(nope + rope)) v_h[s]
                where [kn_h | v_h][s] = split(c[s] Wkb) a head
    out       = concat_h(a_h) Wo

What a position leaves behind is [c | kr] (Lkv + rope numbers, shared
by every head) and ki (Di numbers): a `latent_cache` (B, max_len, Lkv +
rope) and an `index_cache` (B, max_len, Di) in the served dtype, rows
written where they lie (`attention._write_rows`) at one depth for every
row or at one a row. Keys and values are never expanded a head: the
query is carried into the latent space (qn_h Wkb_nope,h^T against c,
qr_h against kr: one product against the cached row), the weighted sum
of c goes through Wkb_v,h after it. The same numbers, H x (nope + v) /
(Lkv + rope) times fewer bytes a selected row.

The selection is exact, never an approximate top-k: the K-th largest
float32 score of the visible rows is found bit by bit and the rows
over it selected, of those equal to it the lowest positions
(`select_keys`; the order `jax.lax.top_k` gives, without its sort).
With K >= max_len every visible row is selected and the indexer's
scores are not computed (its key rows are still written: they are the
layer's state).

One form for a chunk of a prompt and for the one-token step: both
products run over the cached rows under the selection's mask, a block
of heads at a time (every head reads the same rows). Gathering each
query's K rows instead was measured and taken out: 2 048 rows of
1 152 B a query, read once for each product, took 11.2-16.5 ms a
layer for a chunk of 512 where the masked products take 8.4-8.8, and
the sort that yields indices 18.9 where the threshold takes 1.9 (my
chip runs, PR 46). The part over cached rows is compiled for four
column counts (`_extents`) and a forward runs the smallest that holds
its deepest query.

Device work carries `jax.named_scope`: "mla.project" (the four
projections Wqa, Wqb, Wkva, Wo, the two latent norms, the rotation)
and, inside "mla.keys" (all that touches cached rows), "dsa.index"
(the indexer's projections, its key rows' write and the scores),
"dsa.select" (the threshold, the mask, the counts) and "mla.attend"
(the latent rows' write, Wkb, both products, the softmax). The second
output counts what the selection did: [keys visible, keys selected],
summed over rows and positions, int32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .attention import (_NEG_INF, _SCORE_BYTES, _causal, _row_pos,
                        _write_rows, rope)
from .mamba2 import _rms
from .nn import _layer_norm
from .registry import register

_F32 = jnp.float32

MLA_SIZES = ("q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
             "qk_rope_head_dim", "v_head_dim", "index_heads",
             "index_head_dim", "index_topk")



def _divisor(n, unit_bytes):
    """The largest divisor of n whose `unit_bytes` a piece stay within
    `attention._SCORE_BYTES` (the float32 scores one step of a map may
    hold: the indexer's of a group of index heads, the attention's of
    a block of heads); 1 where none does."""
    return max(d for d in range(1, n + 1)
               if n % d == 0 and (d == 1 or d * unit_bytes <= _SCORE_BYTES))


def _rotate(x, positions, base):
    """x (B, T, N, R) rotated by its positions ((T,) or (B, T)), over
    all R channels given: `attention.rope` (half-split pairs, channel
    i with i + R / 2) on the layout this mixer keeps its heads in."""
    return jnp.swapaxes(rope(jnp.swapaxes(x, 1, 2), positions, base), 1, 2)


def _fc(x, w):
    """x (..., in) through a weight held as FullyConnected holds it,
    (out, in)."""
    return jnp.dot(x, w.astype(x.dtype).T)


def index_scores(qi, w, ki_rows):
    """I (B, T, C) float32 = sum_j w[..., j] relu(qi[..., j, :] .
    ki_rows[c]): qi (B, T, J, Di), w (B, T, J) float32, ki_rows
    (B, C, Di). A group of index heads at a time, so that a group's
    (B, T, g, C) scores stay within the budget (`_divisor`)."""
    B, T, J, _ = qi.shape
    C = ki_rows.shape[1]
    g = _divisor(J, B * T * C * 4)

    def group(acc, part):
        q, wj = part                           # (B, T, g, Di), (B, T, g)
        s = jnp.einsum("btjd,bcd->btjc", q, ki_rows,
                       preferred_element_type=_F32)
        return acc + (jax.nn.relu(s) * wj[..., None]).sum(axis=2), None

    parts = (jnp.moveaxis(qi.reshape(B, T, J // g, g, -1), 2, 0),
             jnp.moveaxis(w.reshape(B, T, J // g, g), 2, 0))
    return jax.lax.scan(group, jnp.zeros((B, T, C), _F32), parts)[0]


def select_keys(scores, valid, k):
    """(B, T, C) bool: each query's k visible columns of largest score,
    a tie to the lower column; all of them where a query sees fewer
    than k. scores (B, T, C) float32, valid (1 or B, T, C).

    Exact, without a sort: the k-th largest score is found bit by bit
    (32 passes that count the scores at or over a candidate, over the
    scores' bits in an order-preserving unsigned form), the columns
    over it are selected, and of those equal to it the lowest, by a
    running count, as many as are still owed. On the chip, 512 queries
    over 16 896 columns (ms): this 1.90, `jax.lax.top_k` (one full
    sort) 18.86; one query a row over as many, 0.59 against 1.10 (my
    chip runs, PR 46)."""
    x = jnp.where(valid, scores, -jnp.inf)
    x = jnp.where(x == 0, 0.0, x)             # -0.0 orders as +0.0 does
    u = jax.lax.bitcast_convert_type(x, jnp.uint32)
    u = jnp.where(u >> 31 == 1, ~u, u | jnp.uint32(1 << 31))

    def bit(i, kth):
        more = kth | (jnp.uint32(1 << 31) >> i.astype(jnp.uint32))
        enough = (u >= more[..., None]).sum(-1) >= k
        return jnp.where(enough, more, kth)

    kth = jax.lax.fori_loop(0, 32, bit,
                            jnp.zeros(u.shape[:-1], jnp.uint32))[..., None]
    over, at = u > kth, u == kth
    owed = k - over.sum(-1, keepdims=True)
    return (over | (at & (jnp.cumsum(at, axis=-1) <= owed))) & valid


def attend_selected(q, rows, sel, wv, scale):
    """softmax over each query's selected rows, in the latent space.
    q (B, T, H, F) the query carried into it ([qn Wkb_nope^T | qr]),
    rows (B, C, F) the cached [c | kr], sel (B, T, C) the selection,
    wv (H, L, V) the values' half of Wkb (L = the latent's width, the
    first L of F). Returns (B, T, H, V). Both products run over all C
    rows under the mask, a block of heads at a time, so that a block's
    float32 scores (B, Hb, T, C) stay within the budget (`_divisor`):
    every head reads the same rows, so nothing is gathered."""
    B, T, H, F = q.shape
    C, L = rows.shape[1], wv.shape[1]
    Hb = _divisor(H, B * T * C * 4)

    def block(qb):                                     # (B, T, Hb, F)
        s = jnp.einsum("bthf,bcf->bhtc", qb, rows,
                       preferred_element_type=_F32) * scale
        p = jax.nn.softmax(jnp.where(sel[:, None], s, _NEG_INF),
                           axis=-1).astype(rows.dtype)
        return jnp.einsum("bhtc,bcl->bthl", p, rows[..., :L],
                          preferred_element_type=_F32).astype(qb.dtype)

    o = jax.lax.map(block, jnp.moveaxis(
        q.reshape(B, T, H // Hb, Hb, F), 2, 0))
    o = jnp.moveaxis(o, 0, 2).reshape(B, T, H, L)
    return jnp.einsum("bthl,hlv->bthv", o, wv.astype(o.dtype))


def _extents(C):
    """The column counts the part over cached rows is compiled for,
    ascending, the last all C: quarters of the buffer where they are
    whole. A forward runs the smallest that holds its deepest query
    (`lax.switch`: one program), so a chunk early in a prompt does not
    score, rank and mask the columns no query of it can see."""
    return [C * i // 4 for i in range(1, 5)] if C % 4 == 0 else [C]


def latent_select_attention(x, positions, w, latent_cache, index_cache,
                            pos, *, num_heads, qk_nope_head_dim,
                            qk_rope_head_dim, v_head_dim, index_heads,
                            index_topk, rope_base=10000.0, eps=1e-5):
    """The whole mixer (see the module docstring). x (B, T, D);
    positions (T,) or (B, T): each new row's position; w: the twelve
    weights by the operator's argument names; pos (1,) or (B,): rows
    already cached. Returns (out (B, T, D), stats (2,) int32, the two
    caches with the new rows written)."""
    B, T, _ = x.shape
    H, nope, rd, vd = (int(num_heads), int(qk_nope_head_dim),
                       int(qk_rope_head_dim), int(v_head_dim))
    J = int(index_heads)
    C, F = latent_cache.shape[1:]
    L, Di = F - rd, index_cache.shape[2]
    K = min(int(index_topk), C)
    pos = _row_pos(pos, B)
    with jax.named_scope("mla.project"):
        cq = _rms(_fc(x, w["q_a_weight"]).astype(_F32),
                  w["q_a_norm_gamma"], eps, x.dtype)
        q = _fc(cq, w["q_b_weight"]).reshape(B, T, H, nope + rd)
        ckr = _fc(x, w["kv_a_weight"])
        c = _rms(ckr[..., :L].astype(_F32), w["kv_a_norm_gamma"], eps,
                 x.dtype)
        kr = _rotate(ckr[..., None, L:], positions, rope_base)[:, :, 0]
        qr = _rotate(q[..., nope:], positions, rope_base)
        new_rows = jnp.concatenate([c, kr], axis=-1)
    with jax.named_scope("mla.keys"):
        with jax.named_scope("mla.attend"):
            latent_cache = _write_rows(
                latent_cache, new_rows.astype(latent_cache.dtype), pos)
            wkb = w["kv_b_weight"].reshape(H, nope + vd, L)
            ql = jnp.einsum("bthn,hnl->bthl", q[..., :nope],
                            wkb[:, :nope].astype(q.dtype))
            ql = jnp.concatenate([ql, qr], axis=-1)
        with jax.named_scope("dsa.index"):
            ki = _layer_norm(_fc(x, w["index_k_weight"]).astype(_F32),
                             w["index_k_norm_gamma"].astype(_F32),
                             w["index_k_norm_beta"].astype(_F32),
                             eps=1e-6).astype(x.dtype)
            ki = jnp.concatenate(
                [_rotate(ki[..., None, :rd], positions, rope_base)[:, :, 0],
                 ki[..., rd:]], axis=-1)
            index_cache = _write_rows(
                index_cache, ki.astype(index_cache.dtype), pos)
            if K < C:
                qi = _fc(cq, w["index_q_weight"]).reshape(B, T, J, Di)
                qi = jnp.concatenate(
                    [_rotate(qi[..., :rd], positions, rope_base),
                     qi[..., rd:]], axis=-1)
                wj = _fc(x, w["index_head_weight"]).astype(_F32) * \
                    (J ** -0.5 * Di ** -0.5)

        def over(columns):
            """Scores, selection and attention over the first
            `columns` cached rows: (a, stats)."""
            seen = jnp.broadcast_to(_causal(pos, T, columns, 0),
                                    (B, T, columns))
            sel = seen
            if K < C:
                with jax.named_scope("dsa.index"):
                    scores = index_scores(qi, wj,
                                          index_cache[:, :columns])
                with jax.named_scope("dsa.select"):
                    sel = select_keys(scores, seen, K)
            with jax.named_scope("dsa.select"):
                stats = jnp.stack([seen.sum(dtype=jnp.int32),
                                   sel.sum(dtype=jnp.int32)])
            with jax.named_scope("mla.attend"):
                return attend_selected(
                    ql, latent_cache[:, :columns], sel,
                    jnp.swapaxes(wkb[:, nope:], 1, 2),
                    (nope + rd) ** -0.5), stats

        extents = _extents(C)
        deepest = jnp.max(pos) + T
        a, stats = jax.lax.switch(
            sum((deepest > e).astype(jnp.int32) for e in extents[:-1]),
            [functools.partial(over, e) for e in extents])
    with jax.named_scope("mla.project"):
        out = _fc(a.reshape(B, T, H * vd), w["o_weight"])
    return out.astype(x.dtype), stats, latent_cache, index_cache


_WEIGHTS = ("q_a_weight", "q_a_norm_gamma", "q_b_weight", "kv_a_weight",
            "kv_a_norm_gamma", "kv_b_weight", "o_weight",
            "index_q_weight", "index_k_weight", "index_k_norm_gamma",
            "index_k_norm_beta", "index_head_weight")
_ARGS = ("data", "positions") + _WEIGHTS + ("latent_cache",
                                            "index_cache", "pos")


@register("_contrib_LatentSelectAttention", arg_names=_ARGS,
          state_inputs=(14, 15), nondiff_inputs=(1, 16),
          differentiable=False, num_visible=2,
          defaults=dict({k: 0 for k in MLA_SIZES}, num_heads=0,
                        max_len=0, rope_base=10000.0, eps=1e-5))
def _latent_select_attention_op(data, positions, *rest, num_heads=0,
                                qk_nope_head_dim=0, qk_rope_head_dim=0,
                                v_head_dim=0, index_heads=0,
                                index_topk=0, rope_base=10000.0,
                                eps=1e-5, **_):
    """Latent attention over a learned selection of keys, the whole
    mixer in one node (its twelve weights are the operator's own
    inputs: "<name>_q_a_weight" ... "<name>_index_head_weight", each a
    projection as FullyConnected holds it, (out, in)). Two aux states
    threaded in place by the executor like a KV cache: `latent_cache`
    (B, max_len, kv_lora_rank + qk_rope_head_dim) and `index_cache`
    (B, max_len, index_head_dim), in the served dtype. Any T: prefill,
    a chunk of one and the one-token step are one op; `pos` (1,) or
    one depth a row, (B,). Outputs: out (B, T, D) and stats (2,) int32
    = keys visible, keys selected. Inference-only."""
    weights = dict(zip(_WEIGHTS, rest[:len(_WEIGHTS)]))
    latent_cache, index_cache, pos = rest[len(_WEIGHTS):]
    return latent_select_attention(
        data, positions, weights, latent_cache, index_cache, pos,
        num_heads=num_heads, qk_nope_head_dim=qk_nope_head_dim,
        qk_rope_head_dim=qk_rope_head_dim, v_head_dim=v_head_dim,
        index_heads=index_heads, index_topk=index_topk,
        rope_base=float(rope_base), eps=float(eps))
