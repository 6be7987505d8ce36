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
chip runs, PR 46).

The part over cached rows runs to the forward's own depth, a block of
columns at a time: `ceil((max(pos) + T) / W)` blocks, counted in the
program, in loops whose trip count that is (the indexer's scores a
block at a time; the selection's threshold, found over the blocks
scored; the mask a block at a time; the attention with a running
maximum and sum, heads outside and columns inside). W follows from the
forward's shape (`_block_width`): 512 columns for a chunk of 512
queries, a quarter of the buffer for one query a row. A forward no
deeper than K computes no index scores and no selection: it keeps
every row it sees by definition. Measured against it and not kept:
the one-shot softmax of PR 46 behind a `lax.switch` over 33 column
counts in place of four (every 512), ONE
mixer of a chunk of 512 at depths 0 / 3 584 / 7 680 / 11 776 / 15 872:
2.55 / 4.25 / 6.58 / 8.61 / 11.08 ms where this form takes 2.51 / 4.76
/ 6.88 / 9.03 / 11.25 and four counts took 4.25 / 4.30 / 6.50 / 8.95 /
13.05, but 52 s to compile a mixer where this takes 4-7 and four
counts took 10 (sixteen counts: 29 s, and 13.1 ms at the deepest
chunk; my chip runs, PR 47).

Device work carries `jax.named_scope`: "mla.project" (the four
projections Wqa, Wqb, Wkva, Wo, the two latent norms, the rotation)
and, inside "mla.keys" (all that touches cached rows), "dsa.index"
(the indexer's projections, its key rows' write and the scores),
"dsa.select" (the threshold, the mask, the counts) and "mla.attend"
(the latent rows' write, Wkb, both products, the softmax); a loop's
body carries the scope of the code that built it. The second output
counts what the selection did: [keys visible, keys selected, keys
computed (the columns the products ran over, times the queries)],
summed over rows and positions, int32.
"""
from __future__ import annotations

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


def _block_width(T, H, C):
    """The width of a column block: the part over cached rows runs a
    block of columns at a time, as many blocks as hold the forward's
    deepest query. The widest divisor of C, a whole number of lanes
    where one is, that is at most a quarter of the buffer and at which
    one row's float32 scores of every head against a block, (H, T, W),
    stay within the budget (the head block then takes up the rows:
    `attend_selected`): 512 columns for chunks of 512 queries over
    16 896 rows, a quarter of the buffer for one query a row, whose
    work is the overhead of small operations and not the columns (one
    block of the whole buffer would have the compiler copy each
    layer's rows into another layout and back, 78 MB each way a step:
    PR 47)."""
    fit = [w for w in range(1, max(1, C // 4) + 1)
           if C % w == 0 and (w == 1 or T * H * w * 4 <= _SCORE_BYTES)]
    return max(fit, key=lambda w: (w % 128 == 0, w))


def _cut(x, j, width, axis):
    """Column block j of x."""
    return jax.lax.dynamic_slice_in_dim(x, j * width, width, axis)


def _paste(x, part, j, axis):
    """x with column block j replaced by `part`."""
    return jax.lax.dynamic_update_slice_in_dim(
        x, part, j * part.shape[axis], axis)


def select_keys(scores, pos, k, width=None, blocks=None):
    """(B, T, C) bool: each query's k visible columns of largest score,
    a tie to the lower column; all of them where a query sees fewer
    than k. scores (B, T, C) float32; pos () or (B,): query r of row b
    sees the columns up to pos[b] + r. Only the first `blocks`
    (traced; all by default) blocks of `width` columns are read,
    counted and written, the rest left unselected: no query may see
    past them.

    Exact, without a sort: the k-th largest score is found bit by bit
    (32 passes that count the scores at or over a candidate, over the
    scores' bits in an order-preserving unsigned form), the columns
    over it are selected, and of those equal to it the lowest, by a
    count that runs on across the blocks, as many as are still owed.
    On the chip, 512 queries over 16 896 columns at once (ms): 1.90,
    `jax.lax.top_k` (one full sort) 18.86; one query a row over as
    many, 0.59 against 1.10 (my chip runs, PR 46); by blocks of 512,
    1.00 to a depth of 4 096 and 1.22 to one of 16 384 (PR 47)."""
    B, T, C = scores.shape
    W = width or C
    blocks = C // W if blocks is None else blocks

    def seen(j):
        return _causal(pos - j * W, T, W, 0)

    def ordered(j, u):
        x = jnp.where(seen(j), _cut(scores, j, W, 2), -jnp.inf)
        x = jnp.where(x == 0, 0.0, x)         # -0.0 orders as +0.0 does
        b = jax.lax.bitcast_convert_type(x, jnp.uint32)
        return _paste(u, jnp.where(b >> 31 == 1, ~b,
                                   b | jnp.uint32(1 << 31)), j, 2)

    u = jax.lax.fori_loop(0, blocks, ordered,
                          jnp.zeros((B, T, C), jnp.uint32))

    # a counting pass reads as many blocks together as the budget holds,
    # or it is all loop overhead: where that is the whole buffer, one
    # read of it from fast memory (the columns past the blocks run hold
    # 0, under every score)
    wide = W * _divisor(C // W, B * T * W * 4)

    def count(hit):
        """(B, T, 1): how many of a query's columns `hit` holds for."""
        return jax.lax.fori_loop(
            0, (blocks * W + wide - 1) // wide,
            lambda j, n: n + hit(_cut(u, j, wide, 2)).sum(
                -1, keepdims=True, dtype=jnp.int32),
            jnp.zeros((B, T, 1), jnp.int32))

    def bit(i, kth):
        more = kth | (jnp.uint32(1 << 31) >> i.astype(jnp.uint32))
        return jnp.where(count(lambda x: x >= more) >= k, more, kth)

    kth = jax.lax.fori_loop(0, 32, bit, jnp.zeros((B, T, 1), jnp.uint32))
    owed = k - count(lambda x: x > kth)

    def mask(j, carry):
        sel, ties = carry
        x = _cut(u, j, W, 2)
        at = x == kth
        rank = ties + jnp.cumsum(at, axis=-1, dtype=jnp.int32)
        part = ((x > kth) | (at & (rank <= owed))) & seen(j)
        return _paste(sel, part, j, 2), rank[..., -1:]

    return jax.lax.fori_loop(
        0, blocks, mask, (jnp.zeros((B, T, C), bool),
                          jnp.zeros((B, T, 1), jnp.int32)))[0]


def attend_selected(q, rows, sel, wv, scale, width=None, blocks=None):
    """softmax over each query's selected rows, in the latent space.
    q (B, T, H, F) the query carried into it ([qn Wkb_nope^T | qr]),
    rows (B, C, F) the cached [c | kr], sel (B, T, C) the selection,
    wv (H, L, V) the values' half of Wkb (L = the latent's width, the
    first L of F). Returns (B, T, H, V). Both products run under the
    mask over the first `blocks` (traced; all by default) blocks of
    `width` columns, a block of heads at a time: heads outside and
    columns inside, the first block as a softmax of its own and each
    further one under a running maximum and sum, so that a block's
    float32 scores (B, Hb, T, W) and what the column blocks hand on,
    one head block's (B, T, Hb, L), stay within the budget
    (`_divisor`). Every head reads the same rows, so nothing is
    gathered. A block in which a query selected nothing leaves its
    maximum, sum and output as they were, bit for bit (its weights are
    exp(-1e30 - m) = 0 and its rescaling exp(0) = 1; where that is
    its FIRST blocks, what they summed under a maximum of -1e30 is
    multiplied by exp(-1e30 - m) = 0 at its first selected column), so
    a row's result does not depend on how deep the other rows of the
    forward lie."""
    B, T, H, F = q.shape
    C, L = rows.shape[1], wv.shape[1]
    W = width or C
    blocks = C // W if blocks is None else blocks
    Hb = _divisor(H, B * T * (W + L) * 4)

    def heads(qb):                                     # (B, T, Hb, F)
        def block(j, carry=None):
            r = _cut(rows, j, W, 1)
            s = jnp.einsum("bthf,bcf->bhtc", qb, r,
                           preferred_element_type=_F32) * scale
            s = jnp.where(_cut(sel, j, W, 2)[:, None], s, _NEG_INF)
            top = s.max(axis=-1)
            if carry is not None:
                top = jnp.maximum(carry[0], top)
            p = jnp.exp(s - top[..., None])
            part = jnp.einsum("bhtc,bcl->bthl", p.astype(rows.dtype),
                              r[..., :L], preferred_element_type=_F32)
            if carry is None:
                return top, p.sum(axis=-1), part
            keep = jnp.exp(carry[0] - top)
            return (top, carry[1] * keep + p.sum(axis=-1),
                    carry[2] * jnp.swapaxes(keep, 1, 2)[..., None] + part)

        _, total, acc = jax.lax.fori_loop(1, blocks, block, block(0))
        return (acc / jnp.swapaxes(total, 1, 2)[..., None]).astype(qb.dtype)

    o = jax.lax.map(heads, jnp.moveaxis(
        q.reshape(B, T, H // Hb, Hb, F), 2, 0))
    o = jnp.moveaxis(o, 0, 2).reshape(B, T, H, L)
    return jnp.einsum("bthl,hlv->bthv", o, wv.astype(o.dtype))


def latent_select_attention(x, positions, w, latent_cache, index_cache,
                            pos, *, num_heads, qk_nope_head_dim,
                            qk_rope_head_dim, v_head_dim, index_heads,
                            index_topk, rope_base=10000.0, eps=1e-5):
    """The whole mixer (see the module docstring). x (B, T, D);
    positions (T,) or (B, T): each new row's position; w: the twelve
    weights by the operator's argument names; pos (1,) or (B,): rows
    already cached. Returns (out (B, T, D), stats (3,) int32, the two
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
        W = _block_width(T, H, C)
        deepest = jnp.max(pos) + T
        blocks = (deepest + W - 1) // W

        def every():
            """Each query keeps all it sees."""
            with jax.named_scope("mla.attend"):
                return jnp.broadcast_to(_causal(pos, T, C, 0), (B, T, C))

        def chosen():
            """Each query keeps the K it scores highest."""
            with jax.named_scope("dsa.index"):
                qi = _fc(cq, w["index_q_weight"]).reshape(B, T, J, Di)
                qi = jnp.concatenate(
                    [_rotate(qi[..., :rd], positions, rope_base),
                     qi[..., rd:]], axis=-1)
                wj = _fc(x, w["index_head_weight"]).astype(_F32) * \
                    (J ** -0.5 * Di ** -0.5)
                scores = jax.lax.fori_loop(
                    0, blocks, lambda j, s: _paste(s, index_scores(
                        qi, wj, _cut(index_cache, j, W, 1)), j, 2),
                    jnp.zeros((B, T, C), _F32))
            with jax.named_scope("dsa.select"):
                return select_keys(scores, pos, K, W, blocks)

        # a forward no deeper than K keeps every visible row by
        # definition: it computes no index scores and no selection
        sel = jax.lax.cond(deepest <= K, every, chosen) if K < C \
            else every()
        with jax.named_scope("dsa.select"):
            stats = jnp.stack([
                T * jnp.sum(jnp.broadcast_to(pos, (B,))) +
                B * T * (T + 1) // 2,
                sel.sum(dtype=jnp.int32), B * T * W * blocks])
        with jax.named_scope("mla.attend"):
            a = attend_selected(ql, latent_cache, sel,
                                jnp.swapaxes(wkb[:, nope:], 1, 2),
                                (nope + rd) ** -0.5, W, blocks)
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
