"""Mixture-of-experts FFNs: two routings over one expert-weight layout
(``gate_w (D, E)``, ``w1 (E, D, H)``, ``w2 (E, H, D)``).

**Switch routing with a capacity** (:func:`dense_moe` in one program,
:func:`moe_ffn` expert-parallel over ``lax.all_to_all``): top-1 of the
softmax scores, the expert's output scaled by that score; each expert
takes at most ``ceil(N * capacity_factor / E)`` tokens, in token order,
and a token past the capacity contributes zeros (the residual around
the layer carries it). ReLU experts. Every expert's FFN runs over a
full capacity buffer ``(E, cap, D)``, whatever was routed: the training
form (Shazeer et al. 2017; Fedus et al., Switch Transformer, 2021),
where the buffers are what the all_to_all exchanges — experts shard
over a mesh axis; each device routes its local tokens, packs them into
per-expert capacity buffers, exchanges buffers with one all_to_all
(ICI), runs its resident experts' FFN, and all_to_alls results back.

**Top-k routing, nothing dropped** (:func:`routed_experts`): the
``top_k`` experts of each token by float32 scores, every (token,
expert) pair computed, under any imbalance, and nothing else: the
pairs are sorted by expert and each expert's weights meet its own
ragged batch in one grouped matrix product (no ``(E, N, D)`` buffer, no
capacity). The serving form: the decode symbol's expert layers are
this one. The routings it holds (:func:`route_topk`):

- ``"softmax"``: the k largest softmax scores, each the pair's weight,
  renormalised to sum to one where the model says so;
- ``"sigmoid"``: scores ``sigmoid(x W_r)``; the k largest of score +
  bias are CHOSEN (the score-correction bias chooses and does not
  weigh), the weights are the chosen scores themselves, renormalised
  where the model says so (``s / (sum + 1e-20)``);
- either way times a ``scale`` (the routed scaling factor).

Experts are ReLU, squared-ReLU (``"relu2"``) or gated-SiLU (``w1`` then
holds ``[gate | up]``, ``(E, D, 2H)``). Around them, where the model
has them: a latent pair of projections (``D -> Z`` once a token before
the experts, which then map ``Z -> H -> Z``, and ``Z -> D`` once on the
weighted sum) and a shared expert over the full width, added whole.

**The chip's share**: ``w1`` and ``w2`` may hold fewer experts than the
router has outputs: ``first_expert`` says which run of them. Routing
is over all E; the pairs whose expert is held are computed and the
others contribute nothing (their chips would add their parts), so the
shares of all chips, with the shared expert counted once, add up to
the whole layer. Absent pairs sort behind every held expert's and the
grouped product never visits them.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P


__all__ = ["moe_ffn", "dense_moe", "routed_experts", "route_topk"]


def _route(x, gate_w, num_experts, capacity):
    """Top-1 routing of local tokens: returns (expert_id, slot, keep,
    gate_prob) per token — slot is the token's position in its expert's
    capacity buffer, assigned in token order (first come first served,
    the Switch discipline)."""
    probs = jax.nn.softmax(
        (x.astype(jnp.float32) @ gate_w.astype(jnp.float32)), axis=-1)
    gate = jnp.max(probs, axis=-1)
    expert = jnp.argmax(probs, axis=-1)
    onehot = jax.nn.one_hot(expert, num_experts, dtype=jnp.int32)
    slot = jnp.sum((jnp.cumsum(onehot, axis=0) - 1) * onehot, axis=-1)
    keep = slot < capacity
    return expert, jnp.clip(slot, 0, capacity - 1), keep, gate


def _dispatch(x, expert, slot, keep, num_buckets, cap):
    """Scatter kept tokens into (num_buckets, cap, D) capacity
    buffers."""
    disp = jnp.zeros((num_buckets, cap, x.shape[-1]), x.dtype)
    return disp.at[expert, slot].add(jnp.where(keep[:, None], x, 0))


def _combine(y, expert, slot, keep, gate, dtype):
    """Gather each token's expert output back, gated; dropped tokens
    zero."""
    out = y[expert, slot] * gate[:, None].astype(dtype)
    return jnp.where(keep[:, None], out, 0.0).astype(dtype)


def dense_moe(x, gate_w, w1, w2, capacity_factor=1.25):
    """Single-program Switch MoE: route local tokens into capacity
    buffers, run every expert's FFN, combine. Shares _route/_dispatch/
    _combine with the expert-parallel moe_ffn below (which inserts the
    all_to_all exchanges between the same stages).

    x (N, D); gate_w (D, E); w1 (E, D, H); w2 (E, H, D) -> (N, D),
    capacity-dropped tokens zero."""
    N = x.shape[0]
    E = gate_w.shape[1]
    cap = max(1, int(math.ceil(N * float(capacity_factor) / E)))
    expert, slot, keep, gate = _route(x, gate_w, E, cap)
    disp = _dispatch(x, expert, slot, keep, E, cap)
    h = jax.nn.relu(jnp.einsum("ecd,edh->ech", disp, w1))
    y = jnp.einsum("ech,ehd->ecd", h, w2)
    return _combine(y, expert, slot, keep, gate, x.dtype)


def moe_ffn(x, gate_w, w1, w2, mesh, axis_name="expert",
            capacity_factor=1.25):
    """Expert-parallel MoE FFN.

    x: (T, D) tokens, T sharded over ``axis_name``.
    gate_w: (D, E) router weights (replicated).
    w1: (E, D, H), w2: (E, H, D) expert weights, E sharded over the axis.
    Returns (T, D) with x's sharding; dropped-capacity tokens yield 0.
    """
    n = mesh.shape[axis_name]
    E = gate_w.shape[1]
    if E % n:
        raise ValueError("num_experts %d must divide over %d devices"
                         % (E, n))

    def local(xl, gw, w1l, w2l):
        # xl (Tl, D); w1l (El, D, H); w2l (El, H, D)
        Tl, D = xl.shape
        El = E // n
        cap = max(1, int(math.ceil(Tl * capacity_factor / E)))
        expert, slot, keep, gate = _route(xl, gw, E, cap)
        disp = _dispatch(xl, expert, slot, keep, E, cap)
        # exchange: device d keeps buffers for its El resident experts
        # from every sender -> (n senders, El, cap, D)
        recv = lax.all_to_all(disp.reshape(n, El, cap, D), axis_name,
                              split_axis=0, concat_axis=0, tiled=False)
        # recv: (n senders, El, cap, D) -> expert-major token queues
        tokens = recv.transpose(1, 0, 2, 3).reshape(El, n * cap, D)

        h = jax.nn.relu(jnp.einsum("ecd,edh->ech", tokens, w1l))
        y = jnp.einsum("ech,ehd->ecd", h, w2l)          # (El, n*cap, D)

        # back to sender-major and return to the owning devices
        y = y.reshape(El, n, cap, D).transpose(1, 0, 2, 3)
        back = lax.all_to_all(y, axis_name,
                              split_axis=0, concat_axis=0, tiled=False)
        # back: (n expert-groups, El, cap, D); group-major flatten IS
        # global expert order -> my tokens' buffers (E, cap, D)
        mine = back.reshape(E, cap, D)
        return _combine(mine, expert, slot, keep, gate, xl.dtype)

    fn = jax.shard_map(local, mesh=mesh,
                    in_specs=(P(axis_name), P(), P(axis_name), P(axis_name)),
                    out_specs=P(axis_name))
    return fn(x, gate_w, w1, w2)


def route_topk(x, gate_w, top_k, renormalize, scoring="softmax",
               score_bias=None, scale=1.0, renorm_eps=1e-20):
    """The ``top_k`` experts of each token and their weights, in
    float32 throughout (a bf16 product would move near-tied scores
    past each other). ``scoring="softmax"``: softmax over all E
    scores, the k largest (a tie goes to the lower expert index, as
    ``lax.top_k`` orders them), divided by their sum under
    ``renormalize``. ``scoring="sigmoid"``: sigmoid scores; the k
    largest of score + ``score_bias`` are chosen, the weights are the
    chosen SCORES (the bias chooses and does not weigh), divided by
    their sum + ``renorm_eps`` under ``renormalize`` (the published
    equations differ in that term: 1e-20 here by default, 1e-6 in the
    ``lfm2_moe`` block, which is 4 float32 ulps of a sum near 2).
    Either way times ``scale``. x (N, D); gate_w (D, E); score_bias
    (E,) -> weights (N, k) f32, experts (N, k) int32."""
    scores = jnp.dot(x.astype(jnp.float32), gate_w.astype(jnp.float32),
                     precision=lax.Precision.HIGHEST)
    if scoring == "softmax":
        weights, experts = lax.top_k(jax.nn.softmax(scores, axis=-1),
                                     int(top_k))
        if renormalize:
            weights = weights / weights.sum(axis=-1, keepdims=True)
    elif scoring == "sigmoid":
        scores = jax.nn.sigmoid(scores)
        _, experts = lax.top_k(
            scores + score_bias.astype(jnp.float32), int(top_k))
        weights = jnp.take_along_axis(scores, experts, axis=-1)
        if renormalize:
            weights = weights / (weights.sum(axis=-1, keepdims=True)
                                 + renorm_eps)
    else:
        raise ValueError("scoring must be 'softmax' or 'sigmoid', got "
                         "%r" % (scoring,))
    if scale != 1.0:
        weights = weights * jnp.float32(scale)
    return weights, experts.astype(jnp.int32)


# rows of the sorted (token, expert) pairs that one visit of the
# grouped product takes: a pass of the MXU (128), or four where an
# expert's mean batch is 256 rows and more (fewer visits, so each
# expert's weights are read fewer times). One layer at 2048 -> 128
# experts of 768, top 8, bf16, ms (my chip runs, PR 31; TPU v5 lite):
# 64 tokens 1.71 at 128 rows, against 1.65 for one read of all the
# expert weights and 3.93 through `lax.ragged_dot`; 960 tokens 2.66 at
# 128 rows, 4.25 at 512 (ragged_dot 4.73); 8 128 tokens 12.55 at 128
# rows, 10.88 at 512 (ragged_dot 12.79).
_PAIR_TILE = 128
_PAIR_TILE_WIDE = 512


def _grouped_dot(rows, weights, sizes):
    """``rows[start_e:end_e] @ weights[e]`` for each group e of the
    sorted rows: rows (M, K), M a multiple of the pair tile; weights
    (E, K, N); sizes (E,) int32 summing to at most M (rows past the
    last group come back undefined). The Pallas grouped matmul of
    ``jax.experimental.pallas.ops.tpu.megablox``: a group's weights
    are read once for each row tile the group touches, and a tile's
    rows outside the group are masked, so the work follows the pairs
    and not E x M."""
    from jax.experimental.pallas.ops.tpu.megablox import gmm
    from ..ops import _pallas
    M, K = rows.shape
    N = weights.shape[2]
    tm = _PAIR_TILE_WIDE if M >= 256 * weights.shape[0] and \
        M % _PAIR_TILE_WIDE == 0 else _PAIR_TILE
    # the kernel's products take the arrays' own type with float32
    # accumulation; the package-wide float32 default is a policy for
    # XLA's f32 matmuls, and Mosaic refuses it ("Bad lhs type")
    with jax.default_matmul_precision("default"):
        return gmm(rows, weights, sizes,
                   preferred_element_type=rows.dtype,
                   tiling=(tm, min(K, 1024), min(N, 1024)),
                   interpret=_pallas.interpret())


_ACTS = {"relu": jax.nn.relu,
         "relu2": lambda h: jnp.square(jax.nn.relu(h))}


def _activate(h, act):
    if act == "gated_silu":
        half = h.shape[1] // 2
        return jax.nn.silu(h[:, :half]) * h[:, half:]
    return _ACTS[act](h)


def routed_experts(x, gate_w, w1, w2, top_k=1, act="relu",
                   renormalize=False, scoring="softmax",
                   score_bias=None, scale=1.0, first_expert=0,
                   latent=None, shared=None, renorm_eps=1e-20):
    """Top-k mixture-of-experts FFN that drops nothing and computes
    only the routed (token, expert) pairs whose expert is held here.

    x (N, D); gate_w (D, E); w1 (Eh, Z, H) for ``act`` "relu" or
    "relu2", (Eh, Z, 2H) holding ``[gate | up]`` for "gated_silu"; w2
    (Eh, H, Z): the Eh experts ``first_expert .. first_expert + Eh -
    1`` of the router's E (all of them by default), over Z = D or,
    with ``latent=(down (D, Z), up (Z, D))``, over the latent width:
    ``down`` runs once a token before the experts, ``up`` once on the
    weighted sum. ``shared=(p (D, Hs), q (Hs, D))`` adds
    ``act(x p) q`` whole (p is (D, 2 Hs) = [gate | up] for
    "gated_silu"). ``scoring``, ``score_bias``, ``scale``,
    ``renorm_eps``: see :func:`route_topk`. Returns ``(y, stats)``: y
    (N, D) in x's dtype; stats int32 = pairs routed (N * k), distinct
    held experts with at least one token, the largest expert batch
    and, where fewer experts are held than routed over, the pairs
    computed here.

    The N * k pairs are sorted by expert (a stable sort: within an
    expert, token order; pairs of experts not held here behind all
    others), the tokens' rows gathered in that order, and each of the
    two projections is ONE grouped product over the ragged per-expert
    batches (:func:`_grouped_dot`), which visits only the held
    experts' rows; the outputs return to token order through the
    inverse permutation and are summed over k in float32 — a gather,
    never a scatter. The stages carry ``jax.named_scope("moe.route" |
    "moe.latent" | "moe.experts" | "moe.combine" | "moe.shared")`` so
    a device trace can tell them apart."""
    N, D = x.shape
    E = gate_w.shape[1]
    held = w1.shape[0]
    k = int(top_k)
    if act not in ("relu", "relu2", "gated_silu"):
        raise ValueError("act must be 'relu', 'relu2' or 'gated_silu', "
                         "got %r" % (act,))
    first = int(first_expert)
    if first < 0 or first + held > E:
        raise ValueError(
            "experts %d..%d are held but the router has %d outputs"
            % (first, first + held - 1, E))
    part = held != E
    inner = x
    if latent is not None:
        with jax.named_scope("moe.latent"):
            inner = jnp.dot(x, latent[0].astype(x.dtype),
                            preferred_element_type=jnp.float32) \
                .astype(x.dtype)
    with jax.named_scope("moe.route"):
        weights, experts = route_topk(x, gate_w, k, renormalize,
                                      scoring, score_bias, scale,
                                      renorm_eps)
        flat = experts.reshape(-1)                      # (N*k,)
        if part:
            here = (flat >= first) & (flat < first + held)
            flat = jnp.where(here, flat - first, held)
        order = jnp.argsort(flat, stable=True)
        # (a compare and a sum, not a scatter-add: the TPU runs a
        # scatter as a loop over its updates)
        sizes = (flat[:, None] == jnp.arange(held)).sum(
            axis=0, dtype=jnp.int32)
        M = -(-N * k // _PAIR_TILE) * _PAIR_TILE        # whole tiles
        token = jnp.pad(order // k, (0, M - N * k))
        rows = jnp.take(inner, token, axis=0)           # (M, Z)
        stats = [jnp.int32(N * k),
                 (sizes > 0).sum().astype(jnp.int32), sizes.max()]
        if part:
            stats.append(sizes.sum())
        stats = jnp.stack(stats)
    with jax.named_scope("moe.experts"):
        h = _activate(_grouped_dot(rows, w1, sizes), act)
        out = _grouped_dot(h, w2, sizes)                # (M, Z)
    with jax.named_scope("moe.combine"):
        back = jnp.argsort(order)                       # pair -> row
        y = jnp.take(out, back, axis=0).reshape(N, k, -1)
        if part:
            # rows no group covers come back undefined: a pair whose
            # expert lives elsewhere adds nothing here
            y = jnp.where(here.reshape(N, k, 1), y, 0)
        y = (y.astype(jnp.float32) * weights[:, :, None]).sum(axis=1)
    if latent is not None:
        with jax.named_scope("moe.latent"):
            y = jnp.dot(y.astype(x.dtype), latent[1].astype(x.dtype),
                        preferred_element_type=jnp.float32)
    if shared is not None:
        with jax.named_scope("moe.shared"):
            hs = _activate(
                jnp.dot(x, shared[0].astype(x.dtype),
                        preferred_element_type=jnp.float32)
                .astype(x.dtype), act)
            y = y + jnp.dot(hs, shared[1].astype(x.dtype),
                            preferred_element_type=jnp.float32)
    return y.astype(x.dtype), stats
