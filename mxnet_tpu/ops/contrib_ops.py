"""Contrib op tail: fft/ifft, count_sketch, quantize/dequantize.

Reference: src/operator/contrib/{fft,ifft,count_sketch,quantize,
dequantize}-inl.h. The cuFFT-backed ops become jnp.fft (XLA lowers to
the TPU FFT implementation); count_sketch's scatter-add hashing becomes
one segment_sum; quantization keeps the reference's affine uint8
mapping and min/max plumbing.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from .registry import register, set_arg_select


@register("_contrib_fft", arg_names=("data",),
          aliases=("fft",), defaults={"compute_size": 128})
def _fft(data, **_):
    """Real input (..., d) -> (..., 2d) interleaved [re, im] along the
    last axis (reference fft-inl.h layout)."""
    out = jnp.fft.fft(data.astype(jnp.float32), axis=-1)
    inter = jnp.stack([out.real, out.imag], axis=-1)
    return inter.reshape(data.shape[:-1] + (2 * data.shape[-1],)) \
        .astype(data.dtype)


@register("_contrib_ifft", arg_names=("data",),
          aliases=("ifft",), defaults={"compute_size": 128})
def _ifft(data, **_):
    """Interleaved (..., 2d) -> real (..., d). Like the reference (cuFFT
    inverse), the result is NOT normalized: ifft(fft(x)) == d * x."""
    d = data.shape[-1] // 2
    pairs = data.reshape(data.shape[:-1] + (d, 2)).astype(jnp.float32)
    comp = jax.lax.complex(pairs[..., 0], pairs[..., 1])
    return (jnp.fft.ifft(comp, axis=-1).real * d).astype(data.dtype)


@register("_contrib_count_sketch", arg_names=("data", "h", "s"),
          nondiff_inputs=(1, 2),
          defaults={"out_dim": 0, "processing_batch_size": 32})
def _count_sketch(data, h, s, out_dim=0, **_):
    """Count-sketch projection (reference count_sketch-inl.h):
    out[..., h[j]] += s[j] * in[..., j]; h (1, in_dim) hash buckets,
    s (1, in_dim) signs."""
    in_dim = data.shape[-1]
    hh = h.reshape(-1)[:in_dim].astype(jnp.int32)
    ss = s.reshape(-1)[:in_dim].astype(data.dtype)
    flat = data.reshape(-1, in_dim)
    contrib = flat * ss[None, :]
    out = jax.ops.segment_sum(contrib.T, hh,
                              num_segments=int(out_dim)).T
    return out.reshape(data.shape[:-1] + (int(out_dim),))


@register("_contrib_quantize", arg_names=("data", "min_range", "max_range"),
          differentiable=False, aliases=("quantize",),
          defaults={"out_type": "uint8"})
def _quantize(data, min_range, max_range, out_type="uint8", **_):
    """Affine quantization to uint8/int8 (reference quantize-inl.h):
    out = (in - min) * (limit_range / (max - min)) + 0.5; min/max pass
    through as outputs 1/2."""
    lo, hi = (0.0, 255.0) if out_type == "uint8" else (-127.0, 127.0)
    dt = jnp.uint8 if out_type == "uint8" else jnp.int8
    scale = (hi - lo) / (max_range - min_range)
    # floor(v + 0.5): round-half-up on both signs (int8 negatives would
    # truncate toward zero under a bare cast)
    q = jnp.floor((data - min_range) * scale + lo + 0.5)
    return (jnp.clip(q, lo, hi).astype(dt),
            min_range.reshape(()).astype(jnp.float32),
            max_range.reshape(()).astype(jnp.float32))


@register("_contrib_dequantize", arg_names=("data", "min_range",
                                            "max_range"),
          differentiable=False, aliases=("dequantize",),
          defaults={"out_type": "float32"})
def _dequantize(data, min_range, max_range, out_type="float32", **_):
    """Inverse of quantize (reference dequantize-inl.h): for uint8,
    out = in * ((max - min) / 255) + min."""
    if data.dtype == jnp.uint8:
        lo, hi = 0.0, 255.0
    else:                      # int8
        lo, hi = -127.0, 127.0
    scale = (max_range - min_range) / (hi - lo)
    return ((data.astype(jnp.float32) - lo) * scale + min_range) \
        .astype(np.dtype(out_type))


@register("_contrib_QuantizedFullyConnected",
          arg_names=("data", "weight", "scale", "bias"),
          differentiable=False,
          defaults={"num_hidden": 0, "no_bias": False,
                    "flatten": True})
def _quantized_fc(data, weight, scale, bias=None, num_hidden=0,
                  no_bias=False, flatten=True, **_):
    """Weight-only int8 FullyConnected — the TPU serving quantization.

    weight: int8 (num_hidden, in), per-output-channel symmetric;
    scale: f32 (num_hidden,) with w_f32 ~= weight * scale[:, None].
    Decode is HBM-bandwidth-bound (every token streams the full weight
    set), so halving weight bytes directly buys decode throughput; the
    int8->compute-dtype convert fuses into the matmul's operand read.
    The scale applies AFTER the matmul (per output channel — identical
    algebra, O(N*out) instead of O(out*in) multiplies).

    Modernizes the reference's contrib quantize story
    (src/operator/contrib/quantize-inl.h — elementwise affine quantize
    ops, kept as `_contrib_quantize`/`_contrib_dequantize` above) into
    an actual quantized-layer op. Inference-only (not differentiable);
    generation.Generator(quantize="int8") builds on it."""
    cdt = data.dtype
    if flatten and data.ndim > 2:
        data = data.reshape(data.shape[0], -1)
    w = weight.astype(cdt)
    y = jax.lax.dot_general(
        data, w, (((data.ndim - 1,), (1,)), ((), ())),
        precision=jax.lax.Precision.DEFAULT,
        preferred_element_type=jnp.float32)
    y = (y * scale.astype(jnp.float32)).astype(cdt)
    if not no_bias and bias is not None:
        y = y + bias.astype(cdt)
    return y


@register("_contrib_QuantizedEmbedding",
          arg_names=("data", "weight", "scale"),
          differentiable=False,
          defaults={"input_dim": 0, "output_dim": 0,
                    "dtype": "float32"})
def _quantized_embedding(data, weight, scale, dtype="float32", **_):
    """Weight-only int8 Embedding: weight int8 (V, D) with per-ROW
    symmetric scales (V,) — a token lookup reads one int8 row and one
    f32 scalar. Halves the (often largest) parameter's HBM footprint
    at serving; the gather itself is unchanged. dtype: output dtype
    (same attr convention as Embedding) so a bf16 compute stream is
    not silently promoted to f32."""
    ids = data.astype(jnp.int32)
    rows = jnp.take(weight, ids, axis=0).astype(jnp.float32)
    out = rows * jnp.take(scale, ids, axis=0)[..., None]
    return out.astype(np.dtype(dtype))


@register("_contrib_ScaleF32", arg_names=("data",),
          defaults={"scalar": 1.0})
def _scale_f32(data, scalar=1.0, **_):
    """data * scalar with the product taken in float32 and rounded
    ONCE, to data's dtype. `bfloat16_array * 0.22` rounds the SCALAR
    to bfloat16 first (0.2197: every product 0.12% low, a bias and
    not a noise); a model's published multipliers are meant at full
    precision."""
    return (data.astype(jnp.float32) * np.float32(scalar)) \
        .astype(data.dtype)


@register("_contrib_AddScaledF32", arg_names=("lhs", "rhs"),
          defaults={"scalar": 1.0})
def _add_scaled_f32(lhs, rhs, scalar=1.0, **_):
    """lhs + scalar * rhs in float32, rounded once to lhs's dtype —
    a residual stream taking a scaled branch (see _contrib_ScaleF32
    for why the scalar must not be rounded to the arrays' dtype)."""
    return (lhs.astype(jnp.float32) +
            np.float32(scalar) * rhs.astype(jnp.float32)) \
        .astype(lhs.dtype)


@register("_contrib_RowsAt", arg_names=("data", "start"),
          nondiff_inputs=(1,), defaults={"rows": 1})
def _rows_at(data, start, rows=1, **_):
    """``data[b, start[b]:start[b] + rows]`` for every batch row:
    (B, T, ...) and (B,) -> (B, rows, ...). Which of a forward's
    positions go on (a diffusion pool's step runs 2L positions a row
    and its head reads the L of the open block, wherever a row has
    them: models/transformer.py ``head_rows``). ``start[b] + rows``
    must not pass T: a gather clamps where a slice would raise."""
    idx = start.astype(jnp.int32)[:, None] + jnp.arange(int(rows))
    idx = idx.reshape(idx.shape + (1,) * (data.ndim - 2))
    return jnp.take_along_axis(data, idx, axis=1)


@register("_contrib_MoEFFN",
          arg_names=("data", "gate_weight", "expert_w1", "expert_w2"),
          aliases=("_contrib_moe_ffn",),
          defaults={"capacity_factor": 1.25, "expert_axis": None})
def _moe_ffn_op(data, gate_weight, expert_w1, expert_w2,
                capacity_factor=1.25, expert_axis=None, **_):
    """Switch-style top-1 mixture-of-experts FFN (single-program form of
    parallel/moe.py — same routing math, no collectives; under a GSPMD
    mesh the expert dim shards like any other tensor).

    data (B, T, D) or (N, D); gate_weight (D, E); expert_w1 (E, D, H);
    expert_w2 (E, H, D). Tokens beyond an expert's capacity
    (ceil(N * capacity_factor / E)) output zero — pair with a residual.

    expert_axis: mesh-axis name for EXPLICIT expert parallelism. When
    the surrounding graph lowers over a mesh carrying that axis (>1
    devices), experts live sharded on it and tokens exchange via
    all_to_all (parallel/moe.py moe_ffn) instead of relying on GSPMD
    propagation. Inert eagerly / off-mesh — same ambient-mesh contract
    as FlashAttention's seq_axis.
    """
    orig_shape = data.shape
    x = data.reshape(-1, orig_shape[-1])
    if expert_axis:
        from ._mesh_ctx import active_mesh_axis
        mesh = active_mesh_axis(expert_axis)
        if mesh is not None:
            n = mesh.shape[expert_axis]
            if x.shape[0] % n:
                raise ValueError(
                    "expert_axis=%r: token count %d (=prod of %r[:-1]) "
                    "must divide over the %d devices of that mesh axis"
                    % (expert_axis, x.shape[0], orig_shape, n))
            if gate_weight.shape[1] % n:
                raise ValueError(
                    "expert_axis=%r: num_experts %d must divide over "
                    "the %d devices of that mesh axis"
                    % (expert_axis, gate_weight.shape[1], n))
            from ..parallel.moe import moe_ffn
            out = moe_ffn(x, gate_weight, expert_w1, expert_w2, mesh,
                          axis_name=expert_axis,
                          capacity_factor=float(capacity_factor))
            return out.astype(data.dtype).reshape(orig_shape)
    from ..parallel.moe import dense_moe
    out = dense_moe(x, gate_weight, expert_w1, expert_w2,
                    capacity_factor=float(capacity_factor))
    return out.astype(data.dtype).reshape(orig_shape)


_ROUTED_ARGS = ("data", "gate_weight", "expert_w1", "expert_w2",
                "score_bias", "latent_down", "latent_up", "shared_w1",
                "shared_w2")


def _routed_experts_args(attrs):
    """The inputs a routed expert layer takes under its attrs: the
    score-correction bias with sigmoid scores, the latent pair and the
    shared expert's pair where the layer has them."""
    names = list(_ROUTED_ARGS[:4])
    if attrs.get("scoring", "softmax") == "sigmoid":
        names.append("score_bias")
    if attrs.get("latent"):
        names += ["latent_down", "latent_up"]
    if attrs.get("shared"):
        names += ["shared_w1", "shared_w2"]
    return tuple(names)


@register("_contrib_RoutedExperts", arg_names=_ROUTED_ARGS,
          differentiable=False, num_visible=2,
          defaults={"top_k": 1, "act": "relu", "renormalize": False})
def _routed_experts_op(data, gate_weight, expert_w1, expert_w2, *more,
                       top_k=1, act="relu", renormalize=False,
                       scoring="softmax", scale=1.0, first_expert=0,
                       latent=False, shared=False, renorm_eps=1e-20,
                       **_):
    """Top-k mixture-of-experts FFN as it is served: float32 routing
    over every expert, every routed (token, expert) pair whose expert
    is held here computed by a grouped product over the ragged
    per-expert batches, nothing dropped under any imbalance
    (parallel/moe.py::routed_experts; contrast _contrib_MoEFFN, the
    capacity-buffer training form).

    data (B, T, D) or (N, D); gate_weight (D, E); expert_w1 (Eh, Z, H)
    for act "relu" | "relu2", (Eh, Z, 2H) = [gate | up] for
    "gated_silu"; expert_w2 (Eh, H, Z): experts first_expert ..
    first_expert + Eh - 1 of the E routed over. Then, as the attrs
    say (_routed_experts_args): score_bias (E,) with scoring
    "sigmoid" (renorm_eps: what its renormalisation adds to the sum
    it divides by); latent_down (D, Z) and latent_up (Z, D) with latent
    (else Z = D); shared_w1 (D, Hs) and shared_w2 (Hs, D) with shared.
    Outputs: y, shaped like data, and stats int32 = pairs routed,
    distinct held experts hit, largest expert batch and, where Eh < E,
    the pairs computed here. Inference-only."""
    from ..parallel.moe import routed_experts
    more = dict(zip(_routed_experts_args(
        dict(scoring=scoring, latent=latent, shared=shared))[4:], more))
    y, stats = routed_experts(
        data.reshape(-1, data.shape[-1]), gate_weight, expert_w1,
        expert_w2, top_k=int(top_k), act=str(act),
        renormalize=bool(renormalize), scoring=str(scoring),
        score_bias=more.get("score_bias"), scale=float(scale),
        first_expert=int(first_expert), renorm_eps=float(renorm_eps),
        latent=(more["latent_down"], more["latent_up"])
        if latent else None,
        shared=(more["shared_w1"], more["shared_w2"])
        if shared else None)
    return y.reshape(data.shape), stats


set_arg_select("_contrib_RoutedExperts", _routed_experts_args)
