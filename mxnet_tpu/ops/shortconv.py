"""The gated short convolution: the mixer of the LFM2 family's `conv`
layers (the public `lfm2` / `lfm2_moe` blocks), whole, over ONE carried
state.

For a position's normed hidden x (D channels; no bias anywhere):

    [b | c | u] = x W_in                 (D -> 3D, chunked in that order)
    g_t = b_t * u_t                      the input gate
    v_t = sum_j w[:, j] * g_{t-(K-1)+j}  depthwise, causal, K taps (3),
                                         rows before position 0 are
                                         zero, NO activation
    y_t = c_t * v_t                      the output gate
    out = y W_out                        (D -> D)

The decode state is the last K-1 rows of `g`, the gated rows BEFORE the
taps: a (B, K-1, D) window in the served dtype, laid out and updated
as Mamba-2's convolution window is (`ops/mamba2.py::causal_conv`,
shared, not copied). It has no length axis, and there is no scan state
and no key/value row beside it: a slot costs (K-1) * D values a layer at
any depth.

`g` is rounded to the served dtype before the taps, at prefill and in
the step alike: the window holds `g` in that dtype, so a step reads the
rows a longer prefill would have read, and prefill-then-steps equals
one pass over the whole sequence bit for bit (tests/test_lfm2_moe.py).
The taps and the output gate accumulate in float32, as `mamba2_conv`
does; `y` is rounded once, before `W_out`.

One operator (`_contrib_ShortConvCached`) holds both projections, the
gates, the taps and the window's update, and its device work carries
`jax.named_scope("shortconv.conv")` for more than one position and
`"shortconv.step"` for one, which the benchmark's readers select
operations by (as `mamba2.conv` / `mamba2.step`).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .mamba2 import causal_conv
from .registry import register

_F32 = jnp.float32


def short_conv(x, in_proj, conv_weight, out_proj, conv_state):
    """x (B, T, D); in_proj (3D, D) and out_proj (D, D) as
    FullyConnected holds them, (out, in); conv_weight (D, K) with
    conv_weight[:, K-1] on the current row; conv_state (B, K-1, D).
    Returns (out (B, T, D) in x's dtype, the new window)."""
    D = x.shape[-1]
    if x.ndim != 3 or in_proj.shape != (3 * D, D) or \
            out_proj.shape != (D, D) or conv_weight.shape[0] != D:
        raise ValueError(
            "ShortConv needs x (B, T, D), in_proj (3D, D), conv_weight "
            "(D, K) and out_proj (D, D): got %r, %r, %r, %r"
            % (x.shape, in_proj.shape, conv_weight.shape,
               out_proj.shape))
    with jax.named_scope("shortconv.step" if x.shape[1] == 1
                         else "shortconv.conv"):
        bcu = jnp.dot(x, in_proj.astype(x.dtype).T)
        b, c, u = (bcu[..., i * D:(i + 1) * D] for i in range(3))
        g = (b.astype(_F32) * u.astype(_F32)).astype(x.dtype)
        v, conv_state = causal_conv(g, conv_state, conv_weight)
        y = (c.astype(_F32) * v).astype(x.dtype)
        return jnp.dot(y, out_proj.astype(x.dtype).T), conv_state


@register("_contrib_ShortConvCached",
          arg_names=("data", "in_proj_weight", "conv_weight",
                     "out_proj_weight", "conv_state", "pos"),
          state_inputs=(4,), nondiff_inputs=(5,), differentiable=False,
          defaults={"d_conv": 3, "max_len": 0})
def _short_conv_cached_op(data, in_proj_weight, conv_weight,
                          out_proj_weight, conv_state, pos, **_):
    """The gated short convolution over one carried aux state, threaded
    in place by the executor like a KV cache: `conv_state`
    (B, d_conv-1, D), the last gated rows in the served dtype. Any T:
    prefill, chunked prefill and the one-token step are one op (the
    scope's name says which ran). `pos` is accepted and ignored, as in
    `_contrib_Mamba2Cached`: the window carries its own position, so
    the per-row-position graph is this same graph. Returns (out,
    conv_state). Inference-only."""
    del pos
    return short_conv(data, in_proj_weight, conv_weight, out_proj_weight,
                      conv_state)
