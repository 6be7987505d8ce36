"""Mamba-2 mixer core: causal depthwise convolution + selective scan,
over TWO carried states of different shapes and lifetimes.

What `ops/ssm.py` does for a bare gated linear-attention layer (one
(B, H, hd, hd) blob), this module does for the Mamba-2 layer of the
hybrid models (Dao & Gu 2024, "Transformers are SSMs"; the public
`granitemoehybrid` / `mamba2` implementations name the pieces the same
way). After the input projection a layer holds `xBC` (B, T, conv_dim)
and per-head step logits `dt` (B, T, H), with conv_dim = H*P + 2*G*N
(G groups: head h reads B and C of group floor(h / (H/G))):

    xBC_t   = silu(b + sum_j w_j * xBC_{t-(K-1)+j})   depthwise, causal
    x, B, C = split(xBC_t)                    (H, P), (G, N), (G, N)
    D_t     = softplus(dt_t + dt_bias)        per head
    S_t     = exp(D_t * A) * S_{t-1} + D_t * x_t (x) B_t
    y_t     = S_t . C_t + D * x_t             A = -exp(a_log) per head

With G > 1 the scan and the step are the one-group forms mapped over
the groups (`jax.vmap`: a group's H/G heads with its own B and C), and
the gated norm takes its mean square over each group's channels; G = 1
runs the one-group code as it always has, operation for operation.

The convolution needs the last K-1 rows of `xBC` BEFORE the
convolution (the "convolution window", in the served dtype: it holds
projection outputs as they were computed); the recurrence needs S
(B, H, P, N), ALWAYS float32: it is a running sum over the whole
sequence, and a 16-bit S rounds away every contribution smaller than
2^-9 of what it already holds (tests/test_mamba2.py shows a bf16 S
failing the comparison the f32 one passes). Neither has a length axis:
a decode slot costs the same bytes at any depth.

Two execution forms, as in `ops/ssm.py`:

- `mamba2_chunk_scan` (prefill, multi-token windows): the sequence is
  cut into chunks of `chunk` tokens; inside a chunk every position
  reads the carried state decayed to it plus a masked (W, W) matrix of
  decayed C.B scores, on the MXU; a `lax.scan` threads S across
  chunks. A ragged tail is padded with D = 0 (decay 1, no input): the
  exit state and the real rows are untouched, exactly.
- `mamba2_step` (decode, T == 1): one elementwise update of S and one
  reduction over N. No matrix unit: the step is bound by reading and
  writing S.

Prefill-then-steps against one long chunked scan: the same terms are
summed in a different order (the chunk form adds C.(decayed S_0) and
(C.B) D x; the step reduces C.S_t), in float32 throughout, so the two
agree to float32 rounding of a sum of N + W terms — 1e-5 relative on
values of order 1 (tests/test_mamba2.py), not bit for bit. The scan's
matrix products run at `Precision.HIGHEST`: their operands are float32
decays and sums, and one bf16 pass would round each to 8 bits.

The device work carries `jax.named_scope("mamba2.conv" | "mamba2.scan"
| "mamba2.step")`, which the benchmark's readers select operations by.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .registry import register

_F32 = jnp.float32
_HI = jax.lax.Precision.HIGHEST


def _sizes(xbc, dt, num_heads, head_dim, d_state, n_groups=1):
    """(H, P, N, G) checked against the inputs' shapes."""
    H, P, N, G = (int(num_heads), int(head_dim), int(d_state),
                  int(n_groups))
    if xbc.ndim != 3 or dt.ndim != 3 or dt.shape[:2] != xbc.shape[:2]:
        raise ValueError(
            "Mamba2 xbc must be (B, T, conv_dim) and dt (B, T, H): got "
            "%r and %r" % (xbc.shape, dt.shape))
    if G < 1 or H % G:
        raise ValueError("Mamba2 num_heads (%d) must be a multiple of "
                         "n_groups (%d)" % (H, G))
    if xbc.shape[2] != H * P + 2 * G * N or dt.shape[2] != H:
        raise ValueError(
            "Mamba2 sizes disagree: conv_dim %d must be num_heads*"
            "head_dim + 2*n_groups*d_state = %d*%d + 2*%d*%d, and dt's "
            "last axis %d must be num_heads"
            % (xbc.shape[2], H, P, G, N, dt.shape[2]))
    return H, P, N, G


def causal_conv(x, conv_state, weight, bias=None, act=None):
    """Causal depthwise convolution over [window | x], in float32.

    x (B, T, C); conv_state (B, K-1, C), the K-1 rows before position
    0; weight (C, K) with weight[:, K-1] on the current row; bias (C,)
    and act (a function of the float32 sum), where the layer has them.
    Returns (act(bias + sum_j weight[:, j] * row_{t-(K-1)+j}) (B, T, C)
    float32, the new window (B, K-1, C) in conv_state's dtype: the
    last K-1 rows seen). The window's layout and its update are every
    short convolution's here: Mamba-2's over x|B|C (`mamba2_conv`) and
    the gated one's over its gated rows (ops/shortconv.py)."""
    K = weight.shape[1]
    T = x.shape[1]
    if conv_state.shape != (x.shape[0], K - 1, x.shape[2]):
        raise ValueError(
            "conv_state must be (B, d_conv-1, channels) = %r: got %r"
            % ((x.shape[0], K - 1, x.shape[2]), conv_state.shape))
    full = jnp.concatenate([conv_state.astype(x.dtype), x], axis=1)
    w = weight.astype(_F32)
    acc = None if bias is None else \
        jnp.broadcast_to(bias.astype(_F32), x.shape)
    for j in range(K):
        tap = full[:, j:j + T].astype(_F32) * w[:, j]
        acc = tap if acc is None else acc + tap
    if act is not None:
        acc = act(acc)
    return acc, full[:, T:].astype(conv_state.dtype)


def mamba2_conv(xbc, conv_state, weight, bias):
    """Mamba-2's convolution: `causal_conv` with a bias and SiLU over
    xbc (B, T, conv_dim). Returns (activated (B, T, C) float32, new
    window (B, K-1, C) in conv_state's dtype)."""
    return causal_conv(xbc, conv_state, weight, bias, jax.nn.silu)


def _split(act, H, P, N, G=1):
    """activated xBC (B, T, C) -> x (B, T, H, P), B and C (B, T, N);
    with G > 1 groups x (B, T, G, H/G, P), B and C (B, T, G, N)."""
    B_, T = act.shape[:2]
    d = H * P
    if G == 1:
        return (act[..., :d].reshape(B_, T, H, P), act[..., d:d + N],
                act[..., d + N:])
    return (act[..., :d].reshape(B_, T, G, H // G, P),
            act[..., d:d + G * N].reshape(B_, T, G, N),
            act[..., d + G * N:].reshape(B_, T, G, N))


def _by_group(fn, seq, **kw):
    """`fn` (mamba2_chunk_scan or mamba2_step) over all groups at once:
    x (B, [T,] G, Hg, P), dt (B, [T,] G, Hg), A and D (G, Hg), B and C
    (B, [T,] G, N), state (B, G, Hg, P, N); `seq` says whether the
    time axis is there. One group's heads meet that group's B and C,
    as the one-group form has them meet the only one."""
    g = 2 if seq else 1
    return jax.vmap(lambda *a: fn(*a, **kw),
                    in_axes=(g, g, 0, g, g, 0, 1), out_axes=(g, 1))


def _chunk(S, x, dt, A, Bm, Cm):
    """One chunk of the selective scan. S (B, H, P, N); x (B, W, H, P);
    dt (B, W, H) step sizes (0 on padding); Bm, Cm (B, W, N). Returns
    (S at the chunk's end, y (B, W, H, P)) — all float32."""
    W = x.shape[1]
    a = dt * A                                        # (B, W, H), <= 0
    L = jnp.cumsum(a, axis=1)
    Lh = jnp.moveaxis(L, 1, 2)                        # (B, H, W)
    mask = jnp.tril(jnp.ones((W, W), bool))           # s <= t
    # zero the exponent BEFORE exp: above the diagonal L_t - L_s is
    # positive and can be large, and inf * 0 is nan
    decay = jnp.where(mask, Lh[..., :, None] - Lh[..., None, :], 0.0)
    cb = jnp.einsum("btn,bsn->bts", Cm, Bm, precision=_HI)
    scores = jnp.where(mask, cb[:, None] * jnp.exp(decay), 0.0)
    xdt = x * dt[..., None]
    y = jnp.einsum("bhts,bshp->bthp", scores, xdt, precision=_HI)
    y = y + jnp.exp(L)[..., None] * jnp.einsum(
        "btn,bhpn->bthp", Cm, S, precision=_HI)
    last = L[:, -1]                                   # (B, H)
    tail = jnp.exp(last[:, None] - L)[..., None]      # decay to the end
    S = jnp.exp(last)[..., None, None] * S + jnp.einsum(
        "bshp,bsn->bhpn", xdt * tail, Bm, precision=_HI)
    return S, y


def mamba2_chunk_scan(x, dt, A, Bm, Cm, D, state, chunk=256):
    """Chunked selective scan. x (B, T, H, P), dt (B, T, H) step sizes
    (after softplus), A (H,) negative, Bm/Cm (B, T, N), D (H,), state
    (B, H, P, N); all float32. Returns (y (B, T, H, P), exit state)."""
    B_, T, H, P = x.shape
    N = Bm.shape[-1]
    W = max(1, min(int(chunk), T))
    nc = -(-T // W)
    pad = nc * W - T
    if pad:
        # dt = 0 on the tail: decay exp(0) and a zero outer product
        x, dt, Bm, Cm = (jnp.pad(v, ((0, 0), (0, pad)) +
                                 ((0, 0),) * (v.ndim - 2))
                         for v in (x, dt, Bm, Cm))
    if nc == 1:
        state, y = _chunk(state, x, dt, A, Bm, Cm)
    else:
        def cut(v):
            return jnp.moveaxis(
                v.reshape((B_, nc, W) + v.shape[2:]), 1, 0)

        state, ys = jax.lax.scan(
            lambda S, c: _chunk(S, c[0], c[1], A, c[2], c[3]),
            state, (cut(x), cut(dt), cut(Bm), cut(Cm)))
        y = jnp.moveaxis(ys, 0, 1).reshape(B_, nc * W, H, P)
    y = y[:, :T] + D[:, None] * x[:, :T]
    return y, state


def mamba2_step(x, dt, A, Bm, Cm, D, state):
    """One-token selective update. x (B, H, P), dt (B, H), Bm/Cm
    (B, N), state (B, H, P, N); float32. Elementwise and one reduction
    over N: nothing for the matrix unit, bound by S's bytes."""
    state = jnp.exp(dt * A)[..., None, None] * state + \
        (dt[..., None] * x)[..., None] * Bm[:, None, None, :]
    y = jnp.sum(state * Cm[:, None, None, :], axis=-1) + D[:, None] * x
    return y, state


def mamba2_mix(xbc, dt, conv_weight, conv_bias, dt_bias, a_log, d_skip,
               conv_state, scan_state, num_heads, head_dim, d_state,
               chunk=256, n_groups=1):
    """Convolution then scan over both carried states; static dispatch
    on T: one token runs the step form, more run the chunked scan;
    with n_groups > 1 either form is mapped over the groups, the heads
    of A, D, the step sizes and the state cut into (G, H/G) for it.
    Returns (y (B, T, H*P) in xbc's dtype, conv_state, scan_state)."""
    H, P, N, G = _sizes(xbc, dt, num_heads, head_dim, d_state, n_groups)
    B_, T = xbc.shape[:2]
    if scan_state.shape != (B_, H, P, N):
        raise ValueError(
            "Mamba2 scan_state must be (B, H, head_dim, d_state) = %r:"
            " got %r" % ((B_, H, P, N), scan_state.shape))
    if G == 1:
        by_group = lambda v: v
        one_step = mamba2_step
        scan = functools.partial(mamba2_chunk_scan, chunk=chunk)
    else:
        by_group = lambda v: v.reshape(v.shape[:-1] + (G, H // G))
        one_step = _by_group(mamba2_step, seq=False)
        scan = _by_group(mamba2_chunk_scan, seq=True, chunk=chunk)
    A = by_group(-jnp.exp(a_log.astype(_F32)))
    D = by_group(d_skip.astype(_F32))
    S = scan_state.astype(_F32)
    if G > 1:
        S = S.reshape(B_, G, H // G, P, N)
    if T == 1:
        with jax.named_scope("mamba2.step"):
            act, conv_state = mamba2_conv(xbc, conv_state, conv_weight,
                                          conv_bias)
            x, Bm, Cm = _split(act, H, P, N, G)
            step = by_group(jax.nn.softplus(dt.astype(_F32) +
                                            dt_bias.astype(_F32)))
            y, S = one_step(x[:, 0], step[:, 0], A, Bm[:, 0],
                            Cm[:, 0], D, S)
            y = y[:, None]
    else:
        with jax.named_scope("mamba2.conv"):
            act, conv_state = mamba2_conv(xbc, conv_state, conv_weight,
                                          conv_bias)
        with jax.named_scope("mamba2.scan"):
            x, Bm, Cm = _split(act, H, P, N, G)
            step = by_group(jax.nn.softplus(dt.astype(_F32) +
                                            dt_bias.astype(_F32)))
            y, S = scan(x, step, A, Bm, Cm, D, S)
    return (y.reshape(B_, T, H * P).astype(xbc.dtype), conv_state,
            S.reshape(B_, H, P, N).astype(scan_state.dtype))


_ATTRS = {"num_heads": 0, "head_dim": 0, "d_state": 0, "d_conv": 4,
          "chunk": 256}
_PARAMS = ("xbc", "dt", "conv_weight", "conv_bias", "dt_bias", "a_log",
           "d_skip")


@register("_contrib_Mamba2Cached",
          arg_names=_PARAMS + ("conv_state", "scan_state", "pos"),
          state_inputs=(7, 8), nondiff_inputs=(9,),
          differentiable=False,
          defaults=dict(_ATTRS, max_len=0))
def _mamba2_cached_op(xbc, dt, conv_weight, conv_bias, dt_bias, a_log,
                      d_skip, conv_state, scan_state, pos, num_heads=0,
                      head_dim=0, d_state=0, d_conv=4, chunk=256,
                      n_groups=1, **_):
    """Incremental Mamba-2 over two carried aux states, threaded in
    place by the executor like a KV cache: `conv_state`
    (B, d_conv-1, conv_dim), the convolution window in the served
    dtype (conv_dim = H*head_dim + 2*n_groups*d_state), and
    `scan_state` (B, H, head_dim, d_state) float32. T == 1
    runs the one-token update, T > 1 the chunked scan continuing from
    the carried states, so prefill, chunked prefill and decode are one
    op. `pos` is accepted and ignored, as in `_contrib_SSMCached`: the
    recurrence carries its own position, so the per-row-position graph
    is this same graph. Returns (y, conv_state, scan_state)."""
    del pos
    return mamba2_mix(xbc, dt, conv_weight, conv_bias, dt_bias, a_log,
                      d_skip, conv_state, scan_state, num_heads,
                      head_dim, d_state, chunk=int(chunk),
                      n_groups=int(n_groups))


def _rms(x, gamma, eps, dtype):
    """x (float32) / sqrt(mean(x^2) + eps) * gamma over the last axis,
    rounded once to `dtype`."""
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) +
                          float(eps))
    return (x * gamma.astype(_F32)).astype(dtype)


@register("RMSNorm", arg_names=("data", "gamma"),
          defaults={"eps": 1e-5})
def _rms_norm(data, gamma, eps=1e-5, **_):
    """Root-mean-square norm over the last axis, in float32, back in
    the input's dtype: x / sqrt(mean(x^2) + eps) * gamma."""
    return _rms(data.astype(_F32), gamma, eps, data.dtype)


@register("_contrib_GatedRMSNorm", arg_names=("data", "gate", "gamma"),
          defaults={"eps": 1e-5})
def _gated_rms_norm(data, gate, gamma, eps=1e-5, groups=1, **_):
    """RMSNorm(data * silu(gate)) — the gate first, then the norm
    (Mamba-2's output norm): the mean square over the whole last axis,
    or with groups > 1 over each of that many equal runs of it."""
    x = data.astype(_F32) * jax.nn.silu(gate.astype(_F32))
    G = int(groups)
    if G == 1:
        return _rms(x, gamma, eps, data.dtype)
    by_group = x.shape[:-1] + (G, x.shape[-1] // G)
    return _rms(x.reshape(by_group), gamma.reshape(by_group[-2:]), eps,
                data.dtype).reshape(x.shape)
