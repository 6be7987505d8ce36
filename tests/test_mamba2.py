"""The Mamba-2 op pair (ops/mamba2.py) against the layer's equations
written out token by token: chunk widths, the one-token form, both
states carried across a prefill -> decode boundary, a padded chunk,
the registered ops and their aux states, and the two norms that close
the mixer. Toy widths, float32 on the CPU."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu.ops import mamba2

H, P, N, K = 4, 8, 16, 4
C = H * P + 2 * N
B = 2


def _inputs(T, seed=0):
    rng = np.random.default_rng(seed)
    f = lambda *s: rng.standard_normal(s).astype(np.float32)
    return dict(
        xbc=f(B, T, C), dt=f(B, T, H),
        conv_weight=0.5 * f(C, K), conv_bias=0.5 * f(C),
        dt_bias=(-4.5 + 1.3 * rng.uniform(-1, 1, H)).astype(np.float32),
        a_log=rng.uniform(0.0, 2.77, H).astype(np.float32),
        d_skip=(1 + 0.1 * f(H)))


def _zeros():
    return (jnp.zeros((B, K - 1, C), jnp.float32),
            jnp.zeros((B, H, P, N), jnp.float32))


def _by_token(p, conv_state=None, scan_state=None):
    """The equations, one token at a time, in float64 numpy."""
    xbc = p["xbc"].astype(np.float64)
    T = xbc.shape[1]
    win = np.zeros((B, K - 1, C)) if conv_state is None \
        else np.asarray(conv_state, np.float64)
    S = np.zeros((B, H, P, N)) if scan_state is None \
        else np.asarray(scan_state, np.float64)
    A = -np.exp(p["a_log"].astype(np.float64))
    ys = []
    for t in range(T):
        full = np.concatenate([win, xbc[:, t:t + 1]], axis=1)
        act = p["conv_bias"] + np.einsum("bkc,ck->bc", full,
                                         p["conv_weight"])
        act = act / (1 + np.exp(-act))
        win = full[:, 1:]
        x = act[:, :H * P].reshape(B, H, P)
        Bm, Cm = act[:, H * P:H * P + N], act[:, H * P + N:]
        step = np.log1p(np.exp(p["dt"][:, t] + p["dt_bias"]))
        S = np.exp(step * A)[..., None, None] * S + \
            (step[..., None] * x)[..., None] * Bm[:, None, None, :]
        ys.append((S * Cm[:, None, None, :]).sum(-1) +
                  p["d_skip"][:, None] * x)
    return np.stack(ys, 1).reshape(B, T, H * P), win, S


_MIX = jax.jit(mamba2.mamba2_mix, static_argnames=(
    "num_heads", "head_dim", "d_state", "chunk"))


def _mix(p, conv_state, scan_state, chunk, lo=0, hi=None):
    """Under jit (one program per shape and chunk width)."""
    return _MIX(p["xbc"][:, lo:hi], p["dt"][:, lo:hi], p["conv_weight"],
                p["conv_bias"], p["dt_bias"], p["a_log"], p["d_skip"],
                conv_state, scan_state, num_heads=H, head_dim=P,
                d_state=N, chunk=chunk)


@pytest.mark.parametrize("chunk", [1, 3, 8, 13, 64])
def test_chunked_scan_equals_the_equations(chunk):
    p = _inputs(13)
    want_y, want_win, want_S = _by_token(p)
    y, win, S = _mix(p, *_zeros(), chunk=chunk)
    np.testing.assert_allclose(y, want_y, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(win, want_win, rtol=0, atol=0)
    np.testing.assert_allclose(S, want_S, rtol=2e-5, atol=2e-6)


def test_one_token_form_equals_a_one_wide_chunk():
    """T == 1 dispatches to the elementwise update; the chunked form
    at width 1 sums the same terms in another order: float32 rounding,
    not bit for bit."""
    p = _inputs(1, seed=3)
    _y0, win0, S0 = _by_token(_inputs(9, seed=4))
    conv = jnp.asarray(win0, jnp.float32)
    S = jnp.asarray(S0, jnp.float32)
    y1, win1, S1 = _mix(p, conv, S, chunk=8)
    x = mamba2.mamba2_conv(jnp.asarray(p["xbc"]), conv,
                           p["conv_weight"], p["conv_bias"])[0]
    xs, Bm, Cm = mamba2._split(x, H, P, N)
    step = jax.nn.softplus(p["dt"] + p["dt_bias"])
    y2, S2 = mamba2.mamba2_chunk_scan(
        xs, step, -jnp.exp(p["a_log"]), Bm, Cm, jnp.asarray(p["d_skip"]),
        S, chunk=1)
    np.testing.assert_allclose(y1, y2.reshape(B, 1, H * P), rtol=1e-5,
                               atol=1e-5)
    np.testing.assert_allclose(S1, S2, rtol=1e-5, atol=1e-6)
    want_y, want_win, want_S = _by_token(p, win0, S0)
    np.testing.assert_allclose(y1, want_y, rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(win1, want_win.astype(np.float32))


def test_both_states_cross_the_prefill_decode_boundary():
    """A prefill of 13 tokens, then 7 one-token steps, under jit: y
    and both exit states equal one long scan of 20 to float32
    rounding (1e-5 on values of order 1: the same terms, summed in a
    different order)."""
    p = _inputs(20, seed=1)
    long_y, long_win, long_S = _mix(p, *_zeros(), chunk=8)
    y, win, S = _mix(p, *_zeros(), chunk=8, lo=0, hi=13)
    ys = [y]
    for t in range(13, 20):
        y, win, S = _mix(p, win, S, chunk=8, lo=t, hi=t + 1)
        ys.append(y)
    np.testing.assert_allclose(np.concatenate(ys, 1), long_y,
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(win, long_win)
    np.testing.assert_allclose(S, long_S, rtol=1e-5, atol=1e-6)
    # and a window carried in is read: the same 7 tokens from a zeroed
    # window give other outputs
    y_cold, _w, _s = _mix(p, _zeros()[0], S, chunk=8, lo=19, hi=20)
    assert np.abs(np.asarray(y_cold) - np.asarray(y)).max() > 1e-3


def test_a_padded_chunk_is_exact():
    """13 tokens in chunks of 8 pad the second chunk with 3 steps of
    size 0: the real rows and the exit state are those of the same
    scan given 16 tokens whose last 3 have step size 0, bit for bit,
    and a chunk of padding alone returns its state unchanged."""
    p = _inputs(13, seed=2)
    x = mamba2.mamba2_conv(jnp.asarray(p["xbc"]), _zeros()[0],
                           p["conv_weight"], p["conv_bias"])[0]
    xs, Bm, Cm = mamba2._split(x, H, P, N)
    step = jax.nn.softplus(p["dt"] + p["dt_bias"])
    A, D = -jnp.exp(p["a_log"]), jnp.asarray(p["d_skip"])
    scan = jax.jit(mamba2.mamba2_chunk_scan, static_argnames="chunk")
    y, S = scan(xs, step, A, Bm, Cm, D, _zeros()[1], chunk=8)
    pad = lambda v: jnp.pad(v, ((0, 0), (0, 3)) + ((0, 0),) * (v.ndim - 2))
    y16, S16 = scan(pad(xs), pad(step), A, pad(Bm), pad(Cm), D,
                    _zeros()[1], chunk=8)
    np.testing.assert_array_equal(y, y16[:, :13])
    np.testing.assert_array_equal(S, S16)
    S_in = jnp.asarray(np.random.default_rng(5).standard_normal(
        (B, H, P, N)), jnp.float32)
    S_out, _y = jax.jit(mamba2._chunk)(
        S_in, jnp.ones((B, 3, H, P)), jnp.zeros((B, 3, H)), A,
        jnp.ones((B, 3, N)), jnp.ones((B, 3, N)))
    np.testing.assert_array_equal(S_out, S_in)


def test_a_bfloat16_scan_state_fails_where_float32_passes():
    """Why the scan state is float32: 300 decode steps with the state
    rounded to bfloat16 after each drift from the equations by more
    than the tolerance the float32 state meets."""
    T = 300
    p = _inputs(T, seed=6)
    want_y, _w, _S = _by_token(p)

    def run(state_dtype):
        conv, S = _zeros()
        S = S.astype(state_dtype)
        ys = []
        for t in range(T):
            y, conv, S = _mix(p, conv, S, chunk=8, lo=t, hi=t + 1)
            assert S.dtype == state_dtype
            ys.append(np.asarray(y))
        return np.abs(np.concatenate(ys, 1) - want_y)[:, -50:].max()

    scale = np.abs(want_y).max()
    assert run(jnp.float32) < 1e-4 * scale
    assert run(jnp.bfloat16) > 1e-3 * scale


def test_registered_op_threads_both_aux_states():
    p = _inputs(9, seed=7)
    attrs = dict(num_heads=H, head_dim=P, d_state=N, d_conv=K, chunk=4)
    sym = mx.sym.contrib.Mamba2Cached(
        mx.sym.Variable("xbc"), mx.sym.Variable("dt"),
        pos=mx.sym.Variable("pos"), name="m", **attrs)
    assert sym.list_auxiliary_states() == ["m_conv_state",
                                           "m_scan_state"]
    assert sym.list_arguments() == [
        "xbc", "dt", "m_conv_weight", "m_conv_bias", "m_dt_bias",
        "m_a_log", "m_d_skip", "pos"]
    _args, _outs, aux = sym.infer_shape(xbc=(B, 9, C), dt=(B, 9, H))
    assert aux == [(B, K - 1, C), (B, H, P, N)]
    # a 6-token window, then three single tokens, through bound
    # executors that hand the aux arrays on (an executor writes aux back
    # on a training forward, as for BatchNorm): the op continues from
    # what was carried, and leaves both states where the token-by-token
    # equations end
    want_y, want_win, want_S = _by_token(p)
    params = {"m_" + k: mx.nd.array(v) for k, v in p.items()
              if k not in ("xbc", "dt")}
    got, aux = [], None
    for lo, hi in ((0, 6), (6, 7), (7, 8), (8, 9)):
        exe = sym.simple_bind(mx.cpu(), grad_req="null",
                              xbc=(B, hi - lo, C), dt=(B, hi - lo, H))
        for k, v in params.items():
            v.copyto(exe.arg_dict[k])
        if aux is not None:
            for k, v in aux.items():
                v.copyto(exe.aux_dict[k])
        exe.forward(is_train=True, xbc=p["xbc"][:, lo:hi],
                    dt=p["dt"][:, lo:hi], pos=np.array([lo], np.float32))
        got.append(exe.outputs[0].asnumpy())
        aux = exe.aux_dict
    np.testing.assert_allclose(np.concatenate(got, 1), want_y, rtol=2e-5,
                               atol=2e-5)
    np.testing.assert_allclose(aux["m_conv_state"].asnumpy(), want_win,
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(aux["m_scan_state"].asnumpy(), want_S,
                               rtol=2e-5, atol=2e-5)


def test_sizes_that_disagree_are_refused():
    p = _inputs(3)
    with pytest.raises(ValueError, match="conv_dim"):
        mamba2.mamba2_mix(p["xbc"][..., :-1], p["dt"], p["conv_weight"],
                          p["conv_bias"], p["dt_bias"], p["a_log"],
                          p["d_skip"], *_zeros(), H, P, N)
    with pytest.raises(ValueError, match="scan_state"):
        mamba2.mamba2_mix(p["xbc"], p["dt"], p["conv_weight"],
                          p["conv_bias"], p["dt_bias"], p["a_log"],
                          p["d_skip"], _zeros()[0],
                          jnp.zeros((B, H, P, N + 1)), H, P, N)


@pytest.mark.parametrize("gated", [False, True])
def test_rms_norms(gated):
    rng = np.random.default_rng(8)
    x = rng.standard_normal((2, 5, 32)).astype(np.float32)
    z = rng.standard_normal((2, 5, 32)).astype(np.float32)
    g = (1 + 0.1 * rng.standard_normal(32)).astype(np.float32)
    if gated:
        got = mx.nd.contrib.GatedRMSNorm(
            mx.nd.array(x), mx.nd.array(z), mx.nd.array(g), eps=1e-5)
        x = x * (z / (1 + np.exp(-z)))
    else:
        got = mx.nd.RMSNorm(mx.nd.array(x), mx.nd.array(g), eps=1e-5)
    want = x / np.sqrt((x * x).mean(-1, keepdims=True) + 1e-5) * g
    np.testing.assert_allclose(got.asnumpy(), want, rtol=1e-5, atol=1e-6)


def test_silu_activation():
    x = np.linspace(-4, 4, 17).astype(np.float32)
    got = mx.nd.Activation(mx.nd.array(x), act_type="silu").asnumpy()
    np.testing.assert_allclose(got, x / (1 + np.exp(-x)), rtol=1e-6,
                               atol=1e-7)
