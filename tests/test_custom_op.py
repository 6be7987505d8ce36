"""Custom-op infrastructure tests (reference:
tests/python/unittest/test_operator.py test_custom_op and the
example/numpy-ops softmax CustomOp). Custom ops are jax host
callbacks; a host-attached TPU runs them too.
"""
import numpy as np
import pytest

import mxnet_tpu as mx


class _Softmax(mx.operator.CustomOp):
    def forward(self, is_train, req, in_data, out_data, aux):
        x = in_data[0].asnumpy()
        y = np.exp(x - x.max(axis=1).reshape((x.shape[0], 1)))
        y /= y.sum(axis=1).reshape((x.shape[0], 1))
        self.assign(out_data[0], req[0], mx.nd.array(y))

    def backward(self, req, out_grad, in_data, out_data, in_grad, aux):
        lbl = in_data[1].asnumpy().ravel().astype(np.int64)
        y = out_data[0].asnumpy()
        y[np.arange(lbl.shape[0]), lbl] -= 1.0
        self.assign(in_grad[0], req[0], mx.nd.array(y / y.shape[0]))


@mx.operator.register("test_softmax")
class _SoftmaxProp(mx.operator.CustomOpProp):
    def __init__(self):
        super().__init__(need_top_grad=False)

    def list_arguments(self):
        return ["data", "label"]

    def infer_shape(self, in_shape):
        return ([in_shape[0], (in_shape[0][0],)], [in_shape[0]], [])

    def create_operator(self, ctx, shapes, dtypes):
        return _Softmax()


class _Scale(mx.operator.CustomOp):
    def __init__(self, factor):
        self.factor = factor

    def forward(self, is_train, req, in_data, out_data, aux):
        self.assign(out_data[0], req[0], in_data[0] * self.factor)

    def backward(self, req, out_grad, in_data, out_data, in_grad, aux):
        self.assign(in_grad[0], req[0], out_grad[0] * self.factor)


@mx.operator.register("test_scale")
class _ScaleProp(mx.operator.CustomOpProp):
    def __init__(self, factor="2.0"):
        super().__init__(need_top_grad=True)
        self.factor = float(factor)

    def create_operator(self, ctx, shapes, dtypes):
        return _Scale(self.factor)


class TestEager:
    def test_forward(self):
        x = mx.nd.array(np.random.randn(4, 3).astype("float32"))
        lbl = mx.nd.array(np.array([0, 1, 2, 0], "float32"))
        y = mx.nd.Custom(x, lbl, op_type="test_softmax")
        np.testing.assert_allclose(y.asnumpy().sum(1), np.ones(4),
                                   rtol=1e-5)

    def test_backward(self):
        x = mx.nd.array(np.random.randn(4, 3).astype("float32"))
        lbl = mx.nd.array(np.array([0, 1, 2, 0], "float32"))
        x.attach_grad()
        with mx.autograd.record():
            y = mx.nd.Custom(x, lbl, op_type="test_softmax")
        y.backward()
        g = x.grad.asnumpy()
        np.testing.assert_allclose(g.sum(1), np.zeros(4), atol=1e-6)

    def test_top_grad_chain(self):
        """need_top_grad=True op composes with downstream jax-native ops."""
        x = mx.nd.array(np.random.randn(5).astype("float32"))
        x.attach_grad()
        with mx.autograd.record():
            y = mx.nd.Custom(x, op_type="test_scale", factor="3.0")
            z = (y * y).sum()
        z.backward()
        np.testing.assert_allclose(x.grad.asnumpy(),
                                   2 * 9 * x.asnumpy(), rtol=1e-5)

    def test_kwargs_reordering(self):
        x = mx.nd.array(np.random.randn(4, 3).astype("float32"))
        lbl = mx.nd.array(np.array([0, 1, 2, 0], "float32"))
        a = mx.nd.Custom(label=lbl, data=x, op_type="test_softmax")
        b = mx.nd.Custom(x, lbl, op_type="test_softmax")
        np.testing.assert_allclose(a.asnumpy(), b.asnumpy())


class TestSymbolic:
    def test_infer_shape_fills_label(self):
        data = mx.sym.Variable("data")
        net = mx.sym.Custom(data=data, name="sm", op_type="test_softmax")
        assert net.list_arguments() == ["data", "sm_label"]
        args, outs, _ = net.infer_shape(data=(4, 3))
        assert args == [(4, 3), (4,)]
        assert outs == [(4, 3)]

    def test_positional_compose_auto_creates_label(self):
        net = mx.sym.Custom(mx.sym.Variable("data"), name="sm",
                            op_type="test_softmax")
        assert net.list_arguments() == ["data", "sm_label"]

    def test_executor_forward(self):
        data = mx.sym.Variable("data")
        net = mx.sym.Custom(data=data, name="sm", op_type="test_softmax")
        ex = net.simple_bind(data=(4, 3))
        x = np.random.randn(4, 3).astype("float32")
        out = ex.forward(data=x, sm_label=np.zeros(4, "float32"))
        np.testing.assert_allclose(out[0].asnumpy().sum(1), np.ones(4),
                                   rtol=1e-5)

    def test_module_training(self):
        """Custom softmax as the head of a Module-trained MLP: loss-driven
        accuracy must beat chance (VERDICT #8 done criterion)."""
        np.random.seed(0)
        mx.random.seed(0)
        N = 128
        X = np.random.randn(N, 8).astype("float32")
        w = np.random.randn(8)
        ylab = (X @ w > 0).astype("float32")

        data = mx.sym.Variable("data")
        fc = mx.sym.FullyConnected(data, num_hidden=2, name="fc")
        net = mx.sym.Custom(data=fc, name="softmax",
                            op_type="test_softmax")
        train = mx.io.NDArrayIter(X, ylab, batch_size=32, shuffle=True,
                                  label_name="softmax_label")
        mod = mx.mod.Module(net, ("data",), ("softmax_label",))
        mod.fit(train, num_epoch=6, optimizer="sgd",
                optimizer_params={"learning_rate": 0.5})
        score = mod.score(train, "acc")[0][1]
        assert score > 0.9, score


class TestRegistry:
    def test_unknown_type_raises(self):
        with pytest.raises(KeyError):
            mx.nd.Custom(mx.nd.zeros((2,)), op_type="no_such_op")

    def test_listing(self):
        assert "test_softmax" in mx.operator.get_all_registered()

    def test_aux_states_rejected(self):
        @mx.operator.register("test_auxful")
        class _AuxProp(mx.operator.CustomOpProp):
            def list_auxiliary_states(self):
                return ["counter"]

            def infer_shape(self, in_shape):
                return [in_shape[0]], [in_shape[0]], [(1,)]

        with pytest.raises(NotImplementedError):
            mx.nd.Custom(mx.nd.zeros((2,)), op_type="test_auxful")
