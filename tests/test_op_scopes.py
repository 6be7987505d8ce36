"""Device scopes: every operation a `Symbol` graph lowers to carries
`<node>/op.<Operator>` on its name stack (`executor._graph_eval_fn`),
`TrainStep`'s step names what is no graph node `train.*`, hand-placed
scopes nest below their node's two parts, and none of it changes a
compiled program (docs/observability.md, "Device scopes")."""
import contextlib
import re

import jax
import jax.numpy as jnp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import guardrail, models
from mxnet_tpu.executor import _graph_eval_fn
from mxnet_tpu.generation import Generator
from mxnet_tpu.initializer import Uniform
from mxnet_tpu.parallel import make_train_step
from mxnet_tpu.symbol.symbol import _topo_order

SHAPES = {"data": (4, 3, 32, 32), "softmax_label": (4,)}
# operators whose backward pass has device work of its own (the
# cotangent of an addition or a copy is passed on as it is)
DIFFERENTIATED = ("Convolution", "BatchNorm", "Activation", "Pooling",
                  "Flatten", "FullyConnected", "SoftmaxOutput")
FORWARD_ONLY = ("broadcast_add",)

V, T = 97, 48
NEMOTRON = {
    "family": "nemotron_h", "hidden_size": 32, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "intermediate_size": 48,
    "vocab_size": V, "num_hidden_layers": 3,
    "hybrid_override_pattern": "EM*", "max_position_embeddings": 64,
    "mamba_num_heads": 8, "mamba_head_dim": 8, "ssm_state_size": 16,
    "n_groups": 4, "conv_kernel": 4, "chunk_size": 8, "expand": 2,
    "n_routed_experts": 4, "routed_experts_first": 4,
    "router_outputs": 16, "num_experts_per_tok": 5,
    "moe_intermediate_size": 24, "moe_latent_size": 16,
    "n_shared_experts": 1, "moe_shared_expert_intermediate_size": 40,
    "norm_topk_prob": True, "routed_scaling_factor": 2.5, "n_group": 1,
    "topk_group": 1, "mlp_hidden_act": "relu2",
    "mamba_hidden_act": "silu", "use_bias": False,
    "attention_bias": False, "use_conv_bias": True,
    "tie_word_embeddings": False, "layer_norm_epsilon": 1e-5,
    "initializer_range": 0.2, "compute_dtype": "float32"}


class _NoScope(contextlib.ContextDecorator):
    """`jax.named_scope` that names nothing."""

    def __init__(self, name):
        del name

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


def _stacks(lowered):
    return set(re.findall(r'loc\("([^"]+)"',
                          lowered.as_text(debug_info=True)))


def _resnet():
    return models.get_symbol(network="resnet", num_layers=18,
                             image_shape=(3, 32, 32), num_classes=10)


def _lower_train_step(guard=None, clip_norm=None, sym=None):
    """`step_with_metric` of a toy ResNet as `TrainStep.fit` builds it."""
    step = make_train_step(
        sym or _resnet(), optimizer="sgd", clip_norm=clip_norm,
        optimizer_params={"momentum": 0.9, "wd": 1e-4},
        compute_dtype="bfloat16")
    state = step.init_state(Uniform(0.01), SHAPES)
    if guard is not None:
        state = step._ensure_scaler_state(state, guard)
    metric = mx.metric.CrossEntropy()
    raw, fused = step._metric_fused_step(metric, guard)
    placed = {k: jnp.zeros(s, jnp.float32) for k, s in SHAPES.items()}
    rng = jax.random.PRNGKey(0)
    mstats = step._zero_metric_stats(raw, metric, state, placed, 0.1,
                                     rng, guarded=guard is not None)
    args = [*state, placed, jnp.float32(0.1), rng, mstats]
    if guard is not None:
        args.append(jnp.float32(1.0))
    return fused.lower(*args)


def _lower_decode_step():
    """`decode_step` of a toy pool with a routed-experts layer, a
    Mamba-2 layer and an attention layer."""
    from cellbench.models import nemotron_h as model
    from cellbench.reference import nemotron_h as ref
    gen = Generator(ref.make_params(NEMOTRON, 1, "float32"), V, T,
                    batch_size=2, dtype="float32",
                    **model.generator_args(NEMOTRON))
    dec = gen.serving_decoder()
    try:
        spec = lambda tree: jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), tree)
        args = dict(spec(gen._params),
                    data=jax.ShapeDtypeStruct((2, 1), jnp.float32),
                    cache_pos=jax.ShapeDtypeStruct((2,), jnp.float32))
        return dec._step_fn.lower(args, spec(dec._aux), dec._rng0)
    finally:
        dec.close(10)


@pytest.fixture(scope="module")
def train_step():
    """(name stacks, {operator: [node names]}) of one lowered step; the
    nodes are this symbol's own (unnamed ones are numbered by a
    process-wide counter)."""
    sym = _resnet()
    nodes = {}
    for n in _topo_order(sym._entries):
        if n.op is not None:
            nodes.setdefault(n.op.name, []).append(n.name)
    return _stacks(_lower_train_step(sym=sym)), nodes


@pytest.fixture(scope="module")
def train_stacks(train_step):
    return train_step[0]


@pytest.fixture(scope="module")
def decode_stacks():
    return _stacks(_lower_decode_step())


@pytest.mark.parametrize("kind", DIFFERENTIATED + FORWARD_ONLY)
def test_every_node_names_its_operations_forward(train_step, kind):
    train_stacks, nodes = train_step
    for node in nodes[kind]:
        want = "/jvp(train.fwd)/%s/op.%s/" % (node, kind)
        assert any(want in s for s in train_stacks), want


@pytest.mark.parametrize("kind", DIFFERENTIATED)
def test_backward_inherits_the_forward_names(train_step, kind):
    train_stacks, nodes = train_step
    for node in nodes[kind]:
        want = "/transpose(jvp(train.fwd))/%s/op.%s/" % (node, kind)
        assert any(want in s for s in train_stacks), want


@pytest.mark.parametrize("scope", ["train.cast", "train.update",
                                   "train.metric"])
def test_the_step_names_what_is_no_graph_node(train_stacks, scope):
    assert any(scope in s.split("/") for s in train_stacks), scope
    if scope == "train.cast":           # inside the differentiated part
        assert any("/jvp(train.fwd)/train.cast/" in s
                   for s in train_stacks)
        assert any("/transpose(jvp(train.fwd))/train.cast/" in s
                   for s in train_stacks)


def test_only_the_outermost_scope_takes_the_wrapper(train_stacks):
    """Every part below `train.fwd` is plain in both directions, so a
    reader selects `op.Convolution` forward and backward alike."""
    inside = [s for s in train_stacks if "train.fwd" in s]
    assert inside
    for s in inside:
        parts = s.split("/")
        at = next(i for i, p in enumerate(parts) if "train.fwd" in p)
        assert parts[at] in ("jvp(train.fwd)",
                             "transpose(jvp(train.fwd))"), s
        assert not any(p.startswith(("jvp(", "transpose("))
                       for p in parts[at + 1:]), s
    # what the step leaves unnamed: the head gradient's seed, a constant
    loose = {s for s in train_stacks
             if s.startswith("jit(step_with_metric)") and
             not any(p.startswith(("op.", "train.")) or "train.fwd" in p
                     for p in s.split("/"))}
    assert loose <= {"jit(step_with_metric)/broadcast_in_dim"}, loose


@pytest.mark.parametrize("scope,build", [
    ("train.guard", lambda: _lower_train_step(guardrail.GuardSpec())),
    ("train.clip", lambda: _lower_train_step(clip_norm=1.0))])
def test_guard_and_clip_are_named_where_they_run(scope, build,
                                                 train_stacks):
    assert not any(scope in s.split("/") for s in train_stacks)
    assert any(scope in s.split("/") for s in _stacks(build()))


@pytest.mark.parametrize("scope,node,kind", [
    ("mamba2.step", "layer1_mamba", "_contrib_Mamba2Cached"),
    ("moe.experts", "layer0_moe", "_contrib_RoutedExperts"),
    ("moe.route", "layer0_moe", "_contrib_RoutedExperts")])
def test_hand_placed_scopes_nest_below_the_nodes_two(decode_stacks,
                                                     scope, node, kind):
    want = "jit(decode_step)/%s/op.%s/%s/" % (node, kind, scope)
    assert any(s.startswith(want) for s in decode_stacks), want
    # a whole part wherever it appears: what `device_scope.under` needs
    for s in decode_stacks:
        if scope in s:
            assert scope in s.split("/"), s


def test_a_node_outside_a_transform_is_a_plain_part(decode_stacks):
    ours = [s for s in decode_stacks if "/op." in s]
    assert ours
    assert not any("jvp(" in s or "transpose(" in s for s in ours)


def test_a_slash_in_a_node_name_yields_no_extra_part():
    data = mx.sym.Variable("data")
    net = mx.sym.Activation(data, act_type="relu", name="block/a/relu")
    fn = _graph_eval_fn(net)

    def f(x):
        return fn({"data": x}, {}, jax.random.PRNGKey(0), False)[0][0]

    stacks = _stacks(jax.jit(f).lower(jnp.ones((2, 3))))
    ours = [s for s in stacks if "op.Activation" in s]
    assert ours
    for s in ours:
        assert s.split("/")[:3] == ["jit(f)", "block_a_relu",
                                    "op.Activation"], s


def test_without_a_train_step_the_node_takes_the_wrapper():
    """`Executor.backward`'s shape: the graph differentiated directly.
    The node is the outermost scope, the kind below it stays plain."""
    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(data, num_hidden=3, name="fc")
    fn = _graph_eval_fn(net)

    def loss(w):
        out = fn({"data": jnp.ones((2, 4)), "fc_weight": w,
                  "fc_bias": jnp.zeros((3,))}, {},
                 jax.random.PRNGKey(0), True)[0][0]
        return jnp.sum(out * out)

    stacks = _stacks(jax.jit(jax.grad(loss)).lower(jnp.ones((3, 4))))
    assert any("/jvp(fc)/op.FullyConnected/" in s for s in stacks)
    assert any("/transpose(jvp(fc))/op.FullyConnected/" in s
               for s in stacks)


@pytest.mark.parametrize("lower", [_lower_train_step, _lower_decode_step],
                         ids=["train_step", "decode_step"])
def test_the_program_is_what_it_is_without_any_scope(lower, monkeypatch):
    """A scope is metadata: the text without debug information is
    byte-equal to the one lowered with `jax.named_scope` naming
    nothing; with debug information the two differ."""
    named = lower()
    monkeypatch.setattr(jax, "named_scope", _NoScope)
    bare = lower()
    assert named.as_text() == bare.as_text()
    assert any("/op." in s for s in _stacks(named))
    assert not any("/op." in s or "train." in s for s in _stacks(bare))
