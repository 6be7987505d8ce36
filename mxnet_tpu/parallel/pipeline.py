"""Pipeline parallelism — stages sharded over a mesh axis, GPipe
microbatch schedule.

New TPU-native capability (SURVEY §2.3: the reference's nearest feature
is `PartialForward` staged execution + the model-parallel LSTM example;
it has no pipeline schedule). Each device on the ``pipe`` axis holds ONE
stage's parameters; microbatches stream through, activations hop to the
next stage over ``lax.ppermute`` (neighbour ICI links). The bubble is
the standard (S-1)/(M+S-1) GPipe fraction.

The schedule runs inside ``shard_map`` and is itself jittable/
differentiable — wrap it in a loss and `jax.grad` works through the
collectives, so the same function serves train and inference.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P


__all__ = ["pipeline_apply", "pipeline_from_symbol"]


def pipeline_apply(stage_fn, stage_params, microbatches, mesh,
                   axis_name="pipe"):
    """Run ``stage_fn`` composed over S pipeline stages.

    stage_fn(params_i, x) -> y: one stage's computation; every stage
        must map (mb, ...) -> (mb, ...) of the same shape/dtype (pad
        feature dims to a common width if stages differ). A 3-argument
        stage_fn additionally receives the schedule tick t (traced
        int32) — combine it with ``lax.axis_index(axis_name)`` for
        per-(stage, microbatch) randomness (dropout keys).
    stage_params: pytree whose leaves have leading dim S (stage i's
        slice lives on device i of the axis).
    microbatches: (M, mb, ...) — M microbatches streamed through.
    Returns (M, mb, ...): stage S-1's outputs for every microbatch,
    replicated across the axis.

    Equivalent to ``for p in stages: x = stage_fn(p, x)`` per
    microbatch (asserted in tests/test_pipeline_moe.py).
    """
    import inspect

    S = mesh.shape[axis_name]
    M = microbatches.shape[0]
    fwd_perm = [(j, (j + 1) % S) for j in range(S)]
    # tick is passed only to a stage_fn whose THIRD parameter is a
    # plain positional without a default — a defaulted/keyword-only
    # third param (eps=1e-6, *, cfg=None) must not receive it
    _pos = [p for p in inspect.signature(stage_fn).parameters.values()
            if p.kind in (p.POSITIONAL_ONLY, p.POSITIONAL_OR_KEYWORD)]
    takes_tick = len(_pos) >= 3 and _pos[2].default is _pos[2].empty

    def local(params, stream):
        # params: leaves (1, ...) = my stage; stream: (M, mb, ...) the
        # full microbatch queue (replicated — activations, not params)
        my = jax.tree.map(lambda l: l[0], params)
        me = lax.axis_index(axis_name)
        mb_shape = stream.shape[1:]
        carry = jnp.zeros(mb_shape, stream.dtype)
        carry = lax.pcast(carry, (axis_name,), to="varying")
        outs0 = jnp.zeros((M,) + mb_shape, stream.dtype)
        outs0 = lax.pcast(outs0, (axis_name,), to="varying")

        def tick(t, state):
            carry, outs = state
            # stage 0 ingests microbatch t (zeros once the stream ends)
            feed = lax.dynamic_index_in_dim(
                stream, jnp.minimum(t, M - 1), 0, keepdims=False)
            feed = jnp.where(t < M, feed, jnp.zeros_like(feed))
            x = jnp.where(me == 0, feed, carry)
            y = stage_fn(my, x, t) if takes_tick else stage_fn(my, x)
            # microbatch t reaches the last stage at tick t + S - 1
            out_slot = t - (S - 1)
            take = (me == S - 1) & (out_slot >= 0)
            outs = lax.cond(
                take,
                lambda o: lax.dynamic_update_index_in_dim(
                    o, y, jnp.maximum(out_slot, 0), 0),
                lambda o: o, outs)
            carry = lax.ppermute(y, axis_name, fwd_perm)
            return carry, outs

        _, outs = lax.fori_loop(0, M + S - 1, tick, (carry, outs0))
        # replicate the last stage's collected outputs to every device
        return lax.psum(jnp.where(me == S - 1, outs, 0.0), axis_name)

    pspec = jax.tree.map(lambda _: P(axis_name), stage_params)
    fn = jax.shard_map(local, mesh=mesh,
                    in_specs=(pspec, P()),
                    out_specs=P())
    return fn(stage_params, microbatches)


def pipeline_from_symbol(layer_sym, stage_params, microbatches, mesh,
                         axis_name="pipe", data_name="data",
                         is_train=False, rng=None):
    """GPipe over a SYMBOL-defined stage — pipeline parallelism for the
    symbolic API (dp/tp: TrainStep mesh; sp: seq_axis; ep: expert_axis;
    this is the pp leg).

    layer_sym: a Symbol mapping input ``data_name`` of shape
        (mb, ...) to a single same-shape/dtype output — e.g.
        ``models.transformer.get_stage_symbol``. Must carry no
        auxiliary states (BN moving stats can't live inside the
        rotating schedule; use LayerNorm-style stages).
    stage_params: dict name -> (S, ...) stacked per-stage values for
        every non-data argument of ``layer_sym`` (stage i's slice is
        row i).
    microbatches: (M, mb, ...) streamed through all S stages.
    Returns (M, mb, ...), differentiable; same contract as
    ``pipeline_apply``.
    """
    from ..executor import _graph_eval_fn

    if layer_sym.list_auxiliary_states():
        raise ValueError(
            "pipeline stages cannot carry auxiliary states %r — the "
            "GPipe schedule has no slot for cross-microbatch mutable "
            "state" % layer_sym.list_auxiliary_states())
    if data_name not in layer_sym.list_arguments():
        raise ValueError(
            "data_name %r is not an argument of the stage symbol "
            "(has %r) — the microbatch stream would be ignored"
            % (data_name, layer_sym.list_arguments()))
    arg_names = [n for n in layer_sym.list_arguments() if n != data_name]
    missing = set(arg_names) - set(stage_params)
    if missing:
        raise ValueError("stage_params missing %r" % sorted(missing))
    if len(layer_sym.list_outputs()) != 1:
        raise ValueError("a pipeline stage must have exactly 1 output, "
                         "got %r" % layer_sym.list_outputs())

    eval_fn = _graph_eval_fn(layer_sym)
    key = rng if rng is not None else jax.random.PRNGKey(0)

    def stage_fn(params, x, t):
        # distinct randomness per (stage, tick): dropout masks must not
        # repeat across stages or microbatches
        k = jax.random.fold_in(
            jax.random.fold_in(key, lax.axis_index(axis_name)), t)
        outs, _aux = eval_fn({**params, data_name: x}, {}, k, is_train)
        return outs[0]

    return pipeline_apply(stage_fn,
                          {n: stage_params[n] for n in arg_names},
                          microbatches, mesh, axis_name=axis_name)
