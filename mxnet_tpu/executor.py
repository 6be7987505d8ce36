"""Executor — runs a bound Symbol graph.

Reference: src/executor/graph_executor.cc + python/mxnet/executor.py.
The reference's bind pipeline (gradient pass, device placement, shape
inference, memory planning, op fusion into engine segments) collapses here
into: lower the Symbol to ONE pure JAX function, `jax.jit` it (XLA does
placement/planning/fusion), and get the backward pass from `jax.vjp` of that
same function — the whole-graph analogue of the reference's symbolic
Gradient pass.

Training forwards run a FUSED fwd+vjp program: one XLA executable computes
outputs, updated aux state and parameter gradients together, so the
Module.fit hot path pays forward FLOPs once (the reference reused forward
activations from its executor memory plan; XLA shares them inside the one
program).

Device placement (the reference's PlaceDevice pass over `ctx_group`
attributes, graph_executor.cc:309-410) maps to GSPMD sharding constraints:
nodes annotated `__shard__="data,model"` (or `__ctx_group__=g` with a
group2ctx entry naming a spec) get `with_sharding_constraint` applied to
their outputs when the executor runs over a mesh.

Aux states (BatchNorm moving stats) are threaded functionally through the
compiled fn and written back to their NDArrays after each forward — the
reference mutated them in-place from inside kernels.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from .base import MXNetError
from .context import Context, current_context
from .ndarray import ndarray as _nd
from .ndarray.ndarray import NDArray, _wrap

__all__ = ["Executor"]


def _parse_pspec(spec):
    """'data,model' / '(data, None)' / 'model' / 'data+fsdp,None' ->
    tuple for PartitionSpec. None/'None'/'' entries mean unsharded
    dims; '+' joins multiple axes on one dim (and tuple entries pass
    through) — shared grammar with parallel.sharding.parse_spec."""
    from .parallel.sharding import parse_spec
    return parse_spec(spec)


def _shard_constraint(mesh, spec, val, strict=True):
    """Apply a sharding constraint to one node output.

    strict (the __shard__ attr): a spec naming an axis the mesh lacks,
    or an indivisible dim, is an error. strict=False (the
    __shard_hint__ attr): such specs are silently skipped — the lenient
    form for annotations baked into reusable model builders (e.g. the
    transformer's seq_axis residual-stream hint), where the same symbol
    must still bind on meshes without that axis."""
    parts = _parse_pspec(spec)
    if len(parts) > np.ndim(val):
        return val  # annotation written for a different-rank tensor
    for dim, axis in enumerate(parts):
        if axis is None:
            continue
        axes = axis if isinstance(axis, tuple) else (axis,)
        missing = [a for a in axes if a not in mesh.axis_names]
        if missing:
            if not strict:
                return val
            raise MXNetError(
                "__shard__ axis %r not in mesh axes %r"
                % (missing[0], mesh.axis_names))
        n_shards = int(np.prod([mesh.shape[a] for a in axes]))
        if val.shape[dim] % n_shards != 0:
            if not strict:
                return val
            raise MXNetError(
                "__shard__=%r: dim %d of shape %r not divisible by mesh "
                "axes %r (total shards %d)"
                % (spec, dim, tuple(val.shape), axes, n_shards))
    return jax.lax.with_sharding_constraint(
        val, NamedSharding(mesh, P(*parts)))


def _node_shard_spec(node, group2spec):
    """The sharding annotation of a node, if any: explicit __shard__ wins,
    else its ctx_group's entry in group2spec."""
    attrs = node.misc_attrs
    spec = attrs.get("__shard__")
    if spec is not None:
        return spec
    group = attrs.get("__ctx_group__") or attrs.get("ctx_group")
    if group is not None and group2spec:
        return group2spec.get(group)
    return None


def _graph_eval_fn(symbol, mesh=None, group2spec=None, capture=None,
                   layout=None):
    """Build the pure function evaluating `symbol`'s graph.

    Returns fn(arg_vals: dict name->array, aux_vals: dict, rng, is_train)
      -> (tuple outputs, dict new_aux).

    mesh/group2spec: lower ctx_group/__shard__ annotations to sharding
    constraints (the PlaceDevice analogue). layout (a
    parallel.sharding.SpecLayout): additionally pins activation batch
    dims at module boundaries (sharding.BOUNDARY_OPS) with LENIENT
    constraints — explicit __shard__/__shard_hint__ annotations win.
    capture: debugging hook called with (node_name, [outputs]) for
    every op node — only useful un-jitted (Monitor path).

    Every op node is lowered under two nested ``jax.named_scope``s, its
    name and ``op.<operator>``, so a device trace says which node and
    operator each operation came from (docs/observability.md)."""
    from .symbol.symbol import _topo_order

    boundary_ops = None
    if mesh is not None and layout is not None:
        from .parallel import sharding as _shd
        if getattr(layout, "act_parts", None) is not None and \
                layout.act_parts(2) is not None:
            boundary_ops = _shd.BOUNDARY_OPS

    entries = symbol._entries
    order = _topo_order(entries)
    node_uid = {id(n): i for i, n in enumerate(order)}

    def eval_fn(arg_vals, aux_vals, rng, is_train):
        from .ops._mesh_ctx import use_mesh
        with use_mesh(mesh):
            return _eval_body(arg_vals, aux_vals, rng, is_train)

    def _eval_body(arg_vals, aux_vals, rng, is_train):
        env = {}
        aux_out = dict(aux_vals)
        for node in order:
            if node.op is None:
                if node.is_aux:
                    env[id(node)] = [aux_out[node.name]]
                else:
                    env[id(node)] = [arg_vals[node.name]]
                if capture is not None:
                    capture(node.name, env[id(node)])
                continue
            xs = [env[id(m)][i] for (m, i) in node.inputs]
            attrs = dict(node.attrs)
            if node.op.takes_is_train:
                attrs["is_train"] = is_train
            kw = {}
            if node.op.needs_rng:
                kw["rng"] = jax.random.fold_in(rng, node_uid[id(node)])
            # the device operations this node lowers to carry its name
            # and its operator's on the profiler's timeline (`tf_op`):
            # `<node>/op.<Operator>/...`. The node comes first: under a
            # transform JAX wraps the outermost scope (`jvp(<node>)`,
            # `transpose(jvp(<node>))`) and leaves the kind a whole
            # part. `/` separates the parts of a name stack
            with jax.named_scope(node.name.replace("/", "_")), \
                    jax.named_scope("op." + node.op.name):
                raw = node.op.fn(*xs, **kw, **attrs)
            outs = list(raw) if isinstance(raw, (tuple, list)) else [raw]
            n_state = node.op.num_state
            if n_state:
                state_outs = outs[-n_state:]
                outs = outs[:-n_state]
                # state_inputs index the FULL signature; node.inputs holds
                # only the active (arg_select-filtered) args — map by name
                active = node.op.active_args(node.attrs)
                for slot, val in zip(node.op.state_inputs, state_outs):
                    sname = node.op.arg_names[slot]
                    if sname not in active:
                        continue
                    m, _i = node.inputs[active.index(sname)]
                    if m.op is None and m.is_aux:
                        aux_out[m.name] = val
            if mesh is not None:
                spec = _node_shard_spec(node, group2spec)
                if spec is not None:
                    outs = [_shard_constraint(mesh, spec, o) for o in outs]
                else:
                    hint = node.misc_attrs.get("__shard_hint__")
                    if hint is not None:
                        outs = [_shard_constraint(mesh, hint, o,
                                                  strict=False)
                                for o in outs]
                    elif boundary_ops is not None and \
                            node.op.name in boundary_ops:
                        # module boundary: pin the batch dim to the
                        # layout's data axes (lenient — indivisible or
                        # batchless tensors pass through untouched)
                        outs = [o if layout.act_parts(np.ndim(o)) is None
                                else _shard_constraint(
                                    mesh, layout.act_parts(np.ndim(o)),
                                    o, strict=False)
                                for o in outs]
            if capture is not None:
                capture(node.name, outs)
            env[id(node)] = outs
        outputs = tuple(env[id(n)][i] for (n, i) in entries)
        return outputs, aux_out

    return eval_fn


class Executor:
    """Executor over a lowered symbol graph (reference graph_executor.h:57)."""

    def __init__(self, symbol, ctx=None, args=None, args_grad=None,
                 grad_req="write", aux_states=None, group2ctx=None,
                 mesh=None, layout=None):
        self._symbol = symbol
        self._ctx = ctx if ctx is not None else current_context()
        self._group2ctx = group2ctx or {}
        self._mesh = mesh
        self._layout = layout
        self._monitor_callback = None
        self._monitor_all = False
        # host-python ops (CustomOp -> jax pure_callback): forward and
        # backward serialize such graphs (see forward)
        try:
            import json as _json
            self._has_host_callback_ops = any(
                n.get("op") == "Custom"
                for n in _json.loads(symbol.tojson())["nodes"])
        except Exception:  # noqa: BLE001
            self._has_host_callback_ops = False

        arg_names = symbol.list_arguments()
        aux_names = symbol.list_auxiliary_states()
        self._arg_names = arg_names
        self._aux_names = aux_names

        self.arg_arrays = self._align("args", args, arg_names)
        self.aux_arrays = self._align("aux_states", aux_states, aux_names,
                                      allow_missing=not aux_names)

        if isinstance(grad_req, str):
            self._grad_req = {n: grad_req for n in arg_names}
        elif isinstance(grad_req, (list, tuple)):
            self._grad_req = dict(zip(arg_names, grad_req))
        else:
            self._grad_req = {n: grad_req.get(n, "null") for n in arg_names}

        if args_grad is None:
            self.grad_arrays = [
                _nd.zeros_like(a) if self._grad_req[n] != "null" else None
                for n, a in zip(arg_names, self.arg_arrays)]
        else:
            self.grad_arrays = self._align("args_grad", args_grad, arg_names,
                                           allow_missing=True)
            for i, n in enumerate(arg_names):
                if self.grad_arrays[i] is None and \
                        self._grad_req[n] != "null":
                    self._grad_req[n] = "null"

        # group2ctx: entries whose value is a partition-spec string (or
        # P tuple) become sharding constraints; Context values (reference
        # device placement) have no single-program analogue and replicate
        self._group2spec = {g: v for g, v in self._group2ctx.items()
                            if not isinstance(v, Context)}
        self._eval_fn = _graph_eval_fn(symbol, mesh=mesh,
                                       group2spec=self._group2spec,
                                       layout=layout)
        self._jit_fwd = jax.jit(self._eval_fn, static_argnums=(3,))
        self._grad_names = [n for n in arg_names
                            if self._grad_req[n] != "null"]
        self._jit_fwd_bwd = jax.jit(self._fwd_bwd_impl)
        self._jit_bwd = jax.jit(self._bwd_impl)
        self._compile_logged = set()   # telemetry compile events, per fn
        self.outputs = []
        self._fwd_inputs = None
        self._cached_grads = None
        # adaptive: fused fwd+grads is only worth it when backward() takes
        # the default ones-cotangent path; a backward with explicit
        # out_grads (e.g. SequentialModule interior stages) flips this off
        # so later forwards don't compute grads that get thrown away
        self._prefer_fused = True

    # -- construction helpers ----------------------------------------------
    def _align(self, what, values, names, allow_missing=False):
        if values is None:
            if allow_missing:
                return [None] * len(names)
            raise MXNetError("%s must be provided for %r" % (what, names))
        if isinstance(values, dict):
            out = []
            for n in names:
                if n in values:
                    v = values[n]
                    out.append(v if isinstance(v, NDArray) or v is None
                               else _nd.array(v))
                elif allow_missing:
                    out.append(None)
                else:
                    raise MXNetError("%s: missing entry for %r" % (what, n))
            return out
        values = list(values)
        if len(values) != len(names):
            raise MXNetError("%s: length %d != expected %d"
                             % (what, len(values), len(names)))
        return [v if isinstance(v, NDArray) or v is None else _nd.array(v)
                for v in values]

    @staticmethod
    def _simple_bind(symbol, ctx=None, grad_req="write", type_dict=None,
                     group2ctx=None, **kwargs):
        arg_shapes, _, aux_shapes = symbol.infer_shape(**kwargs)
        arg_names = symbol.list_arguments()
        aux_names = symbol.list_auxiliary_states()
        type_dict = type_dict or {}
        arg_types, _, aux_types = symbol.infer_type(**type_dict)
        args = [_nd.zeros(s, dtype=t) for s, t in zip(arg_shapes, arg_types)]
        aux = [_nd.zeros(s, dtype=t) for s, t in zip(aux_shapes, aux_types)]
        return Executor(symbol, ctx, args=args, grad_req=grad_req,
                        aux_states=aux, group2ctx=group2ctx)

    # -- dict views ----------------------------------------------------------
    @property
    def arg_dict(self):
        return dict(zip(self._arg_names, self.arg_arrays))

    @property
    def grad_dict(self):
        return dict(zip(self._arg_names, self.grad_arrays))

    @property
    def aux_dict(self):
        return dict(zip(self._aux_names, self.aux_arrays))

    @property
    def output_dict(self):
        return dict(zip(self._symbol.list_outputs(), self.outputs))

    def copy_params_from(self, arg_params, aux_params=None,
                         allow_extra_params=False):
        for name, arr in (arg_params or {}).items():
            if name in self.arg_dict:
                self.arg_dict[name]._set_data(
                    jnp.asarray(arr.asnumpy() if isinstance(arr, NDArray)
                                else arr,
                                self.arg_dict[name]._data.dtype))
            elif not allow_extra_params:
                raise MXNetError("Found name %r not in arguments" % name)
        for name, arr in (aux_params or {}).items():
            if name in self.aux_dict:
                self.aux_dict[name]._set_data(
                    jnp.asarray(arr.asnumpy() if isinstance(arr, NDArray)
                                else arr,
                                self.aux_dict[name]._data.dtype))
            elif not allow_extra_params:
                raise MXNetError("Found name %r not in aux states" % name)

    def set_monitor_callback(self, callback, monitor_all=False):
        """Install a per-op value callback (reference ExecuteMonCallback,
        graph_executor.h:200). Fires for every graph node's outputs;
        monitor_all additionally fires for variable (arg/aux) nodes."""
        self._monitor_callback = callback
        self._monitor_all = monitor_all

    # -- execution -----------------------------------------------------------
    def _current_rng(self):
        from . import random as mx_random
        return mx_random.next_key()

    def _monitor_active(self):
        if self._monitor_callback is None:
            return False
        mon = getattr(self._monitor_callback, "mon", None)
        return bool(getattr(mon, "activated", True))

    def _run_monitored(self, arg_vals, aux_vals, rng, is_train):
        """Un-jitted graph evaluation with a per-node capture hook — the
        Monitor debugging path (intermediate tensors are materialized,
        which jit+fusion would never do)."""
        cb = self._monitor_callback
        want_vars = self._monitor_all
        var_names = set(self._arg_names) | set(self._aux_names)

        def capture(name, outs):
            if not want_vars and name in var_names:
                return
            for i, o in enumerate(outs):
                label = name if len(outs) == 1 else "%s_out%d" % (name, i)
                cb(label, _wrap(jnp.asarray(o)))

        fn = _graph_eval_fn(self._symbol, mesh=self._mesh,
                            group2spec=self._group2spec, capture=capture,
                            layout=self._layout)
        return fn(arg_vals, aux_vals, rng, is_train)

    def forward(self, is_train=False, **kwargs):
        """Run forward (reference MXExecutorForward →
        GraphExecutor::Forward). kwargs update named input arrays.

        Training forwards with gradients requested run the fused
        fwd+vjp executable and cache the gradients for backward()."""
        for k, v in kwargs.items():
            if k not in self.arg_dict:
                raise MXNetError("unknown forward argument %r" % k)
            dst = self.arg_dict[k]
            src = v._data if isinstance(v, NDArray) else jnp.asarray(v)
            dst._set_data(src.astype(dst._data.dtype)
                          if src.dtype != dst._data.dtype else src)

        arg_vals = {n: a._data for n, a in zip(self._arg_names,
                                               self.arg_arrays)}
        aux_vals = {n: a._data for n, a in zip(self._aux_names,
                                               self.aux_arrays)}
        rng = self._current_rng()

        from . import profiler
        from . import telemetry as _telemetry

        # telemetry compile events: the FIRST call of each jitted
        # variant blocks through XLA trace+compile, so its wall time IS
        # the compile cost. Monitored (un-jitted) runs are excluded.
        jr = _telemetry.journal()
        if self._monitor_active():
            variant = None
        elif is_train and self._grad_names and self._prefer_fused:
            variant = "fwd_bwd"
        else:
            variant = "train_fwd" if is_train else "infer_fwd"
        log_compile = jr is not None and variant is not None \
            and variant not in self._compile_logged
        t_compile = _telemetry.now_ms() if log_compile else 0.0

        self._cached_grads = None
        with profiler.scope("executor_forward%s" %
                            ("_train" if is_train else ""),
                            "executor"):
            if self._monitor_active():
                outs, new_aux = self._run_monitored(
                    arg_vals, aux_vals, rng, bool(is_train))
            elif is_train and self._grad_names and \
                    self._prefer_fused:
                outs, new_aux, grads = self._jit_fwd_bwd(
                    arg_vals, aux_vals, rng)
                self._cached_grads = grads
            else:
                outs, new_aux = self._jit_fwd(arg_vals, aux_vals,
                                              rng, bool(is_train))
        if log_compile:
            self._compile_logged.add(variant)
            # per-variant model FLOPs from XLA cost analysis: a
            # one-off re-trace + lower at the compile event (the
            # executable itself is already cached — no second XLA
            # compile, no execution, no host sync). Feeds the MFU
            # line in tools/telemetry_report.py (MXNET_PEAK_FLOPS).
            flops = self._variant_flops(variant, arg_vals,
                                        aux_vals, rng)
            # only the FUSED step variant feeds the MFU gauge: it
            # is the one whole-step program. train_fwd alone would
            # undercount a split fwd+bwd step ~3x and infer_fwd
            # isn't a training step at all (both still record
            # their flops on the compile event below).
            if flops and variant == "fwd_bwd":
                _telemetry.gauge("step.model_flops").set(flops)
            _telemetry.journal_event(
                "compile", site="Executor.forward", variant=variant,
                wall_ms=round(_telemetry.now_ms() - t_compile, 3),
                flops=flops)
        if self._has_host_callback_ops:
            # Custom-op graphs run host-python callbacks on the runtime's
            # execution threads, and that host code dispatches jax ops of
            # its own. Letting the program run async while the caller
            # keeps dispatching eagerly can deadlock the CPU client (the
            # callback's dispatch waits on the pool the still-running
            # program occupies). Custom ops are a host round trip by
            # design ("escape hatch, not a fast path") — serialize them.
            jax.block_until_ready((outs, new_aux, self._cached_grads))
        if is_train:
            for n, a in zip(self._aux_names, self.aux_arrays):
                a._set_data(new_aux[n])
            self._fwd_inputs = (arg_vals, aux_vals, rng)
        else:
            # a non-train forward invalidates the training residuals so a
            # later backward() cannot silently use stale inputs
            self._fwd_inputs = None
        self.outputs = [_wrap(o) for o in outs]
        return self.outputs

    def _variant_flops(self, variant, arg_vals, aux_vals, rng):
        """XLA ``cost_analysis()`` FLOPs of one jit variant, read off the
        lowered module (trace cost only; ``.compile()`` here would redo
        the whole XLA compilation).
        None when the backend reports nothing."""
        try:
            if variant == "fwd_bwd":
                lowered = self._jit_fwd_bwd.lower(arg_vals, aux_vals,
                                                  rng)
            else:
                lowered = self._jit_fwd.lower(arg_vals, aux_vals, rng,
                                              variant == "train_fwd")
            ca = lowered.cost_analysis()
            if isinstance(ca, (list, tuple)):
                ca = ca[0] if ca else {}
            flops = float((ca or {}).get("flops", 0.0))
            return flops or None
        except Exception:    # noqa: BLE001 — cost analysis is advisory
            return None

    def _fwd_bwd_impl(self, arg_vals, aux_vals, rng):
        """One XLA program: outputs + new aux + grads (ones cotangent —
        the reference's head-grad convention, where loss heads ignore the
        incoming cotangent)."""
        from .base import env_flag
        wrt = {n: arg_vals[n] for n in self._grad_names}

        def f(wrt_vals):
            merged = dict(arg_vals)
            merged.update(wrt_vals)
            outs, new_aux = self._eval_fn(merged, aux_vals, rng, True)
            return outs, new_aux

        if env_flag("MXNET_BACKWARD_DO_MIRROR"):
            # gradient mirroring (reference graph_executor.cc:276-287,
            # env_var.md memonger): trade forward recompute for
            # activation memory — on TPU this is jax rematerialization
            f = jax.checkpoint(f)
        outs, vjp, new_aux = jax.vjp(f, wrt, has_aux=True)
        cots = tuple(jnp.ones(o.shape, o.dtype) for o in outs)
        grads = vjp(cots)[0]
        return outs, new_aux, grads

    def _bwd_impl(self, arg_vals, aux_vals, rng, head_grads):
        """Re-derivation path for explicit head gradients."""
        from .base import env_flag
        wrt = tuple(arg_vals[n] for n in self._grad_names)

        def f(wrt_vals):
            merged = dict(arg_vals)
            merged.update(dict(zip(self._grad_names, wrt_vals)))
            outs, _ = self._eval_fn(merged, aux_vals, rng, True)
            return outs

        if env_flag("MXNET_BACKWARD_DO_MIRROR"):
            f = jax.checkpoint(f)
        outs, vjp = jax.vjp(f, wrt)
        grads = vjp(tuple(head_grads))[0]
        return dict(zip(self._grad_names, grads))

    def backward(self, out_grads=None, is_train=True):
        """Backprop through the bound graph (reference MXExecutorBackwardEx).

        With no `out_grads`, each head receives an all-ones cotangent —
        the reference's head-grad convention for loss-layer ops
        (SoftmaxOutput, MakeLoss). Heads propagate the incoming
        cotangent as a scale (identity under the ones default; the
        hook dynamic loss scaling rides on, ops/loss.py). In the
        default case the gradients were already produced by the fused
        forward program and this only writes them out."""
        if self._fwd_inputs is None:
            raise MXNetError("backward() requires a prior "
                             "forward(is_train=True)")
        arg_vals, aux_vals, rng = self._fwd_inputs
        if out_grads is None:
            self._prefer_fused = True
            if self._cached_grads is not None:
                grads = self._cached_grads
            else:
                head_grads = [jnp.ones(o.shape, o._data.dtype)
                              for o in self.outputs]
                grads = self._jit_bwd(arg_vals, aux_vals, rng,
                                      tuple(head_grads))
        else:
            self._prefer_fused = False
            if isinstance(out_grads, (NDArray, jax.Array, np.ndarray)):
                out_grads = [out_grads]
            head_grads = [g._data if isinstance(g, NDArray)
                          else jnp.asarray(g) for g in out_grads]
            grads = self._jit_bwd(arg_vals, aux_vals, rng,
                                  tuple(head_grads))
        if self._has_host_callback_ops:
            # see forward(): host-callback programs are serialized so
            # their callbacks can't deadlock against eager dispatch
            jax.block_until_ready(grads)
        for n, gbuf in zip(self._arg_names, self.grad_arrays):
            if gbuf is None or self._grad_req[n] == "null":
                continue
            if self._grad_req[n] == "add":
                gbuf._set_data(gbuf._data + grads[n])
            else:
                gbuf._set_data(grads[n])
        return [self.grad_dict[n] for n in self._grad_names]

    def reshape(self, partial_shaping=False, allow_up_sizing=False, **kwargs):
        """Return a new executor with new input shapes (reference
        executor.py:reshape). jit recompiles per shape automatically, so this
        just reallocates the data arrays."""
        arg_shapes, _, aux_shapes = self._symbol.infer_shape(**kwargs)
        new_args = []
        for n, a, s in zip(self._arg_names, self.arg_arrays, arg_shapes):
            if tuple(a.shape) == tuple(s):
                new_args.append(a)
            else:
                new_args.append(_nd.zeros(s, dtype=a.dtype))
        new_aux = []
        for n, a, s in zip(self._aux_names, self.aux_arrays, aux_shapes):
            new_aux.append(a if tuple(a.shape) == tuple(s)
                           else _nd.zeros(s, dtype=a.dtype))
        return Executor(self._symbol, self._ctx, args=new_args,
                        grad_req={n: r for n, r in self._grad_req.items()},
                        aux_states=new_aux, group2ctx=self._group2ctx,
                        mesh=self._mesh, layout=self._layout)

    def debug_str(self):
        return self._symbol.debug_str()
