"""The compiled SPMD training step.

This is the TPU-native replacement for the reference's whole training hot
path: GraphExecutor::Forward/Backward + KVStore push/pull + fused
optimizer_op, all inside ONE `jax.jit`. XLA fuses forward, backward and the
parameter update, overlaps the grad all-reduce with backprop (the same
overlap the reference achieved by pushing KVStore reductions onto
prioritized engine queues, comm.h:109-178), and donates parameter buffers
so updates are in-place in HBM.

Reference call stack being replaced: SURVEY.md §3.1 (fit loop internals).
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np

from .. import guardrail as _guardrail
from .. import telemetry as _telemetry
from .. import trace as _trace
from ..executor import _graph_eval_fn
from ..ops.registry import get_op
from . import sharding as shd

__all__ = ["make_train_step", "TrainStep"]


def _nd_wrap(x):
    from ..ndarray.ndarray import _wrap
    return _wrap(x)


class _SimpleBatchEnd:
    """BatchEndParam-compatible namespace for Speedometer-style
    callbacks (reference model.py:BatchEndParam)."""

    def __init__(self, epoch, nbatch, eval_metric):
        self.epoch = epoch
        self.nbatch = nbatch
        self.eval_metric = eval_metric
        self.locals = None

# fused optimizer ops: name -> (#state tensors, op name)
_OPT_OPS = {
    "sgd": (1, "sgd_mom_update"),       # momentum (0.0 => plain sgd math)
    "adam": (2, "adam_update"),
    "rmsprop": (1, "rmsprop_update"),
    "ftrl": (2, "ftrl_update"),
    "signum": (0, "signsgd_update"),
}


class TrainStep:
    """A compiled train step over an optional mesh.

    state = (params: dict, opt_state: dict name->tuple, aux: dict)
    step(state, batch, lr, rng) -> (state, outputs)
    """

    def __init__(self, symbol, data_names=("data",),
                 label_names=("softmax_label",), optimizer="sgd",
                 optimizer_params=None, mesh=None, donate=True,
                 compute_dtype=None, remat=None, optimizer_sharding=None,
                 clip_norm=None, layout=None):
        """compute_dtype: cast params+data to this dtype for fwd/bwd
        (e.g. 'bfloat16' for MXU-rate compute) while master weights,
        gradients, optimizer state and BN statistics stay float32 — the
        TPU mapping of the reference's multi-precision mp_sgd_* path.

        remat: rematerialize the forward in backward (gradient
        mirroring, reference MXNET_BACKWARD_DO_MIRROR /
        graph_executor.cc:276-287) — activation memory traded for
        recompute FLOPs, the lever for long sequences / deep nets.
        Default: the MXNET_BACKWARD_DO_MIRROR env var.

        optimizer_sharding: None (replicated update on every chip) or
        'zero1' — optimizer state sharded 1/N along the 'data' mesh axis,
        grads reduce-scattered onto the owned slice, fused update on the
        slice, params all-gathered back. The TPU mapping of the
        reference's server-side optimizer / update_on_kvstore=True path
        (kvstore_dist_server.h:109-433): state memory drops to 1/N per
        chip and the update FLOPs shard with it. Same math as the
        replicated path, equal up to float reduction order (tests
        assert allclose).

        layout: a ``sharding.SpecLayout`` — the GSPMD partition-spec
        registry (docs/parallelism.md "One-jit GSPMD path"). Carries
        its own mesh (don't also pass ``mesh=``); params/opt state are
        placed per its rules, batches shard over its data axes
        (data × fsdp), activations are pinned at module boundaries,
        and ``optimizer_sharding='zero1'`` folds optimizer state
        across the data × fsdp replicas (1/N state + update per
        device). A bare ``mesh=`` keeps the original name-suffix
        heuristics — both paths run through the same placement layer.

        clip_norm: clip gradients by GLOBAL norm before the optimizer
        (the LM-training standard; the per-element clip_gradient knob
        on the optimizer still applies inside the fused update). The
        SPMD counterpart of gluon.utils.clip_global_norm — all grads
        scale by min(1, clip_norm / ||g||_2) computed over the whole
        gradient pytree, inside the compiled step."""
        from ..base import env_flag
        self.symbol = symbol
        if layout is not None:
            if mesh is not None and mesh is not layout.mesh:
                raise ValueError(
                    "pass either layout= or mesh=, not both — the "
                    "layout carries its own mesh")
            mesh = layout.mesh
        self.mesh = mesh
        # ONE placement seam for both the registry (SpecLayout) and the
        # legacy heuristic path; None = single device, no placement
        self._layout = layout if layout is not None \
            else shd.as_layout(mesh)
        # SpecLayout-only extras (activation pinning, describe report,
        # layout telemetry) key off this
        self._spec_layout = layout
        self.compute_dtype = (None if compute_dtype is None
                              else jnp.dtype(compute_dtype))
        self.remat = bool(remat) if remat is not None else \
            env_flag("MXNET_BACKWARD_DO_MIRROR")
        self.data_names = list(data_names)
        self.label_names = list(label_names)
        self.arg_names = symbol.list_arguments()
        self.aux_names = symbol.list_auxiliary_states()
        self.input_names = self.data_names + self.label_names
        self.param_names = [n for n in self.arg_names
                            if n not in self.input_names]
        self.opt_name = optimizer
        self.opt_params = dict(optimizer_params or {})
        if optimizer not in _OPT_OPS:
            raise ValueError("TrainStep supports fused optimizers %r"
                             % sorted(_OPT_OPS))
        if optimizer_sharding not in (None, "zero1"):
            raise ValueError("optimizer_sharding must be None or 'zero1', "
                             "got %r" % (optimizer_sharding,))
        if optimizer_sharding == "zero1" and (
                self._layout is None or not self._layout.zero_axes):
            raise ValueError(
                "optimizer_sharding='zero1' needs a replica axis to "
                "shard the optimizer state over: a bare mesh= with a "
                "'data' axis, or a layout=SpecLayout(...) (which folds "
                "over 'data' and 'fsdp') — got mesh axes %r"
                % (None if mesh is None else list(mesh.axis_names)))
        if clip_norm is not None and not float(clip_norm) > 0:
            # "not > 0" (rather than "<= 0") also rejects NaN, which
            # would silently poison every gradient inside the jit
            raise ValueError("clip_norm must be positive, got %r"
                             % (clip_norm,))
        self.clip_norm = None if clip_norm is None else float(clip_norm)
        self.optimizer_sharding = optimizer_sharding
        self._n_state, self._opt_op = _OPT_OPS[optimizer]
        # data inputs that carry token/category ids (feed an Embedding)
        # must NOT be cast to the compute dtype: bf16's 8-bit significand
        # aliases ids >= 256. Found from the graph, not by name.
        self._id_inputs = self._embedding_fed_inputs(symbol) \
            & set(self.data_names)
        # mesh passed through so __shard__/ctx_group annotations lower
        # to sharding constraints inside the step; a SpecLayout
        # additionally pins activation batch dims at module boundaries
        self._eval_fn = _graph_eval_fn(symbol, mesh=mesh, layout=layout)

        self._donate = bool(donate)
        # last fit's guardrail outcome: masked_steps/rollbacks/lr_mult
        # ({} until a guarded fit ran) — tests and relaunchers read it
        self.guard_report = {}
        step = self._build_step()
        self._jit_step = jax.jit(
            step, donate_argnums=(0, 1, 2) if donate else ())

    @staticmethod
    def _embedding_fed_inputs(symbol):
        """Variable names whose value feeds an Embedding lookup's data
        slot somewhere in the graph (ids, not numbers)."""
        import json as _json
        graph = _json.loads(symbol.tojson())
        nodes = graph.get("nodes", [])
        out = set()
        for n in nodes:
            if n.get("op") == "Embedding" and n.get("inputs"):
                src = nodes[n["inputs"][0][0]]
                if src.get("op") == "null":
                    out.add(src["name"])
        return out

    # -- state -------------------------------------------------------------
    def init_state(self, initializer, batch_shapes, batch_dtypes=None,
                   dtype=None, arg_params=None, aux_params=None):
        """Initialize (params, opt_state, aux) with mesh placement.

        initializer: mxnet_tpu.initializer.Initializer applied host-side
        (reference init path), then placed per the sharding rules.

        arg_params/aux_params: pretrained values (NDArray or array) to
        adopt instead of initializing — the ``Module.fit(arg_params=)``
        surface for the SPMD path, e.g. a ``model.load_checkpoint`` or
        ``HybridBlock.export`` checkpoint. Anything not supplied falls
        back to the initializer; optimizer state starts at zero either
        way."""
        from ..initializer import InitDesc
        from ..ndarray import NDArray, zeros as nd_zeros

        def _raw(x):
            return x._data if isinstance(x, NDArray) else jnp.asarray(x)

        arg_params = {k: _raw(v) for k, v in (arg_params or {}).items()}
        aux_params = {k: _raw(v) for k, v in (aux_params or {}).items()}

        input_shapes = dict(batch_shapes)
        arg_shapes, _, aux_shapes = self.symbol.infer_shape(**input_shapes)
        name2shape = dict(zip(self.arg_names, arg_shapes))
        aux2shape = dict(zip(self.aux_names, aux_shapes))

        params, opt_state, aux = {}, {}, {}
        for n in self.param_names:
            if n in arg_params:
                v = arg_params[n]
                if tuple(v.shape) != tuple(name2shape[n]):
                    raise ValueError(
                        "arg_params[%r] has shape %r, symbol wants %r"
                        % (n, tuple(v.shape), tuple(name2shape[n])))
            else:
                arr = nd_zeros(name2shape[n])
                initializer(InitDesc(n), arr)
                v = arr._data
            if dtype is not None:
                v = v.astype(dtype)
            params[n] = self._place_param(n, v)
            opt_state[n] = tuple(
                self._place_opt(n, jnp.zeros_like(params[n]))
                for _ in range(self._n_state))
        for n in self.aux_names:
            if n in aux_params:
                init_v = aux_params[n]
            else:
                init_v = jnp.ones(aux2shape[n], jnp.float32) \
                    if n.endswith("var") else jnp.zeros(aux2shape[n],
                                                        jnp.float32)
            aux[n] = self._place_rep(init_v)
        if self._spec_layout is not None:
            self._report_layout(params, opt_state)
        return params, opt_state, aux

    def _report_layout(self, params, opt_state):
        """GSPMD layout telemetry at placement time: rule-claim counts
        and the per-device optimizer-state bytes, all host-side shape
        math (zero device syncs). The full per-parameter report is
        ``describe_layout()``."""
        lay = self._spec_layout
        sharded = sum(
            1 for v in params.values()
            if np.prod(v.sharding.shard_shape(v.shape))
            < np.prod(v.shape))
        opt_bytes = sum(
            int(np.prod(s.sharding.shard_shape(s.shape)))
            * s.dtype.itemsize
            for states in opt_state.values() for s in states)
        _telemetry.gauge("gspmd.sharded_params").set(sharded)
        _telemetry.gauge("gspmd.opt_state_bytes_per_dev").set(opt_bytes)
        _telemetry.journal_event(
            "layout.bind", mesh=dict(lay.mesh.shape),
            params=len(params), sharded_params=sharded,
            opt_state_bytes_per_dev=opt_bytes,
            rules=len(lay.rules))

    def describe_layout(self):
        """The layout's per-parameter placement report (which rule
        claimed each parameter, global -> per-device shard shapes).
        Populated by ``init_state``/``load_state``."""
        if self._layout is None:
            return "no mesh/layout bound (single-device step)"
        return self._layout.describe()

    def _raw_feed(self, batch):
        """Named feed dict from a DataBatch with NO host round trip:
        NDArrays unwrap to their backing device arrays (the old path
        paid an asnumpy D2H + re-upload per batch)."""
        from ..ndarray import NDArray as _ND
        feed = dict(zip(self.data_names, batch.data))
        if batch.label is not None:
            feed.update(zip(self.label_names, batch.label))
        return {k: (v._data if isinstance(v, _ND) else v)
                for k, v in feed.items()}

    def make_placer(self):
        """place_fn for ``io.PrefetchingIter(place_fn=...)``: assembles
        the named feed and dispatches its device placement, so the H2D
        for batch t+1 runs on the prefetch thread while step t
        computes. ``fit`` picks the result up from ``batch.placed``."""
        def place(batch):
            return self.place_batch(self._raw_feed(batch))
        return place

    def _stage(self, batch):
        """(batch, placed-feed): reuse an io-layer placement when the
        iterator staged one, else dispatch it now."""
        placed = getattr(batch, "placed", None)
        if placed is None:
            placed = self.place_batch(self._raw_feed(batch))
        return batch, placed

    def _metric_fused_step(self, metric, guard=None):
        """One compiled program: train step + on-device metric update.
        The metric stats tree rides along as an extra carry, so a full
        epoch dispatches without a single device→host read. Guarded
        steps additionally mask the batch's stats by the step's
        all-finite flag — a masked step contributes to neither ``sum``
        nor ``num``, so metrics exclude it entirely."""
        raw_step = self._build_step(guard=guard)
        label_names = list(self.label_names)
        layout = self._layout
        pin_state = self._spec_layout is not None

        def accumulate(mstats, stats):
            new = jax.tree.map(jnp.add, mstats, stats)
            if pin_state:
                # the stats carry is donated like params/opt-state; left
                # to GSPMD output propagation it comes back sharded,
                # misses the jit cache and recompiles at every epoch
                # boundary (tools/perf_gate.py gspmd scenario gauges
                # trainstep.jit_cache_size == 1 against exactly this)
                new = jax.tree.map(
                    lambda v: shd.constrain(
                        v, layout.replicated_nsharding()), new)
            return new

        if guard is not None:
            def step_with_metric(params, opt_state, aux, batch, lr,
                                 rng, mstats, inject):
                (p, o, a), outs, ok = raw_step(
                    params, opt_state, aux, batch, lr, rng, inject)
                with jax.named_scope("train.metric"):
                    stats = metric.device_update(
                        [batch[n] for n in label_names], list(outs))
                    stats = _guardrail.mask_stats(stats, ok)
                    mstats = accumulate(mstats, stats)
                return (p, o, a), outs, mstats, ok
        else:
            def step_with_metric(params, opt_state, aux, batch, lr,
                                 rng, mstats):
                (p, o, a), outs = raw_step(params, opt_state, aux,
                                           batch, lr, rng)
                with jax.named_scope("train.metric"):
                    stats = metric.device_update(
                        [batch[n] for n in label_names], list(outs))
                    mstats = accumulate(mstats, stats)
                return (p, o, a), outs, mstats

        return raw_step, jax.jit(
            step_with_metric,
            donate_argnums=(0, 1, 2) if self._donate else ())

    def _zero_metric_stats(self, raw_step, metric, state, placed, lr,
                           rng, guarded=False):
        """Zeros with the exact structure/dtypes of the metric's stats
        tree, via abstract evaluation only (no compile, no execute)."""
        params, opt_state, aux = state
        args = (params, opt_state, aux, placed,
                jnp.asarray(lr, jnp.float32), rng)
        if guarded:
            shapes = jax.eval_shape(raw_step, *args,
                                    jnp.asarray(1.0, jnp.float32))
        else:
            shapes = jax.eval_shape(raw_step, *args)
        outs_s = shapes[1]
        stats_s = jax.eval_shape(
            metric.device_update,
            [placed[n] for n in self.label_names], list(outs_s))
        zeros = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                             stats_s)
        if self._spec_layout is not None:
            # match the layout the fused step pins the carry to, so the
            # epoch's first step shares the steady-state executable
            zeros = jax.tree.map(self._place_rep, zeros)
        return zeros

    def fit(self, train_data, num_epoch, initializer=None, lr=0.01,
            lr_scheduler=None, eval_metric="acc", state=None,
            arg_params=None, aux_params=None, checkpoint_prefix=None,
            checkpoint_period=1, resume=True, batch_end_callback=None,
            epoch_end_callback=None, seed=0, logger=None,
            fuse_metric=None, dispatch_ahead=None):
        """Module.fit for the SPMD path: epochs over a DataIter, metric
        tracking, periodic checkpointing, and crash resume — the
        reference fit-loop UX (base_module.py:fit) on the compiled
        train step.

        The hot loop is pipelined: batch t+1 is placed (async H2D)
        while step t runs, metrics accumulate ON DEVICE (fused into
        the compiled step when the metric supports it — the single
        host read happens in ``metric.get()`` at epoch end), and a
        bounded dispatch window keeps at most MXNET_DISPATCH_AHEAD
        steps in flight by blocking on the step K back — an
        instrumented epoch performs at most one blocking host sync
        per step.

        fuse_metric: None (auto: fuse when the metric has a device
            impl) | True | False (False = host metric path, as before).
        dispatch_ahead: in-flight step window; default the
            MXNET_DISPATCH_AHEAD env knob (2).

        train_data: DataIter yielding DataBatch (batch size must match
            across batches — one compiled program).
        lr_scheduler: callable(update_count) -> lr (mxnet_tpu
            lr_scheduler instances work).
        checkpoint_prefix: save_state to ``prefix_NNNN`` each
            ``checkpoint_period`` epochs; with resume=True an existing
            latest checkpoint is loaded and training continues AFTER
            it (the elastic-restart story — kill the process anywhere,
            rerun the same command; the scheduler/rng update counter
            resumes too, via the checkpoint's sidecar meta file).

        Guardrails (docs/robustness.md, MXNET_GUARDRAIL default on):
        the compiled step carries a device-side all-finite flag over
        loss and gradients; a non-finite step's update is masked out on
        device (weights never ingest the NaN) and fused metrics exclude
        it. The host reads the flag at the dispatch-window wait it
        already pays — zero extra blocking syncs. After
        MXNET_MAX_BAD_STEPS consecutive masked steps the loop restores
        the newest readable checkpoint (MXNET_ROLLBACK_LR_FACTOR drops
        the lr per rollback) and raises NumericalDivergence once
        MXNET_MAX_ROLLBACKS is spent. With a checkpoint_prefix, SIGTERM
        or SIGINT requests a checkpoint at the next step boundary and
        the process exits with code guardrail.EXIT_PREEMPTED; a rerun
        with resume=True continues from that exact step.
        MXNET_LOSS_SCALE enables (dynamic) loss scaling, its state
        riding the checkpointed aux pytree.

        Returns (state, final_metric_value) — metric is None when a
        resumed run has no epochs left."""
        import logging
        from collections import deque

        from .. import config as _config
        from .. import metric as metric_mod
        from .. import profiler as _profiler
        from ..initializer import Uniform

        log = logger or logging.getLogger(__name__)
        metric = metric_mod.create(eval_metric) \
            if not hasattr(eval_metric, "update") else eval_metric

        begin_epoch = 0
        n_update = 0
        skip_batches = 0
        if checkpoint_prefix and resume:
            found = self._scan_checkpoints(checkpoint_prefix, log)
            if found is not None:
                state, begin_epoch, n_update, skip_batches = found
        if begin_epoch >= num_epoch:
            log.info("checkpoints already cover all %d epochs; "
                     "nothing to train", num_epoch)
            return state, None
        if state is None:
            shapes = {}
            for name, shape in (train_data.provide_data
                                + train_data.provide_label):
                shapes[name] = tuple(shape)
            state = self.init_state(initializer or Uniform(0.01),
                                    shapes, arg_params=arg_params,
                                    aux_params=aux_params)

        guard = _guardrail.FitGuard.create(
            logger=log, checkpointing=bool(checkpoint_prefix))
        spec = guard.spec
        state = self._ensure_scaler_state(state, spec)

        ahead = dispatch_ahead if dispatch_ahead is not None \
            else _config.get("MXNET_DISPATCH_AHEAD")
        ahead = max(1, int(ahead))
        use_dev = bool(getattr(metric, "supports_device_update", False))
        fuse = use_dev if fuse_metric is None else bool(fuse_metric)
        fuse = fuse and use_dev
        raw_step = fused_step = guarded_step = None
        if fuse:
            raw_step, fused_step = self._metric_fused_step(metric, spec)
        elif spec is not None:
            guarded_step = jax.jit(
                self._build_step(guard=spec),
                donate_argnums=(0, 1, 2) if self._donate else ())

        # telemetry (docs/observability.md): the journal handle is
        # hoisted out of the hot loop — when telemetry is off, the loop
        # pays literally nothing. All instrumentation below is host-side
        # wall-clock only: it adds ZERO blocking host syncs (asserted
        # against profiler.host_sync_count in tests/test_telemetry.py).
        # The trace handle (docs/observability.md §tracing) is hoisted
        # the same way: this call resolves MXNET_TRACE once, and every
        # phase in the loop reads the module flag only.
        jr = _telemetry.journal()
        _trace.tracer()
        step_hist = _telemetry.histogram("trainstep.step_ms") \
            if jr is not None else None
        _telemetry.journal_event("fit.start", loop="trainstep",
                                 num_epoch=num_epoch,
                                 begin_epoch=begin_epoch)
        compile_logged = False

        rng = jax.random.PRNGKey(seed)
        inflight = deque()

        def drain_one():
            # the one blocking sync per step either way: the bounded-
            # dispatch-window wait. With the guardrail on it reads the
            # step's finite flag — the value the wait was already
            # materializing — so detection adds zero extra syncs.
            item = inflight.popleft()
            _profiler.count_host_sync("dispatch_window")
            if spec is not None:
                guard.policy.record(bool(np.asarray(item)))
            else:
                item.block_until_ready()

        last_val = None
        with guard.shutdown_scope():
            epoch = begin_epoch
            while epoch < num_epoch:
                with _trace.phase("train.epoch_begin", epoch=epoch):
                    train_data.reset()
                    metric.reset()
                    mstats = None
                    batches = iter(train_data)
                    if skip_batches:
                        log.info("mid-epoch resume: skipping %d "
                                 "already-trained batches of epoch %d",
                                 skip_batches, epoch)
                        for _ in range(skip_batches):
                            if next(batches, None) is None:
                                break
                        skip_batches = 0
                    nxt = next(batches, None)
                    staged = None if nxt is None else self._stage(nxt)
                nbatch = 0
                t_iter = _telemetry.now_ms() if jr is not None else 0.0
                try:
                    while staged is not None:
                        inject = guard.poll_faults() \
                            if spec is not None or \
                            guard.shutdown is not None else None
                        if guard.preempt_requested():
                            self._preempt_exit(
                                checkpoint_prefix, epoch, nbatch,
                                state, n_update, log)
                        batch, placed = staged
                        # step phase: annotated with the journal's step
                        # seq (n_update pre-increment == the record's
                        # `step`), so traces and the telemetry report
                        # cross-reference. Live (not retroactive) so
                        # any RPC spans dispatched inside join it, and
                        # so a device trace carries it on its own clock
                        with _trace.phase("train.step", loop="trainstep",
                                          step=n_update, epoch=epoch):
                            cur_lr = (lr_scheduler(n_update)
                                      if lr_scheduler
                                      else lr) * guard.lr_mult
                            step_rng = jax.random.fold_in(rng, n_update)
                            flag = None
                            t_disp = _telemetry.now_ms() \
                                if jr is not None else 0.0
                            with _profiler.step_scope(n_update), \
                                    _trace.phase("step.dispatch"):
                                lr_arr = jnp.asarray(cur_lr, jnp.float32)
                                if fuse:
                                    if mstats is None:
                                        mstats = self._zero_metric_stats(
                                            raw_step, metric, state,
                                            placed, cur_lr, step_rng,
                                            guarded=spec is not None)
                                    params, opt_state, aux = state
                                    if spec is not None:
                                        (params, opt_state, aux), outs, \
                                            mstats, flag = fused_step(
                                                params, opt_state, aux,
                                                placed, lr_arr, step_rng,
                                                mstats,
                                                jnp.asarray(inject,
                                                            jnp.float32))
                                    else:
                                        (params, opt_state, aux), outs, \
                                            mstats = fused_step(
                                                params, opt_state, aux,
                                                placed, lr_arr, step_rng,
                                                mstats)
                                    state = (params, opt_state, aux)
                                    # the metric VIEWS the live epoch
                                    # totals, so get() works mid-epoch
                                    # (Speedometer) at the cost of that
                                    # caller's one sync
                                    metric.set_device_stats(mstats)
                                elif spec is not None:
                                    params, opt_state, aux = state
                                    (params, opt_state, aux), outs, \
                                        flag = guarded_step(
                                            params, opt_state, aux,
                                            placed, lr_arr, step_rng,
                                            jnp.asarray(inject,
                                                        jnp.float32))
                                    state = (params, opt_state, aux)
                                else:
                                    state, outs = self(state, placed,
                                                       cur_lr, step_rng)
                            n_update += 1
                            if jr is not None and not compile_logged:
                                # the first dispatch blocks through XLA
                                # trace+compile; later dispatches return
                                # async — its wall IS the compile cost
                                compile_logged = True
                                _telemetry.journal_event(
                                    "compile", site="TrainStep.fit",
                                    wall_ms=round(
                                        _telemetry.now_ms() - t_disp, 3))
                            # stage batch t+1: its H2D overlaps the step
                            # just dispatched (async)
                            t_data = _telemetry.now_ms() \
                                if jr is not None else 0.0
                            with _trace.phase("step.data_wait"):
                                nxt = next(batches, None)
                                staged = None if nxt is None \
                                    else self._stage(nxt)
                            data_ms = _telemetry.now_ms() - t_data \
                                if jr is not None else 0.0
                            if not fuse:
                                # fuse=False is the host metric path
                                # (device accumulation on this loop is
                                # always fused)
                                metric.update(batch.label,
                                              [_nd_wrap(o) for o in outs])
                            # bounded dispatch: block on the step K back
                            # so async dispatch can't run arbitrarily
                            # ahead of the device; the guarded item is
                            # the step's finite flag
                            inflight.append(flag if flag is not None
                                            else outs[0])
                            t_win = _telemetry.now_ms() \
                                if jr is not None else 0.0
                            with _trace.phase("step.window_wait"):
                                while len(inflight) > ahead:
                                    drain_one()
                            if jr is not None:
                                # boundary-to-boundary iteration wall:
                                # the sum over an epoch is the epoch's
                                # wall, so the report's samples/sec
                                # matches a Speedometer-style measurement
                                now_ = _telemetry.now_ms()
                                step_hist.observe(now_ - t_iter)
                                _telemetry.journal_step(
                                    loop="trainstep", step=n_update - 1,
                                    epoch=epoch,
                                    wall_ms=round(now_ - t_iter, 3),
                                    data_wait_ms=round(data_ms, 3),
                                    window_wait_ms=round(now_ - t_win,
                                                         3),
                                    samples=int(placed[
                                        self.data_names[0]].shape[0])
                                    if self.data_names else 0)
                                t_iter = now_
                        if batch_end_callback:
                            batch_end_callback(_SimpleBatchEnd(
                                epoch, nbatch, metric))
                        nbatch += 1
                    if spec is not None:
                        # drain the window so a bad tail is seen BEFORE
                        # this epoch's checkpoint is published
                        with _trace.phase("train.epoch_drain"):
                            while inflight:
                                drain_one()
                except _guardrail.RollbackNeeded:
                    state, epoch, n_update, skip_batches = \
                        self._rollback(checkpoint_prefix, guard, log)
                    state = self._ensure_scaler_state(state, spec)
                    inflight.clear()
                    continue
                # everything between an epoch's last step and the
                # next epoch: the device drains under the metric read,
                # so its idle time here has this name in a trace
                with _trace.phase("train.epoch_end", epoch=epoch):
                    name, val = metric.get()  # the single blocking read
                    last_val = val
                    log.info("Epoch[%d] Train-%s=%f", epoch, name, val)
                    if jr is not None:
                        # fingerprint-friendly jit-cache gauge:
                        # donated-buffer sharding drift shows up as a
                        # second cached executable (the step-2-recompile
                        # class of regression tools/perf_gate.py gates
                        # on)
                        step_fn = fused_step if fuse else (
                            guarded_step if spec is not None
                            else self._jit_step)
                        cache_size = getattr(step_fn, "_cache_size",
                                             None)
                        if cache_size is not None:
                            _telemetry.gauge(
                                "trainstep.jit_cache_size").set(
                                    cache_size())
                        _telemetry.journal_event("epoch.end",
                                                 loop="trainstep",
                                                 epoch=epoch,
                                                 steps=nbatch)
                    # HBM watermark: boundary-only sample, never per
                    # step
                    _profiler.sample_device_memory("epoch.end")
                    if checkpoint_prefix and \
                            (epoch + 1) % checkpoint_period == 0:
                        self._save_fit_checkpoint(checkpoint_prefix,
                                                  epoch, state, n_update)
                    if epoch_end_callback:
                        epoch_end_callback(epoch, state)
                epoch += 1
        self.guard_report = guard.report()
        return state, last_val

    # -- fit plumbing (checkpoint scan / publish / rollback / preempt) -----
    def _ensure_scaler_state(self, state, spec):
        """Seed the loss scaler's device state into aux when enabled
        and absent (fresh runs and checkpoints from unscaled runs)."""
        if spec is None or spec.scaler is None:
            return state
        params, opt_state, aux = state
        if _guardrail.SCALE_KEY in aux:
            return state
        aux = dict(aux)
        for k, v in spec.scaler.init_aux().items():
            aux[k] = self._place_rep(v)
        _telemetry.gauge("guardrail.loss_scale").set(
            spec.scaler.init_scale)
        return params, opt_state, aux

    def _scan_checkpoints(self, checkpoint_prefix, log):
        """Newest readable ``prefix_NNNN.npz`` → (state, begin_epoch,
        n_update, skip_batches), or None. A preemption boundary
        checkpoint (meta carries epoch/nbatch) resumes INSIDE the epoch
        it interrupted, at the exact step."""
        import glob as _glob
        import json as _json
        import re as _re
        import zipfile as _zipfile

        from ..module.base_module import _newest_readable

        found = sorted(
            p for p in _glob.glob(checkpoint_prefix + "_*.npz")
            if _re.search(r"_\d{4}\.npz$", p))
        # model/optimizer MISMATCH (ValueError) is NOT in the torn
        # set: it must fail loudly, not fall back silently
        path, loaded = _newest_readable(
            found, lambda p: self.load_state(p[:-len(".npz")]),
            (OSError, EOFError, _zipfile.BadZipFile), log)
        if path is None:
            return None
        latest = path[:-len(".npz")]
        begin_epoch = int(latest.rsplit("_", 1)[1]) + 1
        n_update = 0
        skip_batches = 0
        try:
            with open(latest + ".meta.json") as f:
                meta = _json.load(f)
            n_update = int(meta["n_update"])
            if "nbatch" in meta:
                begin_epoch = int(meta["epoch"])
                skip_batches = int(meta["nbatch"])
        except (OSError, ValueError, KeyError):
            log.warning(
                "%s.meta.json missing/unreadable; lr schedule "
                "and rng folds restart from update 0", latest)
        log.info("resumed %s (continuing at epoch %d, update %d%s)",
                 latest, begin_epoch, n_update,
                 ", batch %d" % skip_batches if skip_batches else "")
        return loaded, begin_epoch, n_update, skip_batches

    def _save_fit_checkpoint(self, prefix, epoch, state, n_update,
                             extra_meta=None):
        import json as _json
        ck = "%s_%04d" % (prefix, epoch)
        self.save_state(ck, state)
        meta = {"n_update": n_update}
        if extra_meta:
            meta.update(extra_meta)
        tmp = ck + ".meta.json.tmp"
        with open(tmp, "w") as f:
            _json.dump(meta, f)
        _guardrail.durable_replace(tmp, ck + ".meta.json")
        return ck

    def _rollback(self, checkpoint_prefix, guard, log):
        """Escalation: restore the newest readable checkpoint after
        MXNET_MAX_BAD_STEPS consecutive masked steps. Raises
        NumericalDivergence when no checkpoint exists or the rollback
        budget is spent."""
        if not checkpoint_prefix:
            guard.policy.no_checkpoint("no checkpoint_prefix "
                                       "configured")
        guard.policy.begin_rollback()
        found = self._scan_checkpoints(checkpoint_prefix, log)
        if found is None:
            guard.policy.no_checkpoint(
                "no readable checkpoint under %r" % checkpoint_prefix)
        state, begin_epoch, n_update, skip = found
        log.warning(
            "guardrail: rolled back to the newest finite checkpoint "
            "(epoch %d, update %d); lr multiplier now %g "
            "(rollback %d/%d)", begin_epoch, n_update,
            guard.policy.lr_mult, guard.policy.rollbacks_done,
            guard.policy.max_rollbacks)
        return state, begin_epoch, n_update, skip

    def _preempt_exit(self, prefix, epoch, nbatch, state, n_update,
                      log):
        """Graceful-shutdown endgame: publish the boundary checkpoint
        (meta records the exact step) and exit EXIT_PREEMPTED so a
        relauncher rerunning the same command resumes seamlessly."""
        if prefix:
            ck = self._save_fit_checkpoint(
                prefix, epoch, state, n_update,
                {"epoch": epoch, "nbatch": nbatch})
            _telemetry.counter("guardrail.preempt_checkpoints").inc()
            _telemetry.journal_event("guardrail.preempt_checkpoint",
                                     loop="trainstep", epoch=epoch,
                                     nbatch=nbatch)
            log.warning(
                "preemption: boundary checkpoint %s written at epoch "
                "%d batch %d (update %d); exiting with code %d",
                ck, epoch, nbatch, n_update, _guardrail.EXIT_PREEMPTED)
        raise SystemExit(_guardrail.EXIT_PREEMPTED)

    def save_state(self, prefix, state):
        """Checkpoint (params, opt_state, aux) to ``prefix.npz`` —
        the SPMD analogue of Module.save_checkpoint (reference
        model.py:save_checkpoint). Sharded arrays (TP/ZeRO-1) are
        gathered to host; load_state re-places per the step's own
        sharding rules, so checkpoints restore onto a different mesh
        (or none) than they were written from."""
        # one device_get on the whole pytree: batched D2H instead of a
        # blocking round trip per tensor
        params, opt_state, aux = jax.device_get(state)
        if _guardrail.SCALE_KEY in aux:
            # the checkpoint read already materialized the scale on
            # host — the one place the gauge can update without adding
            # a blocking sync of its own
            _telemetry.gauge("guardrail.loss_scale").set(
                float(np.asarray(aux[_guardrail.SCALE_KEY])))
        blob = {}
        for n, v in params.items():
            blob["p:%s" % n] = np.asarray(v)
        for n, states in opt_state.items():
            for i, s in enumerate(states):
                blob["o%d:%s" % (i, n)] = np.asarray(s)
        for n, v in aux.items():
            blob["a:%s" % n] = np.asarray(v)
        # durable atomic publish: the crash-resume story (and now the
        # guardrail's auto-rollback) depends on the newest checkpoint
        # never being torn OR lost — write aside, fsync, rename, fsync
        # the directory (a bare rename is not crash-durable)
        tmp = prefix + ".npz.tmp"
        with open(tmp, "wb") as f:
            np.savez(f, **blob)
        _guardrail.durable_replace(tmp, prefix + ".npz")
        return prefix + ".npz"

    def load_state(self, prefix):
        """Restore a save_state checkpoint, placed for THIS step's mesh
        and optimizer sharding. Mismatched checkpoints (different
        model's params/aux, different optimizer's state-slot count)
        fail loudly at load time."""
        path = prefix + ".npz"
        params, opt_state, aux = {}, {}, {}
        slots = {}
        with np.load(path, allow_pickle=False) as blob:
            for key in blob.files:
                kind, name = key.split(":", 1)
                if kind == "p":
                    params[name] = self._place_param(
                        name, jnp.asarray(blob[key]))
                elif kind == "a":
                    aux[name] = self._place_rep(jnp.asarray(blob[key]))
                else:
                    slots.setdefault(name, {})[int(kind[1:])] = \
                        jnp.asarray(blob[key])

        def _mismatch(what, names):
            raise ValueError("checkpoint %s %s %r — saved from a "
                             "different model/optimizer"
                             % (path, what, sorted(names)))

        if set(params) != set(self.param_names):
            missing = set(self.param_names) - set(params)
            _mismatch("is missing params" if missing else
                      "has unknown params",
                      missing or set(params) - set(self.param_names))
        # guardrail state (loss scale etc.) rides aux under reserved
        # __gr_* keys; it is optional — not part of the model contract
        aux_model = {n for n in aux
                     if not n.startswith(_guardrail.GR_PREFIX)}
        if aux_model != set(self.aux_names):
            missing = set(self.aux_names) - aux_model
            _mismatch("is missing aux states" if missing else
                      "has unknown aux states",
                      missing or aux_model - set(self.aux_names))
        for n in self.param_names:
            saved = slots.get(n, {})
            if sorted(saved) != list(range(self._n_state)):
                raise ValueError(
                    "checkpoint %s has optimizer slots %r for %r; this "
                    "step's %r optimizer needs exactly %d — resuming "
                    "across optimizers would silently corrupt the "
                    "trajectory" % (path, sorted(saved), n,
                                    self.opt_name, self._n_state))
            opt_state[n] = tuple(
                self._place_opt(n, saved[i])
                for i in range(self._n_state))
        if self._spec_layout is not None:
            # a resumed run reports the same gauges/journal event an
            # init_state-started run does
            self._report_layout(params, opt_state)
        return params, opt_state, aux

    def _place_param(self, name, value):
        if self._layout is None:
            return value
        return shd.place(
            value, self._layout.param_nsharding(name, value.shape))

    def _place_opt(self, name, value):
        """Optimizer state: 'zero1' folds it 1/N across the layout's
        replica axes (data × fsdp); otherwise it follows the param."""
        if self._layout is None:
            return value
        return shd.place(value, self._layout.opt_nsharding(
            name, value.shape, zero=self.optimizer_sharding == "zero1"))

    def _place_rep(self, value):
        if self._layout is None:
            return value
        return shd.place(value, self._layout.replicated_nsharding())

    def place_batch(self, batch):
        """Move batch arrays to device once (sharded along the layout's
        data axes when a mesh/layout is set; meshes with no replica
        axis — sp/pipe/expert — replicate, and the mesh-aware ops shard
        what they need) — call before the step loop so the H2D transfer
        isn't repaid every iteration."""
        if self._layout is None:
            return {k: shd.place(jnp.asarray(v))
                    for k, v in batch.items()}
        return {k: shd.place(
            v, self._layout.batch_nsharding(np.ndim(v)))
            for k, v in batch.items()}

    # -- the step ----------------------------------------------------------
    def _build_step(self, guard=None):
        """The step function. ``guard`` (a ``guardrail.GuardSpec``)
        fuses the non-finite guardrail into the compiled program: an
        all-finite flag over loss outputs and gradients is computed on
        device and returned as a THIRD result, the whole update
        (params, optimizer state, BN statistics) is masked out with
        ``jnp.where`` when the flag is false, and — when the spec
        carries a loss scaler — the head cotangent is scaled and the
        gradients exactly unscaled around the overflow check. Guarded
        steps take a 7th ``inject`` scalar (1.0, or NaN to poison the
        gradients — the deterministic ``nan@N`` fault-injection path)."""
        eval_fn = self._eval_fn
        param_names = self.param_names
        opt_attrs = dict(self.opt_params)
        opt_fn = get_op(self._opt_op).fn
        n_state = self._n_state
        layout = self._layout
        pin_state = self._spec_layout is not None
        data_names = self.data_names
        cdt = self.compute_dtype
        remat = self.remat
        zero1 = self.optimizer_sharding == "zero1"
        id_inputs = self._id_inputs
        clip_norm = self.clip_norm
        scaler = guard.scaler if guard is not None else None

        def step(params, opt_state, aux, batch, lr, rng, inject=None):
            # guardrail state (loss scale, good-step count) rides the
            # aux pytree under reserved __gr_* keys: device-resident,
            # checkpointed with the rest of aux, but stripped before
            # the graph ever sees aux and merged back after
            gr_state = {k: v for k, v in aux.items()
                        if k.startswith(_guardrail.GR_PREFIX)}
            if gr_state:
                aux = {k: v for k, v in aux.items()
                       if not k.startswith(_guardrail.GR_PREFIX)}
            # Module.init_optimizer defaults rescale_grad=1/batch; match
            # that here so the SPMD path's effective lr does not scale with
            # global batch unless the caller overrides (ADVICE r1). Local
            # copy: batch size is a static trace-time value, and mutating
            # the closed-over dict would leak across retraces.
            attrs = dict(opt_attrs)
            if "rescale_grad" not in attrs and data_names:
                attrs["rescale_grad"] = 1.0 / batch[
                    data_names[0]].shape[0]
            if layout is not None and layout.batch_axes:
                # pin batch layout so sharding does not rest only on input
                # propagation; params keep their init_state placement
                # (meshes without a replica axis replicate the batch)
                batch = {k: shd.constrain(
                    v, layout.batch_nsharding(jnp.ndim(v)))
                    for k, v in batch.items()}

            # device scopes (`docs/observability.md`): `train.fwd` is
            # the outermost scope of the differentiated function, so it
            # takes the transform's wrapper (`jvp(train.fwd)` forward,
            # `transpose(jvp(train.fwd))` backward) and every part
            # below it, `train.cast` and the graph's `<node>/op.<Kind>`,
            # stays a whole part in both directions
            @jax.named_scope("train.fwd")
            def fwd(p):
                feed = dict(batch)
                if cdt is not None:
                    # compute-dtype cast: params + real-valued data only.
                    # Labels and Embedding-fed inputs carry ids — bf16
                    # would alias ids >= 256 (8-bit significand). The
                    # cast is linear so vjp returns float32 grads.
                    with jax.named_scope("train.cast"):
                        p = {k: v.astype(cdt) for k, v in p.items()}
                        for k in data_names:
                            if k not in id_inputs:
                                feed[k] = feed[k].astype(cdt)
                outs, new_aux = eval_fn({**feed, **p}, aux, rng, True)
                if cdt is not None:
                    # BN moving stats stay float32 master copies
                    with jax.named_scope("train.cast"):
                        new_aux = {k: v.astype(aux[k].dtype)
                                   for k, v in new_aux.items()}
                return outs, new_aux

            fwd_fn = jax.checkpoint(fwd) if remat else fwd
            outs, vjp, new_aux = jax.vjp(fwd_fn, params, has_aux=True)
            # ones is the reference's head-grad convention
            # (Executor.backward); heads propagate the cotangent as a
            # scale, so the loss scaler rides it: the whole backprop
            # chain carries the (power-of-two) scale and the gradients
            # unscale exactly afterwards
            scale = gr_state[_guardrail.SCALE_KEY] \
                if scaler is not None else None
            cot = tuple(jnp.full_like(o, scale) if scale is not None
                        else jnp.ones_like(o) for o in outs)
            grads = vjp(cot)[0]

            finite = None
            if guard is not None:
                if inject is not None:
                    # deterministic nan@N injection: the poison rides
                    # the real detection/masking path below
                    grads = {n: g_ * inject for n, g_ in grads.items()}
                # the overflow check runs on the SCALED gradients (the
                # signal dynamic scaling reacts to) plus the loss
                # outputs; fused into the step, it piggybacks on work
                # XLA already scheduled — no extra host sync ever
                with jax.named_scope("train.guard"):
                    finite = _guardrail.all_finite(
                        list(grads.values()) + list(outs))
                    if scale is not None:
                        inv = 1.0 / scale
                        grads = {n: (g_ * inv).astype(g_.dtype)
                                 for n, g_ in grads.items()}

            if clip_norm is not None:
                # bound the EFFECTIVE gradient's global norm (after the
                # optimizer's rescale_grad, i.e. the per-example mean) —
                # "clip at 1.0" then means what LM recipes mean by it
                rescale = float(attrs.get("rescale_grad", 1.0))
                with jax.named_scope("train.clip"):
                    gnorm = rescale * jnp.sqrt(sum(
                        jnp.sum(jnp.square(g.astype(jnp.float32)))
                        for g in grads.values()))
                    gscale = jnp.minimum(1.0, clip_norm /
                                         jnp.maximum(gnorm, 1e-12))
                    grads = {n: (g * gscale).astype(g.dtype)
                             for n, g in grads.items()}

            with jax.named_scope("train.update"):
                new_params, new_opt = {}, {}
                for n in param_names:
                    p, g = params[n], grads[n]
                    if zero1:
                        # reduce-scatter the grad onto the owned 1/N slice,
                        # run the fused update there, all-gather the result
                        # back to the parameter's own layout. XLA turns the
                        # psum+constraint pair into a reduce_scatter and the
                        # final constraint into an all_gather over the
                        # replica axes (data × fsdp under a SpecLayout).
                        zs = layout.opt_nsharding(n, p.shape, zero=True)
                        p = shd.constrain(p, zs)
                        g = shd.constrain(g, zs)
                    res = opt_fn(p, g, *opt_state[n], lr=lr, **attrs)
                    new_params[n] = res[0] if n_state else res
                    new_opt[n] = tuple(res[1:]) if n_state else ()
                if guard is not None:
                    # mask the whole update out on device: a non-finite
                    # step leaves params, optimizer state AND BN statistics
                    # exactly as they were — the weights never ingest a NaN
                    new_params = {n: jnp.where(finite, new_params[n],
                                               params[n])
                                  for n in param_names}
                    new_opt = {n: tuple(
                        jnp.where(finite, s_new, s_old)
                        for s_new, s_old in zip(new_opt[n], opt_state[n]))
                        for n in param_names}
                    new_aux = {k: jnp.where(finite, v, aux[k])
                               for k, v in new_aux.items()}
                    if scaler is not None:
                        new_scale, new_good = scaler.next_state(
                            gr_state[_guardrail.SCALE_KEY],
                            gr_state[_guardrail.GOOD_KEY], finite)
                        gr_state = {_guardrail.SCALE_KEY: new_scale,
                                    _guardrail.GOOD_KEY: new_good}
                if zero1 or pin_state:
                    # pin the OUTGOING layouts explicitly, and pin them
                    # LAST — after the guardrail masking, so the pinned
                    # value IS the jit output (a constraint upstream of the
                    # jnp.where mask pins only the where's operand; the
                    # partitioner then re-chooses the output layout and the
                    # donated buffers miss the jit cache on the next step —
                    # the step-2-recompile class tools/perf_gate.py gates
                    # via the trainstep.jit_cache_size gauge). Fresh params
                    # all-gather back to the parameter layout; persistent
                    # optimizer state STAYS in its 1/N zero1 slice (a
                    # propagated replicated choice would also break the
                    # sharded-optimizer memory claim).
                    new_params = {n: shd.constrain(
                        v, layout.param_nsharding(n, v.shape))
                        for n, v in new_params.items()}
                    new_opt = {n: tuple(
                        shd.constrain(s_, layout.opt_nsharding(
                            n, s_.shape, zero=zero1))
                        for s_ in ss) for n, ss in new_opt.items()}
                if pin_state:
                    # aux (BN moving stats) must come back REPLICATED like
                    # init_state placed it — left to propagation, the
                    # boundary constraints shard it over fsdp and the
                    # drifted layout misses the jit cache (a full step-2
                    # recompile, measured ~2 s on the CPU mesh)
                    new_aux = {k: shd.constrain(
                        v, layout.replicated_nsharding())
                        for k, v in new_aux.items()}
            new_aux = {**new_aux, **gr_state}
            if guard is not None:
                return (new_params, new_opt, new_aux), outs, finite
            return (new_params, new_opt, new_aux), outs

        return step

    def __call__(self, state, batch, lr, rng):
        params, opt_state, aux = state
        return self._jit_step(params, opt_state, aux, batch,
                              jnp.asarray(lr, jnp.float32), rng)

    def lower(self, state, batch, lr, rng):
        """Lower (for AOT compile checks) without executing."""
        params, opt_state, aux = state
        return self._jit_step.lower(params, opt_state, aux, batch,
                                    jnp.asarray(lr, jnp.float32), rng)

    # -- AOT training export -------------------------------------------------
    def export(self, prefix, state, batch):
        """Serialize the WHOLE training step (forward + backward +
        optimizer update) as a portable StableHLO artifact, plus the
        current state and a flat-calling-convention manifest:

            prefix.train.stablehlo   the exported step program
            prefix.train.meta.json   flat layout: state/batch/output
                                     names, shapes, dtypes
            prefix.state.npz         initial state values (flat order)

        Reload with :class:`CompiledTrainStep` (no symbol/source
        needed) or drive from C via the MXTpuTrain* ABI
        (_native/predict_shim.cc) — the TPU-native answer to the
        reference's 146-entry C training API (include/mxnet/c_api.h):
        where the reference exposed per-op graph construction to
        foreign hosts, here the natural C boundary is the COMPILED
        program; see docs/c_abi.md for the decision memo.

        The exported program is a pure function
            (seed, lr, *state_flat, *batch_flat) -> (*state_flat', *outs)
        so a host loops: feed batch, call, carry the returned state.
        Flat order: params (sorted), optimizer slots (per param,
        sorted), aux (sorted) — recorded in the manifest."""
        from jax import export as jexport

        params, opt_state, aux = state
        pn = sorted(params)
        an = sorted(aux)
        n_slots = self._n_state
        batch_names = list(self.data_names) + [
            k for k in sorted(batch) if k not in self.data_names]

        def pack(params, opt_state, aux):
            flat = [params[n] for n in pn]
            for n in pn:
                flat.extend(opt_state[n])
            flat.extend(aux[n] for n in an)
            return flat

        def unpack(flat):
            i = len(pn)
            params = dict(zip(pn, flat[:i]))
            opt_state = {}
            for n in pn:
                opt_state[n] = tuple(flat[i:i + n_slots])
                i += n_slots
            aux = dict(zip(an, flat[i:i + len(an)]))
            return params, opt_state, aux

        raw_step = self._build_step()

        def flat_step(seed, lr, *arrs):
            n_state_leaves = len(pn) * (1 + n_slots) + len(an)
            p, o, a = unpack(list(arrs[:n_state_leaves]))
            b = dict(zip(batch_names, arrs[n_state_leaves:]))
            rng = jax.random.PRNGKey(seed)
            (np_, no_, na_), outs = raw_step(p, o, a, b, lr, rng)
            return tuple(pack(np_, no_, na_)) + tuple(outs)

        state_flat = [np.asarray(x) for x in
                      jax.device_get(pack(params, opt_state, aux))]
        batch_vals = [np.asarray(jax.device_get(batch[n]))
                      for n in batch_names]
        structs = [jax.ShapeDtypeStruct((), np.uint32),
                   jax.ShapeDtypeStruct((), np.float32)]
        structs += [jax.ShapeDtypeStruct(a.shape, a.dtype)
                    for a in state_flat]
        structs += [jax.ShapeDtypeStruct(a.shape, a.dtype)
                    for a in batch_vals]
        blob = jexport.export(jax.jit(flat_step))(*structs).serialize()
        with open(prefix + ".train.stablehlo", "wb") as f:
            f.write(blob)

        import json as _json
        n_outputs = len(self.symbol.list_outputs())
        meta = {
            "param_names": pn,
            "n_opt_slots": n_slots,
            "aux_names": an,
            "batch_names": batch_names,
            "batch_shapes": {n: list(np.shape(v)) for n, v in
                             zip(batch_names, batch_vals)},
            "batch_dtypes": {n: str(v.dtype) for n, v in
                             zip(batch_names, batch_vals)},
            "n_state_leaves": len(state_flat),
            "n_outputs": n_outputs,
            "output_names": self.symbol.list_outputs(),
        }
        with open(prefix + ".train.meta.json", "w") as f:
            _json.dump(meta, f)
        np.savez(prefix + ".state.npz", step_count=np.int64(0),
                 **{"s%05d" % i: a for i, a in enumerate(state_flat)})
        return prefix + ".train.stablehlo"


class CompiledTrainStep:
    """Runs an exported training-step artifact — training with no
    framework source, symbol JSON, or optimizer code at run time (all
    of it is baked into the StableHLO program). The C ABI's MXTpuTrain*
    entries drive exactly this class through the embedded interpreter.

    State lives host-side as the flat array list and is carried
    between calls; step() feeds a batch, runs one compiled update, and
    swaps in the new state."""

    def __init__(self, exported, meta, state_flat, step_count=0):
        self._exported = exported
        self._meta = meta
        self._state = list(state_flat)
        self._step_count = int(step_count)

    @classmethod
    def load(cls, prefix):
        import json as _json
        from jax import export as jexport
        with open(prefix + ".train.stablehlo", "rb") as f:
            exported = jexport.deserialize(f.read())
        with open(prefix + ".train.meta.json") as f:
            meta = _json.load(f)
        with np.load(prefix + ".state.npz") as blob:
            state = [blob["s%05d" % i]
                     for i in range(meta["n_state_leaves"])]
            # step_count persists so a resumed run CONTINUES the
            # default-seed sequence instead of replaying masks from 0
            count = int(blob["step_count"]) \
                if "step_count" in blob.files else 0
        return cls(exported, meta, state, step_count=count)

    @property
    def batch_names(self):
        return list(self._meta["batch_names"])

    @property
    def batch_shapes(self):
        return {n: tuple(s) for n, s in
                self._meta["batch_shapes"].items()}

    def step(self, batch, lr, seed=None):
        """One compiled train step. batch: dict name -> array matching
        the exported shapes. Returns the step's outputs (loss heads).
        seed defaults to the running step count (fresh dropout noise
        per step, reproducible across runs)."""
        missing = [n for n in self._meta["batch_names"]
                   if n not in batch]
        if missing:
            raise ValueError("batch missing inputs: %s" % missing)
        feed = []
        for n in self._meta["batch_names"]:
            a = np.asarray(batch[n],
                           dtype=self._meta["batch_dtypes"][n])
            want = tuple(self._meta["batch_shapes"][n])
            if a.shape != want:
                raise ValueError("input %r: shape %s, exported %s"
                                 % (n, a.shape, want))
            feed.append(a)
        if seed is None:
            seed = self._step_count
        res = self._exported.call(
            np.uint32(seed), np.float32(lr), *self._state, *feed)
        n = self._meta["n_state_leaves"]
        self._state = [np.asarray(x) for x in res[:n]]
        self._step_count += 1
        return [np.asarray(x) for x in res[n:]]

    def get_params(self):
        """Current parameter dict (e.g. to hand to a Predictor export
        after compiled fine-tuning)."""
        pn = self._meta["param_names"]
        return dict(zip(pn, self._state[:len(pn)]))

    def get_param_shape(self, name):
        """Shape of a parameter without materializing a copy."""
        pn = self._meta["param_names"]
        if name not in pn:
            raise KeyError("unknown param %r; params: %s"
                           % (name, sorted(pn)))
        return tuple(self._state[pn.index(name)].shape)

    def save_state(self, prefix):
        np.savez(prefix + ".state.npz",
                 step_count=np.int64(self._step_count),
                 **{"s%05d" % i: np.asarray(a)
                    for i, a in enumerate(self._state)})
        return prefix + ".state.npz"


def make_train_step(symbol, **kwargs):
    """Factory: TrainStep (see class docs)."""
    return TrainStep(symbol, **kwargs)
