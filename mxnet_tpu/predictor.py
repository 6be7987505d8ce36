"""Deploy-time inference: the predict-only API + AOT export.

Reference: src/c_api/c_predict_api.cc:363 (MXPredCreate/SetInput/
Forward/GetOutput — load a symbol JSON + param blob, run forward-only)
and the amalgamation build that ships it without the full framework.

TPU-native upgrade: besides the in-process ``Predictor`` (params baked
into one jitted forward), ``Predictor.export`` serializes the compiled
computation as a portable StableHLO artifact via ``jax.export`` — the
result reloads and runs with ``CompiledPredictor`` WITHOUT the symbol
source, the op registry, or the parameter files (the analogue of the
reference's amalgamated predict-only deployment).
"""
from __future__ import annotations

import json

import jax
import jax.numpy as jnp
import numpy as np

from .executor import _graph_eval_fn
from .ndarray import NDArray, _wrap

__all__ = ["Predictor", "CompiledPredictor", "load_checkpoint_predictor"]


def _as_jnp(x):
    if isinstance(x, NDArray):
        return x._data
    return jnp.asarray(x)


class Predictor:
    """Forward-only executor with parameters baked in as constants
    (reference MXAPIPredictor). Inputs are positional by ``data_names``
    or keyword; outputs are NDArrays.

    Loss-head label variables that feed the loss DIRECTLY are
    auto-zero-filled via shape inference; labels that pass through
    reshaping ops first are not inferable from data alone — declare
    them in ``data_names`` and feed dummy arrays (loss heads ignore
    labels outside training)."""

    def __init__(self, symbol, arg_params, aux_params=None,
                 data_names=("data",)):
        self._symbol = symbol
        self._data_names = list(data_names)
        self._output_names = symbol.list_outputs()
        params = {k: _as_jnp(v) for k, v in arg_params.items()}
        auxs = {k: _as_jnp(v) for k, v in (aux_params or {}).items()}
        missing = [n for n in symbol.list_arguments()
                   if n not in params and n not in self._data_names]
        not_labels = [n for n in missing if "label" not in n]
        if not_labels:
            raise ValueError("predictor missing parameters %r"
                             % not_labels)
        eval_fn = _graph_eval_fn(symbol)
        names = self._data_names

        def fwd(*data):
            arg_vals = dict(params)
            arg_vals.update(zip(names, data))
            if missing:
                # loss-layer labels are dead at inference; zero-fill with
                # inferred shapes (reference: MXPredCreate binds provided
                # args only — loss heads ignore labels when not training)
                shapes, _o, _a = symbol.infer_shape_partial(
                    **{n: arg_vals[n].shape for n in names})
                for n, s in zip(symbol.list_arguments(), shapes):
                    if n in missing and s is not None:
                        arg_vals[n] = jnp.zeros(s, data[0].dtype)
            outs, _aux = eval_fn(arg_vals, dict(auxs),
                                 jax.random.PRNGKey(0), False)
            return outs

        self._fwd = jax.jit(fwd)
        self._outputs = None

    def forward(self, *args, **kwargs):
        """Run inference; accepts arrays positionally (data_names order)
        or by name (reference MXPredSetInput + MXPredForward)."""
        if kwargs:
            args = [kwargs[n] for n in self._data_names]
        # returned from the local: engines on other threads may share
        # this predictor, and get_output()'s slot is last-writer-wins
        outs = self._outputs = self._fwd(*[_as_jnp(a) for a in args])
        return [_wrap(o) for o in outs]

    def get_output(self, index):
        assert self._outputs is not None, "run forward() first"
        return _wrap(self._outputs[index])

    @property
    def output_names(self):
        return list(self._output_names)

    # -- AOT export ----------------------------------------------------------
    def export(self, prefix, data_shapes, dtype="float32"):
        """Serialize the compiled forward (params embedded) to
        ``prefix.stablehlo`` + ``prefix.meta.json``; reload with
        :meth:`CompiledPredictor.load` — no symbol/source needed."""
        from jax import export as jexport
        shapes = dict(data_shapes) if not isinstance(data_shapes, dict) \
            else data_shapes
        structs = [jax.ShapeDtypeStruct(tuple(shapes[n]), np.dtype(dtype))
                   for n in self._data_names]
        blob = jexport.export(self._fwd)(*structs).serialize()
        with open(prefix + ".stablehlo", "wb") as f:
            f.write(blob)
        with open(prefix + ".meta.json", "w") as f:
            json.dump({"data_names": self._data_names,
                       "output_names": self._output_names,
                       "data_shapes": {n: list(shapes[n])
                                       for n in self._data_names},
                       "dtype": dtype}, f)
        return prefix + ".stablehlo"

    def export_buckets(self, prefix, feature_shapes, buckets=None,
                       dtype="float32", model_id=None):
        """Serve-ready AOT export: one StableHLO artifact per batch
        bucket (``prefix.b<K>.stablehlo``) plus a ``prefix.serve.json``
        manifest, so :meth:`~mxnet_tpu.serve.ServeEngine.from_export`
        can serve the model headlessly with every bucket specialization
        compiled ahead of time.

        feature_shapes: one per-input shape WITHOUT the batch axis, in
        ``data_names`` order. buckets: ascending batch sizes (default
        ``MXNET_SERVE_BUCKETS``). model_id: generation stamp written
        into the manifest — replicas serving the artifact report it in
        their ``hello`` frame, so a fleet controller can tell a
        half-promoted fleet from a uniform one. Default: a
        content-derived ``gen-<hash12>`` over the bucket artifacts, so
        re-exporting identical weights yields the same stamp. Returns
        the manifest path."""
        import hashlib

        from . import config as _config
        if buckets is None:
            from .serve.engine import _parse_buckets
            buckets = _parse_buckets(_config.get("MXNET_SERVE_BUCKETS"))
        buckets = sorted(int(b) for b in buckets)
        feats = [tuple(int(d) for d in s) for s in feature_shapes]
        if len(feats) != len(self._data_names):
            raise ValueError(
                "feature_shapes must have one entry per data input %r"
                % (self._data_names,))
        digest = hashlib.sha256()
        for b in buckets:
            path = self.export("%s.b%d" % (prefix, b),
                               {n: (b,) + s for n, s in
                                zip(self._data_names, feats)}, dtype=dtype)
            with open(path, "rb") as f:
                digest.update(f.read())
        if model_id is None:
            model_id = "gen-" + digest.hexdigest()[:12]
        manifest = prefix + ".serve.json"
        with open(manifest, "w") as f:
            json.dump({"buckets": buckets,
                       "data_names": self._data_names,
                       "feature_shapes": [list(s) for s in feats],
                       "dtype": dtype,
                       "model_id": str(model_id)}, f)
        return manifest


class CompiledPredictor:
    """Runs an exported StableHLO artifact — the headless deployment
    target (reference amalgamation/predict-only build)."""

    def __init__(self, exported, meta):
        self._exported = exported
        self._meta = meta
        self._data_names = meta["data_names"]
        self._outputs = None

    @classmethod
    def load(cls, prefix):
        from jax import export as jexport
        with open(prefix + ".stablehlo", "rb") as f:
            exported = jexport.deserialize(f.read())
        with open(prefix + ".meta.json") as f:
            meta = json.load(f)
        return cls(exported, meta)

    def forward(self, *args, **kwargs):
        if kwargs:
            args = [kwargs[n] for n in self._data_names]
        outs = self._outputs = self._exported.call(
            *[_as_jnp(a) for a in args])
        return [_wrap(o) for o in outs]

    def get_output(self, index):
        assert self._outputs is not None, "run forward() first"
        return _wrap(self._outputs[index])

    @property
    def output_names(self):
        return list(self._meta["output_names"])


def load_checkpoint_predictor(prefix, epoch, data_names=("data",)):
    """Build a Predictor straight from ``model.save_checkpoint`` files
    (reference MXPredCreate loading prefix-symbol.json + .params)."""
    from .model import load_checkpoint
    sym, arg_params, aux_params = load_checkpoint(prefix, epoch)
    return Predictor(sym, arg_params, aux_params, data_names=data_names)
