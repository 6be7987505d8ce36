"""Traffic kind `train`: one `TrainStep.fit` call over a seeded
synthetic set already on the device. The same call, with the same
compiled step and state, first makes the steps that the reference
follows (one step an epoch, so that each step's loss and the state
after it can be read at an epoch end), then warms up, then runs the
window in whole epochs: it opens and closes on epoch ends at which
every step has completed, so samples and seconds cover exactly the
same work.
"""
import gc
import importlib
import time

import jax
import jax.numpy as jnp
import numpy as np

from cellbench import window

_NOW = time.perf_counter


class _Stop(Exception):
    """Raised from the epoch-end callback to end `fit` at a boundary."""


class PlannedEpochs:
    """The window's own feed, an `io.NDArrayIter`, with the first
    epochs cut short: epoch k yields `plan[k]` batches, carrying on
    through the set where the last short epoch stopped; later epochs
    yield the whole set from its start."""

    def __init__(self, inner, plan):
        self._inner, self._plan = inner, list(plan)
        self._epoch, self._left = -1, None
        self.batch_size = inner.batch_size

    provide_data = property(lambda self: self._inner.provide_data)
    provide_label = property(lambda self: self._inner.provide_label)

    def reset(self):
        self._epoch += 1
        if self._epoch < len(self._plan):
            if self._epoch == 0:
                self._inner.reset()
            self._left = self._plan[self._epoch]
        else:
            self._inner.reset()
            self._left = None

    def __iter__(self):
        return self

    def __next__(self):
        if self._left is not None:
            if self._left == 0:
                raise StopIteration
            self._left -= 1
        return self._inner.next()

    next = __next__


def run(ctx):
    cfg, traffic = ctx.cfg, ctx.traffic
    family = cfg["family"]
    ref = importlib.import_module("cellbench.reference." + family)
    model = importlib.import_module("cellbench.models." + family)
    from mxnet_tpu import profiler

    seconds = float(ctx.seconds)
    lr = float(traffic["learning_rate"])
    wd = float(traffic["weight_decay"])
    n_check = int(traffic["check_steps"])
    warm = int(traffic["warm_epochs"])
    min_age = float(traffic["window_opens_after_s"])
    per_epoch = int(traffic["batches"])
    batch = int(traffic["batch_per_chip"]) * int(traffic.get("chips", 1))

    step = model.build_step(cfg, traffic)
    ctx.program_hook(step)               # tests break the timed path here
    data, label = ref.make_batches(cfg, traffic, ctx.seed)
    feed = PlannedEpochs(model.build_feed(data, label, traffic),
                         [1] * n_check)
    del data, label
    params0 = ref.make_params(cfg, ctx.seed)
    start = {k: jnp.copy(v) for k, v in params0.items()}  # fit donates
    metric = model.metric()
    jax.block_until_ready(start)
    ctx.log("phase", {"built_s": _NOW() - ctx.t0})

    @jax.jit
    def first_gradient(p0, p1):
        # w1 = w0 - lr * (g + wd * w0) with zero momentum behind it
        return ref.norms({k: (p0[k] - p1[k]) / lr - wd * p0[k]
                          for k in p0})

    @jax.jit
    def change(p0, p1):
        return ref.norms({k: p1[k] - p0[k] for k in p0})

    got = {"loss": []}
    marks = {"ends": [], "warm_ends": [], "trace": None}

    def epoch_end(epoch, state):
        params = state[0]
        if epoch < n_check:
            got["loss"].append(float(metric.get()[1]))
            if epoch == 0:
                got["grad_norm"] = {k: float(v) for k, v in
                                    first_gradient(start, params).items()}
            if epoch == n_check - 1:
                got["update_norm"] = {k: float(v) for k, v in
                                      change(start, params).items()}
                start.clear()
                ctx.log("phase", {"followed_s": _NOW() - ctx.t0})
            elif epoch == 0:
                ctx.log("phase", {"first_step_s": _NOW() - ctx.t0})
            return
        jax.block_until_ready(params)
        now = _NOW()
        if "open" not in marks:
            # warm epochs, until there have been `warm_epochs` of them
            # and the process is as old as the traffic file asks
            marks["warm_ends"].append(now)
            if epoch >= n_check + warm - 1 and now - ctx.t0 >= min_age:
                gc.collect()
                gc.freeze()
                gc.disable()
                marks["syncs"] = profiler.host_sync_count()
                marks["open"] = _NOW()
            return
        marks["ends"].append(now)
        since = now - marks["open"]
        tr = marks["trace"]
        if ctx.trace and tr is None and since >= 1.0:
            marks["trace"] = ctx.start_trace()
        elif tr is not None and "window_s" not in tr and \
                now - tr["t_start"] >= ctx.trace_seconds:
            ctx.stop_trace(tr)
            marks["trace_done"] = _NOW()
        if since >= seconds:
            marks["syncs"] = profiler.host_sync_count() - marks["syncs"]
            raise _Stop

    try:
        step.fit(feed, num_epoch=10 ** 9, arg_params=params0, lr=lr,
                 eval_metric=metric, epoch_end_callback=epoch_end)
    except _Stop:
        pass
    finally:
        gc.enable()
    if marks["trace"] and "window_s" not in marks["trace"]:
        ctx.stop_trace(marks["trace"])
    peak = ctx.memory_peak()
    del step, feed, params0
    gc.unfreeze()
    gc.collect()
    ctx.log("phase", {"program_freed_s": _NOW() - ctx.t0,
                      "bytes_in_use": [
                          (d.memory_stats() or {}).get("bytes_in_use")
                          for d in jax.local_devices()]})

    t_open, ends = marks["open"], marks["ends"]
    t_close = ends[-1]
    epochs = len(ends)
    steps = epochs * per_epoch
    rate = steps * batch / (t_close - t_open)
    durations = [b - a for a, b in zip([t_open] + ends, ends)]
    warm_ends = marks["warm_ends"]
    ctx.log("warm", {"epochs": len(warm_ends),
                     "longest_epochs_s_at_age_s": sorted(
                         ([b - a, b - ctx.t0] for a, b in
                          zip(warm_ends, warm_ends[1:])),
                         reverse=True)[:3]})
    ctx.log("window", {"open_s": t_open - ctx.t0,
                       "length_s": t_close - t_open, "epochs": epochs,
                       "steps": steps,
                       "epoch_median_s": window.median(durations),
                       "epochs_over_1.5x_median_s":
                           window.outliers(durations, 1.5),
                       "epochs_per_whole_second":
                           window.per_second(ends, t_open, t_close)})
    for secs in ctx.prefixes:
        cut = [e for e in ends if e - t_open >= secs]
        if cut and secs < seconds:
            k = ends.index(cut[0]) + 1
            ctx.log("prefix", {"seconds": secs,
                               "train_samples_per_s": k * per_epoch *
                               batch / (cut[0] - t_open)})

    # -- correct: the plain reference follows the first steps
    t_ref = _NOW()
    want = ref.follow(cfg, traffic, ctx.seed, n_check)
    lim = traffic["limits"]
    checks = _compare(ref, got, want, lim, "")
    if ctx.control:
        # the reference in the next precision down, in the program's
        # place: judged by the cell's limits; the program's own
        # numbers are printed beside it
        low = ref.follow(cfg, traffic, ctx.seed, n_check, low=True)
        checks = _compare(ref, low, want, lim, "") + \
            _compare(ref, got, want, {}, "program.")
    ctx.log("reference", {"steps": n_check, "seconds": _NOW() - t_ref})

    if "trace_done" in marks:
        # starting and stopping the profiler holds the host for
        # seconds; the rate behind `model_flops_util` is taken over
        # the epochs after it
        clean = [e for e in ends if e > marks["trace_done"]]
        if len(clean) > 1:
            rate = (len(clean) - 1) * per_epoch * batch / (
                clean[-1] - clean[0])
    readings = {"stats.steps": steps,
                "stats.host_syncs": marks["syncs"],
                "compiles.window": sum(1 for t in ctx.compiles
                                       if t_open < t <= t_close),
                "memory.peak_bytes": peak}
    return {"attempted": steps, "failed": 0,
            "end_to_end": {"train_samples_per_s": rate},  # see above
            "setup_end": t_open, "checks": checks, "readings": readings,
            "memory_peak_bytes": peak, "trace": marks["trace"]}


def _compare(ref, got, want, limits, prefix):
    if len(got.get("loss", ())) != len(want["loss"]) or \
            "update_norm" not in got:
        return [{"name": prefix + "steps_followed",
                 "value": len(got.get("loss", ())), "limit": None,
                 "ok": False}]
    loss_gap = max(abs(a - b) for a, b in zip(got["loss"], want["loss"]))
    print("cellbench: %slosses %r against %r; first gradient, widest "
          "leaves %s; update, widest leaves %s"
          % (prefix, got["loss"], want["loss"],
             ref.widest_leaves(got["grad_norm"], want["grad_norm"]),
             ref.widest_leaves(got["update_norm"], want["update_norm"])),
          flush=True)
    # the gap between norms, by the leaf that falls shortest (a leaf
    # left unchanged, a gradient zeroed) and over all leaves together
    # (the precision): PERF.md says why not by the widest leaf
    out = [{"name": prefix + name, "value": float(value),
            "limit": limits.get(name)} for name, value in (
        ("loss_gap", loss_gap),
        ("grad_total_gap", ref.total_gap(got["grad_norm"],
                                         want["grad_norm"])),
        ("update_total_gap", ref.total_gap(got["update_norm"],
                                           want["update_norm"])),
        ("grad_leaf_deficit", ref.leaf_deficit(got["grad_norm"],
                                               want["grad_norm"])),
        ("update_leaf_deficit", ref.leaf_deficit(got["update_norm"],
                                                 want["update_norm"])))]
    for c in out:
        if not np.isfinite(c["value"]):
            c["ok"] = False
    return out
