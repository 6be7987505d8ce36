"""The quickest proof that the system still starts on the chip.

    python chip_smoke.py              # one chip: kernels, train, serve, nms
    python chip_smoke.py --devices 4  # one host, four chips: train only

Drives the transformer_lm train -> serve path once through the entry
points a user calls, at the flagship widths (vocab 32768, seq 2048,
dim 2048, 16 heads x 128, FFN 8192, bf16 compute over f32 masters, Adam)
with the depth cut to 4 layers and weights drawn from a seed:

  flash  the compiled Pallas flash forward/backward against
         `_attn_reference` at the shape the train step uses
  train  `make_train_step` -> `TrainStep.fit` on an `io.NDArrayIter`
         (guarded, metric fused, dispatch window, donation); the loss
         must fall and the step must contain the Mosaic kernels
  serve  the trained params -> `Generator` -> `serving_decoder()` ->
         `ServeServer` on 127.0.0.1 <- `ServeClient.generate` from
         threads of this process, more requests than slots
  nms    `MultiBoxDetection` at SSD-300's 8732 anchors, the Pallas
         kernel against the XLA path, bit-equal

It runs only on a TPU: it never chooses a platform, and exits non-zero
naming the one it found otherwise. One process holds the chip. A phase
that fails raises, so the exit code carries it. Stdout ends with two
lines, each one JSON object: the report (versions, compile-cache
directory, per-phase smoke timings — not benchmark results), then the
result, `{"ok": true, "device": {"platform", "kind", "count"}}` with
those keys and no others.
"""
import argparse
import dataclasses
import json
import sys
import threading
import time

import numpy as np

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import telemetry
from mxnet_tpu.generation import Generator
from mxnet_tpu.initializer import Xavier
from mxnet_tpu.models import transformer
from mxnet_tpu.ops import attention
from mxnet_tpu.ops.registry import get_op
from mxnet_tpu.parallel import make_mesh, make_train_step
from mxnet_tpu.serve import ServeClient, ServeServer


@dataclasses.dataclass(frozen=True)
class Widths:
    vocab: int
    seq: int
    dim: int
    heads: int
    ffn: int
    layers: int
    batch: int          # per chip
    steps_per_epoch: int
    epochs: int         # the first one compiles
    slots: int
    prompts: tuple      # one request per entry; more entries than slots
    new_tokens: int
    anchors: int


# the transformer_lm at full width; only the depth is a cut
FULL = Widths(vocab=32768, seq=2048, dim=2048, heads=16, ffn=8192,
              layers=4, batch=8, steps_per_epoch=4, epochs=3, slots=4,
              prompts=(16, 48, 128, 16, 48, 128), new_tokens=32,
              anchors=8732)


def require_tpu():
    backend = jax.default_backend()
    if backend != "tpu":
        sys.exit("chip_smoke: needs a TPU; jax.default_backend() is %r "
                 "(devices: %s)" % (backend, jax.devices()))


def result(devs):
    """The last line of stdout, printed once every phase has held: these
    keys and no others, the device as JAX reports it."""
    return {"ok": True,
            "device": {"platform": devs[0].platform,
                       "kind": devs[0].device_kind,
                       "count": len(devs)}}


def _mosaic_calls(lowered, want, what):
    """Pallas kernels in a lowered program. On the TPU every one of them
    must be the compiled kernel: interpret mode and the dense fallback
    lower to ordinary ops and leave no custom call. (On the CPU, which
    only the tests reach, there is none to find.)"""
    calls = lowered.as_text().count("tpu_custom_call")
    if calls != (want if jax.default_backend() == "tpu" else 0):
        raise AssertionError("%s lowered to %d Mosaic calls, want %d: a "
                             "kernel fell back" % (what, calls, want))
    return calls


def _rel_err(got, ref):
    got, ref = got.astype(jnp.float32), ref.astype(jnp.float32)
    return float(jnp.abs(got - ref).max() / jnp.abs(ref).max())


def check_flash(w):
    """Flash forward and FA-2 backward at (batch*heads, seq, head_dim)
    against the dense reference, which runs a few heads at a time so
    its (T, T) scores fit beside the kernel's operands."""
    bh, hd = w.batch * w.heads, w.dim // w.heads
    q, k, v, g = (jax.random.normal(key, (bh, w.seq, hd), jnp.bfloat16)
                  for key in jax.random.split(jax.random.PRNGKey(0), 4))
    scale = hd ** -0.5

    def run(fn, q, k, v, g):
        out, vjp = jax.vjp(fn, q, k, v)
        return (out,) + vjp(g)

    flash = jax.jit(lambda *a: run(
        lambda q, k, v: attention.flash_attention(q, k, v, causal=True),
        *a))
    dense = jax.jit(lambda *a: run(
        lambda q, k, v: attention._attn_reference(q, k, v, scale, True),
        *a))
    calls = _mosaic_calls(flash.lower(q, k, v, g), 3,
                          "flash fwd, dq, dkv")
    t0 = time.perf_counter()
    got = jax.block_until_ready(flash(q, k, v, g))
    secs = time.perf_counter() - t0
    heads = min(bh, 16)
    errs = {}
    for lo in range(0, bh, heads):
        sl = slice(lo, lo + heads)
        ref = dense(q[sl], k[sl], v[sl], g[sl])
        for name, a, b in zip(("o", "dq", "dk", "dv"), got, ref):
            errs[name] = max(errs.get(name, 0.0), _rel_err(a[sl], b))
    # bf16 operands and outputs: 8 significand bits
    bad = {n: e for n, e in errs.items() if not e < 3e-2}
    if bad:
        raise AssertionError("flash kernel disagrees with "
                             "_attn_reference: %r" % (errs,))
    return {"seconds": round(secs, 3), "mosaic_calls": calls,
            "max_rel_err": {n: round(e, 5) for n, e in errs.items()}}


def train(w, n_devices=1):
    """A few epochs of `TrainStep.fit` over one repeating batch."""
    mesh = make_mesh({"data": n_devices},
                     jax.devices()[:n_devices]) if n_devices > 1 else None
    batch = w.batch * n_devices
    sym = transformer.get_symbol(w.vocab, w.seq, num_layers=w.layers,
                                 num_heads=w.heads, dim=w.dim,
                                 ffn_hidden=w.ffn)
    step = make_train_step(sym, optimizer="adam",
                           optimizer_params={"rescale_grad": 1.0 / batch},
                           compute_dtype="bfloat16", mesh=mesh)
    toks = np.random.RandomState(0).randint(
        0, w.vocab, (batch, w.seq)).astype(np.float32)
    labels = np.roll(toks, -1, axis=1)
    labels[:, -1] = -1
    it = mx.io.NDArrayIter(np.tile(toks, (w.steps_per_epoch, 1)),
                           np.tile(labels, (w.steps_per_epoch, 1)),
                           batch_size=batch)
    metric = mx.metric.Perplexity(ignore_label=-1)

    marks = {"epoch_end": [], "loss": []}

    def first_step(param):
        # the one mid-epoch read: it waits for compile + step 0 and
        # gives that step's own loss
        if "first" not in marks:
            marks["first_loss"] = float(np.log(param.eval_metric.get()[1]))
            marks["first"] = time.perf_counter()

    def epoch_end(epoch, state):
        # fit has just read the metric, so the epoch's steps are done
        marks["epoch_end"].append(time.perf_counter())
        marks["loss"].append(float(np.log(metric.get()[1])))

    mx.random.seed(0)
    t0 = time.perf_counter()
    state, _ = step.fit(it, num_epoch=w.epochs, initializer=Xavier(),
                        lr=1e-4, eval_metric=metric,
                        batch_end_callback=first_step,
                        epoch_end_callback=epoch_end)
    ends = marks["epoch_end"]
    steady = (ends[-1] - ends[0]) / (w.steps_per_epoch * (w.epochs - 1))
    first, last = marks["first_loss"], marks["loss"][-1]
    if not (np.isfinite(marks["loss"]).all() and np.isfinite(first)):
        raise AssertionError("non-finite loss: first %r, epochs %r"
                             % (first, marks["loss"]))
    if not last < first:
        raise AssertionError("loss did not fall: first step %.4f, last "
                             "epoch %.4f" % (first, last))
    if step.guard_report.get("masked_steps"):
        raise AssertionError("guard masked steps: %r" % step.guard_report)

    params, opt_state, _ = state
    devs = jax.devices()[:n_devices]
    state_bytes = 0                       # one device's share
    for leaf in jax.tree.leaves((params, opt_state)):
        if leaf.sharding.device_set != set(devs):
            raise AssertionError("state on %r, want %r"
                                 % (leaf.sharding.device_set, devs))
        state_bytes += leaf.dtype.itemsize * int(np.prod(
            leaf.sharding.shard_shape(leaf.shape)))
    # what stays live after fit is the state: the same on each of four
    # chips as on one, not the global batch's activations or a copy
    used = [(d.memory_stats() or {}).get("bytes_in_use") for d in devs]
    if None not in used and max(used) > 1.1 * state_bytes:
        raise AssertionError("live bytes per device %r against %d of "
                             "state" % (used, state_bytes))
    placed = step.place_batch({"data": toks, "softmax_label": labels})
    shard = placed["data"].sharding.shard_shape(placed["data"].shape)
    if placed["data"].sharding.device_set != set(devs) or \
            shard != (w.batch, w.seq):
        raise AssertionError("batch shard %r on %r, want %r on %r"
                             % (shard, placed["data"].sharding.device_set,
                                (w.batch, w.seq), devs))

    lowered = step.lower(state, placed, 1e-4, jax.random.PRNGKey(0))
    calls = _mosaic_calls(lowered, 3 * w.layers,
                          "train step (flash fwd, dq, dkv per layer)")
    out = {"devices": n_devices, "global_batch": batch,
           "compile_and_first_step_s": round(marks["first"] - t0, 3),
           "steady_step_s": round(steady, 4),
           "steps": w.steps_per_epoch * w.epochs,
           "first_loss": round(first, 4), "last_loss": round(last, 4),
           "epoch_loss": [round(x, 4) for x in marks["loss"]],
           "mosaic_calls": calls,
           "state_bytes_per_device": state_bytes,
           "bytes_in_use_per_device": used}
    if n_devices > 1 and calls:
        out.update(_check_partitioned(lowered, w))
    return state, out


def _check_partitioned(lowered, w):
    """Several chips: each runs the flash kernels on its own batch
    shard, with nothing gathered in front of them."""
    hlo = lowered.compile().as_text()
    shard = "bf16[%d,%d,%d]" % (w.batch * w.heads, w.seq,
                                w.dim // w.heads)
    calls = [ln for ln in hlo.splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln]
    wrong = [ln.strip()[:200] for ln in calls if shard not in ln]
    if not calls or wrong:
        raise AssertionError(
            "flash custom calls not on the per-device shard %s: %d "
            "calls, offending: %r" % (shard, len(calls), wrong[:2]))
    if "all-gather" in hlo:
        raise AssertionError("data-parallel step contains an all-gather")
    return {"flash_operand": shard, "all_gathers": 0}


def serve(w, params):
    """More requests than slots, a few prompt lengths, all at once."""
    arch = dict(num_layers=w.layers, num_heads=w.heads, dim=w.dim,
                ffn_hidden=w.ffn, dtype="bfloat16")
    t0 = time.perf_counter()
    pool = Generator(params, w.vocab, w.seq, batch_size=w.slots, **arch)
    decoder = pool.serving_decoder()
    server = ServeServer(decoder)
    rng = np.random.RandomState(1)
    prompts = [rng.randint(0, w.vocab, (p,)) for p in w.prompts]
    rows = [None] * len(prompts)
    errors = []

    def client(i):
        try:
            with ServeClient(server.host, server.port) as c:
                rows[i] = np.asarray(
                    c.generate(prompts[i], w.new_tokens, timeout=600))
        except Exception as exc:     # noqa: BLE001 — re-raised below
            errors.append((i, exc))

    threads = [threading.Thread(target=client, args=(i,))
               for i in range(len(prompts))]
    try:
        t1 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join(900)
        t2 = time.perf_counter()
        if errors:
            raise errors[0][1]
        if any(t.is_alive() for t in threads):
            raise AssertionError("a client is still waiting after 900 s")
    finally:
        server.close()
        decoder.close()

    for p, row in zip(prompts, rows):
        if row.shape != (len(p) + w.new_tokens,) or \
                not np.array_equal(row[:len(p)], p) or \
                row.min() < 0 or row.max() >= w.vocab:
            raise AssertionError("bad row for prompt %d: shape %r"
                                 % (len(p), row.shape))
    programs = telemetry.gauge("serve.decode.jit_cache_size").value
    if programs != 1:
        raise AssertionError("decode step compiled %r programs, want 1"
                             % (programs,))
    stats = decoder.stats()

    # batch-1 reference: finite logits over every served row (gated),
    # greedy equality (reported: bf16 argmax ties may differ)
    single = Generator(params, w.vocab, w.seq, batch_size=1, **arch)
    same = 0
    for p, row in zip(prompts, rows):
        if not np.isfinite(single.log_likelihood(row[None])).all():
            raise AssertionError("non-finite logits on a served row")
        same += int(np.array_equal(
            single.generate(p[None], w.new_tokens)[0], row))
    return {"requests": len(prompts), "slots": w.slots,
            "tokens_served": len(prompts) * w.new_tokens,
            "setup_s": round(t1 - t0, 3),
            "requests_s": round(t2 - t1, 3),
            "decode_steps": stats.get("steps"),
            "prefills": stats.get("prefills"),
            "decode_programs": int(programs),
            "equal_to_batch1_generate": "%d/%d" % (same, len(prompts))}


def check_nms(w):
    """MultiBoxDetection's default (Pallas on the TPU) against the dense
    XLA path on SSD-like boxes: same rows, bit for bit."""
    rng = np.random.RandomState(2)
    a, classes = w.anchors, 21
    ctr = rng.uniform(0.05, 0.95, (a, 2))
    half = rng.uniform(0.02, 0.25, (a, 2))
    anchors = jnp.asarray(np.concatenate([ctr - half, ctr + half], 1)
                          [None], jnp.float32)
    scores = np.exp(rng.standard_normal((1, classes, a)) * 2.0)
    cls_prob = jnp.asarray(scores / scores.sum(1, keepdims=True),
                           jnp.float32)
    loc = jnp.asarray(rng.standard_normal((1, a * 4)) * 0.5, jnp.float32)
    op = get_op("_contrib_MultiBoxDetection").fn
    auto = jax.jit(lambda *x: op(*x))
    dense = jax.jit(lambda *x: op(*x, impl="xla"))
    calls = _mosaic_calls(auto.lower(cls_prob, loc, anchors), 1,
                          "MultiBoxDetection")
    t0 = time.perf_counter()
    got = np.asarray(auto(cls_prob, loc, anchors))
    secs = time.perf_counter() - t0
    ref = np.asarray(dense(cls_prob, loc, anchors))
    kept = int((ref[0, :, 0] >= 0).sum())
    if not np.array_equal(got, ref):
        raise AssertionError("Pallas NMS differs from the XLA path in %d "
                             "rows" % int((got != ref).any(-1).sum()))
    if not 0 < kept < a:
        raise AssertionError("degenerate NMS case: %d of %d kept"
                             % (kept, a))
    return {"seconds": round(secs, 3), "mosaic_calls": calls,
            "anchors": a, "kept": kept}


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--devices", type=int, default=1,
                    help="chips of this host to train over (data "
                         "parallel); above 1 only the train phase runs")
    args = ap.parse_args()
    require_tpu()
    devs = jax.devices()
    if not 1 <= args.devices <= len(devs):
        sys.exit("chip_smoke: --devices %d but jax sees %d"
                 % (args.devices, len(devs)))
    import jaxlib
    from importlib import metadata
    report = {"versions": {"jax": jax.__version__,
                           "jaxlib": jaxlib.__version__,
                           "libtpu": metadata.version("libtpu")},
              "compile_cache_dir": jax.config.jax_compilation_cache_dir,
              "phases": {}}
    phases = report["phases"]

    def timed(name, fn, *a, **kw):
        t0 = time.perf_counter()
        out = fn(*a, **kw)
        res = out[1] if isinstance(out, tuple) else out
        res["wall_s"] = round(time.perf_counter() - t0, 3)
        phases[name] = res
        print("chip_smoke: %s %s" % (name, json.dumps(res)),
              file=sys.stderr, flush=True)
        return out

    t0 = time.perf_counter()
    if args.devices == 1:
        timed("flash", check_flash, FULL)
    state, _ = timed("train", train, FULL, args.devices)
    if args.devices == 1:
        params = state[0]
        del state
        timed("serve", serve, FULL, params)
        del params
        timed("nms", check_nms, FULL)
    report["wall_s"] = round(time.perf_counter() - t0, 3)
    print(json.dumps(report))
    print(json.dumps(result(devs)), flush=True)


if __name__ == "__main__":
    main()
