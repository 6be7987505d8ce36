"""Traffic kind `serve_blocks`: the closed loop of kind `serve` (the
same deck, callers, window, edges and well-formedness checks, imported
from it and not copied) for a program whose step yields a block's
tokens and not one token a row: generation by diffusion over blocks.

What differs from kind `serve`, and why it is a kind of its own:

- With L / T tokens unmasked a forward, a request's gaps at the client
  alternate between about zero and one step, so the median of all gaps
  sits on the edge between the two: the cell does not report
  `serve_itl_p50_ms`. The pace of a stream is reported per block
  instead (`series.block_ms`: between the first tokens of consecutive
  blocks of a request), as a per-layer metric.
- The logits behind served tokens are read through
  `decoder.on_block_logits` (`cellbench/models/<family>.py::
  served_logits`), since the step picks on the device.
- The program has no lower-precision path for expert weights, so the
  control (`--control 1`) is the reference's own int8 twin read in the
  program's place, with the program's numbers printed beside it.
- `traffic["measured"]` is filled for the functions of
  `cellbench/ops/<family>.py` that count bytes by what the run did
  (distinct experts hit a layer and forward, from the device).
"""
import gc
import importlib
import time

import numpy as np

from cellbench import deck, window
from cellbench.drive.serve import (_Callers, _Log, _stats_readings,
                                   _stats_snapshot, _wait_until,
                                   _warm_groups)

_NOW = time.perf_counter
_BLOCK_STATS = ("forwards", "commit_forwards", "blocks_committed",
                "tokens_unmasked", "moe_assignments", "moe_experts_hit")


def block_times(token_times, prompt_lens, block, t_open, t_close):
    """Seconds between the first tokens of consecutive blocks of each
    request, for the later token arriving in (t_open, t_close]. Served
    token j of a request sits at position prompt + j."""
    out = []
    for ts, p in zip(token_times, prompt_lens):
        firsts = [t for j, t in enumerate(ts)
                  if j == 0 or (p + j) % block == 0]
        out.extend(b - a for a, b in zip(firsts, firsts[1:])
                   if t_open < b <= t_close)
    return out


def run(ctx):
    """One run of a serve_blocks cell. See `cellbench/run.py` for `ctx`
    and the shape of what comes back."""
    cfg, traffic = ctx.cfg, ctx.traffic
    family = cfg["family"]
    ref = importlib.import_module("cellbench.reference." + family)
    model = importlib.import_module("cellbench.models." + family)
    from mxnet_tpu import profiler, telemetry
    from mxnet_tpu.serve import ServeClient
    import jax

    seconds = float(ctx.seconds)
    per_block = len(traffic["prompt_lengths"])
    warm_n = int(traffic["warm_requests"])
    min_age = float(traffic["window_opens_after_s"])
    steps = int(traffic["denoising_steps"])
    sizes = ref.sizes(cfg)
    vocab, block = sizes["vocab"], sizes["block"]
    reqs = deck.Stream(traffic, ctx.seed, vocab)
    log = _Log()

    params = ref.make_params(cfg, ctx.seed, cfg["compute_dtype"])
    gen, decoder, server = model.build_server(cfg, traffic, params)
    ctx.program_hook(decoder)            # tests break the timed path here
    del params
    ctx.log("phase", {"built_s": _NOW() - ctx.t0})

    def make_client():
        return ServeClient(server.host, server.port)

    def snap():
        st = decoder.stats()
        return dict(_stats_snapshot(decoder, telemetry, profiler,
                                    ctx.compiles),
                    **{k: st[k] for k in _BLOCK_STATS})

    alive = decoder._thread.is_alive
    try:
        split = _warm_groups(decoder, traffic, vocab, ctx.seed)
        ctx.log("phase", {"groups_warm_s": _NOW() - ctx.t0,
                          "rounds_split": split})
        gc.collect()
        gc.freeze()
        gc.disable()
        # -- warm phase and window: as kind `serve` runs them
        callers = _Callers(make_client, reqs, log,
                           int(traffic["callers"])).start()
        _wait_until(lambda: len(log.completions) >= warm_n, 1100,
                    "the warm phase", alive)
        _wait_until(lambda: _NOW() - ctx.t0 >= min_age, min_age + 60,
                    "the window's earliest start", alive)
        n_open = -(-len(log.completions) // per_block) * per_block
        _wait_until(lambda: len(log.completions) >= n_open, 120,
                    "the block that opens the window", alive)
        t_warm = sorted(log.completions)[n_open - 1]
        before = snap()
        ctx.log("phase", {"warm_s": _NOW() - ctx.t0,
                          "compiles": len(ctx.compiles)})
        trace = None
        if ctx.trace:
            _wait_until(lambda: _NOW() >= t_warm + 1.0, 60,
                        "the trace's start", alive)
            trace = ctx.start_trace()
            time.sleep(float(ctx.trace_seconds))
            ctx.stop_trace(trace)
        _wait_until(lambda: _NOW() >= t_warm + seconds, seconds + 60,
                    "the window's end", alive)
        after = snap()
        t_stop = _NOW()
        callers.stop = True
        ctx.log("phase", {"window_closed_s": t_stop - ctx.t0})
        _wait_until(lambda: callers.join(0.05), 600,
                    "the clients to finish", alive)
        issued = callers.issued
        # -- the logits behind served tokens, from the same decoder:
        # one prompt of each length, `probe_tokens` served tokens each
        rng = deck.rng_for(ctx.seed, 6)
        probe = [rng.integers(0, vocab, n, dtype=np.int64)
                 for n in traffic["prompt_lengths"]]
        probe_rows, probe_logits = model.served_logits(
            decoder, probe, int(traffic["probe_tokens"]))
        peak = ctx.memory_peak()
        jit_programs = int(telemetry.gauge(
            "serve.decode.jit_cache_size").value or 0)
        moe_max_load = decoder.stats()["moe_max_load"]
    finally:
        gc.enable()
        server.close()
        decoder.close(60)
    del gen, decoder, server, callers
    gc.unfreeze()
    gc.collect()
    ctx.log("phase", {"program_freed_s": _NOW() - ctx.t0,
                      "bytes_in_use": [
                          (d.memory_stats() or {}).get("bytes_in_use")
                          for d in jax.local_devices()]})

    # -- reduce the client's log, once
    t_reduce = _NOW()
    done_idx = [i for i in range(issued) if log.done[i] is not None]
    sent = [i for i in range(issued) if log.tokens[i] is not None]
    token_times = [log.tokens[i] for i in sent]
    arrivals = sorted(t for ts in token_times for t in ts)
    marks = window.block_marks(log.completions, per_block)
    edges = window.aligned_edges(marks, t_warm, seconds)
    if edges is None:
        raise RuntimeError("cellbench: no window of %.0f s in the log"
                           % seconds)
    t_open, t_close = edges
    length = t_close - t_open
    n_tokens = window.count_in(arrivals, t_open, t_close)
    gaps_ms, p50, p99, _longest, _by_100 = window.reduce_gaps(
        token_times, t_open, t_close)
    block_ms = sorted(1e3 * g for g in block_times(
        token_times, [len(reqs[i]["prompt"]) for i in sent], block,
        t_open, t_close))
    in_win = [i for i in range(issued) if t_open < log.due[i] <= t_close]
    failed = [i for i in in_win if not isinstance(log.rows[i], np.ndarray)]

    e2e = {"serve_tokens_per_s": n_tokens / length}
    ctx.log("window", {"open_s": t_open - ctx.t0, "length_s": length,
                       "requests_per_s": len(in_win) / length,
                       "queued_at_close": after["queued"],
                       "compiles_in_window": sum(
                           1 for t in ctx.compiles if t_open < t <= t_close),
                       "tokens": n_tokens, "gaps": len(gaps_ms),
                       "requests_due": len(in_win),
                       "gap_ms_p50_p99": [p50, p99],
                       "block_ms_p50_p99": [
                           window.median(block_ms),
                           window.percentile(block_ms, 99)[0]],
                       "longest_block_ms": block_ms[-10:],
                       "moe_max_load": moe_max_load,
                       "tokens_per_whole_second":
                           window.per_second(arrivals, t_open, t_close)})
    for secs in ctx.prefixes:
        cut = window.aligned_edges(marks, t_warm, secs)
        if cut and secs < seconds:
            ctx.log("prefix", {
                "seconds": secs, "length_s": cut[1] - cut[0],
                "serve_tokens_per_s":
                    window.count_in(arrivals, *cut) / (cut[1] - cut[0])})
    ctx.log("phase", {"reduced_s": _NOW() - t_reduce})

    # -- correct: every finished row is well-formed; a seeded sample
    # of the rows finished in the window, the longest among them,
    # holds the tokens the plain reference puts first in the very
    # states the sampler went through; and the logits the decoder
    # served from are the reference's, not its int8 twin's
    checks = []
    finished = [i for i in done_idx if isinstance(log.rows[i], np.ndarray)
                and t_open < log.done[i] <= t_stop]
    bad = 0
    for i in finished:
        p, row = reqs[i]["prompt"], log.rows[i]
        if row.shape != (len(p) + reqs[i]["max_new"],) or \
                not np.array_equal(row[:len(p)], p) or \
                row.min() < 0 or row.max() >= vocab or \
                len(log.tokens[i]) != reqs[i]["max_new"]:
            bad += 1
    checks.append({"name": "malformed_rows", "value": bad, "limit": 0})
    checks.append({"name": "failed_requests",
                   "value": len(failed), "limit": 0})
    lim = traffic["limits"]
    n_check = min(int(traffic["check_requests"]), len(finished))
    if n_check:
        longest = max(finished, key=lambda i: (len(log.rows[i]), -i))
        rest = [i for i in finished if i != longest]
        pick = deck.rng_for(ctx.seed, 4).choice(
            len(rest), size=min(n_check - 1, len(rest)), replace=False)
        sample = [longest] + [rest[k] for k in sorted(pick)]
        rows = [(len(reqs[i]["prompt"]), log.rows[i]) for i in sample]
        probed = [(len(p), r) for p, r in zip(probe, probe_rows)]
        shape = dict(dtype=cfg["compute_dtype"],
                     pad_to=ref.replay_length(
                         max(traffic["prompt_lengths"]),
                         max(traffic["output_lengths"]), block, steps),
                     served_to=max(traffic["output_lengths"]))
        t_ref = _NOW()
        want = list(ref.served_logits(cfg, ctx.seed, rows + probed,
                                      steps, **shape))
        twin = list(ref.served_logits(cfg, ctx.seed, probed, steps,
                                      int8=True, **shape))
        gaps = ref.served_gaps(rows, want[:len(rows)])
        size, share = ref.logit_errors(probe_logits, want[len(rows):],
                                       twin)
        if ctx.control:
            ctx.log("control", {"program_logit_err": size,
                                "program_int8_share": share})
            size, share = ref.logit_errors(twin, want[len(rows):], twin)
        checks += [
            {"name": "gap_widest", "value": max(gaps),
             "limit": lim["gap_widest"]},
            {"name": "gap_mean", "value": float(np.mean(gaps)),
             "limit": lim["gap_mean"]},
            {"name": "logit_err", "value": size,
             "limit": lim["logit_err"]},
            {"name": "int8_share", "value": share,
             "limit": lim["int8_share"]}]
        ctx.log("reference", {"requests": len(sample),
                              "served_tokens": len(gaps),
                              "probed_logit_rows": sum(
                                  len(x) for x in probe_logits),
                              "seconds": _NOW() - t_ref})
    else:
        checks.append({"name": "requests_finished", "value": 0,
                       "limit": None, "ok": False})

    d = lambda k: after[k] - before[k]
    nominal_tokens = window.count_in(arrivals, before["t"], after["t"])
    layer_forwards = d("steps") * sizes["layers"]
    # for cellbench/ops/<family>.py: what the run did, where bytes are
    # counted by it (run.py hands this same dict to the readers)
    traffic["measured"] = {
        "experts_hit_per_layer_forward":
            d("moe_experts_hit") / layer_forwards
            if layer_forwards else None}
    readings = {
        "series": {"gap_ms": gaps_ms, "block_ms": block_ms},
        **_stats_readings(before, after, traffic["slots"]),
        "client.tokens": nominal_tokens,
        "compiles.window": sum(1 for t in ctx.compiles
                               if t_warm < t <= t_stop),
        "jit.decode_programs": jit_programs,
        "memory.peak_bytes": peak,
    }
    readings.update({"stats." + k: d(k) for k in _BLOCK_STATS})
    return {"attempted": len(in_win), "failed": len(failed),
            "end_to_end": e2e, "setup_end": t_open, "checks": checks,
            "readings": readings, "memory_peak_bytes": peak,
            "trace": trace}
