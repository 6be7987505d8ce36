"""Traffic kind `serve_mixed`: the closed loop of kind `serve_stream`
(the same deck, callers, checks, control, expert counts and readings,
its parts imported and not copied) for a pool whose every prompt is
prefilled by chunks, one prompt at a time, between decode steps: a
window of `--seconds` then holds a few requests and not hundreds.

What differs from kind `serve_stream`, and why it is a kind of its own:

- **The window's edges are first tokens, and an answer counts where it
  begins.** Prompts are chunked one after the other in the order they
  were sent, so the time at which the count of FIRST tokens reaches a
  multiple of a block (one prompt of every length) marks a fixed
  amount of prefill behind it, whatever order the seed put the block
  in. The window runs between two such marks, and
  `serve_tokens_per_s` is the tokens delivered by the answers that
  BEGAN in it (every one of them has arrived when the log is reduced:
  the clients finish what they hold) over its length. The other serve
  kinds count the tokens that ARRIVE between two completion marks;
  with 16 requests a window, answers of up to 15 s of its 44 in flight
  at either edge, that count swung by 9-12% between seeds on one
  program (PERF.md §4), and a completion mark itself moves by the
  length of the answer that happens to end the block. Both rates have
  the same long-run value; the arrivals' is logged beside this one
  (`arrived_tokens_per_s`). Gaps are reduced as everywhere: those
  whose later token arrived in the window.
- The window opens on the first such mark AFTER the process is
  `window_opens_after_s` old (never on one already passed), and the
  callers connect when the process is `callers_start_after_s` old
  (at once where set-up took longer): a block lasts 11 s here, so
  marks that moved with every second of set-up would move the
  opening, and `setup_s`, by a whole block.
- `_warm_groups` is left out. It submits 1 .. `slots` prompts of the
  shortest length in one breath and repeats a round until the
  program's `prefills` rose by exactly one. Chunked prompts prefill
  one after the other, so every round of more than one "splits" and
  is driven four times: 37 prompts of 4 352 tokens, 71 s of set-up on
  the chip (PERF.md §4) for a merge program `warm_lengths` has run.
- Two more of the decoder's counters are read over the window beside
  the expert layers': `chunks` (chunk forwards) and `chunk_rows` (the
  rows they ran, of which one a chunk is a prompt's). A program
  without them reads 0, and a metric over them is left out.
- With a trace, the expert layers' counts that the needs of
  `cellbench/ops/<family>.py` take (`traffic["measured"]`) are those
  of the traced seconds, not of the window: with four slots the rows
  that decode swing between one and four, and a step's distinct
  experts with them.
- One line a run (`requests`) keeps each request's due, first-token
  and completion time with its lengths: what either rate, or another
  window, can be reduced from again.
"""
import gc
import importlib
import time

import numpy as np

from cellbench import deck, window
from cellbench.drive.serve import (_Callers, _Log, _stats_readings,
                                   _stats_snapshot, _wait_until)
from cellbench.drive.serve_stream import _EXPERT_STATS, warm_lengths

_NOW = time.perf_counter
_STATS = _EXPERT_STATS + ("chunks", "chunk_rows")


def first_tokens(token_times):
    """{request: arrival of its first token}, for the requests that
    have one. `token_times[i]` is request i's arrivals, or None."""
    return {i: ts[0] for i, ts in enumerate(token_times) if ts}


def begun_in(token_times, t_open, t_close):
    """(requests whose first token arrived in (t_open, t_close], the
    tokens those requests were delivered in all)."""
    begun = [i for i, t in first_tokens(token_times).items()
             if t_open < t <= t_close]
    return begun, sum(len(token_times[i]) for i in begun)


def run(ctx):
    """One run of a serve_mixed cell. See `cellbench/run.py` for `ctx`
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
    start_age = float(traffic.get("callers_start_after_s", 0))
    sizes = ref.sizes(cfg)
    vocab = sizes["vocab"]
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
                    **{k: st.get(k, 0) for k in _STATS})

    def firsts():
        return sorted(first_tokens(list(log.tokens)).values())

    alive = decoder._thread.is_alive
    try:
        warm_lengths(decoder, traffic, vocab, ctx.seed)
        ctx.log("phase", {"lengths_warm_s": _NOW() - ctx.t0})
        gc.collect()
        gc.freeze()
        gc.disable()
        _wait_until(lambda: _NOW() - ctx.t0 >= start_age, start_age + 60,
                    "the callers' start", alive)
        ctx.log("phase", {"callers_start_s": _NOW() - ctx.t0})
        callers = _Callers(make_client, reqs, log,
                           int(traffic["callers"])).start()
        _wait_until(lambda: len(log.completions) >= warm_n, 1100,
                    "the warm phase", alive)
        _wait_until(lambda: _NOW() - ctx.t0 >= min_age, min_age + 60,
                    "the window's earliest start", alive)
        n_open = (len(firsts()) // per_block + 1) * per_block
        _wait_until(lambda: len(firsts()) >= n_open, 120,
                    "the block that opens the window", alive)
        t_warm = firsts()[n_open - 1]
        before = snap()
        ctx.log("phase", {"warm_s": _NOW() - ctx.t0,
                          "compiles": len(ctx.compiles)})
        trace, traced = None, None
        if ctx.trace:
            _wait_until(lambda: _NOW() >= t_warm + 1.0, 60,
                        "the trace's start", alive)
            trace = ctx.start_trace()
            traced = [snap()]
            time.sleep(float(ctx.trace_seconds))
            traced.append(snap())
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
        # one prompt of each length, as many tokens as the shortest
        # answer (every chunk of each length, the step at each depth)
        rng = deck.rng_for(ctx.seed, 6)
        probe = [rng.integers(0, vocab, n, dtype=np.int64)
                 for n in traffic["prompt_lengths"]]
        probe_rows, probe_logits = model.served_logits(
            decoder, probe, min(traffic["output_lengths"]))
        peak = ctx.memory_peak()
        jit_programs = int(telemetry.gauge(
            "serve.decode.jit_cache_size").value or 0)
        moe_max_load = decoder.stats().get("moe_max_load")
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
    by_req = log.tokens[:issued]
    token_times = [ts for ts in by_req if ts is not None]
    arrivals = sorted(t for ts in token_times for t in ts)
    marks = window.block_marks(list(first_tokens(by_req).values()),
                               per_block)
    edges = window.aligned_edges(marks, t_warm, seconds)
    if edges is None:
        raise RuntimeError("cellbench: no window of %.0f s in the log"
                           % seconds)
    t_open, t_close = edges
    length = t_close - t_open
    begun, n_tokens = begun_in(by_req, t_open, t_close)
    n_arrived = window.count_in(arrivals, t_open, t_close)
    gaps_ms, p50, p99, longest, long_by_100 = window.reduce_gaps(
        token_times, t_open, t_close)
    in_win = [i for i in range(issued) if t_open < log.due[i] <= t_close]
    failed = [i for i in in_win if not isinstance(log.rows[i], np.ndarray)]

    e2e = {"serve_tokens_per_s": n_tokens / length,
           "serve_itl_p99_ms": p99, "serve_itl_p50_ms": p50}
    age = lambda t: None if t is None else round(t - ctx.t0, 3)
    ctx.log("requests", {"due_first_done_s_prompt_tokens": [
        [age(log.due[i]), age(by_req[i][0] if by_req[i] else None),
         age(log.done[i]), len(reqs[i]["prompt"]), len(by_req[i] or ())]
        for i in range(issued)]})
    ctx.log("window", {"open_s": t_open - ctx.t0, "length_s": length,
                       "requests_per_s": len(in_win) / length,
                       "queued_at_close": after["queued"],
                       "compiles_in_window": sum(
                           1 for t in ctx.compiles if t_open < t <= t_close),
                       "answers_begun": len(begun), "tokens": n_tokens,
                       "arrived": n_arrived,
                       "arrived_tokens_per_s": n_arrived / length,
                       "gaps": len(gaps_ms),
                       "requests_due": len(in_win),
                       "moe_max_load": moe_max_load,
                       "tokens_per_whole_second":
                           window.per_second(arrivals, t_open, t_close),
                       "gaps_over_3x_median_ms": longest,
                       "long_gaps_by_100_ms": long_by_100})
    for secs in ctx.prefixes:
        cut = window.aligned_edges(marks, t_warm, secs)
        if cut and secs < seconds:
            _g, cut50, cut99, _l, _h = window.reduce_gaps(
                token_times, *cut)
            ctx.log("prefix", {
                "seconds": secs, "length_s": cut[1] - cut[0],
                "serve_tokens_per_s":
                    begun_in(by_req, *cut)[1] / (cut[1] - cut[0]),
                "serve_itl_p99_ms": cut99, "serve_itl_p50_ms": cut50})
    ctx.log("phase", {"reduced_s": _NOW() - t_reduce})

    # -- correct: as kind `serve_stream` decides it. Every finished
    # row is well-formed; a seeded sample of the rows finished in the
    # window, the longest among them, holds the tokens the plain
    # reference puts first; and the logits the decoder served from are
    # the reference's, not its int8 twin's
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
        longest_row = max(finished, key=lambda i: (len(log.rows[i]), -i))
        rest = [i for i in finished if i != longest_row]
        pick = deck.rng_for(ctx.seed, 4).choice(
            len(rest), size=min(n_check - 1, len(rest)), replace=False)
        sample = [longest_row] + [rest[k] for k in sorted(pick)]
        rows = [(len(reqs[i]["prompt"]), log.rows[i]) for i in sample]
        probed = [(len(p), r) for p, r in zip(probe, probe_rows)]
        shape = dict(dtype=cfg["compute_dtype"],
                     pad_to=max(traffic["prompt_lengths"]) +
                     max(traffic["output_lengths"]),
                     served_to=max(traffic["output_lengths"]))
        t_ref = _NOW()
        want = list(ref.served_logits(cfg, ctx.seed, rows + probed,
                                      **shape))
        twin = list(ref.served_logits(cfg, ctx.seed, probed, int8=True,
                                      **shape))
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
    # for cellbench/ops/<family>.py: what the run did, where bytes are
    # counted by it (run.py hands this same dict to the readers). Over
    # the traced seconds where there are any: the needs are set
    # against those seconds' steps, and with four slots the rows that
    # decode, and so the experts a step hits, swing between one and
    # four within a window
    m0, m1 = traced or (before, after)
    layer_steps = (m1["steps"] - m0["steps"]) * sum(
        k == "experts" for k in sizes["kinds"])
    traffic["measured"] = {
        "experts_hit_per_layer_step":
            (m1["moe_experts_hit"] - m0["moe_experts_hit"]) / layer_steps
            if layer_steps else None,
        "pairs_here_per_layer_step":
            (m1["moe_pairs_here"] - m0["moe_pairs_here"]) / layer_steps
            if layer_steps else None}
    ctx.log("experts", dict(traffic["measured"],
                            **{k: d(k) for k in _STATS}))
    readings = {
        "series": {"gap_ms": gaps_ms},
        **_stats_readings(before, after, traffic["slots"]),
        "client.tokens": window.count_in(arrivals, before["t"],
                                         after["t"]),
        "compiles.window": sum(1 for t in ctx.compiles
                               if t_warm < t <= t_stop),
        "jit.decode_programs": jit_programs,
        "memory.peak_bytes": peak,
    }
    readings.update({"stats." + k: d(k) for k in _STATS})
    return {"attempted": len(in_win), "failed": len(failed),
            "end_to_end": e2e, "setup_end": t_open, "checks": checks,
            "readings": readings, "memory_peak_bytes": peak,
            "trace": trace}
