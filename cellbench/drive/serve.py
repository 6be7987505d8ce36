"""Traffic kind `serve`: requests from the deck sent to the program's
`ServeServer` by streaming `ServeClient`s in this process, closed loop:
`callers` callers, each sending its next request when the last
completes. All times are the client's.
"""
import gc
import importlib
import threading
import time

import numpy as np

from cellbench import deck, window

_NOW = time.perf_counter


class _Log:
    """What the clients saw, one record per request of the stream."""

    def __init__(self):
        self.due = []         # when a caller took the request
        self.tokens = []      # arrival time of each token
        self.done = []        # completion time
        self.rows = []        # the full id row, or the exception
        self.completions = []  # completion times, in order

    def open(self, i):
        """Make room for request i (under the callers' lock)."""
        for series in (self.due, self.tokens, self.done, self.rows):
            series.extend([None] * (i + 1 - len(series)))


def _send(client, log, i, req):
    toks = log.tokens[i] = []
    log.due[i] = _NOW()
    try:
        row = client.generate(req["prompt"], req["max_new"],
                              on_token=lambda _t: toks.append(_NOW()),
                              timeout=600)
        log.rows[i] = np.asarray(row)
    except Exception as exc:       # noqa: BLE001 — counted as failed
        log.rows[i] = exc
    log.done[i] = _NOW()
    log.completions.append(log.done[i])


class _Callers:
    """Closed loop: each caller takes the next request of the stream
    when its last one completes, until told to stop."""

    def __init__(self, make_client, reqs, log, n):
        self._reqs, self._log = reqs, log
        self.issued = 0
        self._lock = threading.Lock()
        self.stop = False
        self._threads = [threading.Thread(target=self._run,
                                          args=(make_client,), daemon=True)
                         for _ in range(n)]

    def start(self):
        for t in self._threads:
            t.start()
        return self

    def _run(self, make_client):
        with make_client() as client:
            while True:
                with self._lock:
                    if self.stop:
                        return
                    i = self.issued
                    self.issued += 1
                    req = self._reqs[i]
                    self._log.open(i)
                _send(client, self._log, i, req)

    def join(self, timeout):
        """Join within `timeout` seconds overall; True if all ended."""
        end = _NOW() + timeout
        for t in self._threads:
            t.join(max(0.0, end - _NOW()))
        return not any(t.is_alive() for t in self._threads)


def _wait_until(cond, timeout, what, alive):
    """Poll `cond` every few milliseconds; give up at once if the
    program's decode thread has died (it takes its error with it and
    every client would wait for ever)."""
    end = _NOW() + timeout
    while not cond():
        if not alive():
            raise RuntimeError("cellbench: the program's decode thread "
                               "died while waiting for " + what)
        if _NOW() > end:
            raise RuntimeError("cellbench: timed out waiting for " + what)
        time.sleep(0.005)


def _warm_groups(decoder, traffic, vocab, seed):
    """The program merges admitted prompts into its cache pool with
    eager programs whose shapes follow the NUMBER of same-length
    prompts admitted in one round, and a new number compiles for
    seconds while every stream waits. Which numbers occur in a run
    is a matter of timing, so every one up to the pool's width is
    driven once here: k short prompts submitted in one breath to the
    idle decoder, checked by its own count of prefills, tried again
    where the round split."""
    rng = deck.rng_for(seed, 5)
    plen = min(traffic["prompt_lengths"])
    split = 0
    for k in range(1, int(traffic["slots"]) + 1):
        for _attempt in range(4):
            before = decoder.stats()["prefills"]
            futs = [decoder.submit(rng.integers(0, vocab, plen), 2)
                    for _ in range(k)]
            for f in futs:
                f.result(timeout=600)
            if decoder.stats()["prefills"] - before == 1:
                break
            split += 1
    return split


def _stats_snapshot(decoder, telemetry, profiler, compiles):
    st = decoder.stats()
    fill = telemetry.histogram("serve.decode.slot_fill")
    return {"t": _NOW(), "steps": st["steps"], "prefills": st["prefills"],
            "admitted": st["admitted"], "finished": st["finished"],
            "shed": st["shed"], "queued": st["queued"],
            "prefill_rows": st["prefill_rows"],
            "admit_rounds": st["admit_rounds"], "merges": st["merges"],
            "slot_fill_sum": fill.sum,
            "slot_fill_count": fill.count,
            "host_syncs": profiler.host_sync_count(),
            "compiles": len(compiles)}


def _stats_readings(before, after, slots):
    """The program's counters over the window (two `_stats_snapshot`s
    apart), under the names the `counter` reader's metric files use;
    the same in every serve kind."""
    out = {"stats." + k: after[k] - before[k]
           for k in ("steps", "prefills", "admitted", "shed",
                     "prefill_rows", "admit_rounds", "merges",
                     "slot_fill_sum", "host_syncs")}
    out["stats.slot_rows"] = int(slots) * (
        after["slot_fill_count"] - before["slot_fill_count"])
    return out


def run(ctx):
    """One run of a serve cell. See `cellbench/run.py` for `ctx` and
    the shape of what comes back."""
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
    vocab = ref.sizes(cfg)["vocab"]
    reqs = deck.Stream(traffic, ctx.seed, vocab)
    log = _Log()

    params = ref.make_params(cfg, ctx.seed, cfg["compute_dtype"])
    gen, decoder, server = model.build_server(cfg, traffic, params,
                                              low=ctx.control)
    ctx.program_hook(decoder)            # tests break the timed path here
    del params
    ctx.log("phase", {"built_s": _NOW() - ctx.t0})

    def make_client():
        return ServeClient(server.host, server.port)

    snap = lambda: _stats_snapshot(decoder, telemetry, profiler,
                                   ctx.compiles)
    alive = decoder._thread.is_alive
    try:
        split = _warm_groups(decoder, traffic, vocab, ctx.seed)
        ctx.log("phase", {"groups_warm_s": _NOW() - ctx.t0,
                          "rounds_split": split})
        gc.collect()
        gc.freeze()
        gc.disable()
        # -- warm phase: the closed loop runs from here on; its first
        # requests compile each prompt length's prefill and bring slots
        # and queue to their steady state. The window opens on the
        # completion that ends a block, once `warm_requests` are done
        # and the process is as old as the traffic file asks
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
        # -- the window: the main thread only waits (and traces)
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
        # one prompt of each length, as many tokens as the shortest
        # answer (each length's prefill, the decode step at each depth)
        rng = deck.rng_for(ctx.seed, 6)
        probe = [rng.integers(0, vocab, n, dtype=np.int64)
                 for n in traffic["prompt_lengths"]]
        probe_rows, probe_logits = model.served_logits(
            decoder, probe, min(traffic["output_lengths"]))
        peak = ctx.memory_peak()
        jit_programs = int(telemetry.gauge(
            "serve.decode.jit_cache_size").value or 0)
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
    token_times = [log.tokens[i] for i in range(issued)
                   if log.tokens[i] is not None]
    arrivals = sorted(t for ts in token_times for t in ts)
    marks = window.block_marks(log.completions, per_block)
    edges = window.aligned_edges(marks, t_warm, seconds)
    if edges is None:
        raise RuntimeError("cellbench: no window of %.0f s in the log"
                           % seconds)
    t_open, t_close = edges
    length = t_close - t_open
    n_tokens = window.count_in(arrivals, t_open, t_close)
    gaps_ms, p50, p99, longest, long_by_100 = window.reduce_gaps(
        token_times, t_open, t_close)
    in_win = [i for i in range(issued) if t_open < log.due[i] <= t_close]
    failed = [i for i in in_win if not isinstance(log.rows[i], np.ndarray)]

    e2e = {"serve_tokens_per_s": n_tokens / length,
           "serve_itl_p99_ms": p99, "serve_itl_p50_ms": p50}
    warm_gaps = sorted(((b - a, b) for ts in token_times
                        for a, b in zip(ts, ts[1:]) if b <= t_open),
                       reverse=True)[:3]
    ctx.log("warm", {"longest_gaps_ms_at_age_s": [
        [1e3 * g, b - ctx.t0] for g, b in warm_gaps]})
    ctx.log("window", {"open_s": t_open - ctx.t0, "length_s": length,
                       "requests_per_s": len(in_win) / length,
                       "queued_at_close": after["queued"],
                       "compiles_in_window": sum(
                           1 for t in ctx.compiles if t_open < t <= t_close),
                       "tokens": n_tokens, "gaps": len(gaps_ms),
                       "requests_due": len(in_win),
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
                    window.count_in(arrivals, *cut) / (cut[1] - cut[0]),
                "serve_itl_p99_ms": cut99, "serve_itl_p50_ms": cut50})
    ctx.log("phase", {"reduced_s": _NOW() - t_reduce})

    # -- correct: every finished row is well-formed; a seeded sample
    # of the rows finished in the window, the longest among them,
    # holds the tokens the plain reference puts first; and the logits
    # the decoder served from are the reference's, not its int8 twin's
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
                     pad_to=max(traffic["prompt_lengths"]) +
                     max(traffic["output_lengths"]),
                     served_to=max(traffic["output_lengths"]))
        t_ref = _NOW()
        want = list(ref.served_logits(cfg, ctx.seed, rows + probed,
                                      **shape))
        gaps = ref.served_gaps(rows, want[:len(rows)])
        size, share = ref.logit_errors(
            probe_logits, want[len(rows):],
            list(ref.served_logits(cfg, ctx.seed, probed, int8=True,
                                   **shape)))
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

    nominal_tokens = window.count_in(arrivals, before["t"], after["t"])
    readings = {
        "series": {"gap_ms": gaps_ms},
        **_stats_readings(before, after, traffic["slots"]),
        "client.tokens": nominal_tokens,
        "compiles.window": sum(1 for t in ctx.compiles
                               if t_warm < t <= t_stop),
        "jit.decode_programs": jit_programs,
        "memory.peak_bytes": peak,
    }
    return {"attempted": len(in_win), "failed": len(failed),
            "end_to_end": e2e, "setup_end": t_open, "checks": checks,
            "readings": readings, "memory_peak_bytes": peak,
            "trace": trace}
