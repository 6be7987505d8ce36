"""Deterministic chaos harness for the serve fleet
(docs/robustness.md §fleet failure semantics).

A subprocess replica fleet runs a closed-loop generate workload while
a declarative kill schedule SIGKILLs child replicas mid-run and
restarts them. The acceptance property is the fleet's whole
robustness contract in one sentence: EVERY request resolves to
exactly one successful response, token-for-token equal to the
fault-free run — greedy and seeded alike, and a STREAMED request's
concatenated frame tokens byte-equal the fault-free generated tail
even when the kill fires mid-stream (no duplicated, no missing
frames). Every second streamed request additionally decodes
SPECULATIVELY (a 1-layer truncated draft on every replica via
``MXNET_SPEC_DRAFT``, docs/serving.md §speculative) — the oracle
stays a plain generate, because speculation must never change a
byte, kills and draft-equipped failover replays included.

The schedule is the ``kill<I>`` member of the ``MXNET_FAULT_SPEC``
step-rule family (``parallel/resilience.py``): the call counted is
one COMPLETED fleet request, so the schedule is deterministic in
request-completion order, never wall time::

    python tools/chaos_fleet.py                     # 3 replicas,
                                                    # 6 clients x 25,
                                                    # kill1@40
    python tools/chaos_fleet.py --fault-spec kill0@20;kill2@80
    MXNET_FAULT_SPEC=kill2@60 python tools/chaos_fleet.py
    python tools/chaos_fleet.py --smoke             # perf-gate smoke
    python tools/chaos_fleet.py --controller        # controller tier
    python tools/chaos_fleet.py --controller --smoke

``--controller`` hands replica lifecycle to the ``FleetController``
(docs/serving.md §fleet controller): the harness only SIGKILLs —
the controller's own suspect -> probe -> heal path must respawn the
victim under the same name (the harness's restart thread is disabled,
so a controller that fails to heal FAILS the run: heals must equal
kills). The acceptance contract is unchanged on top: every request
exactly one response, byte-equal to the fault-free oracle.

``kill1@40`` SIGKILLs child replica index 1 when the 40th request
completes; the harness then restarts it (new subprocess, re-admitted
to the router under the same name) while the surviving replicas
absorb the load. Requests in flight on the victim fail over through
the router's recovery record (token-exact replay, dedup-guarded);
established decode sessions re-pin. The fault-free oracle is an
in-process ``Generator`` over the same deterministic seed-0 params
every replica builds, so byte-equality needs no second fleet run.

One JSON line out (``{"metric": "chaos_fleet", "ok": ...}``), exit
status 0 only when every request met the contract.
"""
import argparse
import json
import os
import re
import sys
import threading
import time

os.environ.setdefault("MXNET_MATMUL_PRECISION", "default")
sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import numpy as np  # noqa: E402

_KILL_RE = re.compile(r"(?:^|;)\s*kill(\d+)@")


def _lm_params(args):
    """Deterministic transformer-LM params every process shares (same
    seed everywhere — a migrated session's KV rows must be THIS
    model's rows on the survivor too)."""
    import mxnet_tpu as mx
    from mxnet_tpu.initializer import Xavier
    from mxnet_tpu.models import transformer
    from mxnet_tpu.parallel import make_train_step

    sym = transformer.get_symbol(
        args.lm_vocab, 12, num_layers=args.lm_layers,
        num_heads=args.lm_heads, dim=args.lm_dim,
        max_len=args.lm_max_len)
    step = make_train_step(sym, optimizer="sgd")
    mx.random.seed(0)
    return step.init_state(Xavier(), {"data": (2, 12),
                                      "softmax_label": (2, 12)})[0]


def _lm_generator(args, batch_size):
    from mxnet_tpu.generation import Generator
    return Generator(_lm_params(args), args.lm_vocab, args.lm_max_len,
                     num_layers=args.lm_layers,
                     num_heads=args.lm_heads, dim=args.lm_dim,
                     batch_size=batch_size)


def _replica_child(args):
    """``--replica`` subprocess body: one ContinuousDecoder +
    ServeServer, port announced as one JSON line on stdout, serving
    until stdin closes. ``install_sigterm=True``: a polite TERM
    evacuates active sessions back to the router instead of killing
    them — the harness's SIGKILL is the impolite case the failover
    path owns."""
    from mxnet_tpu.serve import ContinuousDecoder, ServeServer

    eng = ContinuousDecoder(_lm_generator(args, args.slots),
                            queue_cap=256, install_sigterm=True)
    srv = ServeServer(eng)
    print(json.dumps({"port": srv.port, "host": srv.host}), flush=True)
    try:
        while sys.stdin.readline():       # parent holds the pipe open
            pass
    finally:
        srv.close()
        eng.close(timeout=30.0)
    return 0


def _spawn_replica(args):
    """One replica subprocess; returns (proc, (host, port)). The
    child's env drops MXNET_FAULT_SPEC — kill rules schedule the
    PARENT's SIGKILLs; replicas themselves run fault-free."""
    import select
    import subprocess
    env = dict(os.environ)
    env.pop("MXNET_FAULT_SPEC", None)
    cmd = [sys.executable, os.path.abspath(__file__), "--replica",
           "--slots", str(args.slots),
           "--lm-vocab", str(args.lm_vocab),
           "--lm-dim", str(args.lm_dim),
           "--lm-layers", str(args.lm_layers),
           "--lm-heads", str(args.lm_heads),
           "--lm-max-len", str(args.lm_max_len)]
    proc = subprocess.Popen(cmd, stdin=subprocess.PIPE,
                            stdout=subprocess.PIPE, text=True,
                            env=env)
    deadline = time.monotonic() + 300.0   # package import is the cost
    remain = deadline - time.monotonic()
    if remain <= 0 or not select.select([proc.stdout], [], [],
                                        remain)[0]:
        proc.kill()
        raise RuntimeError("replica startup timed out (rc=%s)"
                           % proc.poll())
    line = proc.stdout.readline()
    if not line:
        raise RuntimeError(
            "replica died before announcing its port (rc=%s)"
            % proc.poll())
    rec = json.loads(line)
    return proc, (rec["host"], rec["port"])


def _kill_fleet(procs):
    for p in procs:
        if p is None or p.poll() is not None:
            continue
        try:
            p.stdin.close()               # EOF = drain + exit
        except OSError:
            pass
    for p in procs:
        if p is None:
            continue
        try:
            p.wait(15.0)
        except Exception:  # noqa: BLE001 — escalate to kill
            p.kill()


def _request_plan(args):
    """The full request matrix, deterministic in (client, j): mixed
    greedy / seeded sampling, varied prompt lengths, eos enabled (a
    random tiny LM does emit eos early — the oracle matches
    bit-for-bit, so early stops are covered, not avoided). Every
    third request STREAMS (on_token frames): its collected tokens
    must concatenate byte-equal to the fault-free row's generated
    tail even when the kill schedule fires mid-stream — the
    delivered-prefix replay contract (docs/robustness.md §mid-stream
    failover)."""
    plan = {}
    for c in range(args.clients):
        for j in range(args.requests):
            rng = np.random.RandomState(7919 + 131 * c + j)
            prompt = rng.randint(1, args.lm_vocab,
                                 (3 + (c + j) % 4,)).astype(np.int64)
            seeded = (j % 2 == 1)
            plan[(c, j)] = {
                "prompt": prompt,
                "temperature": 0.8 if seeded else 0.0,
                "top_k": 8 if seeded else None,
                "seed": 1000 * c + j,
                "stream": (c + j) % 3 == 0,
                # every second STREAMED request runs speculatively
                # (docs/serving.md §speculative): the hint must
                # change nothing the oracle can see — same bytes
                # through draft/verify rounds, kills and replays on
                # draft-equipped survivors included
                "speculative": (c + j) % 6 == 0,
            }
    return plan


def _oracle_rows(args, plan):
    """The fault-free run: one in-process Generator emits every
    request's expected row up front (generate is deterministic, so
    this IS what an unfaulted fleet returns)."""
    gen = _lm_generator(args, 1)
    want = {}
    for key in sorted(plan):
        r = plan[key]
        want[key] = gen.generate(
            r["prompt"][None], args.max_new, eos_id=0,
            temperature=r["temperature"], top_k=r["top_k"],
            seed=r["seed"])[0]
    return want


def _run(args):
    from mxnet_tpu import telemetry
    from mxnet_tpu.parallel.resilience import FaultInjector
    from mxnet_tpu.serve import FleetController, ServeRouter

    spec = args.fault_spec or os.environ.get("MXNET_FAULT_SPEC") \
        or args.default_spec
    inj = FaultInjector(spec)             # validates the rule grammar
    kill_points = sorted({int(m) for m in _KILL_RE.findall(spec)})
    for i in kill_points:
        if i >= args.replicas:
            raise SystemExit(
                "kill%d@... targets a replica the fleet does not "
                "have (--replicas %d)" % (i, args.replicas))

    # every replica (restarts included — _spawn_replica copies this
    # env) builds a 1-layer truncated draft: speculative requests run
    # draft/verify rounds, and a kill mid-round fails over to a
    # survivor that decodes them speculatively too. The oracle stays
    # a PLAIN in-process generate — speculation is a performance
    # hint, so byte-equality against the unsped run IS the contract.
    os.environ.setdefault("MXNET_SPEC_DRAFT", "layers=1,gamma=4")

    plan = _request_plan(args)
    want = _oracle_rows(args, plan)

    procs, router, ctrl = [None] * args.replicas, None, None
    procs_by_addr = {}                    # "host:port" -> proc
    restarts, kills = [], []
    tick_lock = threading.Lock()
    completed = [0]
    results = {k: [] for k in plan}
    stream_toks = {k: [] for k in plan if plan[k]["stream"]}
    failures = []

    def ctrl_spawn(manifest=None):
        """Controller spawn hook (also boots the initial fleet):
        one subprocess replica, tracked by address so the retire
        hook can reap exactly the process behind a fleet slot."""
        proc, (host, port) = _spawn_replica(args)
        procs_by_addr["%s:%d" % (host, port)] = proc
        if router is not None:            # heal/rollout, not boot
            restarts.append({"at_request": completed[0]})
        return (host, port)

    def ctrl_retire(name, addr):
        proc = procs_by_addr.pop(addr, None)
        if proc is None:
            return
        if proc.poll() is None:
            try:
                proc.stdin.close()        # EOF = drain + exit
            except OSError:
                pass
        try:
            proc.wait(15.0)
        except Exception:  # noqa: BLE001 — escalate to kill
            proc.kill()

    def restart_replica(i, name):
        """Background: boot a fresh child, then swap it in under the
        victim's name (remove drops the dead entry's pins; in-flight
        requests to it fail over through the normal fault path)."""
        proc, (host, port) = _spawn_replica(args)
        procs[i] = proc
        try:
            router.remove_replica(name)
        except KeyError:
            pass
        router.add_replica(host, port, name=name)
        restarts.append({"replica": i, "at_request": completed[0]})

    def on_complete():
        with tick_lock:
            completed[0] += 1
            fired = [i for i in kill_points
                     if inj.on_chaos_tick("kill%d" % i)]
            for i in fired:
                name = "replica%d" % i
                if args.controller:
                    desc = router.replicas().get(name)
                    p = procs_by_addr.get(
                        "%s:%d" % (desc["host"], desc["port"])) \
                        if desc else None
                else:
                    p = procs[i]
                if p is not None and p.poll() is None:
                    p.kill()              # SIGKILL — no goodbye frame
                    p.wait()
                kills.append({"replica": i,
                              "at_request": completed[0]})
                if args.controller:
                    continue              # the CONTROLLER must heal it
                t = threading.Thread(
                    target=restart_replica,
                    args=(i, name), daemon=True)
                t.start()
                restart_threads.append(t)

    def client(c):
        for j in range(args.requests):
            r = plan[(c, j)]
            toks = [] if r["stream"] else None
            try:
                row = router.generate(
                    r["prompt"], args.max_new, eos_id=0,
                    temperature=r["temperature"], top_k=r["top_k"],
                    seed=r["seed"], session="c%d" % c,
                    timeout=args.deadline,
                    speculative=r["speculative"],
                    on_token=toks.append if r["stream"] else None)
            except Exception as exc:  # noqa: BLE001 — a failed
                # request IS the finding this harness exists to catch
                failures.append({"client": c, "j": j,
                                 "error": "%s: %s"
                                 % (type(exc).__name__, exc)})
                continue
            results[(c, j)].append(np.asarray(row))
            if r["stream"]:
                stream_toks[(c, j)].append(np.asarray(toks,
                                                      np.int64))
            on_complete()

    def heals():
        return int(telemetry.counter("serve.ctrl.heals").value)

    restart_threads = []
    t0 = time.monotonic()
    try:
        for i in range(args.replicas):
            if args.controller:
                addr = ctrl_spawn()
            else:
                procs[i], addr = _spawn_replica(args)
            if i == 0:
                addrs = []
            addrs.append(addr)
        router = ServeRouter(poll_ms=args.poll_ms,
                             conns_per_replica=args.clients + 2)
        for i, (host, port) in enumerate(addrs):
            router.add_replica(host, port, name="replica%d" % i)
        if args.controller:
            # supervision only — the huge sustain keeps autoscaling
            # out of the chaos contract, heal is streak-exempt
            ctrl = FleetController(router, ctrl_spawn,
                                   retire=ctrl_retire,
                                   min_replicas=1,
                                   max_replicas=args.replicas,
                                   sustain=10 ** 6,
                                   poll_ms=100.0)
        threads = [threading.Thread(target=client, args=(c,))
                   for c in range(args.clients)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        for t in restart_threads:
            t.join(300.0)
        if ctrl is not None:
            # the controller owns respawn: hold the fleet open until
            # its heal count catches the kill schedule (bounded)
            deadline = time.monotonic() + 300.0
            while heals() < len(kills) and \
                    time.monotonic() < deadline:
                time.sleep(0.1)
        fleet = router.stats()
    finally:
        if ctrl is not None:
            ctrl.close()
        if router is not None:
            router.close()
        _kill_fleet(procs + list(procs_by_addr.values()))
    wall = time.monotonic() - t0

    mismatches = []
    for key in sorted(plan):
        got = results[key]
        if len(got) != 1:
            mismatches.append({"client": key[0], "j": key[1],
                               "responses": len(got)})
        elif not np.array_equal(got[0], want[key]):
            mismatches.append({"client": key[0], "j": key[1],
                               "got": got[0].tolist(),
                               "want": want[key].tolist()})
        elif key in stream_toks:
            # the streamed contract: concatenated frame tokens ==
            # the fault-free generated tail, exactly once, even when
            # a kill fired mid-stream
            tail = want[key][len(plan[key]["prompt"]):]
            if not np.array_equal(stream_toks[key][0], tail):
                mismatches.append(
                    {"client": key[0], "j": key[1], "kind": "stream",
                     "got": stream_toks[key][0].tolist(),
                     "want": tail.tolist()})

    def cval(name):
        e = telemetry.snapshot().get(name)
        return int(e["value"]) if e else 0

    ok = not failures and not mismatches and \
        len(kills) == len(kill_points) and \
        len(restarts) == len(kills) and \
        (not args.controller or cval("serve.ctrl.heals") == len(kills))
    print(json.dumps({
        "metric": "chaos_fleet",
        "ok": ok,
        "controller": bool(args.controller),
        "heals": cval("serve.ctrl.heals") if args.controller else None,
        "requests": args.clients * args.requests,
        "streamed": len(stream_toks),
        "speculative": sum(1 for r in plan.values()
                           if r["speculative"]),
        "clients": args.clients,
        "replicas": args.replicas,
        "fault_spec": spec,
        "kills": kills,
        "restarts": restarts,
        "failures": failures[:10],
        "mismatches": mismatches[:10],
        "failovers": cval("serve.router.failovers"),
        "replays": cval("serve.router.replays"),
        "migrations": cval("serve.router.migrations"),
        "rerouted": fleet.get("rerouted"),
        "wall_s": round(wall, 2)}))
    return 0 if ok else 1


def require_cpu_fleet():
    """Call before spawning replica processes. Each child builds a
    model on its default backend, and a chip belongs to one process:
    on a chip machine the second child fails or hangs at start-up. So
    a subprocess fleet runs only where the environment pins the
    platform to the CPU, where it checks the protocol and counts, not
    speed. Reads the environment only: the parent must not initialise
    a backend to find out."""
    if os.environ.get("JAX_PLATFORMS", "").strip().lower() != "cpu":
        raise SystemExit(
            "chaos_fleet.py: starts several replica processes, which "
            "cannot share a chip; set JAX_PLATFORMS=cpu for the "
            "subprocess fleet (JAX_PLATFORMS is %r)"
            % os.environ.get("JAX_PLATFORMS"))


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--replicas", type=int, default=3)
    p.add_argument("--clients", type=int, default=6)
    p.add_argument("--requests", type=int, default=25,
                   help="generates per client")
    p.add_argument("--max-new", type=int, default=8)
    p.add_argument("--deadline", type=float, default=240.0,
                   help="per-request end-to-end budget (seconds)")
    p.add_argument("--fault-spec", default=None,
                   help="kill schedule (MXNET_FAULT_SPEC kill<I>@nth "
                        "family; default env MXNET_FAULT_SPEC, then "
                        "the built-in schedule)")
    p.add_argument("--poll-ms", type=int, default=50)
    p.add_argument("--slots", type=int, default=4,
                   help="decode slots per replica")
    p.add_argument("--smoke", action="store_true",
                   help="perf-gate scale: 2 replicas, 2 clients x 3 "
                        "requests, kill1@2")
    p.add_argument("--controller", action="store_true",
                   help="controller tier: the FleetController owns "
                        "respawn (harness restart thread disabled); "
                        "heals must equal kills")
    p.add_argument("--lm-vocab", type=int, default=50)
    p.add_argument("--lm-dim", type=int, default=32)
    p.add_argument("--lm-layers", type=int, default=2)
    p.add_argument("--lm-heads", type=int, default=2)
    p.add_argument("--lm-max-len", type=int, default=24)
    p.add_argument("--replica", action="store_true",
                   help=argparse.SUPPRESS)   # internal: child mode
    args = p.parse_args(argv)
    if args.smoke:
        args.replicas, args.clients, args.requests = 2, 2, 3
        args.default_spec = "kill1@2"
    else:
        args.default_spec = "kill1@40"
    if args.replica:
        return _replica_child(args)
    require_cpu_fleet()
    return _run(args)


if __name__ == "__main__":
    sys.exit(main())
