#!/usr/bin/env python
"""Distributed job launcher (reference: tools/launch.py + dmlc_tracker).

The reference launched scheduler/server/worker processes over
ssh/mpi/sge/yarn. TPU-native clusters run ONE SPMD program per host, so
the launcher's job collapses to: pick a coordinator, assign process ids,
start the same command everywhere with the right env
(mxnet_tpu.parallel.dist.init() reads it — DMLC_* names kept for
reference-script compat).

  # N local processes on one host (the dmlc_tracker 'local' mode —
  # how the multi-process tests run without a cluster). Every worker
  # gets the same environment, so this is for the CPU
  # (JAX_PLATFORMS=cpu): on a host with chips each worker would claim
  # all of them, and a chip belongs to one process. One process drives
  # all the chips of a host (mesh= / layout=).
  JAX_PLATFORMS=cpu python tools/launch.py -n 4 --launcher local \
      python train.py

  # one process per host over ssh:
  python tools/launch.py -n 2 --launcher ssh -H hosts.txt python train.py
"""
from __future__ import annotations

import argparse
import os
import shlex
import socket
import subprocess
import sys


def _free_port():
    with socket.socket() as s:
        s.bind(("", 0))
        return s.getsockname()[1]


def _free_port_block(n):
    """Base port with ports base..base+n-1 all currently bindable —
    dist_async server i binds base+i (ps_async.server_endpoints), so
    checking only the base would let one occupied follow-on port kill
    the whole job at startup."""
    if n <= 1:
        return _free_port()
    for _ in range(100):
        base = _free_port()
        ok = True
        for i in range(1, n):
            with socket.socket() as s:
                try:
                    s.bind(("", base + i))
                except OSError:
                    ok = False
                    break
        if ok:
            return base
    raise RuntimeError("no block of %d consecutive free ports found" % n)


def _worker_env(rank, n, coord_uri, coord_port, extra=()):
    env = dict(os.environ)
    env.update({
        "DMLC_ROLE": "worker",
        "DMLC_PS_ROOT_URI": coord_uri,
        "DMLC_PS_ROOT_PORT": str(coord_port),
        "DMLC_NUM_WORKER": str(n),
        "DMLC_WORKER_ID": str(rank),
    })
    env.update(dict(extra))
    return env


def _server_env(sid, n_workers, n_servers, coord_uri, coord_port,
                extra=()):
    """Server-role env (dist_async parameter-server shard sid; servers
    bind coord_port+sid — parallel/ps_async.server_endpoints)."""
    env = dict(os.environ)
    env.update({
        "DMLC_ROLE": "server",
        "DMLC_PS_ROOT_URI": coord_uri,
        "DMLC_PS_ROOT_PORT": str(coord_port),
        "DMLC_NUM_WORKER": str(n_workers),
        "DMLC_NUM_SERVER": str(n_servers),
        "DMLC_SERVER_ID": str(sid),
    })
    env.update(dict(extra))
    return env


def launch_local(n, command, env_extra=(), num_servers=0):
    """Fork n local worker processes (dmlc_tracker 'local' launcher),
    plus num_servers parameter-server processes for dist_async (the
    reference tracker launched servers the same way: same command,
    DMLC_ROLE=server — the framework import enters the server loop).
    If any process dies, the survivors are killed — a partial cluster
    would block forever inside jax.distributed.initialize."""
    import time
    port = _free_port_block(max(1, num_servers))
    extra = list(env_extra)
    if num_servers:
        extra.append(("DMLC_NUM_SERVER", str(num_servers)))
    procs = [subprocess.Popen(
        command, env=_server_env(s, n, num_servers, "127.0.0.1", port,
                                 env_extra))
        for s in range(num_servers)]
    procs += [subprocess.Popen(
        command, env=_worker_env(r, n, "127.0.0.1", port, extra))
        for r in range(n)]
    rc = 0
    while True:
        codes = [p.poll() for p in procs]
        bad = [c for c in codes if c not in (None, 0)]
        if bad and any(c is None for c in codes):
            for p in procs:
                if p.poll() is None:
                    p.kill()
        if all(c is not None for c in codes):
            rc = next((c for c in codes if c), 0)
            break
        time.sleep(0.1)
    return rc


def launch_ssh(n, hosts, command, env_extra=()):
    """One worker per host over ssh; host 0 is the coordinator."""
    if len(hosts) < n:
        raise SystemExit("need %d hosts, got %d" % (n, len(hosts)))
    port = _free_port()
    cmd_str = " ".join(shlex.quote(c) for c in command)
    procs = []
    for r in range(n):
        env = _worker_env(r, n, hosts[0], port, env_extra)
        keys = ["DMLC_ROLE", "DMLC_PS_ROOT_URI", "DMLC_PS_ROOT_PORT",
                "DMLC_NUM_WORKER", "DMLC_WORKER_ID"] + \
            [k for k, _ in env_extra]
        exports = " ".join("%s=%s" % (k, shlex.quote(str(env[k])))
                           for k in keys)
        procs.append(subprocess.Popen(
            ["ssh", "-o", "StrictHostKeyChecking=no", hosts[r],
             "cd %s && env %s %s" % (shlex.quote(os.getcwd()), exports,
                                     cmd_str)]))
    rc = 0
    for p in procs:
        rc = p.wait() or rc
    return rc


def main(argv=None):
    ap = argparse.ArgumentParser(
        description="launch a distributed mxnet_tpu job")
    ap.add_argument("-n", "--num-workers", type=int, required=True)
    ap.add_argument("-s", "--num-servers", type=int, default=0,
                    help="parameter-server process count (dist_async; "
                         "0 = collective-only job, no servers)")
    ap.add_argument("--launcher", choices=("local", "ssh"),
                    default="local",
                    help="local: N processes on this host with one "
                         "shared environment — CPU multi-process runs "
                         "(JAX_PLATFORMS=cpu), not chips: every worker "
                         "would claim all of them. ssh: one process "
                         "per host, the shape a pod slice wants")
    ap.add_argument("-H", "--hostfile",
                    help="one host per line (ssh launcher)")
    ap.add_argument("--env", action="append", default=[],
                    metavar="K=V", help="extra env for every worker")
    ap.add_argument("command", nargs=argparse.REMAINDER)
    args = ap.parse_args(argv)
    if not args.command:
        ap.error("no command given")
    for kv in args.env:
        if "=" not in kv:
            ap.error("--env expects K=V, got %r" % kv)
    extra = [kv.split("=", 1) for kv in args.env]

    if args.launcher == "local":
        return launch_local(args.num_workers, args.command, extra,
                            num_servers=args.num_servers)
    if args.num_servers:
        ap.error("--num-servers is supported by the local launcher "
                 "only (ssh server placement needs explicit "
                 "MXNET_PS_SERVER_URIS)")
    with open(args.hostfile) as f:
        hosts = [ln.strip() for ln in f if ln.strip()]
    return launch_ssh(args.num_workers, hosts, args.command, extra)


if __name__ == "__main__":
    sys.exit(main())
