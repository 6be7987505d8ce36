"""What the bench family shares (bench.py, bench_serve.py,
bench_scaling.py, benchmark/): the device a result names, the refusal
to measure without one, and the one-JSON-line shape of a failed run.
"""
import json
import os


def device_fields():
    """The device a result ran on, as JAX reports it. Every printed
    result carries these. Initialises the backend."""
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform,
            "device_kind": devs[0].device_kind,
            "device_count": len(devs)}


def require_accelerator(what):
    """A device metric taken on the host CPU is not one: refuse.
    Returns device_fields for the chip the process was given."""
    fields = device_fields()
    if fields["platform"] == "cpu":
        raise SystemExit(
            "%s: measures the accelerator, but jax.default_backend() is "
            "'cpu' — run it on the chip (see README.md, 'Running')"
            % what)
    return fields


def require_cpu_fleet(what):
    """Call before spawning replica processes. Each child builds a
    model on its default backend, and a chip belongs to one process:
    on a chip machine the second child fails or hangs at start-up. So
    a subprocess fleet runs only where the environment pins the
    platform to the CPU, where it measures the protocol and counts,
    not speed. (Replicas that each own a chip belong in one process —
    ROADMAP Reach 10.) Reads the environment only: the parent must not
    initialise a backend to find out."""
    if os.environ.get("JAX_PLATFORMS", "").strip().lower() != "cpu":
        raise SystemExit(
            "%s: starts several replica processes, which cannot share "
            "a chip; set JAX_PLATFORMS=cpu for the subprocess fleet "
            "(JAX_PLATFORMS is %r)"
            % (what, os.environ.get("JAX_PLATFORMS")))


def fail_payload(metric, unit, err, **extra):
    """The diagnostic line of a failed bench run: null value and the
    error. One place to evolve the contract a driver parses."""
    import traceback
    payload = {"metric": metric, "value": None, "unit": unit,
               "vs_baseline": None,
               "error": "".join(traceback.format_exception_only(
                   type(err), err)).strip()[:500]}
    payload.update(extra)
    return payload


def install_death_stub(metric, unit, **extra):
    """SIGTERM/SIGINT -> one parseable diagnostic JSON line, then
    exit 1. A bench killed mid-run (a CI timeout, a session script
    moving on) would otherwise die with nothing on stdout. With the
    stub installed the dying bench still emits the ``fail_payload``
    shape, so every exit of a bench process yields exactly one JSON
    line. Install it in main() BEFORE the heavy imports/workload: the
    whole point is covering the window where nothing else can.

    SIGKILL cannot be caught — that contract stops at the shell.

    Test hook: ``BENCH_TEST_HANG_AFTER_ARM=<seconds>`` prints
    ``BENCH_DEATH_STUB_ARMED`` to stderr and sleeps, so the
    kill-mid-run test (tests/test_bench_tools.py) has a deterministic
    window to deliver the signal in."""
    import signal
    import sys
    import time

    def _die(signum, _frame):
        err = RuntimeError(
            "killed by signal %d mid-run (no capture produced)"
            % signum)
        payload = fail_payload(metric, unit, err, signal=signum,
                               **extra)
        try:
            sys.stdout.write(json.dumps(payload) + "\n")
            sys.stdout.flush()
        finally:
            os._exit(1)

    for sig in (signal.SIGTERM, signal.SIGINT):
        signal.signal(sig, _die)
    hang = float(os.environ.get("BENCH_TEST_HANG_AFTER_ARM", 0) or 0)
    if hang:
        sys.stderr.write("BENCH_DEATH_STUB_ARMED\n")
        sys.stderr.flush()
        time.sleep(hang)
