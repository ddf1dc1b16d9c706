"""TPU backend diagnostics: root-cause a hanging/failing accelerator init.

This module is a tool for a process that does not hold the chip:
``python -m
deppy_tpu.utils.tpu_doctor`` probes the backend in a subprocess with a
timeout, classifies the outcome (healthy / worker-restarting / plugin
failure / no accelerator), reports suspicious sibling processes that may
be holding the chip, and exits 0 only on a healthy accelerator.  bench.py
embeds the same retry logic; this is the standalone "why is my TPU not
answering" entry point.
"""

from __future__ import annotations

import argparse
import os
import subprocess
import sys
import time

# The probe source lives in platform_env.probe_src (shared with
# bench.py): SIGALRM self-destruct, PJRT init, then a tiny
# compile+execute.  Stage markers on stdout (INIT / COMPUTE) ride the
# TimeoutExpired so _probe can tell WHICH stage hung.


def _probe(timeout_s: int) -> dict:
    """One subprocess probe.  Returns {status, backend?, init_s?, detail}.
    status: ok / cpu-only / error / hang (PJRT init never answered) /
    compute-hang (init answered, first compile+execute wedged — a sicker
    worker than a restarting one: init hangs clear in minutes, observed
    compute wedges have lasted hours).

    Uses :func:`platform_env.run_captured` so a wedged runtime helper
    holding the pipes cannot re-hang the doctor past its own timeout."""
    from .platform_env import parse_probe_stages, probe_src, run_captured

    try:
        rc, stdout, stderr = run_captured(
            [sys.executable, "-c", probe_src(timeout_s + 10)],
            timeout_s=timeout_s,
        )
    except subprocess.TimeoutExpired as e:
        partial = (e.output or "").strip()
        if "INIT" in partial:
            return {
                "status": "compute-hang",
                "detail": (
                    f"init ok ({partial.splitlines()[0]}) but a tiny "
                    f"compile+execute exceeded {timeout_s}s"
                ),
            }
        return {"status": "hang", "detail": f"init exceeded {timeout_s}s"}
    if rc != 0:
        tail = (stderr or "").strip().splitlines()[-3:]
        return {"status": "error", "detail": " | ".join(tail)}
    stages = parse_probe_stages(stdout)
    backend = stages.get("backend", "?")
    if backend == "?":
        # rc==0 but no parseable INIT line: the probe ran but its output
        # is garbage — that's a harness bug or output loss, not evidence
        # of a CPU-only host.  Classifying it "cpu-only" once made
        # diagnose() report "no accelerator" for a probe that succeeded.
        return {
            "status": "error",
            "detail": ("probe exited 0 with unparseable output: "
                       + repr((stdout or "").strip()[-200:])),
        }
    return {
        "status": "ok" if backend != "cpu" else "cpu-only",
        "backend": backend,
        # True per-stage timings from the probe's own clock (wall time
        # here would also count interpreter start + jax import).
        "init_s": stages.get("init_s"),
        "compute_s": stages.get("compute_s"),
        "detail": "; ".join((stdout or "").strip().splitlines()),
    }


def _chip_holders() -> list:
    """Best-effort list of other python processes that might hold the chip
    (a held chip makes init fail or hang until they exit)."""
    me = os.getpid()
    holders = []
    try:
        out = subprocess.run(
            ["pgrep", "-af", "python"], capture_output=True, text=True,
            timeout=10,
        )
        for line in (out.stdout or "").splitlines():
            pid_s, _, cmd = line.partition(" ")
            if "tpu_doctor" in cmd:  # ourselves / our parent shell
                continue
            if pid_s.isdigit() and int(pid_s) != me and (
                "jax" in cmd or "deppy" in cmd or "bench" in cmd
            ):
                # Truncate: agent/driver wrappers can carry multi-KB
                # command lines, and the report only needs the gist.
                cmd = cmd.strip()
                if len(cmd) > 160:
                    cmd = cmd[:160] + " ...[truncated]"
                holders.append(f"{pid_s} {cmd}")
    except (OSError, subprocess.TimeoutExpired):
        pass
    return holders


def diagnose(probe_timeout: int = 120, retries: int = 3,
             retry_delay: int = 90) -> int:
    """Run the diagnosis; prints a human report to stderr, returns an exit
    code: 0 healthy accelerator, 1 worker-restart suspected (retry in
    minutes), 2 plugin/config failure, 3 no accelerator configured,
    4 worker compute-wedged (init answers, compute hangs — observed
    recoveries take hours; no point retrying on a minutes scale, so this
    verdict short-circuits the retry loop)."""
    log = lambda m: print(m, file=sys.stderr, flush=True)  # noqa: E731
    plat = os.environ.get("JAX_PLATFORMS", "(unset)")
    log(f"JAX_PLATFORMS={plat}")
    hangs = 0
    for attempt in range(1, retries + 1):
        log(f"probe {attempt}/{retries} (timeout {probe_timeout}s)...")
        r = _probe(probe_timeout)
        if r["status"] == "ok":
            log(f"HEALTHY: backend={r['backend']} init={r['init_s']}s "
                f"compute={r.get('compute_s')}s ({r['detail']})")
            return 0
        if r["status"] == "cpu-only":
            log("NO ACCELERATOR: jax resolved to the CPU backend — either "
                "JAX_PLATFORMS pins cpu or no TPU plugin is registered.")
            return 3
        if r["status"] == "error":
            log(f"PLUGIN FAILURE: probe crashed: {r['detail']}")
            log("Likely a config/env problem, not a busy worker; fix the "
                "plugin before retrying.")
            return 2
        hangs += 1
        if r["status"] == "compute-hang":
            log(f"probe COMPUTE stage hung ({r['detail']}).")
            log("WORKER COMPUTE-WEDGED: the worker answers PJRT init but "
                "wedges on the first compile/execute — observed "
                "recoveries take hours, not minutes; treat the "
                "accelerator as down and use the CPU fallback until a "
                "probe goes fully healthy (`deppy doctor --watch`).")
            return 4
        log(f"probe hung ({r['detail']}).")
        holders = _chip_holders()
        if holders:
            log("other python processes that may hold the chip:")
            for h in holders[:8]:
                log(f"  {h}")
            log("if one of these is a stale run, terminate it and re-probe.")
        if attempt < retries:
            log(f"a crashed worker restarts in ~1-3 min; waiting "
                f"{retry_delay}s before the next probe...")
            time.sleep(retry_delay)
    log(f"WORKER RESTART SUSPECTED: {hangs}/{retries} probes hung. "
        "A crashed/restarting TPU worker blocks PJRT init for minutes; "
        "wait and re-run, and keep per-dispatch lane counts bounded "
        "(DEPPY_TPU_MAX_LANES) so programs do not crash it again.")
    return 1


def watch(interval: int = 600, probe_timeout: int = 120,
          log_path: str = "", until_healthy: bool = False,
          terminal_consecutive: int = 3) -> int:
    """Periodic health monitor: one compute probe per tick, one JSON line
    per result appended to ``log_path`` (and echoed to stderr).  With
    ``until_healthy`` the loop exits 0 at the first fully healthy probe —
    the building block for scripts that wait out a worker outage before
    launching accelerator work (`deppy doctor --watch --until-healthy &&
    make bench`) — and exits with :func:`diagnose`'s code on a status
    waiting cannot heal (no accelerator configured: 3, plugin/config
    failure: 2).  Hang statuses keep waiting; outlasting them is the
    point of the mode.

    Terminal statuses (error / cpu-only) must accumulate
    ``terminal_consecutive`` probes IN A ROW before the loop gives up:
    during a worker flap a single probe can crash (rc!=0 → "error") or
    catch jax mid-fallback-to-CPU ("cpu-only"), and a mode whose whole
    purpose is outlasting instability must not abort on one bad sample.
    The streak is over terminal-ness, not the exact status — a broken
    plugin that alternates error/cpu-only must still terminate (the
    exit code follows the last probe) — and any non-terminal probe
    (hang, compute-hang: the worker exists and may heal) resets it."""
    import json

    terminal_streak = 0
    while True:
        r = _probe(probe_timeout)
        rec = {"ts": round(time.time(), 1), **r}
        line = json.dumps(rec)
        print(line, file=sys.stderr, flush=True)
        if log_path:
            with open(log_path, "a") as f:
                f.write(line + "\n")
        if until_healthy:
            if r["status"] == "ok":
                return 0
            if r["status"] in ("cpu-only", "error"):
                terminal_streak += 1
                if terminal_streak >= terminal_consecutive:
                    return 3 if r["status"] == "cpu-only" else 2
            else:
                terminal_streak = 0
        time.sleep(interval)


def add_doctor_args(ap: argparse.ArgumentParser) -> None:
    """The doctor's flags, shared by this module's CLI and ``deppy
    doctor`` (cli.py) so defaults live in exactly one place — the
    :func:`diagnose` signature."""
    import inspect

    d = {
        k: p.default
        for k, p in inspect.signature(diagnose).parameters.items()
    }
    ap.add_argument("--probe-timeout", type=int, default=d["probe_timeout"])
    ap.add_argument("--retries", type=int, default=d["retries"])
    ap.add_argument("--retry-delay", type=int, default=d["retry_delay"])
    w = {
        k: p.default for k, p in inspect.signature(watch).parameters.items()
    }
    ap.add_argument("--watch", action="store_true",
                    help="loop forever (or until --until-healthy) probing "
                    "every --interval seconds, one JSON line per probe")
    ap.add_argument("--interval", type=int, default=w["interval"])
    ap.add_argument("--log", default=w["log_path"],
                    help="append watch-mode JSON lines to this file")
    ap.add_argument("--until-healthy", action="store_true",
                    help="watch mode exits 0 at the first healthy probe")
    ap.add_argument("--terminal-consecutive", type=int,
                    default=w["terminal_consecutive"],
                    help="watch mode gives up on error/cpu-only only "
                    "after this many consecutive probes agree (1 "
                    "restores fail-fast)")


def run_from_args(args) -> int:
    """Dispatch parsed doctor args (shared by ``deppy doctor`` and the
    module CLI)."""
    if getattr(args, "watch", False):
        return watch(args.interval, args.probe_timeout, args.log,
                     args.until_healthy, args.terminal_consecutive)
    return diagnose(args.probe_timeout, args.retries, args.retry_delay)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    add_doctor_args(ap)
    sys.exit(run_from_args(ap.parse_args()))


if __name__ == "__main__":
    main()
