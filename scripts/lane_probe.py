"""Find the safe dispatch-width boundary of the engine on the chip.

The engine caps dispatches at DEPPY_TPU_MAX_LANES=512, a value not yet
measured on a local chip; whether a limit there is the LANE COUNT or
the total program size (bytes/execution time) was never separated.  This probe escalates the
lane width on two instance sizes — headline (length 48) and half-size
(length 24, ~half the clause planes) — so the two hypotheses give
different outcomes:

  * both shapes fail at 1024  -> lane-count bound: keep 512.
  * half-size passes 1024+ where headline fails -> bytes/time bound:
    the cap should scale with per-lane plane bytes
    (DEPPY_TPU_MAX_LANES can rise for small-problem fleets).

Each step runs in a DISPOSABLE subprocess (run_captured + SIGALRM
self-destruct env) so a worker wedge kills the step, not this process,
and the worker's health is re-probed between steps; the sweep aborts on
the first unhealthy probe since results after a crash measure the
restarting worker, not the policy.  One JSON line per step on stdout.

CAUTION: expected to crash the worker at the boundary, after which PJRT
init can hang for hours.  Run it when a crash is affordable (hours
before the next scheduled benchmark), not right before one.
"""

from __future__ import annotations

import argparse
import os
import subprocess
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from scripts._stage import emit, make_healthy  # noqa: E402

STEP_SRC = """
import os, signal, time
signal.alarm({alarm})
import jax
from deppy_tpu.engine import driver
from deppy_tpu.models import random_instance
from deppy_tpu.sat.encode import encode
problems = [encode(random_instance(length={length}, seed=s))
            for s in range({width})]
t0 = time.perf_counter()
driver.solve_problems(problems)
warm = time.perf_counter() - t0
t0 = time.perf_counter()
res = driver.solve_problems(problems)
run = time.perf_counter() - t0
print("STEP", jax.default_backend(), round(warm, 2), round(run, 3),
      round({width} / run, 1), flush=True)
os._exit(0)
"""


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--widths", default="512,1024,2048,4096")
    ap.add_argument("--lengths", default="24,48")
    ap.add_argument("--step-timeout", type=int, default=420)
    ap.add_argument("--probe-timeout", type=int, default=120)
    ap.add_argument("--log", default="",
                    help="also append each JSON line to this file (the "
                    "revalidation ladder passes its own log so the "
                    "lane verdict survives the stage)")
    a = ap.parse_args()

    from deppy_tpu.utils.platform_env import run_captured

    widths = [int(w) for w in a.widths.split(",")]
    lengths = [int(s) for s in a.lengths.split(",")]
    any_ok = [False]
    # Backend pin (cpu-only acceptance covers forced-CPU smoke runs of
    # the sweep): after a boundary crash the next disposable subprocess
    # would silently fall back to CPU and report widths as "passed" that
    # the device never ran — the pin makes the flip an abort instead.
    expected = [None]
    healthy = make_healthy(a.probe_timeout, True, expected, a.log)
    for width in widths:           # escalate width, small shape first
        for length in sorted(lengths):
            if not healthy():
                # Nonzero so rc-reading callers (ladder stage I) see an
                # aborted sweep as a failure, not a green stage.
                sys.exit(1)
            env = dict(os.environ)
            env["DEPPY_TPU_MAX_LANES"] = str(width)
            rec = {"width": width, "length": length}
            t0 = time.time()
            try:
                rc, out, err = run_captured(
                    [sys.executable, "-c",
                     STEP_SRC.format(alarm=a.step_timeout + 30,
                                     length=length, width=width)],
                    timeout_s=a.step_timeout, env=env,
                    # ROOT, not ".": the subprocess needs deppy_tpu
                    # importable regardless of the operator's cwd.
                    cwd=os.path.dirname(os.path.dirname(
                        os.path.abspath(__file__))),
                )
                line = next((l for l in (out or "").splitlines()
                             if l.startswith("STEP")), "")
                parts = line.split()
                rec.update(
                    ok=rc == 0 and len(parts) == 5,
                    backend=parts[1] if len(parts) > 1 else None,
                    warm_s=float(parts[2]) if len(parts) > 2 else None,
                    run_s=float(parts[3]) if len(parts) > 3 else None,
                    rate=float(parts[4]) if len(parts) > 4 else None,
                )
                if rc != 0:
                    rec["stderr_tail"] = (err or "").strip()[-300:]
            except subprocess.TimeoutExpired:
                rec.update(ok=False, timeout_s=a.step_timeout)
            rec["wall_s"] = round(time.time() - t0, 1)
            emit(rec, a.log)
            if rec["ok"]:
                if expected[0] is None:
                    expected[0] = rec["backend"]
                elif rec["backend"] != expected[0]:
                    # The step subprocess itself fell back (e.g. PJRT
                    # init failed post-crash while the probe cached a
                    # healthier verdict): its numbers are for the wrong
                    # backend — abort rather than record them as passed.
                    emit({"abort": "step backend flipped", "got":
                          rec["backend"], "expected": expected[0]}, a.log)
                    sys.exit(1)
                any_ok[0] = True
            if not rec["ok"]:
                emit({"abort": "step failed; stopping sweep "
                      "before burying the worker deeper"}, a.log)
                # A boundary crash is this probe's EXPECTED terminal
                # outcome and still a completed sweep from the ladder's
                # point of view (stage I runs last for exactly this), but
                # rc must still distinguish "measured up to the boundary"
                # from "measured nothing": exit 0 only if at least one
                # step succeeded.
                sys.exit(0 if any_ok[0] else 1)


if __name__ == "__main__":
    main()
