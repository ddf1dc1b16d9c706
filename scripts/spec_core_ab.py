"""A/B the speculative unsat-core path on the accelerator.

Round-3 verdict weak #4: ``DEPPY_TPU_SPEC_CORE`` defaulted toward a path
with ZERO accelerator measurements, so its ``auto`` now resolves OFF
everywhere (engine/driver.py) until a measured row exists.  This script
produces that row: the giant-pinned-conflict catalog (the workload the
speculative sweep was built for — a 3-constraint core buried in ~1.7k
constraints) solved end to end with the sweep forced ON vs forced OFF,
each in a disposable subprocess with a health probe between runs,
aborting on the first failure or backend flip.

The OFF run routes core extraction to the host spec engine
(HOST_CORE_NCONS); the ON run dispatches the batched deletion probes to
the device.  Outcome parity (the rendered core) is checked as well as
time: trust-but-verify already guarantees correctness, so a divergence
here means a harness bug, not an engine bug.

Run after a green revalidation ladder (it is stage H there):

  python scripts/spec_core_ab.py [--packages 250] [--log /tmp/spec.jsonl]
"""

from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from scripts._stage import emit, make_healthy, run_stage

# {alarm}: SIGALRM self-destruct; {packages}/{versions}: catalog scale.
# DEPPY_TPU_SPEC_CORE is forced via the subprocess env by the runner.
# The STAGE line carries solve time as run_s and 1/run_s as rate so
# _stage's parser applies unchanged; the rendered core rides a separate
# CORE line (captured via capture_prefixes for the parity check).
STAGE_SRC = """
import os, signal, time
signal.alarm({alarm})
from deppy_tpu.utils.platform_env import apply_platform_env
apply_platform_env()
import jax
from deppy_tpu import sat
from deppy_tpu.models import giant_pinned_conflict
vs = giant_pinned_conflict(n_packages={packages},
                           versions_per_package={versions}, seed=0)
solver = sat.Solver(vs, backend="tpu")
t0 = time.perf_counter()
try:
    solver.solve()
    core = "<SAT?!>"
except sat.NotSatisfiable as e:
    core = str(e)
run = time.perf_counter() - t0
print("CORE", repr(core), flush=True)
print("STAGE", jax.default_backend(), 0.0, round(run, 3),
      round(1.0 / run, 4), flush=True)
os._exit(0)
"""


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--packages", type=int, default=250)
    ap.add_argument("--versions", type=int, default=8)
    ap.add_argument("--log", default="")
    ap.add_argument("--step-timeout", type=int, default=900)
    ap.add_argument("--probe-timeout", type=int, default=120)
    ap.add_argument("--allow-cpu", action="store_true")
    a = ap.parse_args()

    expected = [None]
    healthy = make_healthy(a.probe_timeout, a.allow_cpu, expected, a.log)

    src = STAGE_SRC.format(alarm=a.step_timeout + 30,
                           packages=a.packages, versions=a.versions)
    cores: dict = {}
    times: dict = {}
    # OFF first: it is the known-safe path; if ON crashes the worker the
    # safe measurement is already on disk.
    for variant, value in (("spec-core-off", "0"), ("spec-core-on", "1")):
        if not healthy():
            # Nonzero so rc-reading callers (ladder stage H) see an
            # aborted A/B as a failure, not a green stage.
            sys.exit(1)
        env = dict(os.environ)
        # A leftover exported engine knob (a manual experiment's
        # DEPPY_TPU_SEARCH=fused, say) would contaminate BOTH arms of
        # the measurement that decides SPEC_CORE's default — scrub them,
        # as tpu_ab does for the same reason.
        for k in ("DEPPY_TPU_BCP_UNROLL", "DEPPY_TPU_STAGE1_STEPS",
                  "DEPPY_TPU_SEARCH", "DEPPY_TPU_BCP"):
            env.pop(k, None)
        env["DEPPY_TPU_SPEC_CORE"] = value
        rec = run_stage({"variant": variant,
                         "packages": a.packages, "versions": a.versions},
                        [sys.executable, "-c", src], env,
                        a.step_timeout, a.log, capture_prefixes=("CORE",))
        if not rec["ok"]:
            emit({"abort": f"{variant} failed; stopping before burying "
                  "the worker"}, a.log)
            sys.exit(1)
        if expected[0] is None:
            expected[0] = rec["backend"]
        cores[variant] = rec.get("core")
        times[variant] = rec.get("run_s")
    # The SAT sentinel comparing equal on both arms is NOT agreement —
    # the workload is UNSAT by construction, so a double-SAT means the
    # harness solved the wrong problem (exactly the bug class this
    # parity check exists to catch).
    agree = (cores["spec-core-off"] is not None
             and "<SAT?!>" not in (cores["spec-core-off"] or "")
             and cores["spec-core-off"] == cores["spec-core-on"])
    emit({"verdict": "ok" if agree else "CORE-DIVERGENCE",
          "cores_agree": agree,
          "off_s": times.get("spec-core-off"),
          "on_s": times.get("spec-core-on")}, a.log)
    if not agree:
        sys.exit(1)


if __name__ == "__main__":
    main()
