"""A/B the TPU-bet engine knobs on the headline workload.

The round-3 trip-overhead model predicts the chip's search phase is
while-loop-trip-overhead-bound (~175µs/trip vs
~10µs of in-trip compute), so knobs that cut trip count at the price of
extra in-trip compute — measured losers on CPU XLA — should win on the
device.  This script measures them: each variant solves the headline
shape (1024 × length-48 catalog instances, best of 3 timed runs) in a
disposable subprocess (SIGALRM self-destruct), with a health probe
between variants and an abort on the first failure or backend flip.
It refuses to start on a CPU-only backend unless ``--allow-cpu`` is
passed — these knobs are measured losers there and a silent CPU run
would produce a meaningless JSONL.

Run after `scripts/tpu_revalidate.py` reports a green ladder:

  python scripts/tpu_ab.py [--count 1024] [--log /tmp/ab.jsonl]
"""

from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from scripts._stage import emit, make_healthy, run_stage, solve_stage_src

KNOB_VARS = ("DEPPY_TPU_BCP_UNROLL", "DEPPY_TPU_STAGE1_STEPS",
             "DEPPY_TPU_SEARCH", "DEPPY_TPU_MAX_LANES",
             "DEPPY_TPU_DPLL_UNROLL", "DEPPY_TPU_CTL_UNROLL",
             "DEPPY_TPU_BCP", "DEPPY_TPU_PORTFOLIO")

# (name, knobs, tpu_only): tpu_only variants are SKIPPED when the pinned
# backend is cpu — search-fused there runs the Pallas kernel in
# interpret mode, which measures nothing about CPU XLA and takes long
# enough to blow the step timeout (killing the rest of a smoke ladder).
VARIANTS = [
    ("baseline", {}, False),
    # The round-4 escalation: phase-1 search fused into one Pallas kernel
    # per problem (engine/pallas_search.py) — eliminates per-while-trip
    # dispatch overhead entirely at the price of grid-serializing the
    # batch.  The trip-overhead model predicts a large win on the
    # chip; measured-class loser on CPU XLA.  SECOND in the queue:
    # baseline+fused is the pair the round's central bet needs — the
    # knob ladder can wait.
    ("search-fused", {"DEPPY_TPU_SEARCH": "fused"}, True),
    # The ISSUE 12 engine bet: implication-driven propagation over the
    # compressed clause bank (engine/clause_bank.py) instead of
    # scan-every-clause rounds.  The cost model says it pays where
    # clause sets are large and implication chains deep; CPU XLA
    # numbers live in benchmarks/results/bcp_rewrite_r12.json.  A
    # measured win here is what writes the measured-defaults "bcp" row
    # that flips auto to watched on the chip.
    ("bcp-watched", {"DEPPY_TPU_BCP": "watched"}, False),
    ("stage1-96", {"DEPPY_TPU_STAGE1_STEPS": "96"}, False),
    # Decision-level unroll (round 5): K gated dpll decisions per while
    # trip — attacks the middle factor of the trip product (episodes ×
    # decisions × propagation rounds) at ~10µs of redundant gated work
    # against ~175µs of trip overhead saved per elided trip.
    # Exit-state-identical at any K (test_trip_unroll_is_bit_identical).
    ("dpll-unroll-2", {"DEPPY_TPU_DPLL_UNROLL": "2"}, False),
    ("dpll-unroll-4", {"DEPPY_TPU_DPLL_UNROLL": "4"}, False),
    ("ctl-unroll-4", {"DEPPY_TPU_CTL_UNROLL": "4"}, False),
    ("dpll2+ctl2", {"DEPPY_TPU_DPLL_UNROLL": "2",
                    "DEPPY_TPU_CTL_UNROLL": "2"}, False),
    ("unroll2", {"DEPPY_TPU_BCP_UNROLL": "2"}, False),
    ("unroll4", {"DEPPY_TPU_BCP_UNROLL": "4"}, False),
    ("unroll2+stage1-96", {"DEPPY_TPU_BCP_UNROLL": "2",
                           "DEPPY_TPU_STAGE1_STEPS": "96"}, False),
    # Chunk-width DOWN-probe: 512-lane lockstep pays max-steps-in-chunk
    # trips for every lane; smaller chunks trade straggler waste for
    # more per-chunk dispatch.  Round 4's lane_probe only measured
    # WIDER (512->4096, flat then worse on CPU); the narrow side is
    # unmeasured on the chip.
    ("lanes-128", {"DEPPY_TPU_MAX_LANES": "128"}, False),
    ("lanes-256", {"DEPPY_TPU_MAX_LANES": "256"}, False),
]


def run_portfolio_ab(a, expected) -> None:
    """ISSUE 13: the portfolio-racing A/B — the hard-instance workload
    through the scheduler serving path, racing on vs off (the
    ``bench.py --workload hard`` record, in-process byte-identity
    included).  A measured racing win (``vs_baseline`` ≥ 1.5 with
    ``race_identical_to_off`` true) is what writes the
    measured-defaults ``portfolio.<class>`` rows (the hard chains span
    the ``m``/``l`` ladder classes) that let ``auto`` racing engage
    for those classes on this backend — the same
    measured-row-before-default policy every engine bet follows."""
    import json
    import subprocess

    env = dict(os.environ)
    for k in KNOB_VARS:
        env.pop(k, None)
    try:
        proc = subprocess.run(
            [sys.executable, "-m", "deppy_tpu.benchmarks.hard",
             "--passes", "2"],
            capture_output=True, text=True, env=env,
            cwd=os.path.dirname(os.path.dirname(
                os.path.abspath(__file__))),
            timeout=max(a.step_timeout, 600))
    except subprocess.TimeoutExpired:
        emit({"variant": "portfolio-hard", "ok": False,
              "error": "timeout"}, a.log)
        return
    rec = None
    for line in reversed((proc.stdout or "").strip().splitlines()):
        try:
            parsed = json.loads(line)
        except (json.JSONDecodeError, ValueError):
            continue
        if isinstance(parsed, dict) and "metric" in parsed:
            rec = parsed
            break
    if proc.returncode != 0 or rec is None:
        emit({"variant": "portfolio-hard", "ok": False,
              "rc": proc.returncode,
              "tail": (proc.stderr or "")[-500:]}, a.log)
        return
    won = (rec.get("vs_baseline", 0) >= 1.5
           and rec.get("race_identical_to_off"))
    emit({"variant": "portfolio-hard", "ok": True, "won": bool(won),
          **{k: rec[k] for k in ("value", "vs_baseline",
                                 "race_identical_to_off",
                                 "best_fixed_backend") if k in rec}},
         a.log)
    if won and a.write_portfolio_rows:
        from deppy_tpu.engine import defaults_store

        backend = expected[0] or "cpu"
        # Through the shared flock-guarded store (ISSUE 19 satellite):
        # the old unlocked load/dump here could torn-write against a
        # concurrent revalidation ladder, and left no provenance for
        # the route-staleness watcher to age the rows by.
        path = defaults_store.merge_rows(
            backend,
            {f"portfolio.{cls}": "grad_relax,device,host"
             for cls in ("m", "l")},
            evidence={"platform": backend, "source": "tpu_ab",
                      "vs_baseline": rec.get("vs_baseline")})
        emit({"note": f"wrote portfolio.m/.l rows for {backend} "
              f"to {path}"}, a.log)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--count", type=int, default=1024)
    ap.add_argument("--log", default="")
    ap.add_argument("--step-timeout", type=int, default=600)
    ap.add_argument("--probe-timeout", type=int, default=120)
    ap.add_argument("--allow-cpu", action="store_true",
                    help="permit running the A/B on a CPU-only backend "
                    "(smoke tests; the knobs are measured losers there)")
    ap.add_argument("--skip-fused", action="store_true",
                    help="skip the search-fused variant (set by the "
                    "revalidation ladder when the Mosaic compile-smoke "
                    "failed it — a known-broken variant would abort the "
                    "A/B and lose the remaining measurements)")
    ap.add_argument("--portfolio", action="store_true",
                    help="append the ISSUE 13 portfolio-racing A/B "
                    "(the hard-instance workload, racing on vs off)")
    ap.add_argument("--write-portfolio-rows", action="store_true",
                    help="on a measured racing win (>=1.5x, "
                    "byte-identical), write the measured-defaults "
                    "portfolio.<class> rows that let auto racing "
                    "engage for the hard classes on this backend")
    a = ap.parse_args()

    expected = [None]
    healthy = make_healthy(a.probe_timeout, a.allow_cpu, expected, a.log)

    src = solve_stage_src(alarm=a.step_timeout + 30, length=48,
                          count=a.count, reps=3)
    just_probed = False  # skip the loop-top probe right after the
    # fused-failure guard probe: back-to-back probes burn ~2 min of a
    # heal window that tends to die minutes in.
    for name, knobs, tpu_only in VARIANTS:
        # Consume the freshness flag at the top of EVERY iteration: it
        # only excuses the variant immediately after the guard probe.
        # Without this, a skip chain (skip-fused / tpu-only continues)
        # after a fused failure would carry the stale flag forward and
        # run a later variant without a fresh health probe.
        probe_fresh, just_probed = just_probed, False
        if a.skip_fused and knobs.get("DEPPY_TPU_SEARCH") == "fused":
            emit({"variant": name,
                  "skipped": "mosaic compile-smoke failed this substrate"},
                 a.log)
            continue
        if tpu_only and expected[0] == "cpu":
            emit({"variant": name, "skipped":
                  "tpu-only variant on a cpu backend (interpret-mode "
                  "pallas measures nothing and can blow the timeout)"},
                 a.log)
            continue
        if not probe_fresh and not healthy():
            # Nonzero so callers that read rc (the revalidation ladder's
            # stage F runs with require_stage_line=False, where ok is
            # rc==0) see an aborted A/B as a failure, not a green stage.
            sys.exit(1)
        env = dict(os.environ)
        for k in KNOB_VARS:
            # A leftover exported knob would contaminate every variant
            # (both are read at engine import time in the subprocess).
            env.pop(k, None)
        env.update(knobs)
        rec = run_stage({"variant": name, **knobs},
                        [sys.executable, "-c", src], env,
                        a.step_timeout, a.log)
        if not rec["ok"]:
            if knobs.get("DEPPY_TPU_SEARCH") == "fused" and healthy():
                # The fused substrate is the one crash-flagged variant
                # in the queue (tiny-shape smoke cannot catch its
                # full-shape failure class).  Running second must not
                # cost the safe knob ladder: record the failure and
                # continue — the healthy() probe just confirmed the
                # worker survived it.
                emit({"note": "search-fused failed at full shape; "
                      "continuing with the safe variants"}, a.log)
                just_probed = True
                continue
            emit({"abort": "variant failed; stopping before burying the "
                  "worker"}, a.log)
            sys.exit(1)
        if expected[0] is None:
            expected[0] = rec["backend"]
    if a.portfolio:
        run_portfolio_ab(a, expected)


if __name__ == "__main__":
    main()
