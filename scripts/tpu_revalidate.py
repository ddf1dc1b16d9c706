"""Staged TPU revalidation after a worker outage.

Waits for the worker to answer a full compute probe (`deppy doctor
--watch --until-healthy` semantics), then walks an escalating stage
ladder, each stage in a disposable subprocess with a hard timeout and a
health re-probe between stages:

  A. tiny batch (64 problems), persistent compile cache OFF
  B. tiny batch, compile cache ON      — isolates the cache as a wedge
     trigger: the 2026-07-31 outage began at the first compile of a
     cache-enabled run, and A-passes-B-fails would convict it
  B2. Mosaic compile-smoke of every Pallas kernel at tiny shapes
     (``scripts/mosaic_smoke.py``) — the round-4 fused kernels have
     only ever run in interpret mode, so this is the first time Mosaic
     sees them; run EARLY so the verdict lands in the first minutes of
     a recovery window, and a rejection reconfigures stages F/G
     (skip-fused / bits-only) instead of aborting them mid-measurement
  C. headline shape at 1024 problems (cache per B's verdict)
  D. the driver contract: ``bench.py`` end to end — BEFORE the long
     suite, so a worker that recovers ~30 min before a driver bench
     window still lands an accelerator bench record in the ladder log
     (bench.py publishes it and prefers such records over its CPU
     fallback; see bench.py LADDER_LOG)
  F. the trip-overhead A/B queue (``scripts/tpu_ab.py``: baseline /
     search-fused / stage1 / unroll) — BEFORE the suite: heal windows
     have died minutes in (2026-08-01), and the baseline-vs-fused pair
     is the highest-value measurement in the queue
  E. full benchmark suite (``deppy_tpu.benchmarks.suite``)
  G. blockwise over-VMEM single-problem case (``pallas_case
     --packages 1000 --impls bits,blockwise``)
  H. speculative-core A/B (``scripts/spec_core_ab.py``)
  I. lane-width boundary probe (``scripts/lane_probe.py``) — LAST:
     expected to crash the worker at the boundary, so it runs only
     after every safe measurement is on disk.

Aborts at the first failed stage, and whenever the probed backend is no
longer the one stage A ran on — results taken after a crash (or on a
silent CPU fallback) would measure the wrong thing.  One JSON line per
stage on stdout (and appended to --log); run it detached and poll the
log:

  setsid nohup python scripts/tpu_revalidate.py --log /tmp/reval.jsonl &
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from scripts._stage import (  # noqa: E402
    emit as _emit_line, probe_status, run_stage, solve_stage_src)


def _emit(rec: dict, log_path: str) -> None:
    _emit_line(rec, log_path)


def _log_line_count(log_path: str) -> int:
    if not log_path:
        return 0
    try:
        with open(log_path) as f:
            return sum(1 for _ in f)
    except OSError:
        return 0


def _write_measured_default(backend: str, stage: str, updates: dict,
                            evidence: dict, log_path: str) -> None:
    """Merge measured-default ``updates`` for ``backend`` into the
    package-local registry (DEPPY_TPU_MEASURED_DEFAULTS overrides the
    path) through the shared flock-guarded store
    (:mod:`deppy_tpu.engine.defaults_store`): concurrent ladder
    instances (e.g. a CPU smoke ladder racing a device ladder, or two
    heal windows overlapping) compose instead of torn-writing.
    Evidence is nested PER KEY: a later run that measures only one key
    must not re-stamp provenance on rows it never measured."""
    from deppy_tpu.engine import defaults_store

    path = defaults_store.merge_rows(
        backend, updates,
        evidence={**evidence, "ladder_log":
                  os.path.abspath(log_path) if log_path else ""})
    _emit_line({"stage": stage, "backend": backend, **updates,
                "path": path}, log_path)


def _records_since(log_path: str, from_line: int) -> list:
    """Parsed dict records appended to the ladder log at/after
    ``from_line`` (bad/partial lines skipped)."""
    if not log_path:
        return []
    try:
        with open(log_path) as f:
            lines = f.read().splitlines()[from_line:]
    except OSError:
        return []
    out = []
    for line in lines:
        try:
            rec = json.loads(line)
        except (json.JSONDecodeError, ValueError):
            continue
        if isinstance(rec, dict):
            out.append(rec)
    return out


def _spec_core_verdict(log_path: str, from_line: int = 0):
    """Stage H's final verdict line from THIS run: ('on'|'off', rec)
    when the A/B landed with agreeing cores, else None.  ON requires
    both agreement and a time win; a measured loss records OFF (it
    resolves the pending-measurement default either way)."""
    for rec in reversed(_records_since(log_path, from_line)):
        if "verdict" not in rec:
            continue
        if rec.get("verdict") != "ok":
            return None  # divergence: never flip on a wrong answer
        on_s, off_s = rec.get("on_s"), rec.get("off_s")
        if (isinstance(on_s, (int, float))
                and isinstance(off_s, (int, float))):
            return ("on" if on_s < off_s else "off"), rec
        return None
    return None


def _fused_beat_baseline(log_path: str, from_line: int = 0):
    """(baseline_rate, fused_rate) when THIS run's stage-F variant
    records (lines appended at/after ``from_line`` — the shared /tmp log
    carries older runs' records, including fused wins from before a
    Mosaic regression) show search-fused ahead of baseline on a non-cpu
    backend, else None."""
    if not log_path:
        return None
    rates: dict = {}
    for rec in _records_since(log_path, from_line):  # newest-last wins
        if (rec.get("variant") and rec.get("ok")
                and rec.get("backend") != "cpu"
                and isinstance(rec.get("rate"), (int, float))):
            rates[rec["variant"]] = float(rec["rate"])
    base, fused = rates.get("baseline"), rates.get("search-fused")
    # Explicit None checks: a measured 0.0 rate is a real (terrible)
    # measurement, not a missing one — truthiness would silently treat
    # a zero-rate baseline as "never ran" and suppress the F2 capture.
    if base is not None and fused is not None and fused > base:
        return base, fused
    return None


def _run_stage(name: str, cmd, env, timeout_s: int, log_path: str,
               **kwargs) -> dict:
    return run_stage({"stage": name, "ts": round(time.time(), 1)},
                     cmd, env, timeout_s, log_path, **kwargs)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--log", default="")
    ap.add_argument("--wait-interval", type=int, default=600)
    ap.add_argument("--probe-timeout", type=int, default=120)
    ap.add_argument("--skip-wait", action="store_true",
                    help="assume the worker is healthy right now")
    a = ap.parse_args()

    from deppy_tpu.utils.tpu_doctor import watch

    if not a.skip_wait:
        _emit({"stage": "wait", "ts": round(time.time(), 1)}, a.log)
        rc = watch(interval=a.wait_interval, probe_timeout=a.probe_timeout,
                   log_path=a.log, until_healthy=True)
        if rc != 0:  # terminal: no accelerator / plugin failure
            _emit({"stage": "abort", "reason": f"watch rc={rc}",
                   "ts": round(time.time(), 1)}, a.log)
            return
    _emit({"stage": "healthy", "ts": round(time.time(), 1)}, a.log)

    ladder_backend: list = [None]  # set by stage A, enforced after

    def healthy() -> bool:
        r = probe_status(a.probe_timeout)
        # The backend must still be the one the ladder started on: a
        # worker dying mid-ladder can flip probes to "cpu-only", and
        # continuing would record CPU numbers as if they were device
        # results.  (A forced-CPU smoke run sets ladder_backend to
        # "cpu" at stage A, so cpu-only stays healthy there.)
        ok = (r["status"] in ("ok", "cpu-only")
              and r.get("backend") == ladder_backend[0])
        if not ok:
            _emit({"stage": "abort", "reason": "worker unhealthy or "
                   f"backend changed ({r.get('backend')}, "
                   f"expected {ladder_backend[0]})",
                   "ts": round(time.time(), 1)}, a.log)
        return ok

    env_off = dict(os.environ)
    env_off["DEPPY_TPU_COMPILE_CACHE"] = "off"
    env_on = dict(os.environ)
    py = sys.executable
    tiny = solve_stage_src(alarm=330, length=24, count=64)

    # A: tiny, cache off.
    rec = _run_stage("A:tiny-cache-off", [py, "-c", tiny], env_off, 300,
                     a.log)
    if not rec["ok"]:
        return
    ladder_backend[0] = rec["backend"]
    if not healthy():
        return
    # B: tiny, cache on (same shapes — a pure cache-path test).
    cache_ok = _run_stage("B:tiny-cache-on", [py, "-c", tiny], env_on,
                          300, a.log)["ok"]
    if not cache_ok:
        _emit({"stage": "note", "msg": "compile cache implicated; "
               "continuing with cache off"}, a.log)
        if not healthy():
            return
    env_rest = env_on if cache_ok else env_off
    # B2: Mosaic compile-smoke — each Pallas kernel compiled + executed
    # once at tiny shapes and bit-compared vs its XLA twin.  The smoke
    # exits 0 even with failing kernels (the verdict file is the
    # result); only a harness abort / hang fails the stage, and even
    # then the ladder continues with the fused substrates disabled so
    # the safe measurements still land.
    smoke_verdict = ((os.path.abspath(a.log) + ".smoke.json") if a.log
                     else "/tmp/mosaic_smoke_verdict.json")
    try:
        os.unlink(smoke_verdict)
    except FileNotFoundError:
        pass
    smoke_cpu = ["--allow-cpu"] if ladder_backend[0] == "cpu" else []
    _run_stage("B2:mosaic-smoke",
               [py, os.path.join(ROOT, "scripts", "mosaic_smoke.py"),
                "--verdict", smoke_verdict,
                *(["--log", os.path.abspath(a.log)] if a.log else []),
                *smoke_cpu],
               env_rest, 1800, a.log, require_stage_line=False)
    kernels_ok = {}
    try:
        with open(smoke_verdict) as f:
            kernels_ok = {k: v.get("ok", False) for k, v in
                          json.load(f)["kernels"].items()}
    except (OSError, ValueError, KeyError):
        _emit({"stage": "note", "msg": "no mosaic-smoke verdict; "
               "treating all Pallas substrates as unproven"}, a.log)
    search_fused_ok = kernels_ok.get("search-fused", False) \
        and kernels_ok.get("minimize-fused", False) \
        and kernels_ok.get("core-fused", False)
    blockwise_ok = kernels_ok.get("bcp-blockwise", False)
    if not healthy():
        return
    # C: headline shape.
    if not _run_stage(
            "C:headline-1024",
            [py, "-c", solve_stage_src(alarm=630, length=48, count=1024)],
            env_rest, 600, a.log)["ok"]:
        return
    if not healthy():
        return
    # D: the driver contract end to end — BEFORE the long suite so a
    # recent recovery still lands an accelerator bench record quickly.
    # The record is published into the SAME log this ladder writes
    # (bench.py's _publish_record honors DEPPY_TPU_REVAL_LOG), which is
    # the file later bench invocations scan.
    env_bench = dict(env_rest)
    if a.log:
        env_bench["DEPPY_TPU_REVAL_LOG"] = os.path.abspath(a.log)
    # The ladder just probed healthy, so bench.py's worker-restart retry
    # budget (4 probes x 150s) is dead weight here; one probe keeps its
    # worst case (probe + run + re-probe + retry run ≈ 3200s) inside the
    # stage timeout instead of overshooting it and aborting a healthy
    # run mid-retry.
    env_bench["DEPPY_BENCH_PROBE_RETRIES"] = "1"
    if not _run_stage("D:bench.py", [py, os.path.join(ROOT, "bench.py")],
                      env_bench, 3400, a.log,
                      require_stage_line=False)["ok"]:
        return
    if not healthy():
        return
    # F-I: the round-4 recovery measurement queue (verdict items 1,3,4,5)
    # — everything the round needs from a healed worker, captured without
    # a human in the loop, ordered safest-first AND
    # highest-value-first: F (the baseline/fused A/B) runs before the
    # suite because heal windows have died minutes in (2026-08-01)
    # and the fused verdict is what round 5 is for; the crash-risk
    # probes still cannot cost the safe measurements.  Each child script runs
    # its own between-step health probes and writes into THIS log.
    log_args = ["--log", os.path.abspath(a.log)] if a.log else []
    # The ladder's forced-CPU smoke path (ladder_backend == "cpu", see
    # healthy()) must exercise the F-I plumbing too: the A/B children
    # need --allow-cpu there (they rightly refuse silent CPU runs
    # otherwise), and G swaps the TPU workload for a small bits-only
    # smoke — interpret-mode blockwise at 1000 packages would run for
    # hours and measure nothing.
    smoke = ladder_backend[0] == "cpu"
    cpu_args = ["--allow-cpu"] if smoke else []
    # F: the trip-overhead A/B queue (unroll/stage1/search-fused).
    # Smoke shrinks the count like G/H/I shrink theirs: the full
    # 1024×best-of-3 per variant exists to measure the device, not to
    # exercise plumbing, and a slow CPU box could blow the per-variant
    # timeout and kill the tail this smoke exists to cover.
    f_shape = (["--count", "256"] if smoke else [])
    # On a TPU backend the smoke's verdict gates the fused variant; the
    # forced-CPU smoke path skips it anyway (tpu_only) so no flag there.
    f_fused = ([] if smoke or search_fused_ok else ["--skip-fused"])
    if f_fused:
        _emit({"stage": "note", "msg": "mosaic smoke failed the fused "
               "search substrate; running stage F without it"}, a.log)
    f_log_start = _log_line_count(a.log)
    if not _run_stage("F:tpu-ab",
                      [py, os.path.join(ROOT, "scripts", "tpu_ab.py"),
                       *f_shape, *f_fused, *log_args, *cpu_args],
                      env_rest, 5400, a.log,
                      require_stage_line=False)["ok"]:
        return
    if not healthy():
        return
    # F2: when THIS run's smoke passed the fused substrate AND the A/B
    # just measured it beating the XLA baseline, capture the headline
    # bench under the winning knob right now — the heal window may not
    # last until a human flips the default, and bench.py prefers the
    # newest device record in this log, so the driver's next BENCH
    # artifact carries the fused rate (bench.py labels the record with
    # any non-default search knob).  A SUCCESSFUL F2 completes the
    # measured row, and stage F3 records it in the package registry
    # right away — "auto" then resolves to fused on this backend, with
    # human review happening at the end-of-round commit like any other
    # measured default.  F2 is an opportunistic BONUS artifact: its
    # failure is noted and the safe stages E/G/H still run (same policy
    # as tpu_ab's fused-failure continue).
    fused_win = (search_fused_ok
                 and _fused_beat_baseline(a.log, f_log_start))
    if fused_win:
        _emit({"stage": "note", "msg": "fused beat baseline "
               f"({fused_win[1]:.1f} vs {fused_win[0]:.1f}/s); capturing "
               "a fused-knob bench record"}, a.log)
        env_f2 = dict(env_bench)
        env_f2["DEPPY_TPU_SEARCH"] = "fused"
        if not _run_stage("F2:bench-fused",
                          [py, os.path.join(ROOT, "bench.py")],
                          env_f2, 3400, a.log,
                          require_stage_line=False)["ok"]:
            _emit({"stage": "note", "msg": "F2 fused bench failed; "
                   "continuing with the safe stages"}, a.log)
        else:
            # F3: the measured row is complete — same-run Mosaic smoke
            # pass, paired A/B win, full headline bench under the knob —
            # so record the measured default.  core._resolved_search_impl
            # reads this file for "auto" on this backend; the driver's
            # end-of-round commit carries it, and a human reviews the
            # row like any other chip measurement.  The write is
            # instant, so it lands even if the window dies during E-I —
            # but the REMAINING stages must keep measuring the pre-flip
            # substrate (their artifacts are compared round-over-round
            # and would otherwise silently become unlabeled fused
            # measurements), so pin the env knob for them; bench.py
            # labels any non-auto knob in its records.
            _write_measured_default(
                ladder_backend[0] or "tpu", "F3:measured-default",
                {"search": "fused"},
                {"baseline_rate": round(fused_win[0], 1),
                 "fused_rate": round(fused_win[1], 1)}, a.log)
            env_rest = dict(env_rest)
            env_rest["DEPPY_TPU_SEARCH"] = "xla"
        if not healthy():
            return
    # E: full suite; the per-config JSON lines land in the stage log and
    # the aggregate in /tmp for a human to inspect and commit under
    # benchmarks/results/ with a backend-correct name.
    if not _run_stage("E:suite",
                      [py, "-m", "deppy_tpu.benchmarks.suite",
                       "--out", "/tmp/reval_suite.json"],
                      env_rest, 2400, a.log,
                      require_stage_line=False)["ok"]:
        return
    if not healthy():
        return
    # G: blockwise over-VMEM single-problem case (bits must stream
    # planes from HBM each round at this scale; blockwise's bet is that
    # per-block local fixpoints win there).
    g_shape = (["--packages", "120", "--repeats", "1",
                "--impls", "bits"] if smoke else
               ["--packages", "1000", "--repeats", "2",
                "--impls", "bits,blockwise" if blockwise_ok else "bits"])
    if not smoke and not blockwise_ok:
        _emit({"stage": "note", "msg": "mosaic smoke failed blockwise; "
               "stage G runs bits only"}, a.log)
    if not _run_stage("G:blockwise-overvmem",
                      [py, "-m", "deppy_tpu.benchmarks.pallas_case",
                       *g_shape, *log_args],
                      env_rest, 3000, a.log,
                      require_stage_line=False)["ok"]:
        return
    if not healthy():
        return
    # H: speculative-core A/B on the giant-pinned-conflict catalog —
    # the measurement DEPPY_TPU_SPEC_CORE's auto default is waiting on.
    # Known crash-risk class (minutes-long single executions), hence
    # after F/G.
    h_shape = (["--packages", "40", "--versions", "4"] if smoke else [])
    h_log_start = _log_line_count(a.log)
    if not _run_stage("H:spec-core-ab",
                      [py, os.path.join(ROOT, "scripts",
                                        "spec_core_ab.py"),
                       *h_shape, *log_args, *cpu_args],
                      env_rest, 2400, a.log,
                      require_stage_line=False)["ok"]:
        return
    # H3: the full-scale spec-core verdict resolves the two-round-old
    # pending default (driver.SPEC_CORE auto) — record the measured
    # winner either way (OFF is a verdict too; only an agreeing,
    # faster ON flips it on).  Smoke-shape runs measure plumbing, not
    # the device, so only a device-backend ladder records.
    if not smoke:
        sc = _spec_core_verdict(a.log, h_log_start)
        if sc is not None:
            _write_measured_default(
                ladder_backend[0] or "tpu", "H3:measured-default",
                {"spec_core": sc[0]},
                {"spec_core_on_s": sc[1].get("on_s"),
                 "spec_core_off_s": sc[1].get("off_s")}, a.log)
    # I: lane-width boundary probe — LAST, per its own CAUTION: it is
    # EXPECTED to crash the worker at the boundary, and everything worth
    # protecting is already on disk by now.  No healthy() gate after.
    i_shape = (["--widths", "8,16", "--lengths", "8"] if smoke else [])
    rec_i = _run_stage("I:lane-probe",
                       [py, os.path.join(ROOT, "scripts", "lane_probe.py"),
                        *i_shape, *log_args],
                       env_rest, 5400, a.log, require_stage_line=False)
    # ladder-complete is a CONTRACT line (a green ladder-complete line
    # means every safe measurement actually landed, and the fused bet
    # has a recorded verdict either way) —
    # a lane probe that measured nothing (rc!=0: aborted before any
    # step, or backend flip) must not produce it.  lane_probe itself
    # exits 0 when it measured up to a crashed boundary, which IS a
    # landed verdict.  The one exception inside stage F: a full-shape
    # search-fused failure on a still-healthy worker is recorded as its
    # own note line and the queue continues (tpu_ab.py) — the fused
    # VERDICT landed (it failed); what must never be lost silently is
    # the safe knob ladder behind it.
    if rec_i["ok"]:
        _emit({"stage": "ladder-complete", "ts": round(time.time(), 1)},
              a.log)


if __name__ == "__main__":
    main()
