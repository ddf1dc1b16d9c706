"""Shared measurement harness for all benchmarks.

One methodology, used by both the headline benchmark and the full suite:
sampled serial host-engine baseline (the stand-in for the reference's
single-threaded gini solver), an untimed compile warm-up, then one timed
batched device dispatch.
"""

from __future__ import annotations

import sys
import time
from typing import Dict, Sequence


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


_PROBE_WALL_S = None


def probe_wall_s() -> float:
    """Wall-clock seconds for the first touch of the JAX backend (PJRT
    init + device enumeration), measured once per process and cached.

    Backend-probe/init stalls are invisible in the emitted JSON (they
    happen before any timed section); recording the first-touch wall in
    every BENCH record makes them attributable without a rerun.  Call this BEFORE anything
    else touches the backend (``jax.default_backend()``,
    ``jax.devices()``) or the measurement reads ~0."""
    global _PROBE_WALL_S
    if _PROBE_WALL_S is None:
        import jax

        t0 = time.perf_counter()
        jax.devices()
        _PROBE_WALL_S = time.perf_counter() - t0
        if _PROBE_WALL_S > 1.0:
            log(f"backend probe: {_PROBE_WALL_S:.1f}s to first device")
    return _PROBE_WALL_S


def bench_problems(problems: Sequence, host_sample: int = 16,
                   mesh=None, serving_mesh=None) -> Dict:
    """Measure a list of lowered problems: host ms/problem (serial,
    sampled), device rate (batched, post-warm-up).  Returns the raw
    numbers; callers shape them into their own output records.

    ``serving_mesh`` routes the timed dispatch through the ISSUE 6
    batch-axis sharded entry (``driver.solve_problems_sharded``) so the
    mesh scaling curve is measured with the exact code path the
    scheduler serves with; ``mesh`` stays the clause-axis mesh of the
    historical dispatch paths."""
    from ..engine import core, driver
    from ..sat.errors import NotSatisfiable
    from ..sat.host import HostEngine

    if not problems:
        raise ValueError("problems must be non-empty")
    if host_sample <= 0:
        raise ValueError("host_sample must be positive")
    n = len(problems)
    n_devices = int(getattr(serving_mesh, "size", 1) or 1)

    def dispatch():
        if serving_mesh is not None:
            return driver.solve_problems_sharded(problems,
                                                 mesh=serving_mesh)
        return driver.solve_problems(problems, mesh=mesh)
    # First backend touch is timed HERE, before the warm-up pays it
    # invisibly — direct bench_problems callers get the real init stall
    # in their record, not ~0 measured after the fact.
    probe_s = probe_wall_s()

    from ..analysis import compileguard

    sample = problems[: min(host_sample, n)]
    t_start = time.perf_counter()
    pass_times = []
    while True:
        t0 = time.perf_counter()
        for p in sample:
            try:
                HostEngine(p).solve()
            except NotSatisfiable:
                pass  # UNSAT is a valid (timed) outcome; errors propagate
        pass_times.append((time.perf_counter() - t0) / len(sample))
        # Tiny samples (n=1 configs) repeat until the measurement window
        # is long enough to dominate timer/GC jitter.  Best-of-passes, the
        # same statistic the device side uses below — keeping the
        # host/device ratio an apples-to-apples min/min.
        if (time.perf_counter() - t_start >= 0.25
                or len(sample) >= host_sample):
            break
    host_s = min(pass_times)
    log(f"host: {host_s * 1e3:.2f} ms/problem ({1.0 / host_s:.1f}/s serial)")

    compiles_before = compileguard.trace_count()
    t0 = time.perf_counter()
    dispatch()  # includes compile
    warm_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    results = dispatch()
    dev_s = time.perf_counter() - t0
    # Sub-50ms dispatches (the single-problem config) are dominated by
    # timer/GC jitter in one sample: re-time and keep the best.
    if dev_s < 0.05:
        reps = max(3, int(0.2 / max(dev_s, 1e-4)))
        times = []
        for _ in range(reps):
            t0 = time.perf_counter()
            results = dispatch()
            times.append(time.perf_counter() - t0)
        dev_s = min(times + [dev_s])
    n_sat = sum(1 for r in results if r.outcome == core.SAT)
    n_unsat = sum(1 for r in results if r.outcome == core.UNSAT)
    rate = n / dev_s
    log(
        f"device: {n} in {dev_s:.3f}s = {rate:.1f}/s "
        f"({n_sat} sat / {n_unsat} unsat; warm-up {warm_s:.1f}s)"
    )
    from .. import hostpool

    out = {
        "n_problems": n,
        "host_s_per_problem": host_s,
        "device_seconds": dev_s,
        "device_rate": rate,
        "warmup_seconds": warm_s,
        # Backend first-touch wall (ISSUE 4 satellite): whoever touched
        # the backend first — this harness or an earlier probe_wall_s()
        # caller — the measured init cost rides every record.
        "probe_wall_s": probe_s,
        # Host-path concurrency (ISSUE 5 satellite): the worker-pool
        # size the breaker-open / host-backend path would use under this
        # record's configuration (0 = inline serial engine).  The serial
        # host_s_per_problem sample above is deliberately per-CORE — the
        # pool speedup itself is tracked by
        # benchmarks/results/hostpool_baseline.json (host_baseline
        # --pool), not folded into the device-vs-host ratio.
        "host_workers": hostpool.effective_workers(),
        # Mesh-serving columns (ISSUE 6): how many devices the timed
        # dispatch sharded over (1 = historical single-device path) and
        # the per-device throughput — the scaling-curve numerator every
        # MULTICHIP/BENCH round tracks.
        "n_devices": n_devices,
        "per_device_rate": rate / n_devices,
        # Compile-guard ledger delta across warm-up + timed dispatches
        # (ISSUE 8): how many jit-entry traces the measured section
        # paid.  The warm-up should absorb them all — a nonzero count
        # beyond it in later rounds is the compile-storm tell the
        # runtime guard asserts on under DEPPY_TPU_COMPILE_GUARD=1.
        "n_compiles": compileguard.trace_count() - compiles_before,
        "sat": n_sat,
        "unsat": n_unsat,
    }
    # Occupancy/fallback telemetry from the timed dispatch (ISSUE 1): the
    # driver publishes a SolveReport per solve_problems call; carrying it
    # in the record means every BENCH_*.json row shows how much of the
    # measured batch was padding and which escalation stage resolved it.
    from .. import telemetry

    rep = telemetry.last_report()
    if rep is not None:
        out["telemetry"] = {
            "batch_fill_ratio": round(rep.batch_fill_ratio, 4),
            "pad_waste_ratio": round(rep.pad_waste_ratio, 4),
            "escalation_stage": rep.escalation_stage,
            "host_fallback_rows": rep.host_fallback_rows,
            "backtracks": rep.backtracks,
            "steps": rep.steps,
            "n_chunks": rep.n_chunks,
            "n_buckets": rep.n_buckets,
        }
        log(rep.format_table())
    # Engine-economics columns (ISSUE 11): one extra, UNTIMED dispatch
    # with the trip ledger armed at full sampling sources the
    # useful-work / straggler / pad-waste ratios from the profiler's
    # own machinery without perturbing the timed rate above — BENCH_r*
    # trajectories then pin engine economics, not just throughput.
    from .. import profile

    with profile.override("on", 1.0):
        dispatch()
    lrep = telemetry.last_report()
    if lrep is not None and lrep.profiled_dispatches:
        out["useful_work_ratio"] = round(lrep.useful_work_ratio, 4)
        out["straggler_p99_ratio"] = round(lrep.straggler_p99_ratio, 4)
        out["pad_waste_ratio"] = round(lrep.pad_waste_ratio, 4)
        log(f"trip ledger: useful {out['useful_work_ratio']:.3f}  "
            f"straggler-p99 {out['straggler_p99_ratio']:.3f}  "
            f"pad-waste {out['pad_waste_ratio']:.3f}")
    else:
        # The ledger dispatch routed somewhere unprofiled (pure host
        # path): the columns still exist so record schemas stay fixed.
        out["useful_work_ratio"] = 0.0
        out["straggler_p99_ratio"] = 0.0
        out["pad_waste_ratio"] = round(
            rep.pad_waste_ratio, 4) if rep is not None else 0.0
    return out
