"""Headline benchmark: batched catalog resolutions/sec, device vs host.

Workload: BASELINE.json config 2 — a batch of independent catalog
resolutions (random catalog subsets in the reference benchmark's instance
distribution, /root/reference/pkg/sat/bench_test.go:10-64) dispatched to
the tensor engine in one vmapped solve.  Measurement methodology lives in
:mod:`deppy_tpu.benchmarks.harness` (shared with the full suite).

Prints exactly one JSON line:
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N}
plus human-readable detail on stderr.  Invoked by the repo-root
``bench.py`` (the driver's entry point) and ``deppy bench``.
"""

from __future__ import annotations

import json

from .harness import bench_problems, log, probe_wall_s


def run(n_problems: int = 4096, length: int = 48, host_sample: int = 24,
        platform: str | None = None,
        mesh_devices: int | None = None) -> dict:
    import jax

    from ..models import random_instance
    from ..sat.encode import encode

    if n_problems <= 0:
        raise ValueError("n_problems must be positive")

    if platform:
        jax.config.update("jax_platforms", platform)
    probe_s = probe_wall_s()  # time the first backend touch explicitly
    backend = jax.default_backend()
    log(f"jax backend: {backend} devices={jax.devices()}")
    # Mesh serving (ISSUE 6): --mesh-devices / DEPPY_TPU_MESH_DEVICES
    # shards the timed dispatch over a device mesh — the same entry
    # point the scheduler drains through, so the headline number and
    # the serving path stay one code path.
    from ..parallel.mesh import serving_mesh

    smesh = serving_mesh(mesh_devices)
    if smesh is not None:
        log(f"serving mesh: {int(smesh.size)} devices (batch-axis shard)")
    problems = [
        encode(random_instance(length=length, seed=s)) for s in range(n_problems)
    ]
    m = bench_problems(problems, host_sample=host_sample,
                       serving_mesh=smesh)

    # The ratio's denominator: the committed machine-keyed median record
    # when one matches (so vs_baseline moves only when the device rate
    # does — round-4 verdict weak #3), else this run's live sample.  The
    # live rate is always reported alongside for drift visibility.
    from .host_baseline import load_pinned

    pinned = load_pinned(length)
    host_s = pinned["host_s_per_problem"] if pinned else m["host_s_per_problem"]
    if pinned:
        log(f"host denominator: pinned {1.0 / host_s:.1f}/s "
            f"(live sample {1.0 / m['host_s_per_problem']:.1f}/s)")
    else:
        log("host denominator: live sample (no matching committed "
            "host_baseline.json record)")

    result = {
        "metric": "catalog resolutions/sec (batched device vs serial host)",
        "value": round(m["device_rate"], 2),
        "unit": "problems/s",
        "vs_baseline": round(m["device_rate"] * host_s, 3),
        "backend": backend,
        "baseline_source": "pinned" if pinned else "live",
        "host_rate_live": round(1.0 / m["host_s_per_problem"], 1),
        "host_rate_used": round(1.0 / host_s, 1),
        # Startup attribution (ISSUE 4 satellite): backend first-touch
        # wall and the untimed compile warm-up — probe/retry stalls
        # are invisible without these.
        "probe_wall_s": round(probe_s, 3),
        "warmup_seconds": round(m["warmup_seconds"], 3),
        # Host-path pool size (ISSUE 5 satellite; 0 = inline serial).
        "host_workers": m["host_workers"],
        # Mesh-serving scaling columns (ISSUE 6): device count the timed
        # dispatch sharded over + throughput per device.
        "n_devices": m["n_devices"],
        "per_device_rate": round(m["per_device_rate"], 2),
        # Compile-guard ledger delta over warm-up + timed dispatches
        # (ISSUE 8): how many jit-entry traces the record paid.
        "n_compiles": m["n_compiles"],
        # Engine-economics columns (ISSUE 11), sourced from the trip
        # ledger's untimed profiled dispatch: BENCH trajectories pin
        # what the lockstep trips bought, not just throughput.
        "useful_work_ratio": m["useful_work_ratio"],
        "straggler_p99_ratio": m["straggler_p99_ratio"],
        "pad_waste_ratio": m["pad_waste_ratio"],
    }
    if "telemetry" in m:
        # Occupancy and fallback columns ride in every BENCH row (ISSUE
        # 1): a throughput regression can then be attributed to padding
        # waste or host routing without a rerun.
        result["telemetry"] = m["telemetry"]
    print(json.dumps(result), flush=True)
    return result


def main() -> None:
    import argparse
    import os
    import signal

    from ..utils.platform_env import apply_platform_env

    # Armed by bench.py: self-destruct shortly after the caller's
    # watchdog, so an orphaned run (caller killed) cannot sit wedged on
    # the accelerator worker for hours.  SIGALRM's default disposition
    # kills the process even while blocked inside PJRT C code.
    sd = os.environ.get("DEPPY_BENCH_SELF_DESTRUCT")
    if sd and sd.isdigit() and int(sd) > 0:
        signal.alarm(int(sd))

    apply_platform_env()

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--platform", default=None,
                    help="force a jax platform (e.g. cpu) before running")
    ap.add_argument("--n-problems", type=int, default=4096)
    ap.add_argument("--length", type=int, default=48)
    ap.add_argument("--host-sample", type=int, default=24)
    ap.add_argument("--mesh-devices", type=int, default=None,
                    help="shard the timed dispatch over N devices "
                    "(-1 = all; default: DEPPY_TPU_MESH_DEVICES or off)")
    a = ap.parse_args()
    run(n_problems=a.n_problems, length=a.length, host_sample=a.host_sample,
        platform=a.platform, mesh_devices=a.mesh_devices)


if __name__ == "__main__":
    main()
