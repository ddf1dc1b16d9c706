"""Full benchmark suite: the BASELINE.json workload configs + extras.

The reference publishes no numbers (SURVEY.md §6), so this suite produces
the rebuild's own: for each config, a sampled serial host-engine baseline
(the stand-in for the reference's single-threaded gini solver) and the
batched device rate.

Run: ``python -m deppy_tpu.benchmarks.suite [--quick] [--out FILE]``.
Prints one JSON object per config on stdout (one line each), detail on
stderr.
"""

from __future__ import annotations

import argparse
import json
import time
from typing import Dict, List, Optional

from ..models import (
    giant_pinned_conflict,
    gvk_conflict_catalog,
    operatorhub_catalog,
    pinned_tenant_catalog,
    random_instance,
    version_pinned_chains,
)
from .harness import log


def _configs(quick: bool) -> List[Dict]:
    """The five BASELINE.json configs plus two extras (the UNSAT-heavy
    fleet and the giant-UNSAT core-extraction case).  ``quick`` shrinks
    batch sizes for CI smoke runs; full sizes match the config
    descriptions."""
    scale = 8 if quick else 1
    return [
        {
            "name": "single operatorhub catalog resolve (~200 bundles)",
            "gen": lambda s: operatorhub_catalog(
                n_packages=40, versions_per_package=5, seed=s
            ),
            "n": 1,
        },
        {
            "name": "batched 1k independent resolves (random catalog subsets)",
            "gen": lambda s: random_instance(length=48, seed=s),
            "n": 1024 // scale,
        },
        {
            "name": "version-pin + deep transitive chains (AtMost-1 per package)",
            "gen": lambda s: version_pinned_chains(depth=20, width=3, seed=s),
            "n": 256 // scale,
        },
        {
            "name": "GVK-uniqueness Conflict-heavy",
            "gen": lambda s: gvk_conflict_catalog(
                n_groups=20, providers_per_group=4, n_required=10, seed=s
            ),
            "n": 256 // scale,
        },
        {
            "name": "fleet-scale: 10k cluster-states x shared catalog (mesh)",
            "gen": lambda s: gvk_conflict_catalog(
                n_groups=12, providers_per_group=3, n_required=6, seed=s
            ),
            "n": 10_000 // scale,
            "mesh": True,
        },
        # Beyond BASELINE.json's five: the UNSAT-heavy fleet shape, where
        # the unsat-core extraction phase (gated or compacted deletion,
        # chunk-first probing) dominates rather than idles.
        {
            "name": "UNSAT-heavy fleet: pinned tenants over shared GVK catalog",
            "gen": lambda s: pinned_tenant_catalog(seed=s),
            "n": 2048 // scale,
            "mesh": True,
        },
        # ONE giant unsatisfiable catalog: a 3-constraint core buried in
        # ~1.7k constraints — exercises host-routed core extraction
        # (driver.HOST_CORE_NCONS).  Quick mode stays above the routing
        # threshold with a lighter catalog.
        {
            "name": "giant catalog UNSAT: pinned conflict, core extraction",
            "gen": (lambda s: giant_pinned_conflict(
                n_packages=150, versions_per_package=6, seed=s
            )) if quick else (lambda s: giant_pinned_conflict(seed=s)),
            "n": 1,
        },
    ]


def _bench_config(cfg: Dict, host_sample: int = 16) -> Dict:
    from ..sat.encode import encode
    from .harness import bench_problems

    n = cfg["n"]
    log(f"--- {cfg['name']} (n={n})")
    t0 = time.perf_counter()
    problems = [encode(cfg["gen"](s)) for s in range(n)]
    encode_s = time.perf_counter() - t0
    log(f"encode: {n} problems in {encode_s:.2f}s")

    mesh = None
    if cfg.get("mesh"):
        import jax

        from ..parallel import default_mesh

        if len(jax.devices()) > 1:
            mesh = default_mesh(jax.devices())
            log(f"mesh: {len(jax.devices())} devices")

    m = bench_problems(problems, host_sample=host_sample, mesh=mesh)
    host_s = m["host_s_per_problem"]
    return {
        "config": cfg["name"],
        "n_problems": n,
        "host_ms_per_problem": round(host_s * 1e3, 3),
        "host_rate": round(1.0 / host_s, 2),
        "device_seconds": round(m["device_seconds"], 4),
        "device_rate": round(m["device_rate"], 2),
        "speedup_vs_serial_host": round(m["device_rate"] * host_s, 3),
        # Startup attribution (ISSUE 4 satellite): every record carries
        # the backend first-touch wall and this config's compile
        # warm-up, so probe/retry stalls are visible in the JSON.
        "probe_wall_s": round(m["probe_wall_s"], 3),
        "warmup_seconds": round(m["warmup_seconds"], 3),
        # Compile-guard ledger delta (ISSUE 8): jit-entry traces paid
        # by this config's warm-up + timed dispatches.
        "n_compiles": m["n_compiles"],
        # Engine-economics columns (ISSUE 11) from the trip ledger.
        "useful_work_ratio": m["useful_work_ratio"],
        "straggler_p99_ratio": m["straggler_p99_ratio"],
        "pad_waste_ratio": m["pad_waste_ratio"],
        "sat": m["sat"],
        "unsat": m["unsat"],
    }


def _bench_encode_only(n: int = 200) -> Dict:
    """The reference's ``BenchmarkNewInput`` analog (bench_test.go:79-86):
    encode-only (constraint lowering, no solve) on the same seeded
    256-variable random instance the solve benchmark uses."""
    from ..sat.encode import encode

    vs = random_instance()  # length=256, seed=9 — the bench_test instance
    encode(vs)  # warm allocator/caches
    t0 = time.perf_counter()
    for _ in range(n):
        encode(vs)
    per = (time.perf_counter() - t0) / n
    log(f"encode-only: {per * 1e6:.0f} us/encode")
    return {
        "config": "encode-only (BenchmarkNewInput analog, 256-var seeded instance)",
        "encode_us": round(per * 1e6, 1),
        "encodes_per_sec": round(1.0 / per, 1),
    }


def run(quick: bool = False, out_path: Optional[str] = None,
        only: Optional[int] = None) -> List[Dict]:
    import jax

    from .harness import probe_wall_s

    probe_wall_s()  # time the first backend touch before anything else
    log(f"jax backend: {jax.default_backend()} devices={jax.devices()}")
    results = []
    for i, cfg in enumerate(_configs(quick)):
        if only is not None and i != only:
            continue
        res = _bench_config(cfg)
        print(json.dumps(res), flush=True)
        results.append(res)
    if only is None:
        res = _bench_encode_only()
        print(json.dumps(res), flush=True)
        results.append(res)
    if out_path:
        with open(out_path, "w") as f:
            json.dump(results, f, indent=2)
        log(f"wrote {out_path}")
    return results


def main() -> None:
    import os
    import signal

    from ..utils.platform_env import apply_platform_env

    # Same orphan guard as headline.main: a caller that dies mid-suite
    # must not leave this process wedged on the accelerator worker.
    sd = os.environ.get("DEPPY_BENCH_SELF_DESTRUCT")
    if sd and sd.isdigit() and int(sd) > 0:
        signal.alarm(int(sd))

    apply_platform_env()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true",
                    help="shrink batch sizes ~8x for smoke runs")
    ap.add_argument("--out", default=None, help="also write a JSON file")
    ap.add_argument("--only", type=int, default=None,
                    help="run a single config by index (0-6)")
    args = ap.parse_args()
    run(quick=args.quick, out_path=args.out, only=args.only)


if __name__ == "__main__":
    main()
