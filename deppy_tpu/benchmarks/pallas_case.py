"""The Pallas kernel's target workload: ONE giant catalog problem.

The fused VMEM-resident fixpoint kernel (:mod:`deppy_tpu.engine.pallas_bcp`)
loses to the vmapped jnp "bits" path on batched workloads — XLA vectorizes
the batch axis across the VPU lanes — and is predicted by its own docstring
to win only on a single problem whose clause planes approach VMEM capacity,
where each propagation round's HBM re-streaming is the bottleneck.  This
benchmark builds exactly that case — the default 250 packages × 8 versions
is a ~2k-bundle catalog whose padded plane dims sit just under the
kernel's VMEM caps (C ≤ 8192, Wv ≤ 128; see pallas_bcp.py) — and
measures ``bits`` vs ``pallas`` on it.

Run on TPU: ``python -m deppy_tpu.benchmarks.pallas_case``.
Prints one JSON line per impl and a final comparison line.
"""

from __future__ import annotations

import argparse
import json
import time

from .harness import log


def _build(n_packages: int, versions: int):
    from ..models import operatorhub_catalog
    from ..sat.encode import encode

    t0 = time.perf_counter()
    p = encode(operatorhub_catalog(
        n_packages=n_packages, versions_per_package=versions, seed=0
    ))
    log(f"encode: {time.perf_counter() - t0:.2f}s — n_vars={p.n_vars} "
        f"n_cons={p.n_cons} clauses={p.clauses.shape}")
    return p


def _measure(problem, impl: str, repeats: int) -> dict:
    from ..engine import core, driver

    core.set_bcp_impl(impl)
    try:
        t0 = time.perf_counter()
        (res,) = driver.solve_problems([problem])
        warm_s = time.perf_counter() - t0
        times = []
        for _ in range(repeats):
            t0 = time.perf_counter()
            (res,) = driver.solve_problems([problem])
            times.append(time.perf_counter() - t0)
        best = min(times)
        rec = {
            "impl": impl,
            "solve_ms": round(best * 1e3, 2),
            "rate": round(1.0 / best, 2),
            "warmup_s": round(warm_s, 2),
            "outcome": int(res.outcome),
            "steps": int(res.steps),
        }
    finally:
        core.set_bcp_impl("auto")
    return rec


def _append_log(rec: dict, log_path: str) -> None:
    if log_path:
        with open(log_path, "a") as f:
            f.write(json.dumps(rec) + "\n")


def run(n_packages: int, versions: int, repeats: int,
        impls: "list | None" = None, log_path: str = "") -> list:
    import jax

    backend = jax.default_backend()
    log(f"jax backend: {backend} devices={jax.devices()}")
    problem = _build(n_packages, versions)

    # Respect the kernel's VMEM budget (pallas_bcp.py docstring): the
    # dominant planes are 2*C*Wv int32 words.
    from ..engine.driver import _Dims

    d = _Dims([problem], 1)
    vmem_mb = 2 * d.C * d.Wv * 4 / 2**20
    log(f"padded dims: C={d.C} V={d.V} Wv={d.Wv} -> clause planes "
        f"{vmem_mb:.1f} MiB in VMEM")

    if impls is None:
        impls = ["bits", "pallas"] if backend == "tpu" else ["bits"]
        if backend != "tpu":
            log("pallas requires the TPU backend; measuring bits only")
    out = []
    for impl in impls:
        rec = _measure(problem, impl, repeats)
        print(json.dumps(rec), flush=True)
        # Per-record, not end-of-run: a later (riskier) impl wedging the
        # worker must not cost the safe measurement already completed —
        # the same reason the revalidation ladder orders its stages
        # safest-first.
        _append_log(rec, log_path)
        out.append(rec)
    if len(out) >= 2:
        base = out[0]
        for rec in out[1:]:
            cmp = {
                "metric": (f"single giant catalog solve, {rec['impl']} "
                           f"vs {base['impl']}"),
                f"{base['impl']}_ms": base["solve_ms"],
                f"{rec['impl']}_ms": rec["solve_ms"],
                "speedup": round(base["solve_ms"] / rec["solve_ms"], 3),
                "agree": rec["outcome"] == base["outcome"],
            }
            print(json.dumps(cmp), flush=True)
            _append_log(cmp, log_path)
            out.append(cmp)
    return out


def main() -> None:
    from ..utils.platform_env import apply_platform_env

    apply_platform_env()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--packages", type=int, default=250)
    ap.add_argument("--versions", type=int, default=8)
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--impls", default="",
                    help="comma-separated impl list (default: bits,pallas "
                    "on TPU).  The over-VMEM case is 'bits,blockwise' at "
                    "--packages 1000+ (clause planes 2-4x the fixpoint "
                    "kernel's VMEM cap; engine/pallas_blockwise.py)")
    ap.add_argument("--log", default="",
                    help="also append each record as a JSON line here "
                    "(the revalidation ladder passes its own log so the "
                    "measurement survives the stage)")
    args = ap.parse_args()
    run(args.packages, args.versions, args.repeats,
        impls=[s.strip() for s in args.impls.split(",") if s.strip()]
        or None, log_path=args.log)


if __name__ == "__main__":
    main()
