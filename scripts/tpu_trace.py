"""On-chip profiler trace of one headline dispatch (round-4 verdict #10).

Captures a ``jax.profiler.trace`` around ONE warm batched solve at the
headline shape and reduces the raw trace to the numbers the per-trip
overhead model is built on: total traced wall, device-compute total, and the top-N trace
events by accumulated duration.  The point is to replace the DERIVED
~175µs/while-trip model with observed event timings — SURVEY.md §5's
tracing-equivalence row.

Run (on a healthy worker):
  python scripts/tpu_trace.py [--n 4096] [--length 48] [--out FILE]

Writes the summary as one JSON line to stdout (and --out), and leaves
the full TensorBoard trace under --trace-dir for manual inspection.
"""

from __future__ import annotations

import argparse
import glob
import gzip
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _load_trace_events(trace_dir: str) -> list:
    """All complete-events from the newest .trace.json.gz under
    ``trace_dir`` (the TensorBoard dump layout:
    plugins/profile/<run>/<host>.trace.json.gz)."""
    paths = glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.trace.json.gz"))
    if not paths:
        return []
    newest = max(paths, key=os.path.getmtime)
    with gzip.open(newest, "rt") as f:
        doc = json.load(f)
    return [e for e in doc.get("traceEvents", [])
            if e.get("ph") == "X" and "dur" in e]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=4096)
    ap.add_argument("--length", type=int, default=48)
    ap.add_argument("--top", type=int, default=15)
    ap.add_argument("--trace-dir", default="/tmp/deppy_trace")
    ap.add_argument("--out", default="")
    a = ap.parse_args()

    import jax

    from deppy_tpu.engine import driver
    from deppy_tpu.models import random_instance
    from deppy_tpu.sat.encode import encode

    backend = jax.default_backend()
    print(f"backend={backend} devices={jax.devices()}", file=sys.stderr)

    problems = [encode(random_instance(length=a.length, seed=s))
                for s in range(a.n)]

    # Warm-up: compile everything outside the trace so the capture is
    # steady-state execution, not compilation.
    t0 = time.perf_counter()
    driver.solve_problems(problems)
    warm_s = time.perf_counter() - t0

    os.makedirs(a.trace_dir, exist_ok=True)
    t0 = time.perf_counter()
    with jax.profiler.trace(a.trace_dir):
        out = driver.solve_problems(problems)
    wall_s = time.perf_counter() - t0
    from deppy_tpu.engine import core as _core
    n_sat = sum(1 for r in out if int(r.outcome) == _core.SAT)

    events = _load_trace_events(a.trace_dir)
    by_name: dict = {}
    for e in events:
        rec = by_name.setdefault(e.get("name", "?"), [0, 0.0])
        rec[0] += 1
        rec[1] += float(e["dur"])  # microseconds
    top = sorted(by_name.items(), key=lambda kv: -kv[1][1])[:a.top]

    summary = {
        "metric": "headline dispatch trace",
        "backend": backend,
        "n_problems": a.n,
        "warm_s": round(warm_s, 3),
        "traced_wall_s": round(wall_s, 3),
        "rate": round(a.n / wall_s, 1),
        "sat": n_sat,
        "trace_events": len(events),
        "top_events": [
            {"name": k, "count": c, "total_us": round(us, 1),
             "mean_us": round(us / c, 1)}
            for k, (c, us) in top
        ],
        "trace_dir": a.trace_dir,
    }
    from scripts._stage import emit

    emit(summary, a.out)


if __name__ == "__main__":
    main()
