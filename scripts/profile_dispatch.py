"""Instrumented timing breakdown of one batched solve dispatch.

Answers "where does the wall-clock of ``driver.solve_problems`` go on the
TPU?": encode, pad/stack, per-chunk upload+plane derivation,
phase-1/2 dispatch, the small phase-3 strategy fetch, and the final
batched fetch.  Every boundary is forced with ``block_until_ready`` so
the attribution is real (the production path overlaps these stages —
the sum here is an upper bound on the production wall-clock).

Run: python scripts/profile_dispatch.py [--n 4096] [--length 48]
"""

from __future__ import annotations

import argparse
import sys
import time

sys.path.insert(0, ".")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=4096)
    ap.add_argument("--length", type=int, default=48)
    a = ap.parse_args()

    import jax
    import numpy as np

    from deppy_tpu.engine import core, driver
    from deppy_tpu.models import random_instance
    from deppy_tpu.sat.encode import encode

    print(f"backend={jax.default_backend()} devices={jax.devices()}",
          file=sys.stderr)

    t0 = time.perf_counter()
    problems = [encode(random_instance(length=a.length, seed=s))
                for s in range(a.n)]
    t_encode = time.perf_counter() - t0

    # Warm-up: full production path once (compiles everything).
    t0 = time.perf_counter()
    driver.solve_problems(problems)
    t_warm = time.perf_counter() - t0

    # Production wall-clock (what the benchmark reports).
    t0 = time.perf_counter()
    driver.solve_problems(problems)
    t_prod = time.perf_counter() - t0

    # --- instrumented replay of _solve_split's stages, serialized ---
    n = len(problems)
    ch_cap = min(max(n, 1), driver.MAX_LANES)
    d = driver._Dims(problems, ch_cap)
    CH = d.B
    n_chunks = max(1, -(-n // CH))
    total = n_chunks * CH
    budget = driver._budget(None)

    t0 = time.perf_counter()
    pts_np = driver.pad_stack(problems, d, total, pack=False)
    t_pad = time.perf_counter() - t0

    slices = driver._chunk_slices(total, CH)
    en = np.arange(total) < n

    t0 = time.perf_counter()
    pts_all = core.ProblemTensors(**{
        f: (jax.device_put(getattr(pts_np, f))
            if f in driver._COMPACT_FIELDS else getattr(pts_np, f))
        for f in core.ProblemTensors._fields
    })
    pts_dev = [driver._derive_planes(driver._rows(pts_all, sl), d)
               for sl in slices]
    jax.block_until_ready([p.pos_bits for p in pts_dev])
    t_upload = time.perf_counter() - t0

    en_dev = [en[sl] for sl in slices]
    fn_a = core.batched_search(d.V, d.NCON, d.NV, 0)
    t0 = time.perf_counter()
    outs = [fn_a(p, budget, e) for p, e in zip(pts_dev, en_dev)]
    jax.block_until_ready([o[0] for o in outs])
    t_phase1 = time.perf_counter() - t0

    fn_b = core.batched_minimize_gated(d.V, d.NCON, d.NV)
    t0 = time.perf_counter()
    res_b = [fn_b(p, o[0], o[2], o[1], budget, o[3], e)
             for p, o, e in zip(pts_dev, outs, en_dev)]
    jax.block_until_ready([r[0] for r in res_b])
    t_phase2 = time.perf_counter() - t0

    t0 = time.perf_counter()
    small = jax.device_get([(o[0], o[3], o[5]) for o in outs])
    t_small_fetch = time.perf_counter() - t0

    result = np.concatenate([s[0] for s in small])
    unsat_idx = np.nonzero(en & (result == core.UNSAT))[0]

    res_c = []
    t0 = time.perf_counter()
    if unsat_idx.size:
        empty_row = driver.pad_problem(driver._empty_problem(), d, pack=False)
        fn_c = core.batched_core(d.V, d.NCON, d.NV)
        steps = np.concatenate([s[1] for s in small])
        b = min(driver._pad_group(unsat_idx.size, None), CH)
        for idx in [unsat_idx[i: i + b]
                    for i in range(0, unsat_idx.size, b)]:
            res_c.append(fn_c(
                driver._put_chunk(
                    driver._gather_rows(pts_np, idx, b, empty_row),
                    None, d, full=True, red=False),
                budget,
                driver._pad_rows(steps[idx], b),
                np.arange(b) < idx.size,
            ))
        jax.block_until_ready([r[0] for r in res_c])
    t_phase3 = time.perf_counter() - t0

    t0 = time.perf_counter()
    jax.device_get({"b": res_b, "c": res_c})
    t_final_fetch = time.perf_counter() - t0

    rows = [
        ("encode (host)", t_encode),
        ("warm-up (compile + first run)", t_warm),
        ("PRODUCTION wall-clock", t_prod),
        ("— instrumented, serialized —", None),
        ("pad_stack (host)", t_pad),
        (f"upload {n_chunks} chunks + derive planes", t_upload),
        (f"phase 1 search ({n_chunks} dispatches)", t_phase1),
        (f"phase 2 minimize ({n_chunks} dispatches)", t_phase2),
        ("small strategy fetch", t_small_fetch),
        (f"phase 3 core ({len(res_c)} dispatches, "
         f"{unsat_idx.size} unsat lanes)", t_phase3),
        ("final batched fetch", t_final_fetch),
    ]
    for name, v in rows:
        if v is None:
            print(f"{name}")
        else:
            print(f"{name:48s} {v * 1e3:9.1f} ms")
    print(f"{'production rate':48s} {n / t_prod:9.1f} /s")


if __name__ == "__main__":
    main()
