#!/usr/bin/env python3
"""Run the batch resolver end to end on the local TPU, in this one process.

    python chip_smoke.py            one chip: library batch, served path,
                                    Pallas kernels
    python chip_smoke.py --chips 4  four chips: the serving mesh and clause
                                    sharding, each against device 0

Every phase compares its answers with the host spec engine
(``backend="host"``, ``sat/host.py``): installed sets identical and
``NotSatisfiable`` messages byte for byte.  After each phase one line
reports the fault counters; a host-routed fault, a retry, an open
breaker or an exception in any phase makes the exit code non-zero.  The
last line of stdout is the one JSON result object and nothing else; it
is printed only when every phase passed on a TPU.

The process owns the chip, so nothing here starts a child process that
touches JAX, and all work sits under the ``__main__`` check.  The phase
functions take their sizes as arguments: ``tests/test_chip_smoke.py``
runs them at tiny sizes on CPU.
"""

from __future__ import annotations

import argparse
import json
import random
import sys
import time

# BASELINE.json configs 1, 3 and 4 at the fleet widths of
# deppy_tpu/benchmarks/suite.py, plus its UNSAT-heavy pinned-tenant
# fleet, which drives core extraction on the device.
FLEET = {"operatorhub": 4096, "chains": 256, "gvk": 256, "tenants": 2048}
KERNEL_LANES = 512
SERVED_SINGLES = 6
SERVED_BATCH = 8
CLAUSE_PACKAGES = 400
# Above this many seconds of host reference, phase 2 compares a seeded
# sample (every device-UNSAT lane plus SAMPLE_LANES others) instead of
# every lane; every device-SAT lane is still checked to be a model.
HOST_REF_BUDGET_S = 60.0
SAMPLE_LANES = 512


def log(msg: str) -> None:
    print(msg, flush=True)


def fleet_batch(counts=None, seed: int = 0):
    """The phase-2 batch: ``[(family, variables), ...]`` with
    ``counts[family]`` problems per family, seeds from ``seed`` up."""
    from deppy_tpu.models import (gvk_conflict_catalog, operatorhub_catalog,
                                  pinned_tenant_catalog,
                                  version_pinned_chains)

    gens = {
        "operatorhub": lambda s: operatorhub_catalog(
            n_packages=40, versions_per_package=5, seed=s),
        "chains": lambda s: version_pinned_chains(depth=20, width=3, seed=s),
        "gvk": lambda s: gvk_conflict_catalog(
            n_groups=20, providers_per_group=4, n_required=10, seed=s),
        "tenants": lambda s: pinned_tenant_catalog(seed=s),
    }
    counts = FLEET if counts is None else counts
    return [(fam, gens[fam](seed + i))
            for fam, n in counts.items() for i in range(n)]


def render(result) -> str:
    """One answer in the service's wire form, canonical JSON: what two
    backends must agree on byte for byte (installed set, or the
    ``NotSatisfiable`` message with its core)."""
    from deppy_tpu import io as problem_io
    from deppy_tpu.sat.errors import NotSatisfiable

    doc = problem_io.result_to_dict(result)
    if isinstance(result, NotSatisfiable):
        doc["message"] = str(result)
    return json.dumps(doc, sort_keys=True)


def counters() -> dict:
    """The fault and fallback counters every phase reports."""
    from deppy_tpu import faults, telemetry
    from deppy_tpu.analysis import compileguard

    snap = telemetry.default_registry().snapshot()
    return {
        "host_routed": int(snap.get("deppy_fault_host_routed_total", 0)),
        "retries": int(snap.get("deppy_fault_retries", 0)),
        "host_fallback_rows": int(
            snap.get("deppy_host_fallback_rows_total", 0)),
        "breaker": faults.default_breaker().state(),
        "device_breakers": sorted({b.state() for b in
                                   faults.device_breakers().values()}),
        "compiles": compileguard.trace_count(),
    }


def check_counters(phase: str) -> dict:
    """Print the phase's counter line; raise on any hidden fallback."""
    c = counters()
    log(f"[{phase}] counters {json.dumps(c, sort_keys=True)}")
    bad = []
    if c["host_routed"]:
        bad.append(f"deppy_fault_host_routed_total={c['host_routed']}")
    if c["retries"]:
        bad.append(f"deppy_fault_retries={c['retries']}")
    if c["breaker"] != "closed":
        bad.append(f"breaker {c['breaker']}")
    if set(c["device_breakers"]) - {"closed"}:
        bad.append(f"device breakers {c['device_breakers']}")
    if bad:
        raise RuntimeError(f"{phase}: device path fell back: "
                           + ", ".join(bad))
    return c


def compare(phase: str, got, want, idx) -> None:
    """Raise unless ``got[i]`` renders like ``want[i]`` for every i in
    ``idx``."""
    bad = [i for i in idx if render(got[i]) != render(want[i])]
    if bad:
        i = bad[0]
        raise AssertionError(
            f"{phase}: {len(bad)} of {len(idx)} lanes differ; lane {i}: "
            f"got {render(got[i])[:300]} want {render(want[i])[:300]}")


def host_reference(variables, device_results, *, budget_s=HOST_REF_BUDGET_S,
                   sample=SAMPLE_LANES, seed=0):
    """Host-engine answers for the lanes to compare: ``{lane: answer}``.

    Every lane when that fits ``budget_s``; otherwise every lane the
    device answered UNSAT or Incomplete plus a seeded ``sample`` of the
    rest (the first pass over that sample measures the host's rate)."""
    from deppy_tpu import resolution
    from deppy_tpu.sat.errors import Incomplete, NotSatisfiable

    n = len(variables)
    first = sorted(
        {i for i, r in enumerate(device_results)
         if isinstance(r, (NotSatisfiable, Incomplete))}
        | set(random.Random(seed).sample(range(n), min(sample, n))))
    host = resolution.BatchResolver(backend="host")
    t0 = time.perf_counter()
    ref = dict(zip(first, host.solve([variables[i] for i in first])))
    spent = time.perf_counter() - t0
    rest = [i for i in range(n) if i not in ref]
    if rest and spent / len(first) * n <= budget_s:
        ref.update(zip(rest, host.solve([variables[i] for i in rest])))
    return ref, time.perf_counter() - t0


def check_models(phase: str, variables, results) -> None:
    """Every SAT answer satisfies its problem's constraints."""
    from deppy_tpu.utils import check_solution

    for i, r in enumerate(results):
        if isinstance(r, dict):
            bad = check_solution(variables[i],
                                 [k for k, v in r.items() if v])
            if bad:
                raise AssertionError(f"{phase}: lane {i} violates {bad[0]}")


def phase_library(batch, **ref_kw) -> list:
    """Phase 2: ``BatchResolver(backend="tpu")`` on the fleet batch, twice
    (cold, then warm), against the host engine.  Returns the device
    answers."""
    from deppy_tpu import resolution

    variables = [vs for _, vs in batch]
    dev = resolution.BatchResolver(backend="tpu")
    t0 = time.perf_counter()
    results = dev.solve(variables)
    cold = time.perf_counter() - t0
    log(f"[library] first call {cold:.3f}s (compile included), "
        f"{len(variables)} problems")
    t0 = time.perf_counter()
    again = dev.solve(variables)
    warm = time.perf_counter() - t0
    log(f"[library] second call {warm:.3f}s (warm)")
    compare("library warm vs cold", again, results, range(len(results)))
    ref, host_s = host_reference(variables, results, **ref_kw)
    compare("library vs host", results, ref, sorted(ref))
    check_models("library", variables, results)
    by_outcome: dict = {}
    for r in results:
        key = type(r).__name__
        by_outcome[key] = by_outcome.get(key, 0) + 1
    log(f"[library] outcomes {json.dumps(by_outcome, sort_keys=True)}; "
        f"host reference {len(ref)}/{len(results)} lanes in {host_s:.3f}s, "
        f"all identical")
    return results


def _post(port: int, doc: dict) -> dict:
    from http.client import HTTPConnection

    conn = HTTPConnection("127.0.0.1", port, timeout=600)
    try:
        conn.request("POST", "/v1/resolve", json.dumps(doc),
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        body = json.loads(resp.read())
    finally:
        conn.close()
    if resp.status != 200:
        raise RuntimeError(f"/v1/resolve answered {resp.status}: {body}")
    return body


def served_requests(batch, results, singles=SERVED_SINGLES,
                    batch_size=SERVED_BATCH):
    """The requests phase 3 sends: ``[(doc, [lane, ...]), ...]`` — single
    documents from every family, one ``{"problems": [...]}`` batch, and
    one document whose phase-2 answer is UNSAT."""
    from deppy_tpu import io as problem_io
    from deppy_tpu.sat.errors import NotSatisfiable

    def doc_of(i):
        return {"variables": [problem_io.variable_to_dict(v)
                              for v in batch[i][1]]}

    fams: dict = {}
    for i, (fam, _) in enumerate(batch):
        fams.setdefault(fam, []).append(i)
    picks = []
    while len(picks) < singles:
        for lanes in fams.values():
            if len(picks) < singles and lanes:
                picks.append(lanes.pop(0))
    reqs = [(doc_of(i), [i]) for i in picks]
    lanes = list(range(len(batch)))[-batch_size:]
    reqs.append(({"problems": [doc_of(i) for i in lanes]}, lanes))
    unsat = next(i for i, r in enumerate(results)
                 if isinstance(r, NotSatisfiable))
    reqs.append((doc_of(unsat), [unsat]))
    return reqs


def phase_served(batch, results, **req_kw) -> None:
    """Phase 3: ``service.Server(backend="tpu")`` on port 0, in this
    process; every answer equals phase 2's for the same problem."""
    from deppy_tpu import io as problem_io
    from deppy_tpu import service

    srv = service.Server("127.0.0.1:0", "127.0.0.1:0", backend="tpu")
    srv.start()
    try:
        reqs = served_requests(batch, results, **req_kw)
        t0 = time.perf_counter()
        for doc, lanes in reqs:
            body = _post(srv.api_port, doc)
            got = body["results"]
            want = [problem_io.result_to_dict(results[i]) for i in lanes]
            if got != want:
                raise AssertionError(
                    f"served: lanes {lanes}: got {str(got)[:300]} "
                    f"want {str(want)[:300]}")
        log(f"[served] {len(reqs)} POST /v1/resolve requests in "
            f"{time.perf_counter() - t0:.3f}s, all identical to the "
            f"library batch")
    finally:
        srv.shutdown(drain_s=5.0)


KERNEL_SWITCHES = (("bcp", "pallas"), ("bcp", "blockwise"),
                   ("search", "fused"))


def kernel_lanes(batch, n=KERNEL_LANES, seed=0) -> list:
    """A seeded slice of ``n`` phase-2 lanes, drawn from every family."""
    return sorted(random.Random(seed).sample(range(len(batch)),
                                             min(n, len(batch))))


def phase_kernels(batch, results, lanes, switches=KERNEL_SWITCHES) -> None:
    """Phase 4: each Pallas path forced through its switch on
    ``lanes`` of the phase-2 batch; answers identical to phase 2."""
    from deppy_tpu import resolution
    from deppy_tpu.analysis import compileguard
    from deppy_tpu.engine import core

    variables = [batch[i][1] for i in lanes]
    want = [results[i] for i in lanes]
    for kind, impl in switches:
        setter = core.set_bcp_impl if kind == "bcp" else core.set_search_impl
        setter(impl)
        try:
            t0 = time.perf_counter()
            got = resolution.BatchResolver(backend="tpu").solve(variables)
            cold = time.perf_counter() - t0
            # Switching back drops the compile ledger: read it first.
            compiles = compileguard.trace_count()
        finally:
            setter("auto")
        compare(f"kernel {kind}={impl}", got, want, range(len(want)))
        log(f"[kernels] {kind}={impl}: {len(lanes)} lanes in {cold:.3f}s "
            f"(compile included, {compiles} traces), identical to the "
            f"library batch")
        check_counters(f"kernels {kind}={impl}")


def phase_mesh(batch, n_devices: int = 4) -> None:
    """``--chips 4``, part 1: the phase-2 batch through the scheduler's
    serving mesh against the same batch on device 0, and the batch axis
    placed on ``n_devices`` distinct devices."""
    import jax

    from deppy_tpu import telemetry
    from deppy_tpu.engine import driver
    from deppy_tpu.parallel import serving_mesh, shard_batch
    from deppy_tpu.sat.encode import encode

    mesh = serving_mesh(n_devices)
    if mesh is None or mesh.size != n_devices:
        raise RuntimeError(f"serving_mesh({n_devices}) gave {mesh}")
    problems = [encode(vs) for _, vs in batch]

    t0 = time.perf_counter()
    one = driver.decode_results(problems, driver.solve_problems(problems))
    log(f"[mesh] device 0: {len(problems)} problems in "
        f"{time.perf_counter() - t0:.3f}s (compile included)")
    reg = telemetry.default_registry()
    before = dict(reg.counter("deppy_shard_dispatches_total",
                              labelname="device").value)
    t0 = time.perf_counter()
    sharded = driver.decode_results(
        problems, driver.solve_problems_sharded(problems, mesh=mesh))
    log(f"[mesh] serving mesh of {n_devices}: {len(problems)} problems in "
        f"{time.perf_counter() - t0:.3f}s (compile included)")
    compare("mesh vs device 0", sharded, one, range(len(one)))
    after = reg.counter("deppy_shard_dispatches_total",
                        labelname="device").value
    used = sorted(k for k, v in after.items() if v > before.get(k, 0))
    if len(used) != n_devices:
        raise AssertionError(f"mesh: shards dispatched to devices {used}, "
                             f"want {n_devices} distinct")

    chunk = problems[:min(len(problems), driver.MAX_LANES)]
    d = driver._Dims(chunk, len(chunk))
    total = -(-d.B // n_devices) * n_devices
    pts = shard_batch(mesh, driver.pad_stack(chunk, d, total))
    jax.block_until_ready(pts)
    placed = {dev for leaf in jax.tree_util.tree_leaves(pts)
              for dev in leaf.sharding.device_set}
    # memory_stats() is None where the backend keeps none (XLA:CPU).
    in_use = {dev.id: (dev.memory_stats() or {}).get("bytes_in_use")
              for dev in mesh.devices.flat}
    del pts
    if (len(placed) != n_devices
            or not all(v is None or v > 0 for v in in_use.values())):
        raise AssertionError(f"mesh: batch axis on {len(placed)} devices, "
                             f"bytes_in_use {in_use}")
    log(f"[mesh] answers identical; shard dispatches on devices {used}; "
        f"batch axis on {len(placed)} devices, bytes_in_use "
        f"{json.dumps(in_use, sort_keys=True)}")


def phase_clause_shard(n_devices: int = 4,
                       n_packages: int = CLAUSE_PACKAGES) -> None:
    """``--chips 4``, part 2: one large catalog with its clause rows over
    ``clause_mesh`` against the unsharded solve."""
    import jax

    from deppy_tpu import sat
    from deppy_tpu.models import operatorhub_catalog
    from deppy_tpu.parallel import clause_mesh, solve_one_sharded

    vs = operatorhub_catalog(n_packages=n_packages, versions_per_package=5,
                             seed=0)
    mesh = clause_mesh(jax.devices()[:n_devices])

    def outcome(fn):
        try:
            return sorted(v.identifier for v in fn())
        except sat.NotSatisfiable as e:
            return str(e)

    t0 = time.perf_counter()
    want = outcome(lambda: sat.Solver(vs, backend="tpu").solve())
    t1 = time.perf_counter()
    got = outcome(lambda: solve_one_sharded(vs, mesh=mesh))
    t2 = time.perf_counter()
    if got != want:
        raise AssertionError(f"clause shard: {str(got)[:300]} != "
                             f"{str(want)[:300]}")
    log(f"[clause] {len(vs)} variables over clause_mesh({n_devices}) in "
        f"{t2 - t1:.3f}s, unsharded {t1 - t0:.3f}s (compile included); "
        f"identical")


def run_one_chip(batch, *, kernel_lane_count=KERNEL_LANES, ref_kw=None,
                 req_kw=None) -> None:
    """Phases 2-4, each followed by its counter line."""
    t0 = time.perf_counter()
    results = phase_library(batch, **(ref_kw or {}))
    check_counters("library")
    log(f"[library] phase {time.perf_counter() - t0:.3f}s")
    t0 = time.perf_counter()
    phase_served(batch, results, **(req_kw or {}))
    check_counters("served")
    log(f"[served] phase {time.perf_counter() - t0:.3f}s")
    t0 = time.perf_counter()
    phase_kernels(batch, results, kernel_lanes(batch, kernel_lane_count))
    log(f"[kernels] phase {time.perf_counter() - t0:.3f}s")


def run_four_chips(batch, n_devices: int = 4,
                   n_packages: int = CLAUSE_PACKAGES) -> None:
    t0 = time.perf_counter()
    phase_mesh(batch, n_devices)
    check_counters("mesh")
    log(f"[mesh] phase {time.perf_counter() - t0:.3f}s")
    t0 = time.perf_counter()
    phase_clause_shard(n_devices, n_packages)
    check_counters("clause")
    log(f"[clause] phase {time.perf_counter() - t0:.3f}s")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the multi-chip phases")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    dev = devices[0]
    log(f"device: platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devices)}")
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found platform {dev.platform!r}, not a TPU",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX found "
              f"{len(devices)} devices", file=sys.stderr)
        return 2

    from deppy_tpu import hostpool
    from deppy_tpu.utils.platform_env import apply_platform_env

    apply_platform_env()
    # One process: the host reference runs inline, not in pool workers.
    hostpool.configure_pool(0)
    t0 = time.perf_counter()
    batch = fleet_batch()
    log(f"[setup] {len(batch)} problems generated in "
        f"{time.perf_counter() - t0:.3f}s")
    try:
        if args.chips == 4:
            run_four_chips(batch)
        else:
            run_one_chip(batch)
    except Exception as e:  # every phase failure ends the run non-zero
        import traceback

        traceback.print_exc()
        print(f"chip_smoke: FAILED: {type(e).__name__}: {e}",
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
