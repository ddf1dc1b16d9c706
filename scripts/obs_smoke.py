#!/usr/bin/env python
"""Fleet observability smoke test (`make obs-smoke`, ISSUE 16).

Boots a REAL 3-replica fleet — subprocess replicas (the streaming,
profiling, and drift layers are process-global, so in-process servers
would share one registry) behind an in-process router aggregating the
merged fleet sink — and drives the observability plane end to end:

  * **telemetry streaming** — mixed-tenant churn through the router;
    the merged JSONL sink ends up holding replica-stamped events from
    every replica AND the router's own hop spans;
  * **metrics federation** — ``GET /fleet/metrics`` fleet warm-hit
    rollup matches the value recomputed from direct per-replica
    scrapes (within 1%), and every replica's families appear under its
    ``replica`` label;
  * **cross-replica trace assembly** — ``deppy trace --fleet`` on the
    merged sink reconstructs a routed request as ONE span tree: a
    single ``router.forward`` root with the replica's
    ``service.request`` beneath it and the coalesced dispatch grafted;
  * **cost-model drift watchdog** — every replica runs against a
    baseline profiled from the same workload; an injected
    ``driver.device_put`` latency fault (INSIDE the profiled dispatch
    window) trips ``deppy_costmodel_drift_ratio`` past the band on the
    faulted replica only, and its ``costmodel_drift`` event reaches
    the merged sink;
  * **`deppy top`** renders one dashboard snapshot; the router's
    ``POST /debug/dump`` fans the flight-recorder dump out to all
    replicas.

Device path on CPU jax (``--backend tpu``): the watchdog consumes the
trip ledger, which only device dispatches carry.  The subsystem suite
is ``make test-obs`` (tests/test_obs.py).
"""

from __future__ import annotations

import json
import os
import shutil
import socket
import subprocess
import sys
import tempfile
import time
from http.client import HTTPConnection

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

os.environ.setdefault("JAX_PLATFORMS", "cpu")

BUNDLES = 4
BSIZE = 5
FAMILIES = 6
ROUNDS = 6
TENANTS = ("alpha", "beta", "gamma")
BASE_SOLVES = 26   # baseline run: warm-up skip + a full averaging window
DRIFT_SOLVES = 24  # per replica: warm-up skip + >= min_samples verdicts
DRIFT_BAND = 1.0   # only upward drift can trip: |ratio-1| > 1 => ratio > 2
FAULT_LATENCY_S = 0.05
BOOT_TIMEOUT_S = 180.0
FLUSH_TIMEOUT_S = 20.0
AB_REPEATS = 100   # armed-vs-disarmed throughput: warm requests per round


def free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def request(port, method, path, body=None, headers=None, timeout=120):
    conn = HTTPConnection("127.0.0.1", port, timeout=timeout)
    h = dict(headers or {})
    if body is not None:
        h.setdefault("Content-Type", "application/json")
    conn.request(method, path,
                 body=json.dumps(body) if body is not None else None,
                 headers=h)
    resp = conn.getresponse()
    data = resp.read()
    conn.close()
    return resp.status, data


def family_doc(name: str, tgts: dict) -> dict:
    """Disconnected-bundle family (the fleet_smoke shape, smaller)."""
    variables = []
    for b in range(BUNDLES):
        for j in range(BSIZE):
            cons = []
            if j == 0:
                cons.append({"type": "mandatory"})
                cons.append({"type": "dependency",
                             "ids": [f"{name}b{b}v1"]})
            elif j == 1:
                cons.append({"type": "dependency",
                             "ids": [f"{name}b{b}v{tgts.get(b, 2)}"]})
            elif j < BSIZE - 1:
                cons.append({"type": "dependency",
                             "ids": [f"{name}b{b}v{j + 1}"]})
            variables.append({"id": f"{name}b{b}v{j}",
                              "constraints": cons})
    return {"variables": variables}


def mutate(tgts: dict, rnd: int) -> None:
    b = rnd % BUNDLES
    tgts[b] = 2 + (tgts.get(b, 2) - 2 + 1) % (BSIZE - 2)


def boot_replica(name, port, workdir, router_port=None, baseline=None,
                 telemetry_file=None, fault_plan=None):
    """One `deppy serve` subprocess on the device path, profile armed."""
    argv = [sys.executable, "-m", "deppy_tpu.cli", "serve",
            "--bind-address", f"127.0.0.1:{port}",
            "--health-probe-bind-address", "127.0.0.1:0",
            "--backend", "tpu", "--profile", "on", "--profile-sample", "1",
            "--portfolio", "off", "--speculate", "off",
            "--replica", name]
    if router_port is not None:
        argv += ["--obs-stream", f"127.0.0.1:{router_port}",
                 "--obs-flush-ms", "100"]
    if baseline is not None:
        argv += ["--obs-baseline", baseline]
    if telemetry_file is not None:
        argv += ["--telemetry-file", telemetry_file]
    if fault_plan is not None:
        argv += ["--fault-plan", json.dumps(fault_plan)]
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["DEPPY_TPU_OBS_DRIFT_BAND"] = str(DRIFT_BAND)
    # Shared persistent jit cache at a fixed path (the path is part of
    # the cache key): replicas after the first, and later runs, reuse the
    # baseline run's compile instead of paying ~seconds each.
    env.setdefault("JAX_COMPILATION_CACHE_DIR",
                   os.path.join(REPO, ".jax_cache"))
    log = open(os.path.join(workdir, f"{name}.log"), "w")
    proc = subprocess.Popen(argv, cwd=REPO, env=env,
                            stdout=log, stderr=subprocess.STDOUT)
    proc._smoke_log = log  # closed in shutdown_replica
    return proc


def wait_ready(port, proc, name):
    deadline = time.monotonic() + BOOT_TIMEOUT_S
    while time.monotonic() < deadline:
        if proc.poll() is not None:
            raise AssertionError(f"replica {name} exited {proc.returncode} "
                                 f"during boot")
        try:
            status, _ = request(port, "GET", "/metrics", timeout=5)
            if status == 200:
                return
        except OSError:
            pass
        time.sleep(0.25)
    raise AssertionError(f"replica {name} never became ready on :{port}")


def shutdown_replica(proc):
    if proc.poll() is None:
        proc.terminate()
        try:
            proc.wait(timeout=30)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.wait(timeout=10)
    log = getattr(proc, "_smoke_log", None)
    if log is not None:
        log.close()


def sink_events(path):
    out = []
    if not os.path.exists(path):
        return out
    with open(path, "r", encoding="utf-8") as fh:
        for line in fh:
            line = line.strip()
            if not line:
                continue
            try:
                out.append(json.loads(line))
            except ValueError:
                pass
    return out


def steady_costmodel(events) -> dict:
    """Per-size-class steady-state us/trip from a profiled run's sink,
    with the SAME warm-up exclusion the live watchdog applies (the
    first samples per class pay the jit compile)."""
    from deppy_tpu.obs.drift import WARMUP_SAMPLES, WINDOW

    per = {}
    for ev in events:
        if ev.get("kind") != "profile" or not ev.get("trips") \
                or not ev.get("solve_s"):
            continue
        cls = str(ev.get("size_class_name")
                  or ev.get("size_class") or "?")
        per.setdefault(cls, []).append(
            (float(ev["trips"]), float(ev["solve_s"])))
    classes = {}
    for cls, samples in per.items():
        samples = samples[WARMUP_SAMPLES:][-WINDOW:]
        sum_trips = sum(t for t, _ in samples)
        if len(samples) >= 4 and sum_trips > 0:
            classes[cls] = {"us_per_trip": round(
                1e6 * sum(s for _, s in samples) / sum_trips, 3)}
    return {"size_classes": classes}


def drift_ratios(port) -> dict:
    from deppy_tpu.obs.federate import parse_samples

    _, m = request(port, "GET", "/metrics")
    return {labels.get("size_class", "?"): v
            for n, labels, v in parse_samples(m.decode())
            if n == "deppy_costmodel_drift_ratio"}


def main() -> int:
    workdir = tempfile.mkdtemp(prefix="deppy-obs-smoke-")
    print(f"obs-smoke: workdir {workdir}", flush=True)

    # ---- phase 0: profile the baseline cost model -------------------
    base_sink = os.path.join(workdir, "base.jsonl")
    base_port = free_port()
    base = boot_replica("base", base_port, workdir,
                        telemetry_file=base_sink)
    try:
        wait_ready(base_port, base, "base")
        for i in range(BASE_SOLVES):
            s, body = request(base_port, "POST", "/v1/resolve",
                              family_doc(f"base{i}.", {}))
            assert s == 200, (s, body[:200])
    finally:
        shutdown_replica(base)
    costmodel = steady_costmodel(sink_events(base_sink))
    assert costmodel["size_classes"], (
        "baseline run produced no steady device-dispatch samples — "
        "did the device path run? (see base.log in the workdir)")
    baseline_path = os.path.join(workdir, "baseline.json")
    with open(baseline_path, "w", encoding="utf-8") as fh:
        json.dump(costmodel, fh, indent=2)
    print(f"obs-smoke: baseline {costmodel['size_classes']}", flush=True)

    # ---- phase 1: boot the fleet ------------------------------------
    from deppy_tpu.fleet import Router

    fleet_sink = os.path.join(workdir, "fleet.jsonl")
    router_port = free_port()
    ports = [free_port() for _ in range(3)]
    names = ["rep0", "rep1", "rep2"]
    fault_plan = [{"point": "driver.device_put", "kind": "latency",
                   "latency_s": FAULT_LATENCY_S, "times": -1}]
    replicas = [
        boot_replica(name, port, workdir, router_port=router_port,
                     baseline=baseline_path,
                     fault_plan=fault_plan if name == "rep2" else None)
        for name, port in zip(names, ports)]
    addrs = [f"127.0.0.1:{p}" for p in ports]
    router = None
    try:
        for name, port, proc in zip(names, ports, replicas):
            wait_ready(port, proc, name)
        router = Router(bind_address=f"127.0.0.1:{router_port}",
                        replicas=addrs, probe_interval_s=0.2,
                        probe_failures=3, obs_sink=fleet_sink)
        router.start()

        # ---- phase 2: mixed-tenant churn through the router ---------
        states = [dict() for _ in range(FAMILIES)]
        for rnd in range(ROUNDS):
            for f in range(FAMILIES):
                if rnd:
                    mutate(states[f], rnd - 1)
                doc = family_doc(f"f{f}.", states[f])
                s, body = request(
                    router_port, "POST", "/v1/resolve", doc,
                    {"X-Deppy-Tenant": TENANTS[f % len(TENANTS)]})
                assert s == 200, (rnd, f, s, body[:200])

        # One traced request (fresh family => a real dispatch).
        s, body = request(router_port, "POST", "/v1/resolve",
                          family_doc("traced.", {}),
                          {"X-Deppy-Request-Id": "obs-smoke-trace"})
        assert s == 200, (s, body[:200])

        # The ring hashes families over the replicas' (random) ports —
        # a port layout can leave some replica with no routed family at
        # all, and a replica with no traffic has no events to stream.
        # One direct solve per replica guarantees every streamer has
        # something to say before the merged-sink check.
        for i, port in enumerate(ports):
            s, body = request(port, "POST", "/v1/resolve",
                              family_doc(f"direct{i}.", {}))
            assert s == 200, (i, s, body[:200])

        # ---- phase 3: merged sink holds the whole fleet -------------
        want = set(names) | {"router"}
        deadline = time.monotonic() + FLUSH_TIMEOUT_S
        got = set()
        while time.monotonic() < deadline:
            got = {ev.get("replica") for ev in sink_events(fleet_sink)}
            if want <= got:
                break
            time.sleep(0.25)
        assert want <= got, (
            f"merged sink never saw events from the whole fleet: "
            f"have {sorted(x for x in got if x)}, want {sorted(want)}")

        # ---- phase 4: federated metrics match the replicas ----------
        from deppy_tpu.obs.federate import parse_samples

        hits = asks = 0.0
        for port in ports:
            _, m = request(port, "GET", "/metrics")
            samples = parse_samples(m.decode())

            def total(family):
                return sum(v for n, _, v in samples if n == family)

            hits += total("deppy_cache_hits_total") \
                + total("deppy_incremental_hits_total")
            asks += total("deppy_cache_hits_total") \
                + total("deppy_cache_misses_total")
        assert asks > 0
        expected = hits / asks
        s, m = request(router_port, "GET", "/fleet/metrics")
        assert s == 200
        fleet_text = m.decode()
        fleet_samples = parse_samples(fleet_text)
        rollup = [v for n, labels, v in fleet_samples
                  if n == "deppy_fleet_warm_hit_ratio"
                  and "replica" not in labels]
        assert rollup, "no deppy_fleet_warm_hit_ratio in /fleet/metrics"
        assert abs(rollup[0] - expected) <= 0.01 * max(expected, 1e-9), (
            f"fleet warm-hit rollup {rollup[0]} vs per-replica "
            f"{expected:.6f}")
        for addr in addrs:
            assert f'replica="{addr}"' in fleet_text, (
                f"replica {addr} missing from the federated scrape")

        # ---- phase 5: one-tree cross-replica trace ------------------
        out = subprocess.run(
            [sys.executable, "-m", "deppy_tpu.cli", "trace",
             "obs-smoke-trace", "--fleet", "--file", fleet_sink,
             "--output", "json"],
            cwd=REPO, capture_output=True, text=True, timeout=120)
        assert out.returncode == 0, (out.returncode, out.stderr[-2000:])
        doc = json.loads(out.stdout)
        spans = doc["spans"]
        ids = {sp["span_id"] for sp in spans}
        roots = [sp for sp in spans
                 if sp.get("parent_id") not in ids
                 and not sp.get("links")]
        assert len(roots) == 1, (
            f"fleet trace is not one tree: roots "
            f"{[(sp['name'], sp['span_id']) for sp in roots]}")
        assert roots[0]["name"] == "router.forward", roots[0]
        names_seen = {sp["name"] for sp in spans}
        assert "service.request" in names_seen, names_seen
        assert any(n.startswith(("sched.", "driver."))
                   for n in names_seen), (
            f"no dispatch spans grafted into the fleet trace: "
            f"{sorted(names_seen)}")

        # ---- phase 6: drift trips on the faulted replica only -------
        for i, port in enumerate(ports):
            for k in range(DRIFT_SOLVES):
                s, body = request(port, "POST", "/v1/resolve",
                                  family_doc(f"drift{i}x{k}.", {}))
                assert s == 200, (i, k, s, body[:200])
        faulted = drift_ratios(ports[2])
        assert faulted and max(faulted.values()) > 1.0 + DRIFT_BAND, (
            f"injected {FAULT_LATENCY_S * 1e3:.0f}ms device_put latency "
            f"never tripped the watchdog on rep2: ratios {faulted}")
        for name, port in zip(names[:2], ports[:2]):
            ratios = drift_ratios(port)
            assert ratios, f"no drift verdicts on healthy {name}"
            bad = {c: r for c, r in ratios.items()
                   if not 0.2 <= r <= 1.0 + DRIFT_BAND}
            assert not bad, (
                f"healthy {name} drifted off the baseline: {bad}")

        deadline = time.monotonic() + FLUSH_TIMEOUT_S
        drift_reps = set()
        while time.monotonic() < deadline:
            drift_reps = {ev.get("replica")
                          for ev in sink_events(fleet_sink)
                          if ev.get("kind") == "costmodel_drift"}
            if drift_reps:
                break
            time.sleep(0.25)
        assert drift_reps == {"rep2"}, (
            f"costmodel_drift events in the merged sink from "
            f"{sorted(x for x in drift_reps if x)}, want ['rep2']")

        # ---- phase 7: dashboard + fleet-wide dump fan-out -----------
        out = subprocess.run(
            [sys.executable, "-m", "deppy_tpu.cli", "top",
             "--router", f"127.0.0.1:{router_port}", "--once"],
            cwd=REPO, capture_output=True, text=True, timeout=120)
        assert out.returncode == 0, (out.returncode, out.stderr[-2000:])
        assert "deppy fleet @" in out.stdout, out.stdout
        for addr in addrs:
            assert addr in out.stdout, (
                f"replica {addr} missing from `deppy top`:\n{out.stdout}")

        s, body = request(router_port, "POST", "/debug/dump",
                          {"reason": "obs-smoke"})
        assert s == 200, (s, body[:200])
        dump = json.loads(body)
        assert sorted(dump.get("dumped", {})) == sorted(addrs), dump
        assert not dump.get("errors"), dump

        # ---- phase 8: streaming armed vs disarmed -------------------
        # A fresh A/B pair (identical state, unlike the long-served
        # rep0): one replica streaming to the live router with the
        # watchdog armed, one with no obs flags at all.  Bodies must be
        # byte-identical and warm-path throughput within 5%.  Rounds
        # interleave and each side keeps its best — scheduler noise on
        # a shared CI box only ever slows a window, so best-of-N
        # converges on each side's true rate.
        ab_ports = {"armed": free_port(), "plain": free_port()}
        ab_procs = {
            "armed": boot_replica("armed", ab_ports["armed"], workdir,
                                  router_port=router_port,
                                  baseline=baseline_path),
            "plain": boot_replica("plain", ab_ports["plain"], workdir)}
        try:
            for name, proc in ab_procs.items():
                wait_ready(ab_ports[name], proc, name)
            ab_doc = family_doc("ab.", {})
            bodies = {}
            for name, port in ab_ports.items():
                s, bodies[name] = request(port, "POST", "/v1/resolve",
                                          ab_doc)
                assert s == 200, (name, s)
            assert bodies["armed"] == bodies["plain"], (
                "streaming armed vs disarmed responses differ: "
                f"{bodies['armed'][:200]} vs {bodies['plain'][:200]}")

            best = {"armed": None, "plain": None}
            for _ in range(4):
                for name, port in ab_ports.items():
                    t0 = time.perf_counter()
                    for _ in range(AB_REPEATS):
                        s, b = request(port, "POST", "/v1/resolve",
                                       ab_doc)
                        assert s == 200 and b == bodies["armed"]
                    wall = time.perf_counter() - t0
                    if best[name] is None or wall < best[name]:
                        best[name] = wall
            armed_rate = AB_REPEATS / best["armed"]
            plain_rate = AB_REPEATS / best["plain"]
            ab_delta = armed_rate / plain_rate - 1.0
            assert armed_rate >= 0.95 * plain_rate, (
                f"telemetry streaming cost {-ab_delta:.1%} serving "
                f"throughput (armed {armed_rate:.1f}/s vs disarmed "
                f"{plain_rate:.1f}/s)")
        finally:
            for proc in ab_procs.values():
                shutdown_replica(proc)

        n_events = len(sink_events(fleet_sink))
        print(f"obs-smoke: PASS (merged sink {n_events} events from "
              f"{sorted(want)}; fleet warm-hit rollup {rollup[0]:.4f} "
              f"matches replicas ({expected:.4f}); routed trace is one "
              f"tree of {len(spans)} spans rooted at router.forward; "
              f"{FAULT_LATENCY_S * 1e3:.0f}ms device_put fault tripped "
              f"drift ratio {max(faulted.values()):.1f} on rep2 only; "
              f"dump fanned out to {len(dump['dumped'])} replicas; "
              f"armed vs disarmed byte-identical at "
              f"{ab_delta:+.1%} throughput)")
        shutil.rmtree(workdir, ignore_errors=True)
        return 0
    finally:
        if router is not None:
            router.shutdown()
        for proc in replicas:
            shutdown_replica(proc)


if __name__ == "__main__":
    sys.exit(main())
