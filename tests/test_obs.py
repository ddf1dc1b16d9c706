"""Fleet observability plane (ISSUE 16).

The acceptance surface, from the issue:

  * **telemetry streaming** — replicas push batched sink events to the
    router's aggregator; the enqueue path NEVER blocks or raises, a
    slow/dead aggregator costs counted drops, never serving latency;
  * **aggregation** — the merged fleet sink is replica-stamped (the
    transport-level source is authoritative over any forged in-event
    stamp) and stays per-event schema-compatible with local sinks, so
    every existing consumer reads it unchanged;
  * **metrics federation** — ``GET /fleet/metrics`` = fleet rollups
    (warm-hit ratio, queue depth, tenant burn, race win share) over
    per-replica scrapes merged under the ``replica`` label;
  * **cross-replica trace assembly** — ``deppy trace --fleet`` on the
    merged sink reconstructs a routed request as ONE tree whose
    replica subtree is identical to the single-server tree (the
    router hop is the only extra span);
  * **cost-model drift watchdog** — live effective µs/trip per size
    class vs the committed baseline; compile warm-up samples excluded,
    one ``costmodel_drift`` event per band crossing, gauge recovery;
  * **multi-sink merge** — repeated ``--file`` dedupes flight-recorder
    dump copies by per-replica event seq;
  * arming any of it leaves response bodies byte-identical.
"""

from __future__ import annotations

import json
import time
from http.client import HTTPConnection

import pytest

from deppy_tpu import faults, telemetry
from deppy_tpu.fleet import Router
from deppy_tpu.obs import (Aggregator, CostModelWatchdog,
                           TelemetryStreamer, fleet_rollups,
                           load_baseline)
from deppy_tpu.obs.aggregate import ROUTER_REPLICA
from deppy_tpu.obs.drift import WARMUP_SAMPLES
from deppy_tpu.obs.federate import merge_scrapes, parse_samples
from deppy_tpu.service import Server
from deppy_tpu.telemetry.registry import iter_merged_sink_events

pytestmark = pytest.mark.obs


@pytest.fixture(autouse=True)
def fresh_state():
    prev_breaker = faults.set_default_breaker(faults.CircuitBreaker())
    prev_plan = faults.configure_plan(None)
    prev_reg = telemetry.set_default_registry(telemetry.Registry())
    yield
    telemetry.set_default_registry(prev_reg)
    faults.configure_plan(prev_plan)
    faults.set_default_breaker(prev_breaker)


# --------------------------------------------------------------- helpers


def _family_doc(name: str, bundles: int = 3, size: int = 4) -> dict:
    variables = []
    for b in range(bundles):
        for j in range(size):
            cons = []
            if j == 0:
                cons.append({"type": "mandatory"})
                cons.append({"type": "dependency",
                             "ids": [f"{name}b{b}v1"]})
            elif j < size - 1:
                cons.append({"type": "dependency",
                             "ids": [f"{name}b{b}v{j + 1}"]})
            variables.append({"id": f"{name}b{b}v{j}",
                              "constraints": cons})
    return {"variables": variables}


def _request(port, method, path, body=None, headers=None):
    conn = HTTPConnection("127.0.0.1", port, timeout=30)
    h = dict(headers or {})
    payload = None
    if body is not None:
        payload = json.dumps(body)
        h.setdefault("Content-Type", "application/json")
    conn.request(method, path, body=payload, headers=h)
    resp = conn.getresponse()
    data = resp.read()
    conn.close()
    return resp.status, data


def _host_server(**kw):
    srv = Server(bind_address="127.0.0.1:0",
                 probe_address="127.0.0.1:0", backend="host", **kw)
    srv.start()
    return srv


def _profile_event(cls="xs", trips=100, solve_s=0.01):
    return {"kind": "profile", "backend": "device", "trips": trips,
            "solve_s": solve_s, "size_class_name": cls}


# -------------------------------------------------------------- streaming


class TestStreamer:
    def test_enqueue_never_blocks_and_counts_drops(self):
        reg = telemetry.default_registry()
        st = TelemetryStreamer("127.0.0.1:9", replica="r1", queue_cap=4,
                               flush_ms=10_000)
        # No drain thread started: the queue fills and stays full — the
        # overflow must drop (counted), never block or raise.
        for i in range(10):
            st.enqueue({"kind": "fault", "i": i})
        assert st.queue_depth() == 4
        assert reg.counter("deppy_obs_stream_events_total").value == 4
        assert reg.counter("deppy_obs_stream_dropped_total").value == 6

    def test_flush_batches_and_drops_failed_posts(self):
        reg = telemetry.default_registry()
        st = TelemetryStreamer("127.0.0.1:9", replica="r1", batch=2,
                               flush_ms=10_000)
        posted = []

        def _post(batch):
            posted.append(list(batch))
            return True

        st._post = _post
        for i in range(5):
            st.enqueue({"i": i})
        st.flush()
        assert [len(b) for b in posted] == [2, 2, 1]
        assert st.queue_depth() == 0
        assert reg.counter("deppy_obs_stream_batches_total").value == 3
        # A failed POST drops the batch — the bound is real, nothing
        # requeues.
        st._post = lambda batch: False
        st.enqueue({"i": 99})
        st.flush()
        assert st.queue_depth() == 0
        assert reg.counter("deppy_obs_stream_errors_total").value == 1

    def test_failed_post_arms_bounded_exponential_holdoff(
            self, monkeypatch):
        """ISSUE 17 satellite: after a failed POST the streamer holds
        off (doubling from the flush period, capped) instead of
        re-hammering a restarting aggregator every flush period."""
        monkeypatch.setenv("DEPPY_TPU_OBS_BACKOFF_MAX_S", "0.5")
        st = TelemetryStreamer("127.0.0.1:9", replica="r1", batch=2,
                               flush_ms=100)
        st._post = lambda batch: False
        st.enqueue({"i": 0})
        st.flush()
        assert st._down and st._backoff_s == pytest.approx(0.1)
        # While the hold-off is pending, flush is a no-op: events keep
        # queueing (bounded as ever), no further batch is burned.
        st.enqueue({"i": 1})
        st.flush()
        assert st.queue_depth() == 1
        reg = telemetry.default_registry()
        assert reg.counter("deppy_obs_stream_errors_total").value == 1
        # Each expired hold-off that fails again doubles, up to the cap.
        for expect in (0.2, 0.4, 0.5, 0.5):
            st._retry_at = 0.0
            st.enqueue({"i": 2})
            st.flush()
            assert st._backoff_s == pytest.approx(expect)

    def test_first_success_after_down_streak_counts_reconnect(self):
        reg = telemetry.default_registry()
        st = TelemetryStreamer("127.0.0.1:9", replica="r1", batch=2,
                               flush_ms=100)
        st._post = lambda batch: False
        st.enqueue({"i": 0})
        st.flush()
        assert st._down
        st._post = lambda batch: True
        st._retry_at = 0.0
        st.enqueue({"i": 1})
        st.flush()
        assert not st._down and st._backoff_s == 0.0
        assert st.queue_depth() == 0
        assert reg.counter(
            "deppy_obs_stream_reconnects_total").value == 1
        # A healthy streamer's successes are deliveries, not
        # reconnects.
        st.enqueue({"i": 2})
        st.flush()
        assert reg.counter(
            "deppy_obs_stream_reconnects_total").value == 1

    def test_close_flush_bypasses_the_holdoff(self):
        reg = telemetry.default_registry()
        st = TelemetryStreamer("127.0.0.1:9", replica="r1", batch=2,
                               flush_ms=100)
        st._post = lambda batch: False
        st.enqueue({"i": 0})
        st.flush()
        st._post = lambda batch: True
        st.enqueue({"i": 1})
        st.flush()
        assert st.queue_depth() == 1  # hold-off pending
        # The final close() flush gets one last delivery attempt even
        # inside the hold-off window.
        st._stop.set()
        st.flush()
        assert st.queue_depth() == 0
        assert reg.counter("deppy_obs_stream_batches_total").value == 1

    def test_forwarder_captures_sink_events(self):
        reg = telemetry.default_registry()
        st = TelemetryStreamer("127.0.0.1:9", replica="r1",
                               flush_ms=10_000)
        st._post = lambda batch: True
        st.start()
        try:
            reg.event("fault", point="x")
            with reg.span("unit.span"):
                pass
            assert st.queue_depth() == 2
        finally:
            st.close()
        depth = st.queue_depth()
        reg.event("fault", point="y")  # detached: no longer enqueued
        assert st.queue_depth() == depth


# ------------------------------------------------------------ aggregation


class TestAggregator:
    def test_ingest_stamps_the_transport_source(self, tmp_path):
        sink = tmp_path / "fleet.jsonl"
        reg = telemetry.default_registry()
        agg = Aggregator(str(sink), registry=reg)
        accepted, err = agg.ingest({
            "replica": "rep0",
            "events": [{"kind": "fault", "point": "x"},
                       {"kind": "profile", "replica": "forged"}]})
        assert (accepted, err) == (2, None)
        agg.ingest_event(ROUTER_REPLICA, {"kind": "span",
                                          "name": "router.forward"})
        agg.close()
        events = [json.loads(line) for line in
                  sink.read_text().splitlines()]
        assert [ev["replica"] for ev in events] == \
            ["rep0", "rep0", "router"]
        assert agg.counts() == {"rep0": 2, "router": 1}
        assert reg.counter(
            "deppy_obs_ingest_events_total").value == \
            {"rep0": 2, "router": 1}
        assert reg.counter(
            "deppy_obs_ingest_batches_total").value == 1

    def test_malformed_batches_reject_without_writing(self, tmp_path):
        sink = tmp_path / "fleet.jsonl"
        reg = telemetry.default_registry()
        agg = Aggregator(str(sink), registry=reg)
        for doc in ([1, 2], {"events": "nope"}, {"no": "events"}):
            accepted, err = agg.ingest(doc)
            assert accepted == 0 and err
        agg.close()
        assert not sink.exists()
        assert reg.counter(
            "deppy_obs_ingest_rejects_total").value == 3


# -------------------------------------------------------------- federation


SCRAPE_A = """\
deppy_cache_hits_total 8
deppy_cache_misses_total 2
deppy_incremental_hits_total 1
deppy_sched_queue_depth 3
deppy_tenant_burn_rate{tenant="alpha"} 0.2
deppy_tenant_requests_total{tenant="alpha"} 30
deppy_race_wins_total{backend="device"} 3
"""
SCRAPE_B = """\
deppy_cache_hits_total 2
deppy_cache_misses_total 8
deppy_sched_queue_depth 1
deppy_tenant_burn_rate{tenant="alpha"} 0.6
deppy_tenant_requests_total{tenant="alpha"} 10
deppy_race_wins_total{backend="host"} 1
"""


class TestFederation:
    def test_fleet_rollups_math(self):
        r = fleet_rollups([("a:1", SCRAPE_A), ("b:2", SCRAPE_B)])
        # warm = (8+1 + 2+0) / (8+2 + 2+8) — fleet sums, not a mean of
        # per-replica ratios.
        assert r["warm_hit_ratio"] == round(11 / 20, 6)
        assert r["queue_depth"] == 4.0
        # Request-weighted: (0.2*30 + 0.6*10) / 40.
        assert r["tenant_burn_rate"]["alpha"] == round(12 / 40, 6)
        assert r["race_win_share"] == {"device": 0.75, "host": 0.25}
        assert r["per_replica"]["a:1"]["warm_hit_ratio"] == 0.9

    def test_merge_scrapes_relabels_under_replica(self):
        lines = merge_scrapes([
            ("a:1", "# HELP deppy_cache_hits_total h\n"
                    "# TYPE deppy_cache_hits_total counter\n"
                    "deppy_cache_hits_total 8\n"),
            ("b:2", "# HELP deppy_cache_hits_total h\n"
                    "# TYPE deppy_cache_hits_total counter\n"
                    'deppy_cache_hits_total{tenant="t"} 2\n')])
        assert lines == [
            "# HELP deppy_cache_hits_total h",
            "# TYPE deppy_cache_hits_total counter",
            'deppy_cache_hits_total{replica="a:1"} 8',
            'deppy_cache_hits_total{replica="b:2",tenant="t"} 2']

    def test_router_fleet_metrics_endpoint(self):
        replicas = [_host_server(replica=f"rep{i}") for i in range(2)]
        addrs = [f"127.0.0.1:{s.api_port}" for s in replicas]
        router = Router(bind_address="127.0.0.1:0", replicas=addrs,
                        probe_interval_s=0.2, probe_failures=3)
        router.start()
        try:
            for i in range(4):
                s, _ = _request(router.api_port, "POST", "/v1/resolve",
                                _family_doc(f"fed{i}."))
                assert s == 200
            s, body = _request(router.api_port, "GET", "/fleet/metrics")
            assert s == 200
            text = body.decode()
            samples = parse_samples(text)
            fleet = [v for n, labels, v in samples
                     if n == "deppy_fleet_queue_depth"
                     and "replica" not in labels]
            assert fleet == [0.0]
            for addr in addrs:
                assert f'replica="{addr}"' in text
            s, body = _request(router.api_port, "GET", "/fleet/status")
            assert s == 200
            status = json.loads(body)
            assert len(status["replicas"]) == 2
            assert status["telemetry"]["ingested"] == {}  # obs disarmed
        finally:
            router.shutdown()
            for srv in replicas:
                srv.shutdown()


# ------------------------------------------------------------------ drift


class TestDriftWatchdog:
    def test_load_baseline_formats(self, tmp_path):
        bench = tmp_path / "BENCH_r16.json"
        bench.write_text(json.dumps({
            "costmodel": {"us_per_trip": 150.0,
                          "size_classes": {"xs": {"us_per_trip": 90.0}}}}))
        assert load_baseline(str(bench)) == {"xs": 90.0, "*": 150.0}
        report = tmp_path / "profile.json"
        report.write_text(json.dumps({
            "trip_overhead": {"us_per_trip": 175.0},
            "size_classes": {"s": {"trips": 1000, "solve_s": 0.2}}}))
        assert load_baseline(str(report)) == {"s": 200.0, "*": 175.0}
        bare = tmp_path / "bare.json"
        bare.write_text(json.dumps({"us_per_trip": 120.0}))
        assert load_baseline(str(bare)) == {"*": 120.0}
        junk = tmp_path / "junk.json"
        junk.write_text("not json")
        assert load_baseline(str(junk)) is None
        assert load_baseline(str(tmp_path / "missing.json")) is None

    def test_committed_bench_artifact_arms_the_watchdog(self):
        # The shipping drift baseline IS the committed bench record —
        # this pin keeps BENCH_r16.json loadable (a reshaped costmodel
        # section would silently disarm every fleet's watchdog).
        from pathlib import Path

        bench = Path(__file__).resolve().parent.parent / "BENCH_r16.json"
        baseline = load_baseline(str(bench))
        assert baseline and "*" in baseline
        assert all(v > 0 for v in baseline.values())
        dog = CostModelWatchdog.from_baseline(str(bench))
        assert dog is not None

    def test_warmup_band_event_and_recovery(self):
        reg = telemetry.default_registry()
        events = []
        reg.add_forwarder(
            lambda ev: events.append(ev)
            if ev.get("kind") == "costmodel_drift" else None)
        dog = CostModelWatchdog({"xs": 100.0}, band=0.5, min_samples=2,
                                replica="r1", registry=reg)
        # Warm-up exclusion: the first samples per class pay the jit
        # compile inside their measured window — a seconds-scale outlier
        # that must never enter the drift window.
        for _ in range(WARMUP_SAMPLES):
            dog(_profile_event(solve_s=5.0))
        assert dog.snapshot() == {}
        for _ in range(4):
            dog(_profile_event(solve_s=0.01))  # exactly on-model
        snap = dog.snapshot()["xs"]
        assert snap["ratio"] == 1.0 and not snap["drift"]
        assert events == []
        # Drift past the band: ONE event per crossing, gauge sits high.
        for _ in range(64):
            dog(_profile_event(solve_s=0.03))
        snap = dog.snapshot()["xs"]
        assert snap["drift"] and snap["ratio"] > 1.5
        assert len(events) == 1
        ev = events[0]
        assert ev["size_class"] == "xs" and ev["replica"] == "r1"
        assert ev["baseline_us_per_trip"] == 100.0
        lines = dog.render_metric_lines()
        assert any(l.startswith(
            'deppy_costmodel_drift_ratio{size_class="xs",replica="r1"}')
            for l in lines)
        assert any("deppy_costmodel_us_per_trip" in l for l in lines)
        # Recovery: a full on-model window clears the alert latch, so
        # the NEXT crossing alerts again.
        for _ in range(64):
            dog(_profile_event(solve_s=0.01))
        assert not dog.snapshot()["xs"]["drift"]
        for _ in range(64):
            dog(_profile_event(solve_s=0.03))
        assert len(events) == 2

    def test_ignores_unbaselined_and_tripless_events(self):
        dog = CostModelWatchdog({"xs": 100.0}, band=0.5, min_samples=2)
        dog({"kind": "fault", "point": "x"})
        dog(_profile_event(cls="xl"))           # no baseline, no "*"
        dog({"kind": "profile", "backend": "host",
             "solve_s": 0.5})                   # no trips: not a ledger
        assert dog.snapshot() == {}
        assert dog.render_metric_lines() == []


# ------------------------------------------------------- multi-sink merge


class TestMergedSinks:
    def test_dedupes_dump_copies_by_replica_and_seq(self, tmp_path):
        a = tmp_path / "a.jsonl"
        b = tmp_path / "b.jsonl"
        fault = {"kind": "fault", "trace_id": "t1", "seq": 7,
                 "replica": "rep0"}
        span = {"kind": "span", "name": "s", "trace_id": "t1",
                "span_id": "sp1", "replica": "rep0"}
        other = {"kind": "fault", "trace_id": "t1", "seq": 7,
                 "replica": "rep1"}  # seq collision ACROSS replicas
        a.write_text("\n".join(json.dumps(e)
                               for e in (fault, span)) + "\n")
        b.write_text("\n".join(json.dumps(e)
                               for e in (fault, span, other)) + "\n")
        out = [ev for ev in iter_merged_sink_events([str(a), str(b)])
               if ev is not None]
        assert out == [fault, span, other]

    def test_stats_cli_merges_repeated_file(self, tmp_path, capsys):
        from deppy_tpu import cli

        a = tmp_path / "a.jsonl"
        b = tmp_path / "b.jsonl"
        span = {"ts": 1.0, "kind": "span", "name": "service.request",
                "dur_s": 0.01, "trace_id": "t", "span_id": "s1",
                "replica": "rep0"}
        a.write_text(json.dumps(span) + "\n")
        b.write_text(json.dumps(span) + "\n"
                     + json.dumps(dict(span, span_id="s2",
                                       replica="rep1")) + "\n")
        rc = cli.main(["stats", "--file", str(a), "--file", str(b)])
        assert rc == 0
        out = capsys.readouterr().out
        # The dump copy deduped: 2 spans survive, not 3.
        assert "service.request" in out and "2" in out


# ------------------------------------------- service + router integration


class TestServiceIntegration:
    def test_armed_streaming_is_byte_identical(self):
        doc = _family_doc("ident.")
        plain = _host_server()
        try:
            _, m = _request(plain.api_port, "GET", "/metrics")
            assert b"deppy_obs_" not in m  # absent until armed
            s1, b1 = _request(plain.api_port, "POST", "/v1/resolve",
                              doc)
        finally:
            plain.shutdown()
        # Armed, against a DEAD aggregator: every flush fails, events
        # drop counted — and the response bytes must not notice.
        armed = _host_server(replica="r1", obs_stream="127.0.0.1:9",
                             obs_flush_ms=20)
        try:
            s2, b2 = _request(armed.api_port, "POST", "/v1/resolve",
                              doc)
            _, m = _request(armed.api_port, "GET", "/metrics")
            assert b"deppy_obs_stream_events_total" in m
        finally:
            armed.shutdown()
        assert (s1, b1) == (s2, b2)

    def test_stream_to_router_builds_merged_sink(self, tmp_path):
        sink = tmp_path / "fleet.jsonl"
        srv = _host_server(replica="repA")
        addr = f"127.0.0.1:{srv.api_port}"
        router = Router(bind_address="127.0.0.1:0", replicas=[addr],
                        probe_interval_s=0.2, probe_failures=3,
                        obs_sink=str(sink))
        router.start()
        streamer = None
        try:
            # The replica side of the stream, pointed at the live
            # router (in-process servers share one registry, so the
            # streamer is armed directly rather than via a second
            # Server).
            streamer = TelemetryStreamer(
                f"127.0.0.1:{router.api_port}", replica="repA",
                flush_ms=20)
            streamer.start()
            s, _ = _request(router.api_port, "POST", "/v1/resolve",
                            _family_doc("merged."))
            assert s == 200
            deadline = time.monotonic() + 10.0
            stamps: set = set()
            while time.monotonic() < deadline:
                if sink.exists():
                    stamps = {json.loads(line).get("replica")
                              for line in
                              sink.read_text().splitlines()}
                if {"repA", ROUTER_REPLICA} <= stamps:
                    break
                time.sleep(0.05)
            assert {"repA", ROUTER_REPLICA} <= stamps, stamps
            s, body = _request(router.api_port, "GET", "/fleet/status")
            ingested = json.loads(body)["telemetry"]["ingested"]
            assert ingested.get("repA", 0) >= 1
        finally:
            if streamer is not None:
                streamer.close()
            router.shutdown()
            srv.shutdown()

    def test_debug_dump_fans_out_to_every_replica(self):
        replicas = [_host_server(replica=f"rep{i}") for i in range(2)]
        addrs = [f"127.0.0.1:{s.api_port}" for s in replicas]
        router = Router(bind_address="127.0.0.1:0", replicas=addrs,
                        probe_interval_s=0.2, probe_failures=3)
        router.start()
        try:
            s, body = _request(replicas[0].api_port, "POST",
                               "/debug/dump", {"reason": "unit"})
            assert s == 200
            doc = json.loads(body)
            assert doc["replica"] == "rep0" and doc["dumped"] >= 0
            s, body = _request(router.api_port, "POST", "/debug/dump",
                               {"reason": "unit"})
            assert s == 200
            doc = json.loads(body)
            assert sorted(doc["dumped"]) == sorted(addrs)
            assert doc["errors"] == []
        finally:
            router.shutdown()
            for srv in replicas:
                srv.shutdown()


# --------------------------------------------------- fleet trace assembly


def _trace_skeleton(doc: dict):
    """Span-name tree from `deppy trace --output json`, with dispatch
    traces grafted under their link targets exactly as the text
    renderer does.  Timings and ids are run-dependent; the NAME
    structure is the pinned surface.  Siblings keep the CLI's order:
    by ``ts`` (milliseconds), then in the order they were recorded —
    adjacent spans that close in the same millisecond keep their real
    order instead of an alphabetical one."""
    spans = doc["spans"]
    by_id = {sp["span_id"]: sp for sp in spans}
    children: dict = {}
    roots = []
    for sp in sorted(spans, key=lambda s: s.get("ts", 0.0)):
        parent = sp.get("parent_id")
        if parent not in by_id and sp.get("links"):
            parent = sp["links"][0].get("span_id")
        if parent in by_id:
            children.setdefault(parent, []).append(sp)
        else:
            roots.append(sp)

    def _tree(sp):
        kids = tuple(_tree(c) for c in children.get(sp["span_id"], []))
        return (sp["name"], kids)

    return [_tree(sp) for sp in roots]


def _run_trace(capsys, rid, path, fleet=False):
    from deppy_tpu import cli

    argv = ["trace", rid, "--file", str(path), "--output", "json"]
    if fleet:
        argv.insert(1, "--fleet")
    rc = cli.main(argv)
    out = capsys.readouterr().out
    assert rc == 0, out
    return json.loads(out)


class TestFleetTraceAssembly:
    def test_routed_tree_is_single_server_tree_plus_hop(
            self, tmp_path, capsys):
        # Reference: the same request against a bare server, traced
        # from its local sink.
        local = tmp_path / "local.jsonl"
        telemetry.configure_sink(str(local))
        srv = _host_server()
        try:
            s, _ = _request(srv.api_port, "POST", "/v1/resolve",
                            _family_doc("pin."),
                            {"X-Deppy-Request-Id": "pin-local"})
            assert s == 200
        finally:
            srv.shutdown()
        single = _trace_skeleton(
            _run_trace(capsys, "pin-local", local))
        assert len(single) == 1
        assert single[0][0] == "service.request"

        # Routed: same request through an obs-armed router; the merged
        # sink alone must reconstruct hop + request + dispatch.
        telemetry.set_default_registry(telemetry.Registry())
        merged = tmp_path / "fleet.jsonl"
        srv = _host_server(replica="repA")
        router = Router(bind_address="127.0.0.1:0",
                        replicas=[f"127.0.0.1:{srv.api_port}"],
                        probe_interval_s=0.2, probe_failures=3,
                        obs_sink=str(merged))
        router.start()
        try:
            s, _ = _request(router.api_port, "POST", "/v1/resolve",
                            _family_doc("pin2."),
                            {"X-Deppy-Request-Id": "pin-routed"})
            assert s == 200
        finally:
            router.shutdown()
            srv.shutdown()
        routed = _trace_skeleton(
            _run_trace(capsys, "pin-routed", merged, fleet=True))
        assert len(routed) == 1, routed
        hop_name, hop_children = routed[0]
        assert hop_name == "router.forward"
        # Modulo the router hop, the replica's tree is THE tree: byte-
        # identical name structure to the single-server trace.
        assert list(hop_children) == single
