"""Replica fleet with warm-state affinity routing (ISSUE 15).

The acceptance surface, from the issue:

  * a 3-replica fleet behind the affinity router serves a mixed
    request stream byte-identical to a single replica;
  * the affinity key is FAMILY-stable (churn deltas of one family land
    on one replica) and the ring reassigns only a removed replica's
    arcs;
  * the warm-state snapshot round-trips (index entries plan warm
    starts on the importer, cache seeds hit) and is integrity-checked;
  * killing a replica degrades only requests routed to it — by one
    retry on the ring successor, never to a client-visible error — and
    a drain hands warm state to the arc inheritors so the family's
    next delta serves warm instead of cold;
  * the weighted-fair admission gate sheds only the tenant over its
    share (the global-depth 503 replacement) and priority lanes order
    the flush head;
  * trace identity (traceparent / X-Deppy-Request-Id / X-Deppy-Tenant)
    survives the router hop.
"""

from __future__ import annotations

import json
import time
from http.client import HTTPConnection

import pytest

from deppy_tpu import faults, telemetry
from deppy_tpu.fleet import (HashRing, Router, SnapshotFormatError,
                             affinity_key, doc_affinity_keys,
                             export_warm_state, import_warm_state,
                             membership_view, policy_decide, reconcile)
from deppy_tpu.fleet.snapshot import split_snapshot, verify_snapshot
from deppy_tpu.sched import Scheduler
from deppy_tpu.sched.fair import TenantPolicy
from deppy_tpu.sched.scheduler import _Group, _Lane
from deppy_tpu.service import Server

pytestmark = pytest.mark.fleet


@pytest.fixture(autouse=True)
def fresh_fault_state():
    prev_breaker = faults.set_default_breaker(faults.CircuitBreaker())
    prev_plan = faults.configure_plan(None)
    prev_reg = telemetry.set_default_registry(telemetry.Registry())
    yield
    telemetry.set_default_registry(prev_reg)
    faults.configure_plan(prev_plan)
    faults.set_default_breaker(prev_breaker)


# --------------------------------------------------------------- helpers


def _family_doc(name: str, state: int = 0, bundles: int = 5,
                size: int = 5) -> dict:
    """One family's /v1/resolve document: ``bundles`` DISCONNECTED
    dependency chains sharing one vocabulary.  ``state`` rotates one
    mid-chain dependency inside bundle 0 only, so consecutive states
    are one-row deltas of the SAME family (same ids, same affinity
    key) whose touched cone is one bundle — the shape the incremental
    tier warm-serves."""
    variables = []
    for b in range(bundles):
        for j in range(size):
            cons = []
            if j == 0:
                cons.append({"type": "mandatory"})
                cons.append({"type": "dependency",
                             "ids": [f"{name}b{b}v1"]})
            elif j == 1 and b == 0:
                tgt = 2 + state % (size - 2)
                cons.append({"type": "dependency",
                             "ids": [f"{name}b0v{tgt}"]})
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
    hdrs = {k: v for k, v in resp.getheaders()}
    conn.close()
    return resp.status, data, hdrs


def _metric(text: str, name: str):
    total = None
    for line in text.splitlines():
        if line.startswith(name + " ") or line.startswith(name + "{"):
            total = (total or 0.0) + float(line.rsplit(" ", 1)[1])
    return total


def _host_server(**kw):
    srv = Server(bind_address="127.0.0.1:0",
                 probe_address="127.0.0.1:0", backend="host", **kw)
    srv.start()
    return srv


# ------------------------------------------------------------------ ring


class TestRing:
    def test_affinity_key_is_family_stable(self):
        a = _family_doc("f", state=0)
        b = _family_doc("f", state=2)
        ka = doc_affinity_keys(a)
        kb = doc_affinity_keys(b)
        assert ka == kb  # churn delta, same family -> same key
        assert ka != doc_affinity_keys(_family_doc("g"))

    def test_affinity_key_order_sensitive(self):
        assert affinity_key(["a", "b"]) != affinity_key(["b", "a"])
        # No separator aliasing between adjacent identifiers.
        assert affinity_key(["ab", "c"]) != affinity_key(["a", "bc"])

    def test_batch_doc_keys(self):
        doc = {"problems": [_family_doc("x"), _family_doc("y")]}
        keys = doc_affinity_keys(doc)
        assert len(keys) == 2 and keys[0] != keys[1]
        assert doc_affinity_keys({"nope": 1}) == [None]

    def test_route_deterministic_and_exclusion_moves_only_dead_arcs(self):
        ring = HashRing(["a", "b", "c"])
        keys = [affinity_key([f"k{i}"]) for i in range(200)]
        owners = {k: ring.route(k) for k in keys}
        assert owners == {k: ring.route(k) for k in keys}
        assert set(owners.values()) == {"a", "b", "c"}
        moved = 0
        for k, owner in owners.items():
            after = ring.route(k, exclude={"b"})
            if owner != "b":
                assert after == owner  # surviving arcs are untouched
            else:
                assert after in ("a", "c")
                moved += 1
        assert moved > 0

    def test_successor_is_distinct(self):
        ring = HashRing(["a", "b", "c"])
        k = affinity_key(["k"])
        owner = ring.route(k)
        assert ring.successor(k, owner) != owner
        assert ring.route(k, exclude={"a", "b", "c"}) is None

    def test_requires_replicas(self):
        with pytest.raises(ValueError):
            HashRing([])


# -------------------------------------------------------------- snapshot


class TestSnapshot:
    def _warm_scheduler(self):
        sched = Scheduler(backend="host", speculate="off",
                          portfolio="off")
        from deppy_tpu import io as problem_io

        fam = problem_io.problems_from_document(_family_doc("s"))[0]
        fam2 = problem_io.problems_from_document(
            _family_doc("s", state=1))[0]
        sched.submit([fam])
        sched.submit([fam2])
        return sched

    def test_round_trip(self):
        src = self._warm_scheduler()
        try:
            snap = export_warm_state(src)
            assert snap["version"] == 1
            assert len(snap["index"]) >= 1
            assert len(snap["cache"]) >= 1
            assert all(e["affinity"] for e in snap["index"])
            # JSON round trip: exactly what the HTTP handoff ships.
            snap = json.loads(json.dumps(snap))
            dst = Scheduler(backend="host", speculate="off",
                            portfolio="off")
            out = import_warm_state(dst, snap)
            assert out["index_imported"] == len(snap["index"])
            assert out["cache_seeds"] == len(snap["cache"])
            # The imported exact seed hits without a solve...
            from deppy_tpu import io as problem_io
            from deppy_tpu.sched.cache import MISS, fingerprint
            from deppy_tpu.sat.encode import encode

            fam = problem_io.problems_from_document(_family_doc("s"))[0]
            p = encode(fam)
            budget = snap["cache"][0]["budget"]
            hit = dst.cache.lookup(fingerprint(p), budget)
            assert hit is not MISS
            # ...and the imported index entry plans a warm start for
            # the family's NEXT delta (the handoff's whole point).
            nxt = encode(problem_io.problems_from_document(
                _family_doc("s", state=2))[0])
            plan = dst.incremental.plan(nxt, fingerprint(nxt),
                                        1 << 24)
            assert plan is not None
            # Re-import skips resident entries (live state wins).
            again = import_warm_state(dst, snap)
            assert again["index_imported"] == 0
            assert again["index_skipped"] == len(snap["index"])
        finally:
            src.stop()

    def test_integrity_and_version_checks(self):
        src = self._warm_scheduler()
        try:
            snap = export_warm_state(src)
            tampered = json.loads(json.dumps(snap))
            tampered["cache"] = []
            with pytest.raises(SnapshotFormatError):
                verify_snapshot(tampered)
            skewed = json.loads(json.dumps(snap))
            skewed["version"] = 99
            with pytest.raises(SnapshotFormatError):
                verify_snapshot(skewed)
            with pytest.raises(SnapshotFormatError):
                verify_snapshot(["not", "an", "object"])
            dst = Scheduler(backend="host", speculate="off",
                            portfolio="off")
            with pytest.raises(SnapshotFormatError):
                import_warm_state(dst, tampered)
        finally:
            src.stop()

    def test_split_by_owner(self):
        src = self._warm_scheduler()
        try:
            snap = export_warm_state(src)
            shards = split_snapshot(snap, lambda aff: "r1")
            assert set(shards) == {"r1"}
            verify_snapshot(shards["r1"])  # re-sealed
            assert split_snapshot(snap, lambda aff: None) == {}
        finally:
            src.stop()

    def test_import_rejects_nonzero_backtracks(self):
        """A tampered snapshot must not widen the zero-backtrack warm
        certification gate."""
        import numpy as np
        from collections import Counter

        from deppy_tpu.incremental import ClauseSetIndex

        idx = ClauseSetIndex()
        ok = idx.import_entry("k", Counter({("c", 0, 1): 1}),
                              (1, ("a",)), np.ones(1, dtype=bool),
                              10, backtracks=3)
        assert ok is False and len(idx) == 0

    def test_import_rejects_misaligned_model(self):
        """The snapshot checksum has no secret — anyone can seal a
        document — so import must validate that a model is
        index-aligned with its vocabulary: admitting a misaligned
        entry would plant a crash on the live warm path for that
        family's next delta."""
        import numpy as np
        from collections import Counter

        from deppy_tpu.incremental import ClauseSetIndex

        idx = ClauseSetIndex()
        with pytest.raises(ValueError):
            idx.import_entry("k", Counter({("c", 0, 1): 1}),
                             (3, ("a", "b", "c")),
                             np.ones(1, dtype=bool), 10, backtracks=0)
        assert len(idx) == 0


# ------------------------------------------------------- fair admission


class TestFairAdmission:
    def test_policy_spec(self):
        pol = TenantPolicy.from_spec(
            '{"gold": {"weight": 3, "priority": 0}, "bulk": 1, '
            '"default": {"weight": 2}}')
        assert pol.weight("gold") == 3 and pol.priority("gold") == 0
        assert pol.weight("bulk") == 1 and pol.priority("bulk") == 1
        assert pol.weight("stranger") == 2
        assert pol.cap("gold", 100, {"bulk"}) == pytest.approx(75.0)
        with pytest.raises(ValueError):
            TenantPolicy.from_spec('{"a": {"weight": -1}}')
        with pytest.raises(ValueError):
            TenantPolicy.from_spec('["not", "a", "mapping"]')

    def test_noisy_tenant_sheds_victim_admits(self):
        sched = Scheduler(backend="host", max_depth=100, fair="on",
                          speculate="off", portfolio="off")
        with sched._cv:
            sched._tenant_depth.update({"noisy": 60, "victim": 2})
            sched._depth = 62
        assert sched.admission_retry_after(tenant="noisy") is not None
        assert sched.admission_retry_after(tenant="victim") is None
        lines = "\n".join(sched._registry.render_lines())
        assert 'deppy_sched_tenant_sheds_total{tenant="noisy"} 1' \
            in lines

    def test_single_tenant_matches_global_gate(self):
        sched = Scheduler(backend="host", max_depth=10, fair="on",
                          speculate="off", portfolio="off")
        with sched._cv:
            sched._tenant_depth["solo"] = 9
            sched._depth = 9
        assert sched.admission_retry_after(tenant="solo") is None
        with sched._cv:
            sched._tenant_depth["solo"] = 10
            sched._depth = 10
        assert sched.admission_retry_after(tenant="solo") is not None

    def test_fair_off_restores_global_gate(self):
        sched = Scheduler(backend="host", max_depth=10, fair="off",
                          speculate="off", portfolio="off")
        with sched._cv:
            sched._depth = 10
        # Global: EVERY tenant sheds, share or no share.
        assert sched.admission_retry_after(tenant="victim") is not None

    def test_minted_tenants_hit_global_backstop(self):
        """X-Deppy-Tenant is client-controlled: sequentially minted
        fresh tenants must not ratchet aggregate depth unbounded (each
        new tenant's share is computed against the tenants queued at
        ITS arrival).  At 2x max_depth EVERYONE sheds, share or no
        share."""
        sched = Scheduler(backend="host", max_depth=10, fair="on",
                          speculate="off", portfolio="off")
        with sched._cv:
            sched._tenant_depth.update(
                {f"mint{i}": 2 for i in range(10)})
            sched._depth = 20
        # A brand-new tenant's weighted share (10/11 of max_depth) is
        # nowhere near filled — the backstop sheds it anyway.
        assert sched.admission_retry_after(tenant="fresh") is not None
        lines = "\n".join(sched._registry.render_lines())
        assert 'deppy_sched_tenant_sheds_total{tenant="fresh"} 1' \
            in lines

    def test_depth_accounting_through_dispatch(self):
        from deppy_tpu import io as problem_io

        sched = Scheduler(backend="host", speculate="off",
                          portfolio="off")
        sched.start()
        try:
            fam = problem_io.problems_from_document(
                _family_doc("acct"))[0]
            sched.submit([fam], tenant="t1")
            with sched._cv:
                assert sched._tenant_depth.get("t1", 0) == 0
        finally:
            sched.stop()


class TestPriorityLanes:
    def test_priority_head_precedes_older_bulk(self):
        sched = Scheduler(
            backend="host", speculate="off", portfolio="off",
            fair="on",
            tenant_weights='{"gold": {"weight": 1, "priority": 0}}')
        bulk = _Group([_Lane(None, "k1", None, 1, None,
                             tenant="bulk")], 4, 1, priority=1)
        time.sleep(0.002)
        gold = _Group([_Lane(None, "k2", None, 1, None,
                             tenant="gold")], 8, 1, priority=0)
        sched._queue = [bulk, gold]
        with sched._cv:
            assert sched._head_locked() is gold
            sched._depth = 2
            sched._tenant_depth.update({"bulk": 1, "gold": 1})
            take, reason = sched._drain_locked(force=True)
        assert take[0] is gold and reason == "drain"
        with sched._cv:
            assert sched._tenant_depth == {"bulk": 1}

    def test_aged_bulk_beats_sustained_urgent(self):
        """Starvation guard: a bulk group older than the aging bound
        becomes head despite a queued urgent group — a sustained
        priority-0 stream must not park a bulk submitter (blocked on
        group.event with no timeout) forever."""
        sched = Scheduler(backend="host", speculate="off",
                          portfolio="off", fair="on")
        bulk = _Group([_Lane(None, "k1", None, 1, None,
                             tenant="bulk")], 4, 1, priority=1)
        gold = _Group([_Lane(None, "k2", None, 1, None,
                             tenant="gold")], 8, 1, priority=0)
        bulk.enq_t -= max(
            sched.max_wait_s * sched.PRIORITY_AGING_WINDOWS, 0.5) + 0.1
        sched._queue = [bulk, gold]
        with sched._cv:
            assert sched._head_locked() is bulk

    def test_default_priorities_keep_fifo(self):
        sched = Scheduler(backend="host", speculate="off",
                          portfolio="off")
        a = _Group([_Lane(None, "k1", None, 1, None)], 4, 1)
        time.sleep(0.002)
        b = _Group([_Lane(None, "k2", None, 1, None)], 4, 1)
        sched._queue = [a, b]
        with sched._cv:
            assert sched._head_locked() is a


# ----------------------------------------------------------- slo replica


class TestReplicaIdentity:
    def test_slo_lines_carry_replica_label(self):
        from deppy_tpu.profile import SLOAccountant

        acc = SLOAccountant(replica="127.0.0.1:8080")
        acc.observe("tenant1", 0.01)
        lines = "\n".join(acc.render_metric_lines())
        assert ('deppy_tenant_requests_total{tenant="tenant1",'
                'replica="127.0.0.1:8080"} 1') in lines
        bare = SLOAccountant()
        bare.observe("tenant1", 0.01)
        assert 'deppy_tenant_requests_total{tenant="tenant1"} 1' \
            in "\n".join(bare.render_metric_lines())

    def test_debug_slo_reports_replica(self):
        srv = _host_server(replica="r-1")
        try:
            _request(srv.api_port, "POST", "/v1/resolve",
                     _family_doc("slo"))
            status, body, _ = _request(srv.api_port, "GET",
                                       "/debug/slo")
            doc = json.loads(body)
            assert status == 200 and doc["replica"] == "r-1"
        finally:
            srv.shutdown()


# ------------------------------------------------------------- fleet e2e


class TestFanOutFailure:
    def test_all_transport_failures_answer_503(self):
        """A fan-out reaching ZERO replicas must not render success:
        a 200 publish with no recipients reads as "delta propagated"
        (and a 200 empty preview as "no impact") when nothing was
        reached."""
        router = Router(bind_address="127.0.0.1:0",
                        replicas="127.0.0.1:9",  # nothing listens
                        probe_interval_s=0, probe_failures=100)
        router.start()
        try:
            for path in ("/v1/catalog/publish", "/v1/resolve/preview"):
                status, body, _ = _request(router.api_port, "POST",
                                           path, {"updates": []})
                assert status == 503, (path, status, body)
                assert b"no replica reachable" in body
        finally:
            router.shutdown()


@pytest.fixture
def fleet():
    """Three replicas + affinity router + a single reference server.
    The router's prober is slowed way down so the kill test exercises
    the FORWARD-failure retry path deterministically."""
    replicas = [_host_server(replica=f"rep{i}") for i in range(3)]
    addrs = [f"127.0.0.1:{s.api_port}" for s in replicas]
    router = Router(bind_address="127.0.0.1:0", replicas=addrs,
                    probe_interval_s=60.0, probe_failures=2)
    router.start()
    reference = _host_server()
    try:
        yield replicas, addrs, router, reference
    finally:
        router.shutdown()
        for s in replicas + [reference]:
            try:
                s.shutdown()
            # deppy: lint-ok[exception-hygiene] teardown of an already-killed replica
            except Exception:
                pass


class TestFleetEndToEnd:
    def test_three_replicas_byte_identical_to_one(self, fleet):
        replicas, addrs, router, reference = fleet
        stream = []
        for i in range(5):
            for state in range(3):
                stream.append(_family_doc(f"fam{i}", state))
        stream.append({"problems": [_family_doc(f"fam{i}")
                                    for i in range(5)]})
        stream.append({"variables": "malformed"})
        stream.append({"variables": [
            {"id": "u1", "constraints": [{"type": "mandatory"},
                                         {"type": "prohibited"}]}]})
        for doc in stream:
            s1, b1, _ = _request(router.api_port, "POST",
                                 "/v1/resolve", doc)
            s2, b2, _ = _request(reference.api_port, "POST",
                                 "/v1/resolve", doc)
            assert (s1, b1) == (s2, b2)
        # Affinity actually spread families over >1 replica, and the
        # repeat states were warm/cache-served on their owners.
        _, metrics, _ = _request(router.api_port, "GET", "/metrics")
        routed = [line for line in metrics.decode().splitlines()
                  if line.startswith("deppy_fleet_routed_total{")]
        assert len(routed) >= 2

    def test_family_affinity_concentrates_churn(self, fleet):
        replicas, addrs, router, reference = fleet
        for state in range(4):
            _request(router.api_port, "POST", "/v1/resolve",
                     _family_doc("churny", state))
        # All four states of one family hit ONE replica; its warm tier
        # (exact cache for repeats, index for deltas) saw every one.
        hits = []
        for srv in replicas:
            _, m, _ = _request(srv.api_port, "GET", "/metrics")
            text = m.decode()
            looked = (_metric(text, "deppy_cache_misses_total") or 0) \
                + (_metric(text, "deppy_cache_hits_total") or 0)
            hits.append(looked)
        assert sum(1 for h in hits if h) == 1

    def test_replica_kill_retries_on_successor(self, fleet):
        replicas, addrs, router, reference = fleet
        doc = _family_doc("killfam")
        key = doc_affinity_keys(doc)[0]
        owner = router.target_for(key)
        victim = replicas[addrs.index(owner)]
        victim.shutdown()
        # No prober help here (interval 60s): the live forward fails,
        # charges the breaker, and retries once on the ring successor
        # — the client sees a 200, never the crash.
        status, body, _ = _request(router.api_port, "POST",
                                   "/v1/resolve", doc)
        assert status == 200
        s2, b2, _ = _request(reference.api_port, "POST", "/v1/resolve",
                             doc)
        assert body == b2
        _, metrics, _ = _request(router.api_port, "GET", "/metrics")
        assert (_metric(metrics.decode(),
                        "deppy_fleet_retries_total") or 0) >= 1
        # Second failure reaches the threshold: the replica is dead,
        # its arcs reassign, later requests route straight past it.
        _request(router.api_port, "POST", "/v1/resolve", doc)
        states = {s["replica"]: s for s in router.replica_states()}
        assert states[owner]["dead"] is True
        assert router.target_for(key) != owner

    def test_drain_hands_off_warm_state(self, fleet):
        replicas, addrs, router, reference = fleet
        docs = [_family_doc(f"drainfam{i}") for i in range(4)]
        for doc in docs:
            _request(router.api_port, "POST", "/v1/resolve", doc)
        victim_addr = router.target_for(doc_affinity_keys(docs[0])[0])
        status, body, _ = _request(router.api_port, "POST",
                                   "/fleet/drain",
                                   {"replica": victim_addr})
        assert status == 200
        out = json.loads(body)["drain"]
        assert out["handed_off"] >= 1 and out["recipients"]
        # The drained replica is out of the rotation...
        new_owner = router.target_for(doc_affinity_keys(docs[0])[0])
        assert new_owner != victim_addr
        # ...and the family's next delta warm-serves on the inheritor
        # instead of cold-solving (the handoff's acceptance).
        nxt = _family_doc("drainfam0", state=1)
        assert router.target_for(doc_affinity_keys(nxt)[0]) == new_owner
        s, b, _ = _request(router.api_port, "POST", "/v1/resolve", nxt)
        assert s == 200
        inheritor = replicas[addrs.index(new_owner)]
        _, m, _ = _request(inheritor.api_port, "GET", "/metrics")
        assert (_metric(m.decode(),
                        "deppy_incremental_hits_total") or 0) >= 1

    def test_trace_identity_survives_the_hop(self, fleet):
        replicas, addrs, router, reference = fleet
        doc = _family_doc("traced")
        headers = {
            "traceparent": "00-" + "ab" * 16 + "-" + "cd" * 8 + "-01",
            "X-Deppy-Request-Id": "fleet-req-1",
            "X-Deppy-Tenant": "fleet-tenant",
        }
        status, _, hdrs = _request(router.api_port, "POST",
                                   "/v1/resolve", doc, headers)
        assert status == 200
        # The replica honored and echoed the identity through the
        # router (one trace tree fleet-wide in `deppy trace`).
        assert hdrs.get("X-Deppy-Request-Id") == "fleet-req-1"
        assert hdrs.get("traceparent", "").startswith(
            "00-" + "ab" * 16)
        owner = replicas[addrs.index(
            router.target_for(doc_affinity_keys(doc)[0]))]
        _, body, _ = _request(owner.api_port, "GET", "/debug/slo")
        assert "fleet-tenant" in json.loads(body)["slo"]

    def test_publish_fans_out_to_every_replica(self, fleet):
        replicas, addrs, router, reference = fleet
        for i in range(3):
            _request(router.api_port, "POST", "/v1/resolve",
                     _family_doc(f"pub{i}"))
        delta = {"updates": [{"id": "pub0v1", "constraints": [
            {"type": "dependency", "ids": ["pub0v3"]}]}]}
        status, body, _ = _request(router.api_port, "POST",
                                   "/v1/catalog/publish", delta)
        assert status == 200
        merged = json.loads(body)["publish"]
        assert merged["replicas"] == 3 and merged["errors"] == 0
        _, metrics, _ = _request(router.api_port, "GET", "/metrics")
        assert _metric(metrics.decode(),
                       "deppy_fleet_publish_fanout_total") == 3.0

    def test_warmstate_endpoints_404_with_sched_off(self):
        srv = Server(bind_address="127.0.0.1:0",
                     probe_address="127.0.0.1:0", backend="host",
                     sched="off")
        srv.start()
        try:
            s, _, _ = _request(srv.api_port, "GET", "/debug/warmstate")
            assert s == 404
            s, _, _ = _request(srv.api_port, "POST",
                               "/debug/warmstate", {"version": 1})
            assert s == 404
        finally:
            srv.shutdown()

    def test_warmstate_import_rejects_tampering(self):
        srv = _host_server()
        try:
            _request(srv.api_port, "POST", "/v1/resolve",
                     _family_doc("tamper"))
            s, body, _ = _request(srv.api_port, "GET",
                                  "/debug/warmstate")
            snap = json.loads(body)
            snap["checksum"] = "0" * 64
            s, body, _ = _request(srv.api_port, "POST",
                                  "/debug/warmstate", snap)
            assert s == 400
            assert "integrity" in json.loads(body)["error"]
        finally:
            srv.shutdown()

    @pytest.mark.parametrize("count,status", [
        (0, 400), (-3, 400), (10 ** 9, 200)])
    def test_warmstate_import_row_counts(self, count, status):
        """A row count below 1 is refused with nothing admitted.  A huge
        one costs what a count of 1 does: the index's set form holds the
        distinct rows, never one object per copy."""
        from deppy_tpu import io as problem_io
        from deppy_tpu.fleet.snapshot import _seal
        from deppy_tpu.sat.encode import encode
        from deppy_tpu.sched.cache import fingerprint

        srv = _host_server()
        try:
            _request(srv.api_port, "POST", "/v1/resolve",
                     _family_doc("count"))
            _, body, _ = _request(srv.api_port, "GET", "/debug/warmstate")
            raw = dict(json.loads(body)["index"][0], key="forged")
            raw["rows"][0][1] = count
            index = srv.scheduler.incremental
            before = len(index)
            t0 = time.monotonic()
            s, body, _ = _request(srv.api_port, "POST", "/debug/warmstate",
                                  _seal([raw], []))
            assert s == status
            if status == 400:
                assert "row count" in json.loads(body)["error"]
                assert len(index) == before
                return
            assert len(index) == before + 1
            keys, extra, size = index._entries["forged"].rowset
            assert len(keys) == len(raw["rows"])
            assert size == count + len(raw["rows"]) - 1
            nxt = encode(problem_io.problems_from_document(
                _family_doc("count", state=2))[0])
            assert index.plan(nxt, fingerprint(nxt), 1 << 24) is not None
            assert time.monotonic() - t0 < 10.0
        finally:
            srv.shutdown()


# --------------------------------------- elastic membership (ISSUE 17)


def _poison(point, times=-1, kind="error"):
    faults.configure_plan(faults.FaultPlan.from_doc(
        [{"point": point, "kind": kind, "times": times}]))


def _moving_family(router, joiner_addr, prefix="mv"):
    """A family name whose affinity arc the joiner would STEAL: routed
    to a current member now, to ``joiner_addr`` under the prospective
    ring.  Deterministic — names are tried until one moves."""
    prospective = HashRing(
        list(router.ring.replicas) + [joiner_addr],
        vnodes=router.ring.vnodes)
    for i in range(4096):
        name = f"{prefix}{i}"
        key = doc_affinity_keys(_family_doc(name))[0]
        if prospective.route(key) == joiner_addr:
            return name, key
    raise AssertionError("no family arc moves to the joiner")


class TestElasticJoin:
    def test_join_streams_warm_state_then_flips_arcs(self, fleet):
        replicas, addrs, router, reference = fleet
        joiner = _host_server(replica="joiner")
        addr = f"127.0.0.1:{joiner.api_port}"
        try:
            name, key = _moving_family(router, addr)
            # Warm the moving family (plus noise) on its CURRENT owner.
            for other in ("stay0", "stay1"):
                _request(router.api_port, "POST", "/v1/resolve",
                         _family_doc(other))
            s, _, _ = _request(router.api_port, "POST", "/v1/resolve",
                               _family_doc(name))
            assert s == 200
            old_owner = router.target_for(key)
            assert old_owner != addr
            s, body, _ = _request(router.api_port, "POST",
                                  "/fleet/join", {"replica": addr})
            assert s == 200
            out = json.loads(body)["join"]
            assert out["epoch"] == 2
            assert out["chunks"] >= 1 and out["warm_entries"] >= 1
            # The arc flip committed: the family now routes to the
            # joiner, and the membership surface says so.
            assert router.target_for(key) == addr
            s, body, _ = _request(router.api_port, "GET",
                                  "/fleet/replicas")
            doc = json.loads(body)
            assert doc["membership"] == "elastic"
            assert doc["epoch"] == 2 and addr in doc["members"]
            # The streamed warm state is LIVE: the family's next delta
            # warm-serves on the joiner instead of cold-solving.
            nxt = _family_doc(name, state=1)
            s, b1, _ = _request(router.api_port, "POST", "/v1/resolve",
                                nxt)
            assert s == 200
            _, b2, _ = _request(reference.api_port, "POST",
                                "/v1/resolve", nxt)
            assert b1 == b2
            _, m, _ = _request(joiner.api_port, "GET", "/metrics")
            assert (_metric(m.decode(),
                            "deppy_incremental_hits_total") or 0) >= 1
        finally:
            joiner.shutdown()

    def test_join_under_churn_byte_identity(self, fleet):
        """The pinned acceptance: a join landing mid-churn never
        surfaces a non-200 or a response that differs from the
        fault-free single-server oracle."""
        replicas, addrs, router, reference = fleet
        results = []
        stop = False

        def churn():
            state = 0
            while not stop or state < 6:
                for fam in ("cfam0", "cfam1", "cfam2"):
                    doc = _family_doc(fam, state)
                    s, b, _ = _request(router.api_port, "POST",
                                       "/v1/resolve", doc)
                    results.append((doc, s, b))
                state += 1
                if state >= 40:
                    break

        import threading
        t = threading.Thread(target=churn)
        t.start()
        joiner = _host_server(replica="churnjoiner")
        addr = f"127.0.0.1:{joiner.api_port}"
        try:
            time.sleep(0.05)
            s, body, _ = _request(router.api_port, "POST",
                                  "/fleet/join", {"replica": addr})
            assert s == 200
            stop = True
            t.join(timeout=60)
            assert not t.is_alive()
            assert len(results) >= 6
            for doc, s, b in results:
                assert s == 200
                _, ref, _ = _request(reference.api_port, "POST",
                                     "/v1/resolve", doc)
                assert b == ref
        finally:
            stop = True
            t.join(timeout=5)
            joiner.shutdown()

    def test_join_rejects_duplicate_and_malformed(self, fleet):
        replicas, addrs, router, reference = fleet
        s, body, _ = _request(router.api_port, "POST", "/fleet/join",
                              {"replica": addrs[0]})
        assert s == 400
        assert "already a fleet member" in json.loads(body)["error"]
        s, _, _ = _request(router.api_port, "POST", "/fleet/join",
                           {"replica": 42})
        assert s == 400
        s, _, _ = _request(router.api_port, "POST", "/fleet/join",
                           {"replica": "noport"})
        assert s == 400
        assert router.epoch == 1

    def test_join_stream_fault_aborts_without_flip(self, fleet):
        replicas, addrs, router, reference = fleet
        joiner = _host_server(replica="badjoin")
        addr = f"127.0.0.1:{joiner.api_port}"
        try:
            name, key = _moving_family(router, addr)
            _request(router.api_port, "POST", "/v1/resolve",
                     _family_doc(name))
            owner = router.target_for(key)
            _poison("fleet.join_stream", times=-1)
            s, body, _ = _request(router.api_port, "POST",
                                  "/fleet/join", {"replica": addr})
            assert s == 502
            assert "join failed" in json.loads(body)["error"]
            # Membership is exactly as it was: no epoch bump, no
            # member, the family still routes to its old owner.
            faults.configure_plan(None)
            assert router.epoch == 1
            assert addr not in router.ring.replicas
            assert router.target_for(key) == owner
        finally:
            joiner.shutdown()

    def test_join_stream_resumes_after_one_fault(self, fleet):
        """One failed chunk POST re-sends (import is idempotent); the
        join still commits."""
        replicas, addrs, router, reference = fleet
        joiner = _host_server(replica="resumejoin")
        addr = f"127.0.0.1:{joiner.api_port}"
        try:
            name, _ = _moving_family(router, addr)
            _request(router.api_port, "POST", "/v1/resolve",
                     _family_doc(name))
            _poison("fleet.join_stream", times=1)
            s, body, _ = _request(router.api_port, "POST",
                                  "/fleet/join", {"replica": addr})
            assert s == 200
            assert json.loads(body)["join"]["epoch"] == 2
        finally:
            joiner.shutdown()

    def test_arc_flip_fault_aborts_without_flip(self, fleet):
        replicas, addrs, router, reference = fleet
        joiner = _host_server(replica="flipfault")
        addr = f"127.0.0.1:{joiner.api_port}"
        try:
            _poison("fleet.arc_flip")
            s, _, _ = _request(router.api_port, "POST", "/fleet/join",
                               {"replica": addr})
            assert s == 502
            assert router.epoch == 1
            assert addr not in router.ring.replicas
        finally:
            joiner.shutdown()

    def test_elastic_drain_leaves_ring_and_bumps_epoch(self, fleet):
        replicas, addrs, router, reference = fleet
        _request(router.api_port, "POST", "/v1/resolve",
                 _family_doc("dfam"))
        victim = addrs[1]
        s, _, _ = _request(router.api_port, "POST", "/fleet/drain",
                           {"replica": victim})
        assert s == 200
        assert router.epoch == 2
        assert victim not in router.ring.replicas
        view = membership_view(router)
        assert victim in view["drained"]
        assert victim not in view["members"]

    def test_drain_chaos_replica_stays_routable(self, fleet):
        """Satellite pin: a fault-plan-poisoned ``fleet.forward``
        during the drain handoff answers 502 and leaves the victim
        fully routable — a failed handoff must not half-remove a
        member."""
        replicas, addrs, router, reference = fleet
        doc = _family_doc("drainchaos")
        _request(router.api_port, "POST", "/v1/resolve", doc)
        key = doc_affinity_keys(doc)[0]
        victim = router.target_for(key)
        _poison("fleet.forward", times=-1)
        s, body, _ = _request(router.api_port, "POST", "/fleet/drain",
                              {"replica": victim})
        assert s == 502
        assert "drain failed" in json.loads(body)["error"]
        faults.configure_plan(None)
        states = {st["replica"]: st for st in router.replica_states()}
        assert states[victim]["drained"] is False
        assert router.epoch == 1
        assert router.target_for(key) == victim
        s, b, _ = _request(router.api_port, "POST", "/v1/resolve", doc)
        assert s == 200
        _, ref, _ = _request(reference.api_port, "POST", "/v1/resolve",
                             doc)
        assert b == ref

    def test_static_mode_restores_pr15_surface(self, fleet):
        """The off-switch pin: DEPPY_TPU_FLEET=static 404s the
        join/sync/policy endpoints, keeps /fleet/replicas byte-free of
        membership keys, and renders no epoch gauge."""
        replicas, addrs, router, reference = fleet
        static = Router(bind_address="127.0.0.1:0", replicas=addrs,
                        membership="static", probe_interval_s=60.0)
        static.start()
        try:
            for path, method, body in (
                    ("/fleet/join", "POST", {"replica": addrs[0]}),
                    ("/fleet/sync", "POST",
                     {"view": membership_view(router)}),
                    ("/fleet/policy", "GET", None)):
                s, _, _ = _request(static.api_port, method, path, body)
                assert s == 404, path
            s, body, _ = _request(static.api_port, "GET",
                                  "/fleet/replicas")
            assert sorted(json.loads(body)) == ["policy", "replicas",
                                                "vnodes"]
            _, m, _ = _request(static.api_port, "GET", "/metrics")
            assert "deppy_fleet_epoch" not in m.decode()
            assert "deppy_fleet_joins_total" not in m.decode()
            # ...and it still serves byte-identically.
            doc = _family_doc("staticfam")
            s, b, _ = _request(static.api_port, "POST", "/v1/resolve",
                               doc)
            assert s == 200
            _, ref, _ = _request(reference.api_port, "POST",
                                 "/v1/resolve", doc)
            assert b == ref
        finally:
            static.shutdown()

    def test_elastic_metrics_render_epoch_gauge(self, fleet):
        replicas, addrs, router, reference = fleet
        _, m, _ = _request(router.api_port, "GET", "/metrics")
        assert _metric(m.decode(), "deppy_fleet_epoch") == 1.0


class TestPeerSync:
    def _peer(self, addrs, router, **kw):
        r = Router(bind_address="127.0.0.1:0", replicas=addrs,
                   peers=[f"127.0.0.1:{router.api_port}"],
                   probe_interval_s=60.0, sync_interval_s=0.0, **kw)
        return r

    def test_peer_adopts_join_and_drain_by_epoch(self, fleet):
        replicas, addrs, router, reference = fleet
        peer = self._peer(addrs, router)
        joiner = _host_server(replica="syncjoiner")
        addr = f"127.0.0.1:{joiner.api_port}"
        try:
            s, _, _ = _request(router.api_port, "POST", "/fleet/join",
                               {"replica": addr})
            assert s == 200 and router.epoch == 2
            out = peer.sync_peers()
            assert out == {"peers": 1, "ok": 1, "errors": 0}
            assert peer.epoch == 2
            assert addr in peer.ring.replicas
            # Drain on the authoritative router; the peer learns the
            # removal on the next round.
            s, _, _ = _request(router.api_port, "POST", "/fleet/drain",
                               {"replica": addr})
            assert s == 200 and router.epoch == 3
            peer.sync_peers()
            assert peer.epoch == 3
            assert addr not in peer.ring.replicas
            assert membership_view(peer)["drained"] == [addr]
        finally:
            joiner.shutdown()

    def test_sync_converges_the_authoritative_router_too(self, fleet):
        """One exchange reconciles BOTH directions: a peer holding the
        newer epoch pushes it onto the router it syncs with."""
        replicas, addrs, router, reference = fleet
        peer = self._peer(addrs, router)
        peer.epoch = 7
        peer.sync_peers()
        assert router.epoch == 7

    def test_peer_sync_fault_counted_not_raised(self, fleet):
        replicas, addrs, router, reference = fleet
        peer = self._peer(addrs, router)
        _poison("router.peer_sync", times=-1)
        out = peer.sync_peers()
        assert out == {"peers": 1, "ok": 0, "errors": 1}
        assert peer.epoch == 1

    def test_dead_verdicts_merge_only_at_current_epoch(self, fleet):
        replicas, addrs, router, reference = fleet
        peer = self._peer(addrs, router)
        stale = membership_view(router)
        stale["dead"] = [addrs[2]]
        stale["epoch"] = 0
        reconcile(peer, stale)
        states = {st["replica"]: st for st in peer.replica_states()}
        assert states[addrs[2]]["dead"] is False
        fresh = dict(stale, epoch=peer.epoch)
        reconcile(peer, fresh)
        states = {st["replica"]: st for st in peer.replica_states()}
        assert states[addrs[2]]["dead"] is True

    def test_same_epoch_tiebreak_converges_without_flapping(self):
        a = Router(bind_address="127.0.0.1:0",
                   replicas=["127.0.0.1:11", "127.0.0.1:12"],
                   probe_interval_s=60.0)
        b = Router(bind_address="127.0.0.1:0",
                   replicas=["127.0.0.1:11", "127.0.0.1:13"],
                   probe_interval_s=60.0)
        va, vb = membership_view(a), membership_view(b)
        reconcile(a, vb)
        reconcile(b, va)
        assert list(a.ring.replicas) == list(b.ring.replicas)
        # Idempotent at the fixed point: replaying either original
        # view changes nothing (no flapping).
        winner = list(a.ring.replicas)
        reconcile(a, vb)
        reconcile(a, va)
        assert list(a.ring.replicas) == winner

    def test_malformed_sync_view_answers_400(self, fleet):
        replicas, addrs, router, reference = fleet
        for view in (None, {}, {"epoch": "x", "members": ["a:1"]},
                     {"epoch": 2, "members": []}):
            s, _, _ = _request(router.api_port, "POST", "/fleet/sync",
                               {"view": view})
            assert s == 400, view

    def test_probe_jitter_bounds(self):
        r = Router(bind_address="127.0.0.1:0",
                   replicas=["127.0.0.1:11"], probe_jitter=0.5,
                   probe_interval_s=60.0)
        assert r._jittered(2.0, rng=lambda: 0.0) == 2.0
        assert r._jittered(2.0, rng=lambda: 1.0) == 3.0
        clamped = Router(bind_address="127.0.0.1:0",
                         replicas=["127.0.0.1:11"], probe_jitter=7.0,
                         probe_interval_s=60.0)
        assert clamped.probe_jitter == 1.0
        off = Router(bind_address="127.0.0.1:0",
                     replicas=["127.0.0.1:11"], probe_jitter=-1.0,
                     probe_interval_s=60.0)
        assert off._jittered(2.0, rng=lambda: 1.0) == 2.0


class TestFleetAnnounce:
    def test_server_announces_on_start_and_leaves_on_shutdown(
            self, fleet):
        replicas, addrs, router, reference = fleet
        srv = Server(bind_address="127.0.0.1:0",
                     probe_address="127.0.0.1:0", backend="host",
                     replica="announcer",
                     fleet_router=f"127.0.0.1:{router.api_port}")
        srv.start()
        addr = f"127.0.0.1:{srv.api_port}"
        try:
            deadline = time.monotonic() + 15.0
            while time.monotonic() < deadline:
                if addr in router.ring.replicas:
                    break
                time.sleep(0.05)
            assert addr in router.ring.replicas
            assert router.epoch == 2
        finally:
            srv.shutdown()
        # Graceful shutdown drained it back out (leave = drain).
        assert addr not in router.ring.replicas
        assert addr in membership_view(router)["drained"]
        assert router.epoch == 3


class TestScalePolicy:
    def test_decide_hold_without_samples(self):
        out = policy_decide({}, 0.0, 1.0, 0.25)
        assert out["decision"] == "hold" and out["target"] is None

    def test_decide_scale_up_when_no_cold_capacity(self):
        out = policy_decide({"a:1": {"gold": 2.0}, "b:1": {"bulk": 1.5}},
                            0.0, 1.0, 0.25)
        assert out["decision"] == "scale_up" and out["target"] is None

    def test_decide_rebalance_onto_cold_capacity(self):
        out = policy_decide({"a:1": {"gold": 2.0}, "b:1": {"bulk": 0.1}},
                            0.0, 1.0, 0.25)
        assert out["decision"] == "rebalance"
        assert out["target"] == "a:1"

    def test_decide_scale_down_cold_idle_fleet(self):
        out = policy_decide({"a:1": {"gold": 0.2}, "b:1": {"bulk": 0.1}},
                            0.0, 1.0, 0.25)
        assert out["decision"] == "scale_down"
        assert out["target"] == "b:1"
        # A non-idle queue vetoes the shrink.
        out = policy_decide({"a:1": {"gold": 0.2}, "b:1": {"bulk": 0.1}},
                            3.0, 1.0, 0.25)
        assert out["decision"] == "hold"

    def test_decide_tiebreak_is_deterministic(self):
        burns = {"b:1": {"t": 2.0}, "a:1": {"t": 2.0}, "c:1": {"t": 0.1}}
        out = policy_decide(burns, 0.0, 1.0, 0.25)
        assert out["target"] == "b:1"  # (burn, address) max

    def test_policy_endpoint_reports_live_fleet(self, fleet):
        replicas, addrs, router, reference = fleet
        _request(router.api_port, "POST", "/v1/resolve",
                 _family_doc("polfam"))
        s, body, _ = _request(router.api_port, "GET", "/fleet/policy")
        assert s == 200
        out = json.loads(body)["policy"]
        assert out["decision"] in ("hold", "scale_up", "scale_down",
                                   "rebalance")
        assert out["epoch"] == 1 and out["replicas"] == 3
        assert set(out["per_replica_burn"]) <= set(addrs)
