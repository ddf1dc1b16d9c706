"""Driver-contract tests: bench.py and __graft_entry__.dryrun_multichip.

These tests pin the hardened behavior of both driver artifacts: both
entry points must succeed even when the accelerator backend is
unavailable or hangs, because they self-provision a forced-CPU platform
in subprocesses with watchdog timeouts.
"""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_bench_emits_json_and_exits_zero_without_accelerator(tmp_path):
    """bench.py must print one parseable JSON record and exit 0 even when
    the backend probe fails instantly (simulated via a 1s probe
    timeout)."""
    env = dict(os.environ)
    env["DEPPY_BENCH_PROBE_TIMEOUT"] = "1"
    # One probe attempt: the waiting-out-a-worker-restart retry loop is
    # production behavior, but 3 x 60s retry delays would be ~90% of this
    # test's runtime and the contract under test is the JSON line.
    env["DEPPY_BENCH_PROBE_RETRIES"] = "1"
    env["DEPPY_BENCH_N"] = "8"
    env["DEPPY_BENCH_HOST_SAMPLE"] = "2"
    # The test process env forces cpu already (conftest mutates XLA_FLAGS /
    # JAX_PLATFORMS); clear both so the orchestrator's own fallback logic
    # is what provisions the platform.
    env.pop("JAX_PLATFORMS", None)
    env.pop("XLA_FLAGS", None)
    # Don't let a machine-level ladder log's accelerator record replace
    # the CPU fallback this test asserts on.
    env["DEPPY_TPU_REVAL_LOG"] = str(tmp_path / "ladder.jsonl")
    out = subprocess.run(
        [sys.executable, "bench.py"],
        cwd=REPO,
        env=env,
        capture_output=True,
        text=True,
        timeout=420,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    lines = [ln for ln in out.stdout.strip().splitlines() if ln.strip()]
    assert len(lines) == 1, f"expected exactly one stdout line: {lines}"
    rec = json.loads(lines[0])
    for key in ("metric", "value", "unit", "vs_baseline", "backend"):
        assert key in rec, f"missing {key}: {rec}"
    assert rec["value"] > 0, rec
    assert rec["backend"] == "cpu"


def test_dryrun_multichip_self_provisions_devices():
    """dryrun_multichip(n) must succeed regardless of the parent process's
    jax platform state — it forces an n-device virtual CPU platform in a
    fresh subprocess."""
    sys.path.insert(0, REPO)
    try:
        import __graft_entry__ as graft

        graft.dryrun_multichip(4)
    finally:
        sys.path.remove(REPO)


def _import_bench():
    sys.path.insert(0, REPO)
    try:
        import bench
    finally:
        sys.path.remove(REPO)
    return bench


def test_ladder_record_selection(tmp_path, monkeypatch):
    """_ladder_record returns the NEWEST fresh accelerator record, and
    skips CPU records, stale records, and garbage lines."""
    import time

    bench = _import_bench()
    log = tmp_path / "ladder.jsonl"
    now = time.time()
    lines = [
        "not json at all",
        json.dumps({"stage": "wait", "ts": now}),
        json.dumps({"stage": "bench-record", "ts": now,
                    "record": {"metric": "m", "value": 1.0,
                               "backend": "cpu"}}),
        json.dumps({"stage": "bench-record", "ts": now - 99999,
                    "record": {"metric": "m", "value": 2.0,
                               "backend": "tpu"}}),
        json.dumps({"stage": "bench-record", "ts": now - 60,
                    "record": {"metric": "m", "value": 3.0,
                               "backend": "tpu"}}),
    ]
    log.write_text("\n".join(lines) + "\n")
    monkeypatch.setattr(bench, "LADDER_LOG", str(log))
    rec = bench._ladder_record()
    assert rec is not None
    assert rec["value"] == 3.0
    assert rec["source"] == "revalidation-ladder"
    assert rec["ladder_record_age_s"] >= 60


def test_publish_record_roundtrip(tmp_path, monkeypatch):
    bench = _import_bench()
    log = tmp_path / "ladder.jsonl"
    monkeypatch.setattr(bench, "LADDER_LOG", str(log))
    bench._publish_record({"metric": "m", "value": 1.0, "backend": "none"})
    assert not log.exists()  # error records are never published
    # CPU records ARE published (the ladder's stage-D trace) but never
    # PREFERRED: _ladder_record must keep returning None over a
    # cpu-backend record.
    bench._publish_record({"metric": "m", "value": 1.0, "backend": "cpu"})
    assert log.exists()
    assert bench._ladder_record() is None
    bench._publish_record({"metric": "m", "value": 4.5, "backend": "tpu"})
    rec = bench._ladder_record()
    assert rec and rec["value"] == 4.5 and rec["backend"] == "tpu"


def test_bench_prefers_fresh_ladder_record(tmp_path):
    """End to end: with the accelerator down and a fresh ladder-produced
    device record on disk, bench.py must report THAT record (honestly
    tagged) instead of re-running on the CPU fallback (verdict r3 #2)."""
    import time

    log = tmp_path / "ladder.jsonl"
    log.write_text(json.dumps({
        "stage": "bench-record", "ts": round(time.time(), 1),
        "record": {"metric": "catalog resolutions/sec", "value": 9999.0,
                   "unit": "problems/s", "vs_baseline": 2.0,
                   "backend": "tpu"}}) + "\n")
    env = dict(os.environ)
    env["DEPPY_BENCH_PROBE_TIMEOUT"] = "1"
    env["DEPPY_BENCH_PROBE_RETRIES"] = "1"
    env["DEPPY_TPU_REVAL_LOG"] = str(log)
    env.pop("JAX_PLATFORMS", None)
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "bench.py"], cwd=REPO, env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert rec["backend"] == "tpu"
    assert rec["value"] == 9999.0
    assert rec["source"] == "revalidation-ladder"
