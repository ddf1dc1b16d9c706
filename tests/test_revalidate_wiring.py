"""Stage wiring of the revalidation ladder (scripts/tpu_revalidate.py).

The F-I recovery queue was validated end to end by forced-CPU smoke
runs; these tests pin the CONTRACT pieces a smoke run can't isolate:
stage order, abort propagation (a failed stage must stop the ladder and
suppress ladder-complete), the smoke-vs-device argument selection, and
the backend-flip abort — all by scripting run_stage/probe_status, so no
subprocess or engine runs.
"""

from __future__ import annotations

import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from scripts import tpu_revalidate  # noqa: E402


class Script:
    """Scripted run_stage/probe_status doubles recording every call."""

    def __init__(self, backend="tpu", fail_at=None, smoke_fail=()):
        self.backend = backend
        self.fail_at = fail_at  # stage-name prefix that returns ok=False
        self.smoke_fail = smoke_fail  # kernel names the smoke fails
        self.smoke_verdict = True  # write a verdict file at all
        self.f_variants = []    # (name, rate, backend) stage F "emits"
        self.h_verdict = None   # dict stage H "emits" as its verdict line
        self.stages = []        # (name, cmd) in call order

    def run_stage(self, rec, cmd, env, timeout_s, log_path, **kwargs):
        name = rec.get("stage", rec.get("variant", "?"))
        cmd = [str(c) for c in cmd]
        self.stages.append((name, cmd))
        self.envs = getattr(self, "envs", {})
        self.envs[name] = dict(env)
        if "--verdict" in cmd and self.smoke_verdict:
            # Model mosaic_smoke.py's contract: a verdict file keyed by
            # kernel name, written even when kernels fail.
            import json

            kernels = ["search-fused", "minimize-fused", "core-fused",
                       "bcp-fused", "bcp-blockwise"]
            with open(cmd[cmd.index("--verdict") + 1], "w") as f:
                json.dump({"backend": self.backend, "kernels": {
                    k: {"ok": k not in self.smoke_fail} for k in kernels
                }}, f)
        if name == "F:tpu-ab" and self.f_variants:
            # Model tpu_ab.py: variant records are emitted into the
            # ladder log DURING stage F (the F2 gate reads only lines
            # appended after F started).
            import json

            with open(log_path, "a") as f:
                for vname, rate, backend in self.f_variants:
                    f.write(json.dumps({"variant": vname, "ok": True,
                                        "backend": backend,
                                        "rate": rate}) + "\n")
        if name == "H:spec-core-ab" and self.h_verdict is not None:
            import json

            with open(log_path, "a") as f:
                f.write(json.dumps(self.h_verdict) + "\n")
        ok = not (self.fail_at and name.startswith(self.fail_at))
        rec.update(ok=ok, backend=self.backend, warm_s=1.0, run_s=0.1,
                   rate=10.0)
        return rec

    def probe_status(self, timeout):
        return {"status": "ok" if self.backend != "cpu" else "cpu-only",
                "backend": self.backend}


@pytest.fixture()
def scripted(monkeypatch, tmp_path):
    def make(**kw):
        s = Script(**kw)
        monkeypatch.setattr(tpu_revalidate, "run_stage", s.run_stage)
        monkeypatch.setattr(tpu_revalidate, "probe_status", s.probe_status)
        # Stage F3 must never touch the real package registry from a test.
        monkeypatch.setenv("DEPPY_TPU_MEASURED_DEFAULTS",
                           str(tmp_path / "measured_defaults.json"))
        monkeypatch.setattr(
            sys, "argv",
            ["tpu_revalidate.py", "--skip-wait",
             "--log", str(tmp_path / "ladder.jsonl")])
        return s, tmp_path / "ladder.jsonl"
    return make


def _names(s):
    return [n for n, _ in s.stages]


def _log_stages(log):
    import json

    out = []
    for line in log.read_text().splitlines():
        try:
            out.append(json.loads(line).get("stage"))
        except ValueError:
            pass
    return out


def test_device_ladder_runs_all_stages_in_order(scripted):
    s, log = scripted(backend="tpu")
    tpu_revalidate.main()
    # F (the baseline/fused A/B) runs BEFORE the suite: heal windows
    # have died minutes in, and the fused verdict outranks the suite.
    assert _names(s) == [
        "A:tiny-cache-off", "B:tiny-cache-on", "B2:mosaic-smoke",
        "C:headline-1024", "D:bench.py", "F:tpu-ab", "E:suite",
        "G:blockwise-overvmem", "H:spec-core-ab", "I:lane-probe"]
    assert "ladder-complete" in _log_stages(log)
    # Device mode: full shapes, no CPU allowances, Pallas substrates on
    # (the scripted smoke passed every kernel).
    by_name = dict(s.stages)
    assert "--allow-cpu" not in by_name["B2:mosaic-smoke"]
    assert "--allow-cpu" not in by_name["F:tpu-ab"]
    assert "--count" not in by_name["F:tpu-ab"]
    assert "--skip-fused" not in by_name["F:tpu-ab"]
    assert "1000" in by_name["G:blockwise-overvmem"]
    assert "bits,blockwise" in by_name["G:blockwise-overvmem"]
    assert "--widths" not in by_name["I:lane-probe"]


def test_smoke_ladder_shrinks_shapes_and_allows_cpu(scripted):
    s, log = scripted(backend="cpu")
    tpu_revalidate.main()
    assert _names(s)[-1] == "I:lane-probe"
    by_name = dict(s.stages)
    assert "--allow-cpu" in by_name["B2:mosaic-smoke"]
    assert "--allow-cpu" in by_name["F:tpu-ab"]
    assert "256" in by_name["F:tpu-ab"]
    assert "120" in by_name["G:blockwise-overvmem"]
    assert "bits" in by_name["G:blockwise-overvmem"]
    assert "bits,blockwise" not in by_name["G:blockwise-overvmem"]
    assert "--allow-cpu" in by_name["H:spec-core-ab"]
    assert "--widths" in by_name["I:lane-probe"]
    assert "ladder-complete" in _log_stages(log)


def test_smoke_fused_failure_skips_fused_but_keeps_measuring(scripted):
    """A Mosaic rejection of any fused-phase kernel must NOT abort the
    queue: stage F runs with --skip-fused and everything else proceeds
    to ladder-complete (the smoke exists so a broken substrate costs
    one variant, not the round's measurements)."""
    s, log = scripted(backend="tpu")
    s.smoke_fail = ("minimize-fused",)
    tpu_revalidate.main()
    by_name = dict(s.stages)
    assert "--skip-fused" in by_name["F:tpu-ab"]
    assert "bits,blockwise" in by_name["G:blockwise-overvmem"]
    assert "ladder-complete" in _log_stages(log)


def test_smoke_blockwise_failure_drops_blockwise_from_stage_g(scripted):
    s, log = scripted(backend="tpu")
    s.smoke_fail = ("bcp-blockwise",)
    tpu_revalidate.main()
    by_name = dict(s.stages)
    assert "--skip-fused" not in by_name["F:tpu-ab"]
    assert "bits,blockwise" not in by_name["G:blockwise-overvmem"]
    assert "bits" in by_name["G:blockwise-overvmem"]
    assert "ladder-complete" in _log_stages(log)


def test_missing_smoke_verdict_is_conservative(scripted):
    """A smoke that hung or never wrote its verdict leaves every Pallas
    substrate unproven: F skips fused, G runs bits only, and the ladder
    still completes."""
    s, log = scripted(backend="tpu")
    s.smoke_verdict = False
    tpu_revalidate.main()
    by_name = dict(s.stages)
    assert "--skip-fused" in by_name["F:tpu-ab"]
    assert "bits,blockwise" not in by_name["G:blockwise-overvmem"]
    assert "ladder-complete" in _log_stages(log)


def test_failed_cache_stage_continues_with_cache_off(scripted):
    """The ONE exception to abort propagation: stage B (cache on)
    failing must NOT stop the ladder — it convicts the compile cache and
    the remaining stages run cache-off (the 2026-07-31 outage began at
    the first compile of a cache-enabled run)."""
    s, log = scripted(backend="tpu", fail_at="B:")
    tpu_revalidate.main()
    names = _names(s)
    assert "C:headline-1024" in names and "I:lane-probe" in names
    assert "ladder-complete" in _log_stages(log)
    # Every post-B stage runs with the cache forced off.
    import json

    notes = [json.loads(l) for l in log.read_text().splitlines()
             if "note" in l]
    assert any("compile cache implicated" in str(n) for n in notes)
    for stage in ("C:headline-1024", "F:tpu-ab", "I:lane-probe"):
        assert s.envs[stage]["DEPPY_TPU_COMPILE_CACHE"] == "off"


def test_failed_stage_stops_the_ladder(scripted):
    s, log = scripted(backend="tpu", fail_at="F:")
    tpu_revalidate.main()
    assert _names(s)[-1] == "F:tpu-ab"  # nothing after the failure
    assert "G:blockwise-overvmem" not in _names(s)
    assert "ladder-complete" not in _log_stages(log)


def test_failed_lane_probe_suppresses_ladder_complete(scripted):
    s, log = scripted(backend="tpu", fail_at="I:")
    tpu_revalidate.main()
    assert _names(s)[-1] == "I:lane-probe"
    assert "ladder-complete" not in _log_stages(log)


def test_backend_flip_mid_ladder_aborts(scripted, monkeypatch):
    s, log = scripted(backend="tpu")
    # After stage C the worker dies and probes flip to cpu-only.
    orig = s.run_stage

    def run_stage(rec, cmd, env, t, lp, **k):
        rec = orig(rec, cmd, env, t, lp, **k)
        if rec.get("stage") == "C:headline-1024":
            s.backend = "cpu"
        return rec

    monkeypatch.setattr(tpu_revalidate, "run_stage", run_stage)
    tpu_revalidate.main()
    assert "D:bench.py" not in _names(s)
    assert "ladder-complete" not in _log_stages(log)


def test_fused_win_captures_bench_fused(scripted):
    s, log = scripted(backend="tpu")
    s.f_variants = [("baseline", 3000.0, "tpu"),
                    ("search-fused", 9000.0, "tpu")]
    tpu_revalidate.main()
    names = _names(s)
    assert "F2:bench-fused" in names
    assert names.index("F:tpu-ab") < names.index("F2:bench-fused") < \
        names.index("E:suite")
    assert s.envs["F2:bench-fused"]["DEPPY_TPU_SEARCH"] == "fused"


def test_fused_loss_skips_bench_fused(scripted):
    s, log = scripted(backend="tpu")
    s.f_variants = [("baseline", 3000.0, "tpu"),
                    ("search-fused", 2000.0, "tpu")]
    tpu_revalidate.main()
    assert "F2:bench-fused" not in _names(s)


def test_cpu_variant_records_do_not_trigger_bench_fused(scripted):
    s, log = scripted(backend="tpu")
    s.f_variants = [("baseline", 300.0, "cpu"),
                    ("search-fused", 900.0, "cpu")]
    tpu_revalidate.main()
    assert "F2:bench-fused" not in _names(s)


def test_stale_fused_win_in_shared_log_does_not_trigger(scripted):
    """A fused win from a PREVIOUS run lingering in the shared /tmp log
    must not launch F2 when this run's smoke rejected the substrate and
    stage F skipped it (the regression the from_line gate exists for)."""
    import json

    s, log = scripted(backend="tpu")
    s.smoke_fail = ("search-fused",)
    with open(log, "w") as f:
        for name, rate in (("baseline", 3000.0), ("search-fused", 9000.0)):
            f.write(json.dumps({"variant": name, "ok": True,
                                "backend": "tpu", "rate": rate}) + "\n")
    tpu_revalidate.main()
    assert "F2:bench-fused" not in _names(s)


def test_failed_f2_still_runs_safe_stages(scripted):
    """F2 is a bonus artifact: its failure is noted and E/G/H/I still
    run to ladder-complete."""
    s, log = scripted(backend="tpu", fail_at="F2:")
    s.f_variants = [("baseline", 3000.0, "tpu"),
                    ("search-fused", 9000.0, "tpu")]
    tpu_revalidate.main()
    names = _names(s)
    assert "F2:bench-fused" in names
    assert "E:suite" in names and "I:lane-probe" in names
    assert "ladder-complete" in _log_stages(log)


def test_f2_success_writes_measured_default(scripted, tmp_path):
    import json

    s, log = scripted(backend="tpu")
    s.f_variants = [("baseline", 3000.0, "tpu"),
                    ("search-fused", 9000.0, "tpu")]
    tpu_revalidate.main()
    path = tmp_path / "measured_defaults.json"
    assert path.exists()
    data = json.loads(path.read_text())
    assert data["tpu"]["search"] == "fused"
    assert data["tpu"]["evidence"]["search"]["fused_rate"] == 9000.0
    assert "F3:measured-default" in _log_stages(log)


def test_failed_f2_does_not_write_measured_default(scripted, tmp_path):
    s, log = scripted(backend="tpu", fail_at="F2:")
    s.f_variants = [("baseline", 3000.0, "tpu"),
                    ("search-fused", 9000.0, "tpu")]
    tpu_revalidate.main()
    assert not (tmp_path / "measured_defaults.json").exists()


def test_post_f3_stages_pin_the_preflip_substrate(scripted):
    """After F3 records the fused default, the remaining stages must
    keep measuring the PRE-flip substrate explicitly (their artifacts
    are compared round-over-round), so the env knob is pinned to xla."""
    s, log = scripted(backend="tpu")
    s.f_variants = [("baseline", 3000.0, "tpu"),
                    ("search-fused", 9000.0, "tpu")]
    tpu_revalidate.main()
    for stage in ("E:suite", "G:blockwise-overvmem", "H:spec-core-ab"):
        assert s.envs[stage]["DEPPY_TPU_SEARCH"] == "xla", stage
    # And without a fused win, nothing is pinned.
    s2, _ = scripted(backend="tpu")
    tpu_revalidate.main()
    assert "DEPPY_TPU_SEARCH" not in s2.envs["E:suite"]


def test_spec_core_win_records_on(scripted, tmp_path):
    import json

    s, log = scripted(backend="tpu")
    s.h_verdict = {"verdict": "ok", "off_s": 8.6, "on_s": 2.9}
    tpu_revalidate.main()
    data = json.loads((tmp_path / "measured_defaults.json").read_text())
    assert data["tpu"]["spec_core"] == "on"
    assert "H3:measured-default" in _log_stages(log)


def test_spec_core_loss_records_off(scripted, tmp_path):
    import json

    s, log = scripted(backend="tpu")
    s.h_verdict = {"verdict": "ok", "off_s": 2.1, "on_s": 27.6}
    tpu_revalidate.main()
    data = json.loads((tmp_path / "measured_defaults.json").read_text())
    assert data["tpu"]["spec_core"] == "off"


def test_spec_core_divergence_records_nothing(scripted, tmp_path):
    s, log = scripted(backend="tpu")
    s.h_verdict = {"verdict": "CORE-DIVERGENCE", "off_s": 2.0, "on_s": 1.0}
    tpu_revalidate.main()
    assert not (tmp_path / "measured_defaults.json").exists()


def test_smoke_ladder_never_records_spec_core(scripted, tmp_path):
    s, log = scripted(backend="cpu")
    s.h_verdict = {"verdict": "ok", "off_s": 8.0, "on_s": 2.0}
    tpu_revalidate.main()
    assert not (tmp_path / "measured_defaults.json").exists()


def test_f3_and_h3_rows_merge(scripted, tmp_path):
    import json

    s, log = scripted(backend="tpu")
    s.f_variants = [("baseline", 3000.0, "tpu"),
                    ("search-fused", 9000.0, "tpu")]
    s.h_verdict = {"verdict": "ok", "off_s": 8.6, "on_s": 2.9}
    tpu_revalidate.main()
    data = json.loads((tmp_path / "measured_defaults.json").read_text())
    assert data["tpu"]["search"] == "fused"
    assert data["tpu"]["spec_core"] == "on"
