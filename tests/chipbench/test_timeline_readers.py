"""CPU tests of what the benchmark reads from the program's timeline
phases (``telemetry.TIMELINE``):

- ``frontend_ms`` reads nothing without the front end's spans, and the
  right number from a hand-built run;
- phase annotations on the host plane leave the trace reduction's busy,
  window and per-program times as they were, and name the idle gaps.
"""

import os
import sys
from types import SimpleNamespace

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [os.path.join(ROOT, "chipbench"), ROOT]

import run  # noqa: E402
import trace_reduce  # noqa: E402
from traffic import load_json, load_module  # noqa: E402

BENCH = load_json(os.path.join(ROOT, "BENCHMARK.json"))
FRONTEND = load_module(os.path.join(ROOT, "chipbench", "metrics",
                                    "frontend_ms.py"), "frontend_ms_test")


def run_data(spans):
    return run.RunData(seconds=30.0, setup_s=1.0, requests=[], server={},
                       pipeline={}, spans=spans)


@pytest.mark.parametrize("spans", [
    {},
    {"service.request": [12, 3.0]},
    {"service.parse": [24, 0.1], "sched.encode": [12, 0.2]},
    {"service.request": [0, 0.0], "service.parse": [1, 0.1]},
], ids=["no spans", "no front-end span", "no request", "no request done"])
def test_frontend_ms_reads_nothing_without_its_spans(spans):
    assert FRONTEND.read(run_data(spans)) is None


def test_frontend_ms_is_front_end_seconds_per_request():
    spans = {"service.request": [10, 9.0], "service.parse": [20, 0.05],
             "sched.encode": [10, 0.25], "service.render": [20, 0.2],
             "driver.launch": [4, 3.0], "sched.idle": [4, 1.0]}
    assert FRONTEND.read(run_data(spans)) == pytest.approx(50.0)


@pytest.mark.parametrize("cell", ["deppy-sat-bench.fleet-batch",
                                  "deppy-sat-bench.single"])
def test_each_cell_loads_the_frontend_reader(cell):
    names = [m["name"] for m, _ in run.readers(BENCH, "per_layer", cell)]
    assert sum(n.startswith("frontend_ms.") for n in names) == 1


def ev(name, start, dur):
    return SimpleNamespace(name=name, start_ns=start, duration_ns=dur)


def line(name, events):
    return SimpleNamespace(name=name, events=events)


def plane(name, lines):
    return SimpleNamespace(name=name, lines=lines)


def test_phase_annotations_leave_the_reduction_as_it_was():
    device = plane("/device:TPU:0", [
        line("XLA Modules", [ev("jit_search_phase(1)", 100, 200),
                             ev("jit_core_phase(2)", 600, 200)]),
        line("XLA Ops", [ev("while", 100, 200), ev("while", 600, 200)])])
    window = line("python3", [ev(trace_reduce.WINDOW_ANNOTATION, 0, 1000)])
    runtime = line("loop", [ev("shard_args", 320, 60)])
    phases = line("loop", [ev("sched.coalesce", 300, 40),
                           ev("driver.launch", 340, 250),
                           ev("driver.fetch", 800, 200)])
    before = trace_reduce.reduce(
        [plane(trace_reduce.HOST_PLANE, [window, runtime]), device])
    after = trace_reduce.reduce(
        [plane(trace_reduce.HOST_PLANE, [window, runtime, phases]), device])
    assert (after.window_s, after.busy_s, after.devices, after.program_s) == (
        before.window_s, before.busy_s, before.devices, before.program_s)
    assert [s for _, s in after.idle_gaps] == [s for _, s in before.idle_gaps]
    # Idle [300,600]: the launch phase covers 250 ns of it, the runtime
    # event 60; idle [0,100] lies under no host event.
    assert [n for n, _ in before.idle_gaps] == ["shard_args", "no host event",
                                                "no host event"]
    assert [n for n, _ in after.idle_gaps] == ["driver.launch",
                                               "driver.fetch",
                                               "no host event"]
