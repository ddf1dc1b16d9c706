"""CPU tests of the chip benchmark's own parts (``chipbench/``).

    JAX_PLATFORMS=cpu python -m pytest tests/chipbench -q

- the trace reduction: busy is a union on one line, clipped to the
  window, and a window without a device op raises;
- the traffic: distinct problems, the same bodies from the same seed;
- the plain reference against the program's host engine, and its
  control, which must read mismatches;
- a small rehearsal of each cell through ``run.py``'s functions with JAX
  on the CPU, and the same with the served answers broken underneath,
  which must come out not correct.
"""

import json
import os
import random
import sys
import time
from types import SimpleNamespace

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [os.path.join(ROOT, "chipbench"), ROOT]

import control  # noqa: E402
import reference  # noqa: E402
import run  # noqa: E402
import trace_reduce  # noqa: E402
import traffic as traffic_mod  # noqa: E402
from traffic import Cell, Traffic, load_json  # noqa: E402

BENCH = load_json(os.path.join(ROOT, "BENCHMARK.json"))
CELLS = [w["name"] for w in BENCH["workloads"]]
CONFIGS = sorted({w["config"]: w["name"] for w in BENCH["workloads"]}.items())
SEED = 2 ** 31 + 977


# ------------------------------------------------------------------ trace

def ev(name, start, dur):
    return SimpleNamespace(name=name, start_ns=start, duration_ns=dur)


def line(name, events):
    return SimpleNamespace(name=name, events=events)


def plane(name, lines):
    return SimpleNamespace(name=name, lines=lines)


def host(lo, hi, extra=()):
    return plane(trace_reduce.HOST_PLANE, [
        line("python3", [ev(trace_reduce.WINDOW_ANNOTATION, lo, hi - lo)]),
        line("main", list(extra))])


def test_busy_is_the_union_on_the_op_line_not_a_sum():
    ops = [ev("while", 100, 400), ev("fusion", 150, 100), ev("copy", 450, 100)]
    modules = [ev("jit_search_phase(1)", 100, 300),
               ev("jit__core_gated(2)", 400, 200)]
    device = plane("/device:TPU:0", [line("XLA Modules", modules),
                                     line("XLA Ops", ops),
                                     line("Async XLA Ops", [ev("c", 0, 900)])])
    red = trace_reduce.reduce([host(0, 1000), device])
    # union of [100,500] and [450,550] = 450 ns; the sum would be 600 and
    # the module line and async line add nothing.
    assert red.busy_s == pytest.approx(450e-9)
    assert red.window_s == pytest.approx(1000e-9)
    assert red.program_s["jit_search_phase(1)"] == pytest.approx(300e-9)
    assert red.program_s["jit__core_gated(2)"] == pytest.approx(150e-9)
    assert sum(red.program_s.values()) <= red.busy_s + 1e-18


def test_events_straddling_the_window_are_clipped():
    ops = [ev("a", 0, 300), ev("b", 900, 400), ev("c", 2000, 10)]
    device = plane("/device:TPU:0", [line("XLA Ops", ops)])
    red = trace_reduce.reduce([host(200, 1000), device])
    assert red.busy_s == pytest.approx(200e-9)  # [200,300] + [900,1000]
    assert red.busy_s <= red.window_s


def test_idle_gaps_are_named_after_the_host_event_over_them():
    device = plane("/device:TPU:0", [line("XLA Ops", [ev("a", 0, 100),
                                                      ev("b", 600, 100)])])
    red = trace_reduce.reduce([host(0, 1000, [ev("shard_args", 150, 400)]),
                               device])
    assert red.idle_gaps[0] == ("shard_args", pytest.approx(500e-9))


def test_a_window_without_a_device_op_raises():
    device = plane("/device:TPU:0", [line("XLA Ops", [ev("a", 5000, 10)])])
    with pytest.raises(trace_reduce.NoDeviceWork):
        trace_reduce.reduce([host(0, 1000), device])


def test_a_trace_without_a_tpu_plane_or_window_raises():
    with pytest.raises(trace_reduce.TraceError):
        trace_reduce.reduce([host(0, 1000)])
    device = plane("/device:TPU:0", [line("XLA Ops", [ev("a", 0, 10)])])
    with pytest.raises(trace_reduce.TraceError):
        trace_reduce.reduce([plane(trace_reduce.HOST_PLANE, []), device])


# ---------------------------------------------------------------- traffic

@pytest.mark.parametrize("config,cell", CONFIGS)
def test_states_are_distinct_problems(config, cell):
    from deppy_tpu import io as problem_io
    from deppy_tpu.sat.encode import encode
    from deppy_tpu.sched.cache import fingerprint

    c = Cell.load(BENCH, cell)
    traffic = Traffic(c.config_name, c.config, c.mix, SEED)
    states = [s for k in range(8) for s in traffic.states("window", k, 16)]
    states += [s for k in range(4) for s in traffic.states("warm", k, 16)]
    keys = {fingerprint(encode(problem_io.problem_from_dict(s)))
            for s in states}
    assert len(keys) == len(states)


@pytest.mark.parametrize("cell", CELLS)
def test_the_same_seed_gives_the_same_bodies(cell):
    c = Cell.load(BENCH, cell)
    a = Traffic(c.config_name, c.config, c.mix, SEED)
    b = Traffic(c.config_name, c.config, c.mix, SEED)
    other = Traffic(c.config_name, c.config, c.mix, SEED + 1)
    assert [a.body("window", k) for k in range(3)] == \
        [b.body("window", k) for k in range(3)]
    assert a.body("window", 0) != other.body("window", 0)
    doc = json.loads(a.body("window", 0))
    assert ("problems" in doc) == (c.mix["states_per_request"] > 1)


@pytest.mark.parametrize("cell", CELLS)
def test_the_span_is_the_same_for_every_seed_and_holds_each_class(cell):
    c = Cell.load(BENCH, cell)
    mix = dict(c.mix, span_draws=300)
    a = Traffic(c.config_name, c.config, mix, SEED).span()
    b = Traffic(c.config_name, c.config, mix, SEED + 1).span()
    assert a == b
    family = Traffic(c.config_name, c.config, mix, SEED).family
    drawn = [family.state(random.Random(f"span/{k}"), f"span{k}", c.config)
             for k in range(300)]
    classes = {traffic_mod.size_class(s) for s in drawn}
    assert {traffic_mod.size_class(s) for s in a} == classes
    assert len(a) <= traffic_mod.PER_CLASS * len(classes) < len(drawn)


def test_size_classes_round_each_size_up_to_a_power_of_two():
    doc = {"variables": [
        {"id": "a", "constraints": [{"type": "mandatory"},
                                    {"type": "dependency", "ids": ["b", "c", "b"]}]},
        {"id": "b", "constraints": [{"type": "conflict", "id": "c"}]},
        {"id": "c"}]}
    # 3 variables, 3 constraints, 1 dependency on a variable, b occurs 3
    # times; conflict, dependency, mandatory: 1 each; widest 1, 2, 1.
    assert traffic_mod.size_class(doc) == (4, 4, 1, 4, 1, 1, 1, 1, 2, 1)


# -------------------------------------------------------------- reference

@pytest.mark.parametrize("config,cell", CONFIGS)
def test_reference_matches_the_host_engine(config, cell):
    from deppy_tpu import io as problem_io
    from deppy_tpu import resolution

    c = Cell.load(BENCH, cell)
    traffic = Traffic(c.config_name, c.config, c.mix, SEED)
    states = control.window_states(traffic, 48)
    host = resolution.BatchResolver(backend="host").solve(
        [problem_io.problem_from_dict(s) for s in states])
    got = [problem_io.result_to_dict(r) for r in host]
    out = reference.compare(got, states)
    assert out == {"compared": 48, "mismatched": 0, "missing": 0,
                   "first_difference": ""}


@pytest.mark.parametrize("cell", CELLS)
def test_the_control_reads_mismatches(cell):
    out = control.readings(Cell.load(BENCH, cell), SEED, 64)
    assert out["compared"] == 64 and out["mismatched"] > 0


# -------------------------------------------------------------- rehearsal

@pytest.fixture
def small(monkeypatch):
    """A cell's mix cut to a CPU's size, and a short warm-up."""
    monkeypatch.setattr(run, "WARM_QUIET_S", 0.5)
    monkeypatch.setattr(run, "WARM_DISPATCHES", 2)
    monkeypatch.setattr(run, "SAMPLE", 24)

    def cut(name):
        c = Cell.load(BENCH, name)
        c.mix = dict(c.mix, clients=min(c.mix["clients"], 2),
                     states_per_request=min(c.mix["states_per_request"], 4),
                     span_draws=min(c.mix.get("span_draws", 0), 12))
        return c
    return cut


def rehearse(cell, trace=False):
    return run.run_cell(BENCH, cell, SEED, 2.0, trace, answers_min=8)


@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_is_correct_and_reports_its_metrics(cell, small):
    res = rehearse(small(cell))
    assert res["correct"] is True and res["failed"] == 0
    assert res["checks"]["mismatched_answers"]["value"] == 0
    want = {m["name"] for m in BENCH["end_to_end"]
            if cell in m.get("workloads", [cell])}
    assert set(res["metrics"]) == want
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert list(res)[-1] == "checks"


def test_spans_stop_counting_when_the_window_closes():
    spans = run.SpanSum()
    spans({"kind": "span", "name": "driver.decode", "dur_s": 0.25})
    spans({"kind": "counter", "name": "driver.decode", "dur_s": 9.0})
    totals = spans.close()
    spans({"kind": "span", "name": "driver.decode", "dur_s": 15.0})
    assert totals == {"driver.decode": [1, 0.25]}
    assert spans.close() == totals


def test_a_traced_rehearsal_reaches_the_trace_and_needs_a_tpu(small):
    with pytest.raises(trace_reduce.TraceError, match="TPU"):
        rehearse(small(CELLS[0]), trace=True)


def test_the_window_closes_on_time_while_the_trace_is_written(
        small, monkeypatch):
    import jax

    order = []
    stop_trace, close = jax.profiler.stop_trace, run.SpanSum.close

    def slow_stop():
        time.sleep(2.0)
        stop_trace()
        order.append("trace written")

    def closing(self):
        order.append("spans closed")
        return close(self)

    monkeypatch.setattr(jax.profiler, "stop_trace", slow_stop)
    monkeypatch.setattr(run.SpanSum, "close", closing)
    with pytest.raises(trace_reduce.TraceError, match="TPU"):
        rehearse(small(CELLS[0]), trace=True)
    assert order[:2] == ["spans closed", "trace written"]


def broken_after_warm_up(monkeypatch, target, name, make):
    """Break ``target.name`` with ``make(original)`` once warm-up is
    over, so that the window's answers are the broken ones."""
    warm_up = run.warm_up

    def then_break(*a, **kw):
        warm_up(*a, **kw)
        monkeypatch.setattr(target, name, make(getattr(target, name)))

    monkeypatch.setattr(run, "warm_up", then_break)


def altered(result_to_dict):
    """``io.result_to_dict`` with every answer changed where it is made."""
    def wrapped(result):
        doc = result_to_dict(result)
        if doc["status"] == "sat":
            doc["selected"] = doc["selected"][1:]
        else:
            doc["conflicts"] = doc["conflicts"][:-1]
        return doc
    return wrapped


def halved(resolve_document):
    """``Server.resolve_document`` answering the first half of a batch."""
    def wrapped(self, doc, *a, **kw):
        status, out = resolve_document(self, doc, *a, **kw)
        if status == 200:
            out = {"results": out["results"][: len(out["results"]) // 2]}
        return status, out
    return wrapped


@pytest.mark.parametrize("cell", CELLS)
def test_an_altered_answer_is_not_correct(cell, small, monkeypatch):
    from deppy_tpu import io as problem_io

    broken_after_warm_up(monkeypatch, problem_io, "result_to_dict", altered)
    res = rehearse(small(cell))
    assert res["correct"] is False
    assert res["checks"]["mismatched_answers"]["value"] > 0


@pytest.mark.parametrize("cell", CELLS)
def test_half_the_batch_left_out_is_not_correct(cell, small, monkeypatch):
    from deppy_tpu import service

    broken_after_warm_up(monkeypatch, service.Server, "resolve_document",
                         halved)
    res = rehearse(small(cell))
    assert res["correct"] is False and res["failed"] > 0
    assert res["checks"]["missing_answers"]["value"] > 0
