"""The profiler timeline: spans in ``telemetry.TIMELINE`` are also JAX
profiler annotations, so a device trace names what the host was doing in
each idle gap (docs/observability.md, "Profiler timeline").

- a span opens an annotation only when its name is a timeline phase and
  a profiler session records; a process without JAX never imports it;
- a CPU profiler session around served ``/v1/resolve`` requests holds
  every dispatch-loop and handler phase on ``/host:CPU``, and on the
  dispatch thread no phase nests in another;
- every jitted engine entry reaches XLA under its function's name, not
  the compile guard's wrapper (``jit_traced``).
"""

import ast
import json
import subprocess
import sys
import tempfile
from http.client import HTTPConnection
from pathlib import Path

import pytest

from deppy_tpu import telemetry

REPO = Path(__file__).resolve().parent.parent

DISPATCH_PHASES = {
    "sched.idle", "sched.coalesce", "sched.deliver", "driver.pad_pack",
    "driver.device_put", "driver.launch", "driver.fetch",
    "driver.core_stage", "driver.decode",
}
HANDLER_PHASES = {"service.parse", "sched.encode", "service.render"}


def test_the_timeline_is_the_leaf_phases():
    assert telemetry.TIMELINE == DISPATCH_PHASES | HANDLER_PHASES
    # Enclosing spans would win every idle gap they cover.
    for enclosing in ("service.request", "sched.dispatch", "driver.solve",
                      "driver.escalation", "sched.queue_wait"):
        assert enclosing not in telemetry.TIMELINE


class _Recorder:
    """Stands in for ``jax.profiler.TraceAnnotation`` with a session
    recording."""

    opened: list = []
    enabled = True

    def __init__(self, name):
        self.name = name

    @classmethod
    def is_enabled(cls):
        return cls.enabled

    def __enter__(self):
        self.opened.append(("enter", self.name))
        return self

    def __exit__(self, *exc):
        self.opened.append(("exit", self.name))


@pytest.fixture()
def recorder(monkeypatch):
    import jax.profiler

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", _Recorder)
    monkeypatch.setattr(_Recorder, "opened", [])
    monkeypatch.setattr(_Recorder, "enabled", True)
    return _Recorder


def test_a_timeline_span_is_an_annotation_from_entry_to_exit(recorder):
    reg = telemetry.Registry()
    with reg.span("driver.launch") as sp:
        assert recorder.opened == [("enter", "driver.launch")]
    assert recorder.opened == [("enter", "driver.launch"),
                               ("exit", "driver.launch")]
    assert reg.recent_spans()[-1]["name"] == "driver.launch"
    assert sp.dur_s >= 0


@pytest.mark.parametrize("name", ["driver.solve", "sched.dispatch",
                                  "service.request", "race"])
def test_a_span_outside_the_timeline_opens_no_annotation(recorder, name):
    reg = telemetry.Registry()
    with reg.span(name):
        pass
    reg.record_span("driver.launch", 0.5)  # measured elsewhere: no phase
    assert recorder.opened == []


def test_no_annotation_without_a_profiler_session(recorder):
    recorder.enabled = False
    with telemetry.Registry().span("driver.fetch"):
        pass
    assert recorder.opened == []


def test_a_span_in_a_process_without_jax_leaves_jax_unimported():
    code = (
        "import sys\n"
        "from deppy_tpu import telemetry\n"
        "reg = telemetry.Registry()\n"
        "for name in sorted(telemetry.TIMELINE):\n"
        "    with reg.span(name):\n"
        "        pass\n"
        "assert len(reg.recent_spans()) == len(telemetry.TIMELINE)\n"
        "print(sorted(m for m in sys.modules if m.split('.')[0] == 'jax'))\n"
    )
    out = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == "[]"


# ----------------------------------------------------- a profiled dispatch

def _document(tag: str, unsat: bool) -> dict:
    """One problem, SAT or UNSAT, whose identifiers carry ``tag`` (so no
    two requests share a result-cache entry) and whose shape does not
    depend on it."""
    a, b, c = (f"{tag}-{x}" for x in "abc")
    a_cons = [{"type": "mandatory"}, {"type": "dependency", "ids": [b, c]}]
    b_cons = ([{"type": "prohibited"}] if unsat
              else [{"type": "conflict", "id": c}])
    return {"variables": [{"id": a, "constraints": a_cons},
                          {"id": b, "constraints": b_cons},
                          {"id": c, "constraints":
                              [{"type": "prohibited"}] if unsat else []}]}


def _post(port: int, doc: dict) -> dict:
    conn = HTTPConnection("127.0.0.1", port, timeout=120)
    conn.request("POST", "/v1/resolve", body=json.dumps(doc),
                 headers={"Content-Type": "application/json"})
    resp = conn.getresponse()
    body = resp.read()
    conn.close()
    assert resp.status == 200, body
    return json.loads(body)


def _batch(tag: str) -> dict:
    # Four lanes, one UNSAT: the phased path compacts the UNSAT row into
    # its own core dispatch (driver.core_stage).
    return {"problems": [_document(f"{tag}{i}", unsat=i == 3)
                         for i in range(4)]}


@pytest.fixture(scope="module")
def profiled():
    """The ``/host:CPU`` events of a profiler session around two served
    requests, one list ``[(name, start, end), ...]`` per thread's line."""
    import jax
    from jax.profiler import ProfileData

    from deppy_tpu.service import Server

    srv = Server("127.0.0.1:0", "127.0.0.1:0", backend="tpu",
                 sched_max_wait_ms=20.0)
    srv.start()
    try:
        statuses = [r["status"] for r in _post(srv.api_port,
                                               _batch("warm"))["results"]]
        assert statuses == ["sat", "sat", "sat", "unsat"]
        with tempfile.TemporaryDirectory() as out:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(out, profiler_options=opts)
            try:
                for k in range(2):
                    _post(srv.api_port, _batch(f"traced{k}"))
            finally:
                jax.profiler.stop_trace()
            (path,) = list(Path(out).glob("plugins/profile/*/*.xplane.pb"))
            planes = ProfileData.from_file(str(path)).planes
    finally:
        srv.shutdown()
    return [[(e.name, e.start_ns, e.start_ns + e.duration_ns)
             for e in line.events]
            for plane in planes if plane.name == "/host:CPU"
            for line in plane.lines]


def test_a_profiled_dispatch_holds_every_phase(profiled):
    seen = {e[0] for events in profiled for e in events}
    assert DISPATCH_PHASES | HANDLER_PHASES <= seen


def test_on_the_dispatch_thread_no_phase_nests_in_another(profiled):
    (loop,) = [events for events in profiled
               if any(e[0] == "sched.coalesce" for e in events)]
    phases = sorted((e for e in loop if e[0] in telemetry.TIMELINE),
                    key=lambda e: e[1])
    assert {e[0] for e in phases} == DISPATCH_PHASES
    for before, after in zip(phases, phases[1:]):
        assert before[2] <= after[1], (before, after)


# ------------------------------------------------------ stable program names

def _observed_entries(path: Path) -> set:
    """Entry names of every ``compileguard.observe`` call in ``path``."""
    out = set()
    for node in ast.walk(ast.parse(path.read_text())):
        if (isinstance(node, ast.Call)
                and getattr(node.func, "attr", None) == "observe"
                and getattr(node.func.value, "id", None) == "compileguard"):
            out.add(node.args[0].value)
    return out


def _engine_jits():
    """Each jitted entry of ``engine/``, built at a tiny size, by its
    compile-guard entry name."""
    import jax
    import numpy as np
    from jax.sharding import Mesh

    from deppy_tpu.engine import core, driver, grad_relax, pallas_search

    mesh = Mesh(np.array(jax.devices()[:1]), ("batch",))
    return {
        "driver.planes_fn": driver._planes_fn(1, 1, True, True),
        "driver.bank_fn": driver._bank_fn(8, 8, 4, 4, True, True),
        "driver.batched_solve_sharded":
            driver.batched_solve_sharded(mesh, 8, 8, 8),
        "grad_relax.descend": grad_relax._descend_fn(8, 8, 4, 2, 2, 2, 2),
        "core.batched_solve": core.batched_solve(8, 8, 8),
        "core.batched_search": core.batched_search(8, 8, 8),
        "core.batched_core": core.batched_core(8, 8, 8),
        "core.batched_probe_fixpoint": core.batched_probe_fixpoint(8, 8),
        "core.batched_probe": core.batched_probe(8, 8, 8),
        "core.batched_minimize_gated": core.batched_minimize_gated(8, 8, 8),
        "core.batched_warm_check": core.batched_warm_check(8, 8, 8),
        "core.batched_core_gated": core.batched_core_gated(8, 8, 8),
        "pallas_search.batched_minimize_fused":
            pallas_search._batched_minimize_fused,
        "pallas_search.batched_core_fused":
            pallas_search._batched_core_fused,
        "pallas_search.batched_search_fused":
            pallas_search._batched_search_fused,
    }


def test_every_engine_jit_is_named_after_its_function():
    jits = _engine_jits()
    entries = set()
    for path in sorted((REPO / "deppy_tpu" / "engine").glob("*.py")):
        entries |= _observed_entries(path)
    assert set(jits) == entries
    for entry, fn in jits.items():
        assert fn.__name__ != "traced", entry
    # Renamed programs keep clear of the core share's mark.
    assert "core" not in jits["driver.planes_fn"].__name__
    assert "core" not in jits["driver.bank_fn"].__name__


def test_a_partial_lowers_under_its_function_name():
    import functools

    import jax
    import jax.numpy as jnp

    from deppy_tpu.analysis import compileguard

    def derive_widths(x, k):
        return x * k

    fn = jax.jit(compileguard.observe(
        "test.entry", functools.partial(derive_widths, k=2)))
    assert fn.__name__ == "derive_widths"
    # The XLA module, and so the device trace, carries the same name.
    assert "module @jit_derive_widths " in fn.lower(jnp.ones(3)).as_text()


if __name__ == "__main__":  # pragma: no cover
    sys.exit(pytest.main([__file__, "-q"]))
