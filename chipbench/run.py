#!/usr/bin/env python3
"""The chip benchmark: one cell of ``BENCHMARK.json``, one run.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

It serves ``POST /v1/resolve`` from ``service.Server(backend="tpu")`` in
this process, which alone holds the chip, and drives it from
``loadgen.py``, a child process that never imports JAX.  Set-up starts
the server, warms every shape the cell's traffic uses (seeds disjoint
from the window's) until no more compile, then the window runs for
``--seconds``.  ``--trace 1`` also profiles a few seconds in the middle
of the window and reports the cell's per-layer metrics; ``--trace 0``
reports its end-to-end metrics.  Afterwards a seeded sample of the
window's answers is compared with the plain reference (``reference.py``).

The last line of stdout is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device``, in a traced run ``breakdown``, and
last ``checks``, each number compared with its limit.  The same numbers
are the last lines of stderr.  Without a TPU, or with fewer chips than
the cell asks for, it prints no result and exits 2.

Everything that belongs to one configuration, traffic mix or metric is a
file found by its name: ``configs/<config>.json`` and ``.py``,
``traffic/<mix>.json``, ``metrics/<metric>.py`` (``read(run)`` returns
the number, or None where there is nothing to read).
"""

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import glob  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import threading  # noqa: E402
from dataclasses import dataclass, field  # noqa: E402
from typing import Optional  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path[:0] = [HERE, ROOT]

import trace_reduce  # noqa: E402
from traffic import Cell, load_json, load_module  # noqa: E402

WARM_QUIET_S = 5.0       # warm-up ends this long after the last compile
WARM_DISPATCHES = 4      # ... and this many scheduler dispatches after it
WARM_POLL_S = 0.5
WARM_MAX_S = 240.0
TRACE_S = 3.0            # the profiled part of a traced window
SAMPLE = 2048            # answers compared with the reference per run
ANSWERS_MIN = 256        # fewer compared answers is no comparison
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


class RunFailed(RuntimeError):
    """A run that must not print a result."""


def log(msg: str) -> None:
    print(f"[chipbench] {msg}", file=sys.stderr, flush=True)


def percentile(values, q: float) -> Optional[float]:
    """Linear interpolation between closest ranks."""
    s = sorted(values)
    if not s:
        return None
    k = (len(s) - 1) * q
    f = math.floor(k)
    c = min(f + 1, len(s) - 1)
    return s[f] + (s[c] - s[f]) * (k - f)


@dataclass
class RunData:
    """What a metric reader reads.  Times are seconds from the window's
    opening; ``requests`` holds ``[t_due, t_done, problems, ok]`` for
    every request sent in the window or answered in it; ``server`` and
    ``pipeline`` are the changes over the window of the server's and the
    pipeline's registries; ``spans`` maps a span name to
    ``[count, seconds]`` over the window and ``trace`` is the trace's
    reduction (traced runs)."""

    seconds: float
    setup_s: float
    requests: list
    server: dict
    pipeline: dict
    spans: dict = field(default_factory=dict)
    trace: Optional[trace_reduce.Reduction] = None

    def sent(self) -> list:
        """The requests sent in the window (the others were sent before
        it and answered in it)."""
        return [q for q in self.requests if q[0] >= 0]

    def latency_ms(self, q: float) -> Optional[float]:
        return percentile([1000.0 * (t_done - t_due)
                           for t_due, t_done, _, ok in self.sent() if ok], q)


def delta(before: dict, after: dict) -> dict:
    """Change of every family between two registry snapshots."""
    out = {}
    for name, v in after.items():
        b = before.get(name)
        if isinstance(v, dict) and "count" in v and "sum" in v:
            b = b or {"count": 0, "sum": 0.0}
            out[name] = {"count": v["count"] - b["count"],
                         "sum": v["sum"] - b["sum"]}
        elif isinstance(v, dict):
            b = b or {}
            out[name] = {k: x - b.get(k, 0) for k, x in v.items()}
        elif isinstance(v, (int, float)):
            out[name] = v - (b or 0)
    return out


class Child:
    """The load generator process and its line protocol."""

    def __init__(self, spec: dict):
        self.proc = subprocess.Popen(
            [sys.executable, "-u", os.path.join(HERE, "loadgen.py")],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True,
            cwd=ROOT)
        self.send(spec)
        self.expect("ready")

    def send(self, doc: dict) -> None:
        self.proc.stdin.write(json.dumps(doc) + "\n")
        self.proc.stdin.flush()

    def read(self) -> dict:
        line = self.proc.stdout.readline()
        if not line:
            raise RunFailed(f"load generator ended (code {self.proc.poll()})")
        return json.loads(line)

    def expect(self, event: str) -> dict:
        msg = self.read()
        if msg.get("event") != event:
            raise RunFailed(f"load generator said {msg}, expected {event}")
        return msg

    def close(self) -> None:
        if self.proc.poll() is None:
            try:
                self.send({"cmd": "quit"})
                self.proc.wait(timeout=30)
            except (OSError, subprocess.TimeoutExpired):
                self.proc.kill()
                self.proc.wait(timeout=30)


class Compiles:
    """Counts executables built or loaded (JAX's backend-compile event,
    persistent-cache hits included) and the program's jit traces, and
    sums the seconds of each of JAX's compile stages."""

    def __init__(self):
        import jax

        from deppy_tpu.analysis import compileguard

        self._guard = compileguard
        self.n = 0
        self.names: list = []
        self.stage_s: dict = {}
        self.events: dict = {}
        self._lock = threading.Lock()
        jax.monitoring.register_event_duration_secs_listener(self._on)
        jax.monitoring.register_event_listener(self._on_event)

    def _on(self, event: str, duration: float, **kw) -> None:
        with self._lock:
            self.stage_s[event] = self.stage_s.get(event, 0.0) + duration
            if event == BACKEND_COMPILE:
                self.n += 1
                self.names.append(str(kw.get("fun_name", "?")))

    def _on_event(self, event: str, **kw) -> None:
        with self._lock:
            self.events[event] = self.events.get(event, 0) + 1

    def summary(self) -> str:
        with self._lock:
            stages = ", ".join(f"{k.rsplit('/', 1)[-1]} {v:.3f}s"
                               for k, v in sorted(self.stage_s.items())
                               if "compil" in k or "jaxpr" in k)
            events = ", ".join(f"{k.rsplit('/', 1)[-1]} {v}"
                               for k, v in sorted(self.events.items())
                               if "compilation_cache" in k)
        return f"{stages}; {events}"

    def count(self) -> int:
        with self._lock:
            return self.n + self._guard.trace_count()


class SpanSum:
    """An event forwarder that sums span seconds by name until it is
    closed."""

    def __init__(self):
        self.spans: dict = {}
        self._open = True
        self._lock = threading.Lock()

    def __call__(self, event: dict) -> None:
        if event.get("kind") == "span":
            with self._lock:
                if not self._open:
                    return
                rec = self.spans.setdefault(event["name"], [0, 0.0])
                rec[0] += 1
                rec[1] += float(event.get("dur_s", 0.0))

    def close(self) -> dict:
        """Stop summing; ``{name: [count, seconds]}`` so far."""
        with self._lock:
            self._open = False
            return {k: list(v) for k, v in self.spans.items()}


def readers(bench: dict, kind: str, cell: str) -> list:
    """``(metric entry, reader module)`` for each metric of ``kind`` that
    this cell reports.  The reader of ``q.split`` is ``metrics/q.split.py``
    if there is one, else ``metrics/q.py``: one quantity, split by the
    end-to-end metric it moves, keeps one reader."""
    out = []
    for m in bench[kind]:
        if "workloads" in m and cell not in m["workloads"]:
            continue
        path = os.path.join(HERE, "metrics", m["name"] + ".py")
        if not os.path.exists(path):
            path = os.path.join(HERE, "metrics",
                                m["name"].split(".")[0] + ".py")
        out.append((m, load_module(path, "chipbench_metric_"
                                   + m["name"].replace(".", "_"))))
    return out


def dispatches(server) -> int:
    """Coalesced scheduler dispatches the server has made so far."""
    h = server.metrics.registry.snapshot().get(
        "deppy_sched_coalesced_batch_size") or {}
    return int(h.get("count", 0))


def warm_up(child: Child, server, compiles: Compiles) -> None:
    """Start the load and keep it on until every caller has started and
    ``WARM_QUIET_S`` seconds and ``WARM_DISPATCHES`` scheduler dispatches
    have passed since the last compile."""
    child.send({"cmd": "start", "port": server.api_port})
    child.expect("started")
    t_start = t_last = time.perf_counter()
    last, sent_at_last = compiles.count(), 0
    while True:
        time.sleep(WARM_POLL_S)
        child.send({"cmd": "progress"})
        p = child.expect("progress")
        if p["failed"]:
            raise RunFailed(f"{p['failed']} warm-up requests failed: "
                            f"{p['failed_statuses']}")
        now = time.perf_counter()
        if compiles.count() != last or not p["ramped"]:
            last, sent_at_last, t_last = (compiles.count(),
                                          dispatches(server), now)
        if (dispatches(server) - sent_at_last >= WARM_DISPATCHES
                and now - t_last >= WARM_QUIET_S):
            log(f"warm-up: {now - t_start:.3f}s, {p['answered']} requests, "
                f"{dispatches(server)} dispatches, {last} compiles and "
                "traces")
            return
        if now - t_start > WARM_MAX_S:
            log(f"warm-up: still compiling after {WARM_MAX_S}s")
            return


def traced_window(opened_at: float, seconds: float, trace_dir: str) -> None:
    """Profile ``TRACE_S`` seconds in the middle of the window under the
    window annotation, while the traffic goes on."""
    import jax

    time.sleep(max(opened_at + (seconds - TRACE_S) / 2 - time.perf_counter(),
                   0.0))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation(trace_reduce.WINDOW_ANNOTATION):
            time.sleep(TRACE_S)
    finally:
        jax.profiler.stop_trace()


def on_thread(fn):
    """Start ``fn()`` on a thread of its own; return a function that waits
    for it and returns what it returned, or raises what it raised."""
    out: list = []

    def body() -> None:
        try:
            out.append((True, fn()))
        except BaseException as e:  # raised again by the waiter
            out.append((False, e))

    thread = threading.Thread(target=body, daemon=True)
    thread.start()

    def wait():
        thread.join()
        ok, value = out[0]
        if not ok:
            raise value
        return value

    return wait


def reduce_trace(trace_dir: str) -> trace_reduce.Reduction:
    paths = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if len(paths) != 1:
        raise RunFailed(f"expected one trace file, found {len(paths)}")
    return trace_reduce.reduce(trace_reduce.load(paths[0]))


def device_info() -> dict:
    import jax

    devs = jax.devices()
    stats = [d.memory_stats() or {} for d in devs]
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs),
            "memory_peak_bytes": max(int(s.get("peak_bytes_in_use", 0))
                                     for s in stats)}


def run_cell(bench: dict, cell: Cell, seed: int, seconds: float, trace: bool,
             t0: float = T0, answers_min: int = ANSWERS_MIN) -> dict:
    """One run of one cell; returns the result object.  Raises
    :class:`RunFailed` where the run must not print one."""
    kind = "per_layer" if trace else "end_to_end"
    metric_readers = readers(bench, kind, cell.name)
    child = Child({"config_name": cell.config_name, "config": cell.config,
                   "mix": cell.mix, "seed": seed})
    server = None
    trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-") if trace else None
    try:
        from deppy_tpu import faults, service, telemetry
        from deppy_tpu.utils.platform_env import apply_platform_env

        apply_platform_env()
        compiles = Compiles()
        server = service.Server("127.0.0.1:0", "127.0.0.1:0", backend="tpu")
        server.start()
        warm_up(child, server, compiles)
        setup_s = time.perf_counter() - t0
        log(f"set-up {setup_s:.3f}s; JAX compile stages: {compiles.summary()}")

        pipeline_reg = telemetry.default_registry()
        server_reg = server.metrics.registry
        spans = SpanSum()
        if trace:
            pipeline_reg.add_forwarder(spans)
        before = (server_reg.snapshot(), pipeline_reg.snapshot())
        c0, names0 = compiles.count(), len(compiles.names)

        def close():
            # Spans, registry changes and compiles all stop at the close;
            # work that drains after it is not the window's.
            child.expect("closed")
            window_spans = spans.close()
            if trace:
                pipeline_reg.remove_forwarder(spans)
            return (window_spans,
                    (server_reg.snapshot(), pipeline_reg.snapshot()),
                    compiles.count() - c0, compiles.names[names0:])

        child.send({"cmd": "window", "seconds": seconds})
        child.expect("opened")
        opened_at = time.perf_counter()
        if trace:
            # Writing the trace can outlast the window by a minute or
            # more; the close is read meanwhile, on a thread of its own.
            closed = on_thread(close)
            traced_window(opened_at, seconds, trace_dir)
            window_spans, after, in_window, built = closed()
        else:
            window_spans, after, in_window, built = close()
        done = child.expect("done")
        log(f"window: {in_window} compiles and traces {built[:5]}; callers "
            f"waited {done['producer_wait_s']:.3f}s in all "
            f"(longest {done['producer_wait_max_s']:.3f}s) on the generator")
        device = device_info()
        counts = pipeline_reg.snapshot()
        log(f"faults: breaker {faults.default_breaker().state()}, "
            f"host-routed {counts.get('deppy_fault_host_routed_total', 0)}, "
            f"retries {counts.get('deppy_fault_retries', 0)}")
        server.shutdown(drain_s=10.0)
        server = None
        gc.collect()

        child.send({"cmd": "verify", "sample": SAMPLE})
        check = child.read()
        log(f"reference: {check['compared']} answers in "
            f"{check['reference_s']:.3f}s"
            + (f"; first difference: {check['first_difference']}"
               if check["first_difference"] else ""))

        red = None
        if trace:
            red = reduce_trace(trace_dir)
            device = dict(device, busy_s=red.busy_s, window_s=red.window_s)
        run = RunData(seconds=seconds, setup_s=setup_s,
                      requests=done["requests"],
                      server=delta(before[0], after[0]),
                      pipeline=delta(before[1], after[1]),
                      spans=window_spans, trace=red)
        lanes = run.server.get("deppy_sched_coalesced_batch_size") or {}
        if lanes.get("count"):
            log(f"scheduler: {lanes['count']} dispatches of "
                f"{lanes['sum'] / lanes['count']:.1f} lanes in the window")
        hits = run.server.get("deppy_cache_hits_total", 0)
        misses = run.server.get("deppy_cache_misses_total", 0)
        share = hits / (hits + misses) if hits + misses else 0.0
        if share > float(cell.mix.get("repeat_share", 0.0)):
            raise RunFailed(
                f"the result cache answered {hits} of {hits + misses} "
                f"lookups, above the mix's repeat_share: the window was "
                f"served from memory")
        metrics = {}
        for m, mod in metric_readers:
            v = mod.read(run)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        attempted = len(run.sent())
        failed = sum(1 for q in run.sent() if not q[3])
        checks = {
            "mismatched_answers": {"value": check["mismatched"], "limit": 0},
            "missing_answers": {"value": check["missing"], "limit": 0},
            "answers_compared": {"value": check["compared"],
                                 "min": answers_min},
        }
        correct = (check["mismatched"] <= 0 and check["missing"] <= 0
                   and check["compared"] >= answers_min)
        result = {"correct": correct, "attempted": attempted,
                  "failed": failed, "metrics": metrics, "device": device}
        if red is not None:
            result["breakdown"] = {"device_ops": red.top_programs(),
                                   "idle_gaps": [list(g)
                                                 for g in red.idle_gaps]}
        result["checks"] = checks
        return result
    finally:
        if server is not None:
            server.shutdown(drain_s=0.0)
        child.close()
        if "deppy_tpu.hostpool" in sys.modules:
            # The program's host-engine workers, should a fallback have
            # started them.
            sys.modules["deppy_tpu.hostpool"].shutdown_default_pool()
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bench = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell = Cell.load(bench, args.workload)
    # The persistent compile cache lives inside the checkout, at a fixed
    # path; the TPU runtime's own logs go nowhere.
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < cell.chips:
        print(f"chipbench: {args.workload} needs {cell.chips} TPU chip(s); "
              f"JAX found {len(devs)} {devs[0].platform} device(s)",
              file=sys.stderr)
        return 2
    try:
        result = run_cell(bench, cell, args.seed, args.seconds,
                          bool(args.trace))
    except (RunFailed, trace_reduce.TraceError) as e:
        print(f"chipbench: run failed: {e}", file=sys.stderr)
        return 1
    for key, c in result["checks"].items():
        bound = (f"limit {c['limit']}" if "limit" in c
                 else f"at least {c['min']}")
        print(f"check {key} {c['value']} {bound}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
