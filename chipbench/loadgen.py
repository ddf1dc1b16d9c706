"""The load generator: a process of its own that never imports JAX.

``run.py`` starts it with one JSON line on stdin (``config``,
``config_name``, ``mix``, ``seed``), then sends commands as JSON lines and
reads the replies from stdout:

- ``{"cmd": "start", "port": p}``: start the mix's load on the warm-up
  stream, and keep it running;
- ``{"cmd": "progress"}``: requests answered so far, how many failed, and
  whether every caller has started;
- ``{"cmd": "window", "seconds": s}``: from now on the load draws on the
  window's stream.  It answers ``{"event": "opened"}``, then
  ``{"event": "closed"}`` after ``s`` seconds, when it stops sending, and
  ``{"event": "done", ...}`` once every request sent in the window is
  answered (or a minute past the close has gone by);
- ``{"cmd": "verify", "sample": n}``: compare a seeded sample of the
  window's answers with the plain reference;
- ``{"cmd": "quit"}``.

Warm-up first sends the mix's size-class span (``Traffic.span``), one
request at a time.  The load never pauses between warm-up and window, so
the window sees the system in its steady state.  The mix's closed-loop
callers start one after another, spaced by the latency of the first
caller's second request, so that they do not all send and finish
together.  Bodies are built from the seed ahead of need, into bounded
queues, by threads of their own; how long callers waited on them is
reported.
"""

from __future__ import annotations

import json
import os
import queue
import random
import sys
import threading
import time
from http.client import HTTPConnection, HTTPException

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import reference  # noqa: E402
from traffic import Traffic, encode  # noqa: E402

GRACE_S = 60.0
POST_TIMEOUT_S = 300.0
SPAN, WARM, WINDOW = "span", "warm", "window"


def reply(doc: dict) -> None:
    sys.stdout.write(json.dumps(doc) + "\n")
    sys.stdout.flush()


def post(port: int, body: bytes):
    conn = HTTPConnection("127.0.0.1", port, timeout=POST_TIMEOUT_S)
    try:
        conn.request("POST", "/v1/resolve", body,
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        return resp.status, resp.read()
    finally:
        conn.close()


class Producer:
    """Builds request bodies of one stream into a bounded queue."""

    def __init__(self, traffic: Traffic, stream: str, depth: int):
        self.stream = stream
        self.q: queue.Queue = queue.Queue(maxsize=max(depth, 1))
        self._traffic = traffic
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._fill, daemon=True)
        self._thread.start()

    def _fill(self) -> None:
        k = 0
        while not self._stop.is_set():
            item = (k, self._traffic.body(self.stream, k),
                    self._traffic.per_request)
            while not self._stop.is_set():
                try:
                    self.q.put(item, timeout=0.1)
                    break
                except queue.Full:
                    continue
            k += 1

    def stop(self) -> None:
        self._stop.set()
        self._thread.join(timeout=10)


class Record:
    __slots__ = ("stream", "k", "n", "t_due", "t_done", "status", "ok",
                 "raw")

    def __init__(self, stream, k, n, t_due):
        self.stream, self.k, self.n, self.t_due = stream, k, n, t_due
        self.t_done = None
        self.status = None
        self.ok = False
        self.raw = None


def send(port: int, rec: Record, body: bytes) -> None:
    """Send one request and fill in its record; ``t_done`` is set last,
    so a record that has it is complete."""
    try:
        status, raw = post(port, body)
    except (OSError, HTTPException) as e:
        rec.status = f"{type(e).__name__}: {e}"
        rec.t_done = time.perf_counter()
        return
    t_done = time.perf_counter()
    if status != 200:
        rec.status = f"{status}: {raw[:300]!r}"
    else:
        try:
            results = json.loads(raw)["results"]
            rec.ok = isinstance(results, list) and len(results) == rec.n
            rec.status = 200 if rec.ok else (
                f"200 with {len(results)} answers for {rec.n}")
        except (ValueError, KeyError, TypeError) as e:
            rec.status = f"200 unreadable ({e}): {raw[:300]!r}"
        if rec.stream == WINDOW:
            rec.raw = raw
    rec.t_done = t_done


class Load:
    """The mix's load against one server, warm-up stream first."""

    def __init__(self, traffic: Traffic, port: int):
        self.traffic, self.port = traffic, port
        self.clients = int(traffic.mix["clients"])
        depth = 2 * self.clients
        self.producers = {WARM: Producer(traffic, WARM, depth),
                          WINDOW: Producer(traffic, WINDOW, depth)}
        self.lock = threading.Lock()
        self.records: list = []
        self.waits: list = []  # seconds callers waited on a producer
        self.t_open = self.t_close = None
        self.threads: list = []
        threading.Thread(target=self._ramp, daemon=True).start()

    def _producer(self) -> Producer:
        with self.lock:
            opened = self.t_open is not None
        return self.producers[WINDOW if opened else WARM]

    def _closing(self, now: float) -> bool:
        with self.lock:
            return self.t_close is not None and now >= self.t_close

    def _next(self):
        """The next body to send, or None once the window has closed."""
        t0 = time.perf_counter()
        while True:
            prod = self._producer()
            try:
                k, body, n = prod.q.get(timeout=0.1)
                break
            except queue.Empty:
                if self._closing(time.perf_counter()):
                    return None
        now = time.perf_counter()
        with self.lock:
            self.waits.append(now - t0)
        if self._closing(now):
            return None
        rec = Record(prod.stream, k, n, now)
        with self.lock:
            self.records.append(rec)
        return rec, body

    def _client(self, first_done=None) -> None:
        while True:
            item = self._next()
            if item is None:
                return
            rec, body = item
            send(self.port, rec, body)
            if first_done is not None and len(first_done) < 2:
                first_done.append(rec)

    def _span(self) -> None:
        states = self.traffic.span()
        n = self.traffic.per_request
        for j in range(0, len(states), n):
            rec = Record(SPAN, j, len(states[j:j + n]), time.perf_counter())
            with self.lock:
                self.records.append(rec)
            send(self.port, rec, encode(states[j:j + n]))

    def _ramp(self) -> None:
        """Send the span, then start the closed-loop callers one after
        another, spaced by the latency of the first caller's second
        request."""
        self._span()
        seen: list = []
        t = threading.Thread(target=self._client, args=(seen,), daemon=True)
        t.start()
        self.threads.append(t)
        while len(seen) < 2 and t.is_alive():
            time.sleep(0.01)
        gap = (seen[1].t_done - seen[1].t_due) if len(seen) >= 2 else 0.0
        for _ in range(self.clients - 1):
            time.sleep(gap)
            if self._closing(time.perf_counter()):
                return
            t = threading.Thread(target=self._client, daemon=True)
            t.start()
            self.threads.append(t)

    def progress(self) -> dict:
        with self.lock:
            done = [r for r in self.records if r.t_done is not None]
        bad = [r for r in done if not r.ok]
        ramped = len(self.threads) >= self.clients
        return {"event": "progress", "answered": len(done),
                "failed": len(bad), "ramped": ramped,
                "failed_statuses": [str(r.status) for r in bad[:3]]}

    def window(self, seconds: float) -> dict:
        now = time.perf_counter()
        with self.lock:
            self.t_open, self.t_close = now, now + seconds
        reply({"event": "opened"})
        time.sleep(max(self.t_close - time.perf_counter(), 0.0))
        reply({"event": "closed"})
        for t in list(self.threads):
            t.join(timeout=max(self.t_close + GRACE_S - time.perf_counter(),
                               0.1))
        for p in self.producers.values():
            p.stop()
        t_open = self.t_open
        with self.lock:
            kept = [r for r in self.records if r.stream == WINDOW
                    or (r.t_done is not None and r.t_done >= t_open)]
            waits = list(self.waits)
        reqs = [[r.t_due - t_open,
                 None if r.t_done is None else r.t_done - t_open,
                 r.n, r.ok] for r in kept]
        bad = [r.status for r in kept if not r.ok][:3]
        return {"event": "done", "requests": reqs,
                "failed_statuses": [str(s) for s in bad],
                "producer_wait_s": sum(waits),
                "producer_wait_max_s": max(waits, default=0.0)}

    def verify(self, sample: int) -> dict:
        """A seeded sample of the window's answers against the reference.
        Every answer of a window request that failed or never came is
        missing."""
        with self.lock:
            window = [r for r in self.records if r.stream == WINDOW]
        missing = sum(r.n for r in window if not r.ok)
        pairs = [(r, i) for r in window if r.ok for i in range(r.n)]
        rng = random.Random(f"{self.traffic.seed}/sample")
        picked = sorted(rng.sample(range(len(pairs)),
                                   min(sample, len(pairs))))
        got, problems = [], []
        parsed: dict = {}
        for j in picked:
            rec, i = pairs[j]
            if rec.k not in parsed:
                parsed[rec.k] = (json.loads(rec.raw)["results"],
                                 self.traffic.states(WINDOW, rec.k))
            results, states = parsed[rec.k]
            got.append(results[i])
            problems.append(states[i])
        t0 = time.perf_counter()
        out = reference.compare(got, problems)
        out["missing"] += missing
        out["reference_s"] = time.perf_counter() - t0
        return out


def main() -> int:
    spec = json.loads(sys.stdin.readline())
    traffic = Traffic(spec["config_name"], spec["config"], spec["mix"],
                      spec["seed"])
    load = None
    reply({"event": "ready"})
    for line in sys.stdin:
        cmd = json.loads(line)
        op = cmd["cmd"]
        if op == "start":
            load = Load(traffic, int(cmd["port"]))
            reply({"event": "started"})
        elif op == "progress":
            reply(load.progress())
        elif op == "window":
            reply(load.window(float(cmd["seconds"])))
        elif op == "verify":
            reply(load.verify(int(cmd["sample"])))
        elif op == "quit":
            break
    return 0


if __name__ == "__main__":
    sys.exit(main())
