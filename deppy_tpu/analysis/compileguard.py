"""Runtime compile-guard (``DEPPY_TPU_COMPILE_GUARD=1``).

The static ``compile-surface`` checker (:mod:`.compile_surface`) sees
the *construction* discipline — memoized jit factories, declared
statics.  This is its runtime twin, mirroring lockdep: the engine's
jit/pjit entries are created through :func:`observe`, which wraps the
function **inside** the ``jax.jit`` boundary.  A wrapped function body
only executes when JAX actually (re)traces it, so every execution IS a
trace/compile event:

  * every trace is **counted** per ``(entry, abstract signature)`` —
    always, armed or not; the counter costs one dict update per trace
    and feeds the bench harness's ``n_compiles`` column and
    :func:`snapshot`;
  * armed, every trace additionally emits a ``compileguard`` event onto
    the telemetry sink — entry name, abstract signature, call site,
    trace wall time — stamped onto the active request trace when one is
    live (``deppy compiles`` summarizes these; ``deppy trace`` renders
    them in the span tree);
  * armed, tracing the same signature **past the entry's budget**
    raises :class:`CompileGuardError` (the event goes first, like
    lockdep's ``_violation``): a compile storm — a fresh jit cache per
    call, an undeclared static retracing per value — fails
    ``make test-compileguard`` in seconds instead of silently eating
    the tier-1 time budget (PR 6 paid exactly this by hand).

The *signature* is derived from the tracer avals (dtype, shape, weak
type) plus the entry's static configuration (the factory arguments the
wrap site passes as ``static=``).  A retrace with an identical
signature means a cache was lost — the one thing a healthy entry never
does.  Budgets default to ``DEPPY_TPU_COMPILE_BUDGET`` when set, else
``2 x local_device_count``: the per-device serving composition
legitimately traces each signature once per device (committed inputs
key jit's cache by placement), and committed-vs-uncommitted placement
of the same shapes can double that.  Deliberate cache drops
(``engine.clear_compile_caches`` / ``core.clear_batched_caches``) call
:func:`reset_counts` — the recompiles they cause are the point, not a
storm.

Disarmed (the default), :func:`observe` still wraps — the per-trace
counter is the bench ``n_compiles`` source — but emits nothing and
never raises.
"""

from __future__ import annotations

import functools
import threading
import time
from typing import Dict, Optional, Tuple

# Plain lock on purpose: the guard's own bookkeeping must not recurse
# into lockdep instrumentation mid-trace.
_LOCK = threading.Lock()
# (entry, signature) -> trace count; entry -> total traces.
_SIG_COUNTS: Dict[Tuple[str, str], int] = {}
_ENTRY_COUNTS: Dict[str, int] = {}
_TOTAL = 0
# entry -> declared per-signature budget (observe(budget=)/declare_budget).
_BUDGETS: Dict[str, int] = {}
_DEVICE_COUNT: Optional[int] = None


class CompileGuardError(AssertionError):
    """A jit entry retraced one signature past its compile budget."""


def guard_enabled() -> bool:
    """Read ``DEPPY_TPU_COMPILE_GUARD`` live (not cached): entries wrap
    unconditionally, so arming mid-process turns events/assertions on
    for every later trace."""
    from .. import config

    return config.env_bool("DEPPY_TPU_COMPILE_GUARD", False)


def default_budget() -> int:
    """Per-signature trace budget when the entry declares none:
    ``DEPPY_TPU_COMPILE_BUDGET`` if set, else 2 x local_device_count
    (per-device placement keys jit's cache — see module docstring)."""
    from .. import config

    declared = config.env_int("DEPPY_TPU_COMPILE_BUDGET", None,
                              strict=False)
    if declared is not None and declared > 0:
        return declared
    global _DEVICE_COUNT
    if _DEVICE_COUNT is None:
        try:
            import jax

            _DEVICE_COUNT = max(1, jax.local_device_count())
        except Exception:  # deppy: lint-ok[exception-hygiene] backendless probe: the guard must degrade to a host-only budget, not crash the trace
            _DEVICE_COUNT = 1
    return 2 * _DEVICE_COUNT


def declare_budget(entry: str, per_signature: int) -> None:
    """Declare ``entry``'s per-signature trace budget (also settable at
    the wrap site via ``observe(budget=)``)."""
    with _LOCK:
        _BUDGETS[entry] = int(per_signature)


def budget_for(entry: str) -> int:
    with _LOCK:
        declared = _BUDGETS.get(entry)
    return declared if declared is not None else default_budget()


def trace_count() -> int:
    """Total traces observed process-wide (the bench harness diffs this
    around its timed section for the ``n_compiles`` column)."""
    with _LOCK:
        return _TOTAL


def snapshot() -> Dict[str, dict]:
    """Per-entry counters: traces, distinct signatures, retraces
    (traces beyond the first per signature)."""
    with _LOCK:
        out: Dict[str, dict] = {}
        for entry, total in sorted(_ENTRY_COUNTS.items()):
            sigs = [n for (e, _), n in _SIG_COUNTS.items() if e == entry]
            out[entry] = {
                "traces": total,
                "signatures": len(sigs),
                "retraces": sum(n - 1 for n in sigs),
            }
        return out


def reset_counts() -> None:
    """Zero the trace ledger.  Called by the deliberate cache-drop
    paths (``engine.clear_compile_caches``): the recompiles that follow
    a requested drop are expected, and charging them to the budget
    would turn a memory-hygiene call into a false storm."""
    global _TOTAL
    with _LOCK:
        _SIG_COUNTS.clear()
        _ENTRY_COUNTS.clear()
        _TOTAL = 0


# ---------------------------------------------------------------- signature


def _leaf_sig(x) -> Optional[str]:
    shape = getattr(x, "shape", None)
    dtype = getattr(x, "dtype", None)
    if shape is None or dtype is None:
        return None
    weak = getattr(x, "weak_type", None)
    if weak is None:
        weak = getattr(getattr(x, "aval", None), "weak_type", False)
    dims = ",".join(str(d) for d in shape)
    return f"{dtype}[{dims}]" + ("~" if weak else "")


def _walk_sig(x, out) -> None:
    leaf = _leaf_sig(x)
    if leaf is not None:
        out.append(leaf)
        return
    if isinstance(x, (tuple, list)):
        for item in x:
            _walk_sig(item, out)
    elif isinstance(x, dict):
        for key in sorted(x):
            out.append(str(key))
            _walk_sig(x[key], out)
    elif isinstance(x, (int, float, bool, str, type(None))):
        out.append(repr(x))
    else:
        out.append(type(x).__name__)


def signature_of(args, kwargs, static=None) -> str:
    """Abstract signature of one trace: static config + per-leaf
    dtype/shape/weak-type.  Finer than jit's real cache key is safe
    (a genuine cache hit never reaches the wrapper at all); coarser
    would mint false retraces."""
    parts = []
    if static is not None:
        parts.append(f"static={static!r}")
    _walk_sig(tuple(args), parts)
    if kwargs:
        _walk_sig(dict(kwargs), parts)
    return ";".join(parts)


def _call_site() -> str:
    """First stack frame outside this module and outside JAX — the code
    that invoked the jit entry.  Only computed when armed (stack walks
    are not free)."""
    import traceback

    for frame in reversed(traceback.extract_stack()):
        fn = frame.filename.replace("\\", "/")
        if ("/analysis/compileguard" in fn or "/jax/" in fn
                or "/jax_" in fn):
            continue
        return f"{fn.rsplit('/deppy_tpu/', 1)[-1]}:{frame.lineno}"
    return "?"


# ------------------------------------------------------------------ observe


def _bump(entry: str, sig: str) -> int:
    global _TOTAL
    with _LOCK:
        _TOTAL += 1
        _ENTRY_COUNTS[entry] = _ENTRY_COUNTS.get(entry, 0) + 1
        n = _SIG_COUNTS[(entry, sig)] = _SIG_COUNTS.get((entry, sig),
                                                        0) + 1
        return n


def _event(**fields) -> None:
    """Emit one ``compileguard`` sink event, stamped onto the active
    request trace when one is live (the lockdep pattern: the record
    must reach the sink even if a recovery catch swallows the raise)."""
    try:
        from .. import telemetry

        telemetry.default_registry().event("compileguard", **fields)
    except Exception:  # deppy: lint-ok[exception-hygiene] mid-teardown telemetry must not break tracing; the assertion below still fires
        pass


def observe(entry: str, fn, *, static=None, budget: Optional[int] = None):
    """Wrap ``fn`` for placement INSIDE a ``jax.jit``/``pjit`` boundary
    (``jax.jit(observe("core.batched_solve", vfn))``): the wrapper body
    runs once per trace, so each execution records one trace/compile
    event for ``entry``.  ``static`` is the entry's static
    configuration (factory arguments) — it joins the abstract signature
    so two factory instances over the same shapes stay distinct.
    ``budget`` declares the per-signature trace budget (default: see
    :func:`default_budget`)."""
    if budget is not None:
        declare_budget(entry, budget)

    @functools.wraps(fn)
    def traced(*args, **kwargs):
        sig = signature_of(args, kwargs, static)
        n = _bump(entry, sig)
        armed = guard_enabled()
        if armed:
            allowed = budget_for(entry)
            if n > allowed:
                _event(violation="retrace-budget", entry=entry,
                       signature=sig, site=_call_site(), n_trace=n,
                       budget=allowed)
                raise CompileGuardError(
                    f"compileguard: entry `{entry}` traced signature "
                    f"{sig!r} {n} times (budget {allowed}) — a jit "
                    f"cache is being lost or rebuilt per call; see "
                    f"docs/analysis.md (compile-guard)")
            t0 = time.perf_counter()
            out = fn(*args, **kwargs)
            _event(entry=entry, signature=sig, site=_call_site(),
                   n_trace=n, dur_s=round(time.perf_counter() - t0, 6))
            return out
        return fn(*args, **kwargs)

    if isinstance(fn, functools.partial):
        # functools.wraps finds no name on a partial, and XLA would name
        # the program after this wrapper (``jit_traced``): take the
        # wrapped function's name instead.
        traced.__name__ = traced.__qualname__ = fn.func.__name__
    return traced
