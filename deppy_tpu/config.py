"""Typed registry of every ``DEPPY_TPU_*`` environment knob (ISSUE 7).

The env surface grew one knob at a time across six subsystems — 100+
read sites over 20+ files — with the docs chasing the code by hand.
This module is the single declaration point: every knob's name, type,
default, consuming module, and help text live HERE, and three things
hang off the declaration:

  * **Typed reads.**  :func:`env_raw` (and the typed wrappers
    :func:`env_str` / :func:`env_int` / :func:`env_float` /
    :func:`env_bool`) resolve the environment *through* the registry —
    reading an undeclared ``DEPPY_TPU_*`` name raises
    :class:`UndeclaredEnvVar` at the call site instead of silently
    minting a knob nobody documented.  The fault layer's defensive
    parsers (``faults.env_float``, the subsystems' ``_env_int``) call
    :func:`require` first, so every legacy read site resolves through
    the registry without changing its parse-or-degrade semantics.
  * **Generated docs.**  :func:`render_markdown` emits the
    docs/configuration.md table (``python -m deppy_tpu.config``);
    tests/test_doc_sync.py pins the checked-in file against it both
    ways, the same way the observability metric tables are pinned.
  * **Lint.**  The ``registry-sync`` checker (``deppy lint``,
    :mod:`deppy_tpu.analysis.registry_sync`) scans the whole tree for
    ``DEPPY_TPU_*`` tokens and fails on any name missing from this
    registry — and on any declared name no code mentions.

Import-light on purpose (stdlib ``os``/``dataclasses`` only): every
subsystem — including :mod:`deppy_tpu.faults.policy` at the bottom of
the import order — can import it without cycles.
"""

from __future__ import annotations

import os
from dataclasses import dataclass
from typing import List, Optional

_PREFIX = "DEPPY_TPU_"


class UndeclaredEnvVar(KeyError):
    """A ``DEPPY_TPU_*`` read of a name missing from :data:`REGISTRY`."""


@dataclass(frozen=True)
class EnvVar:
    """One declared environment knob.  ``flag`` / ``config_key`` declare
    the knob's CLI-flag and ResolverConfig-file mirrors (ISSUE 8): the
    ``registry-sync`` checker pins them against ``deppy_tpu/cli.py``
    both ways, so a flag added without its env mirror (or a mirror
    declared here without its flag) is a lint finding."""

    name: str
    type: str       # "int" | "float" | "str" | "bool" | "path"
    default: object  # documented default; None = unset/off
    consumer: str   # primary reading module (dotted path)
    help: str
    flag: Optional[str] = None        # mirrored CLI flag (--foo-bar)
    config_key: Optional[str] = None  # mirrored ResolverConfig file key


def _v(name: str, type: str, default, consumer: str, help: str,
       flag: Optional[str] = None,
       config_key: Optional[str] = None) -> EnvVar:
    return EnvVar(name=name, type=type, default=default,
                  consumer=consumer, help=help, flag=flag,
                  config_key=config_key)


# Declaration order groups by subsystem; rendering sorts by name so the
# doc table is stable under insertion.
_DECLARATIONS: List[EnvVar] = [
    # --- telemetry -------------------------------------------------------
    _v("DEPPY_TPU_TELEMETRY_FILE", "path", None, "deppy_tpu.telemetry.registry",
       "JSONL event sink for spans/reports/fault events (also "
       "--telemetry-file); summarize with `deppy stats`.",
       flag="--telemetry-file"),
    _v("DEPPY_TPU_TRACE_RING", "int", 64, "deppy_tpu.telemetry.trace",
       "Flight-recorder capacity: recent completed request traces."),
    _v("DEPPY_TPU_TRACE_ERROR_RING", "int", 256, "deppy_tpu.telemetry.trace",
       "Flight-recorder error ring: errored traces retained separately "
       "so healthy bursts cannot evict incident context."),
    # --- profiler / SLO --------------------------------------------------
    _v("DEPPY_TPU_PROFILE", "str", "off", "deppy_tpu.profile.ledger",
       "Engine cost profiler: 'on' records the per-dispatch trip "
       "ledger (`profile` sink events, deppy_profile_* families, "
       "SolveReport ledger columns; also --profile).  Disarmed is "
       "byte-identical to the pre-profiler pipeline.",
       flag="--profile", config_key="profile"),
    _v("DEPPY_TPU_PROFILE_SAMPLE", "float", 1.0,
       "deppy_tpu.profile.ledger",
       "Fraction of dispatches the armed profiler samples, in (0, 1] "
       "(deterministic 1-in-N; also --profile-sample) — bounds the "
       "armed overhead.",
       flag="--profile-sample", config_key="profileSample"),
    _v("DEPPY_TPU_SLO", "str", None, "deppy_tpu.profile.slo",
       "Declarative per-tenant SLO config: inline JSON, @FILE, or a "
       "path mapping tenant -> {target_p99_s, error_budget} (also "
       "--slo); burn rates render on /metrics and /debug/slo.",
       flag="--slo", config_key="slo"),
    # --- faults ----------------------------------------------------------
    _v("DEPPY_TPU_FAULT_PLAN", "str", None, "deppy_tpu.faults.inject",
       "Fault-injection plan: inline JSON, @FILE, or a file path (also "
       "--fault-plan); see docs/robustness.md.",
       flag="--fault-plan"),
    _v("DEPPY_TPU_FAULT_RETRIES", "int", 2, "deppy_tpu.faults.policy",
       "Total attempts per device dispatch group (2 = one retry)."),
    _v("DEPPY_TPU_FAULT_BACKOFF_S", "float", 0.05, "deppy_tpu.faults.policy",
       "Base exponential-backoff sleep between dispatch retries."),
    _v("DEPPY_TPU_FAULT_BACKOFF_MAX_S", "float", 2.0,
       "deppy_tpu.faults.policy",
       "Backoff clamp: no retry sleeps longer than this."),
    _v("DEPPY_TPU_CHUNK_DEADLINE_S", "float", 0.0, "deppy_tpu.faults.policy",
       "Wall-clock bound on ONE dispatch attempt; exceeding it counts "
       "deppy_deadline_exceeded and charges the breaker (0 = off)."),
    _v("DEPPY_TPU_BATCH_DEADLINE_S", "float", None, "deppy_tpu.faults.policy",
       "Ambient wall-clock budget for a whole resolve batch (also "
       "--deadline / X-Deppy-Deadline-S); expiry degrades undispatched "
       "lanes to Incomplete.",
       flag="--deadline"),
    _v("DEPPY_TPU_BREAKER_THRESHOLD", "int", 3, "deppy_tpu.faults.breaker",
       "Consecutive device failures that trip the accelerator circuit "
       "breaker open (host-only serving)."),
    _v("DEPPY_TPU_BREAKER_RESET_S", "float", 30.0, "deppy_tpu.faults.breaker",
       "Breaker cooldown before one half-open probe dispatch."),
    # --- scheduler / cache ----------------------------------------------
    _v("DEPPY_TPU_SCHED", "str", "on", "deppy_tpu.service",
       "Cross-request continuous-batching scheduler ('off' restores "
       "byte-identical per-request dispatch; also --sched).",
       flag="--sched", config_key="sched"),
    _v("DEPPY_TPU_SCHED_MAX_WAIT_MS", "float", 5.0,
       "deppy_tpu.sched.scheduler",
       "Flush policy: max milliseconds the oldest queued problem waits "
       "for batchmates (also --sched-max-wait-ms).",
       flag="--sched-max-wait-ms", config_key="schedMaxWaitMs"),
    _v("DEPPY_TPU_SCHED_MAX_FILL", "int", 256, "deppy_tpu.sched.scheduler",
       "Flush policy: dispatch once a size class has this many lanes "
       "queued (also --sched-max-fill).",
       flag="--sched-max-fill", config_key="schedMaxFill"),
    _v("DEPPY_TPU_SCHED_MAX_DEPTH", "int", 4096, "deppy_tpu.sched.scheduler",
       "Queue depth past which admission returns 503 + Retry-After "
       "(0 = unbounded)."),
    _v("DEPPY_TPU_SCHED_LANES_PER_DEVICE", "int", 256,
       "deppy_tpu.sched.scheduler",
       "Mesh serving: a full flush targets n_devices x this many lanes "
       "so every device gets a full shard."),
    _v("DEPPY_TPU_CACHE_SIZE", "int", 1024, "deppy_tpu.sched.scheduler",
       "Canonical-form result-cache capacity in entries (0 disables; "
       "also --cache-size).",
       flag="--cache-size", config_key="cacheSize"),
    # --- portfolio racing -------------------------------------------------
    _v("DEPPY_TPU_PORTFOLIO", "str", "auto", "deppy_tpu.sched.scheduler",
       "Portfolio engine racing: 'on' races the top-K candidate "
       "backends per cold flush and serves the first definitive "
       "finisher; 'auto' races only size classes holding a measured "
       "`portfolio` row; 'off' restores the single-backend dispatch "
       "path byte for byte (also --portfolio).",
       flag="--portfolio", config_key="portfolio"),
    _v("DEPPY_TPU_PORTFOLIO_K", "int", 2, "deppy_tpu.sched.scheduler",
       "Top-K candidate backends raced per coalesced flush (min 2)."),
    _v("DEPPY_TPU_PORTFOLIO_SAMPLE_CHECK", "float", 0.0625,
       "deppy_tpu.sched.scheduler",
       "Deterministic 1-in-N fraction of non-canonical race wins "
       "cross-checked against the canonical backend's answer "
       "(mismatches serve canonical and raise a race_mismatch fault "
       "event; 0 disables)."),
    # --- speculative pre-resolution --------------------------------------
    _v("DEPPY_TPU_SPECULATE", "str", "on", "deppy_tpu.sched.scheduler",
       "Speculative pre-resolution: catalog publishes (POST "
       "/v1/catalog/publish, `deppy publish`) invalidate retracted "
       "cache entries and pre-solve affected cached families at idle "
       "priority, and POST /v1/resolve/preview serves read-only "
       "what-if resolutions ('off' restores pre-change dispatch byte "
       "for byte and 404s both endpoints; also --speculate).",
       flag="--speculate", config_key="speculate"),
    _v("DEPPY_TPU_SPECULATE_MAX_BACKLOG", "int", 2048,
       "deppy_tpu.sched.scheduler",
       "Speculative pre-solve backlog cap in lanes; pre-solves past it "
       "are dropped and counted (a drop costs a later cold solve, "
       "never an answer; also --speculate-max-backlog).",
       flag="--speculate-max-backlog", config_key="speculateMaxBacklog"),
    # --- incremental tier ------------------------------------------------
    _v("DEPPY_TPU_INCREMENTAL", "str", "on", "deppy_tpu.sched.scheduler",
       "Delta-aware incremental resolution: clause-set index + "
       "warm-start lane class in front of the exact result cache "
       "('off' restores pre-tier dispatch byte for byte; also "
       "--incremental).",
       flag="--incremental", config_key="incremental"),
    _v("DEPPY_TPU_INCREMENTAL_MAX_DELTA", "float", 0.25,
       "deppy_tpu.sched.scheduler",
       "Warm-start cutoff: deltas whose touched cone covers more than "
       "this fraction of the problem's variables cold-solve instead "
       "(also --incremental-max-delta).",
       flag="--incremental-max-delta", config_key="incrementalMaxDelta"),
    _v("DEPPY_TPU_INCREMENTAL_INDEX_SIZE", "int", 512,
       "deppy_tpu.sched.scheduler",
       "Clause-set index capacity in solved-problem entries (0 "
       "disables the tier; also --incremental-index-size).",
       flag="--incremental-index-size",
       config_key="incrementalIndexSize"),
    # --- optimization tier (ISSUE 18) ------------------------------------
    _v("DEPPY_TPU_OPT", "str", "on", "deppy_tpu.service",
       "Optimization tier: POST /v1/optimize (`deppy optimize` / "
       "`deppy explain`) serves minimal-change upgrade planning, "
       "weighted soft constraints, and explain-why-not blocking sets "
       "via the bound-tightening loop ('off' 404s the endpoint and "
       "restores pre-tier /v1/resolve byte for byte; also --opt).",
       flag="--opt", config_key="opt"),
    _v("DEPPY_TPU_OPT_MAX_ITERATIONS", "int", 64,
       "deppy_tpu.optimize.loop",
       "Bound-tightening iteration cap per optimize request; hitting "
       "it returns the best model found so far flagged non-optimal "
       "(also --opt-max-iterations).",
       flag="--opt-max-iterations", config_key="optMaxIterations"),
    _v("DEPPY_TPU_OPT_ITER_BUDGET", "int", 1048576,
       "deppy_tpu.optimize.loop",
       "Engine-step budget per tightening probe; an exhausted probe "
       "degrades the request to best-so-far instead of stalling a "
       "speculative-class lane (also --opt-iter-budget).",
       flag="--opt-iter-budget", config_key="optIterBudget"),
    _v("DEPPY_TPU_OPT_MAX_WEIGHT", "int", 64, "deppy_tpu.optimize.loop",
       "Largest accepted soft-constraint weight; heavier requests are "
       "rejected as malformed (a weight cap keeps objective values — "
       "and the tightening distance — bounded; also --opt-max-weight).",
       flag="--opt-max-weight", config_key="optMaxWeight"),
    # --- stateful sessions (ISSUE 20) ------------------------------------
    _v("DEPPY_TPU_SESSIONS", "str", "on", "deppy_tpu.service",
       "Stateful resolution sessions: POST /v1/session + "
       "/v1/session/{id}/op serve interactive assume/test/untest "
       "exploration against a retained catalog epoch ('off' constructs "
       "none of it — the endpoints 404 byte-identically, no session "
       "metric family registers, /v1/resolve is untouched; also "
       "--sessions).",
       flag="--sessions", config_key="sessions"),
    _v("DEPPY_TPU_SESSION_LEASE_S", "float", 300.0, "deppy_tpu.sessions",
       "Session lease in seconds: every op renews it; the sweeper "
       "expires sessions whose lease lapsed (also --session-lease-s).",
       flag="--session-lease-s", config_key="sessionLeaseS"),
    _v("DEPPY_TPU_SESSION_MAX", "int", 256, "deppy_tpu.sessions",
       "Hard cap on live sessions per replica; at the cap, expired "
       "sessions are LRU-evicted first and creation sheds 503 with a "
       "counted shed once none remain (also --session-max).",
       flag="--session-max", config_key="sessionMax"),
    _v("DEPPY_TPU_SESSION_MAX_PER_TENANT", "int", 64, "deppy_tpu.sessions",
       "Per-tenant session cap: unauthenticated session creation must "
       "not become a memory DoS, so a tenant at its cap sheds 503 even "
       "with global headroom (also --session-max-per-tenant).",
       flag="--session-max-per-tenant", config_key="sessionMaxPerTenant"),
    # --- fleet (ISSUE 15) ------------------------------------------------
    _v("DEPPY_TPU_FLEET_REPLICAS", "str", None, "deppy_tpu.fleet.router",
       "Replica addresses the affinity router fronts, comma-separated "
       "host:port (also --replicas on `deppy route`).",
       flag="--replicas"),
    _v("DEPPY_TPU_FLEET_VNODES", "int", 64, "deppy_tpu.fleet.router",
       "Virtual nodes per replica on the consistent-hash ring (also "
       "--vnodes); more vnodes = smoother arc split on membership "
       "churn.",
       flag="--vnodes"),
    _v("DEPPY_TPU_FLEET_PROBE_INTERVAL_S", "float", 2.0,
       "deppy_tpu.fleet.router",
       "Seconds between router health probes per replica (also "
       "--probe-interval; 0 disables probing — forwards still charge "
       "the breaker).",
       flag="--probe-interval"),
    _v("DEPPY_TPU_FLEET_PROBE_FAILURES", "int", 3,
       "deppy_tpu.fleet.router",
       "Consecutive transport failures (probe or live forward) that "
       "mark a replica dead and reassign its ring arcs (also "
       "--probe-failures); a later successful probe revives it.",
       flag="--probe-failures"),
    _v("DEPPY_TPU_REPLICA", "str", None, "deppy_tpu.service",
       "This replica's serving identity in a fleet (also --replica): "
       "labels the per-tenant SLO families, /debug/slo, and the "
       "service.request span so burn rate is attributable per tenant "
       "per replica; unset keeps single-process surfaces unchanged.",
       flag="--replica", config_key="replica"),
    # --- elastic membership (ISSUE 17) -----------------------------------
    _v("DEPPY_TPU_FLEET", "str", "elastic", "deppy_tpu.fleet.membership",
       "Fleet membership mode (also --membership on `deppy route`): "
       "'elastic' arms runtime joins (POST /fleet/join — chunked "
       "warm-state streaming, then an atomic arc flip), drain-as-leave "
       "ring removal with a membership epoch, peer gossip (POST "
       "/fleet/sync), and GET /fleet/policy; 'static' restores the "
       "PR 15 immutable-ring surface byte for byte.",
       flag="--membership"),
    _v("DEPPY_TPU_FLEET_PEERS", "str", None, "deppy_tpu.fleet.router",
       "Peer router addresses for membership gossip, comma-separated "
       "host:port (also --peers on `deppy route`): routers exchange "
       "epoch-versioned ring views so clients can hit any of them and "
       "a dead router is not an outage.",
       flag="--peers"),
    _v("DEPPY_TPU_FLEET_SYNC_INTERVAL_S", "float", 2.0,
       "deppy_tpu.fleet.router",
       "Seconds between membership gossip rounds with the peer list "
       "(jittered like the probe loop; 0 disables the background loop "
       "— inbound POST /fleet/sync still reconciles).",),
    _v("DEPPY_TPU_FLEET_PROBE_JITTER", "float", 0.2,
       "deppy_tpu.fleet.router",
       "Random fraction of the probe (and gossip) interval added to "
       "each cycle's sleep, clamped to [0, 1] — the lease renew_jitter "
       "pattern, so a large fleet's probes do not thunder in lockstep."),
    _v("DEPPY_TPU_FLEET_JOIN_CHUNK", "int", 64,
       "deppy_tpu.fleet.membership",
       "Warm-state entries per checksummed join-stream chunk: a "
       "joining replica's inherited index entries and cache seeds "
       "stream in bounded, individually sealed chunks so a truncated "
       "transfer is rejected loudly and resumes per chunk."),
    _v("DEPPY_TPU_FLEET_JOIN_RETRIES", "int", 2,
       "deppy_tpu.fleet.membership",
       "Resend attempts per failed join-stream chunk before the join "
       "aborts (membership unchanged — the arc flip only happens once "
       "the whole stream lands)."),
    _v("DEPPY_TPU_FLEET_ROUTER", "str", None, "deppy_tpu.service",
       "Fleet router address this replica announces itself to (also "
       "--fleet-router): POST /fleet/join once serving starts, and the "
       "drain handoff (leave) on graceful shutdown; unset keeps the "
       "standalone lifecycle byte for byte.",
       flag="--fleet-router", config_key="fleetRouter"),
    _v("DEPPY_TPU_FLEET_ADVERTISE", "str", None, "deppy_tpu.service",
       "host:port this replica advertises when joining a fleet (also "
       "--fleet-advertise); defaults to 127.0.0.1:<api-port>, which "
       "only holds for single-host fleets.",
       flag="--fleet-advertise", config_key="fleetAdvertise"),
    _v("DEPPY_TPU_FLEET_BURN_UP", "float", 1.0, "deppy_tpu.fleet.policy",
       "Per-tenant SLO burn-rate threshold above which the autoscale "
       "policy recommends scale_up (no cold capacity) or rebalance "
       "(cold capacity exists) on GET /fleet/policy."),
    _v("DEPPY_TPU_FLEET_BURN_DOWN", "float", 0.25,
       "deppy_tpu.fleet.policy",
       "Per-tenant SLO burn-rate floor: every replica under it with an "
       "idle queue recommends scale_down; execution stays "
       "operator-driven (`deppy fleet scale --apply` is the "
       "local-process mode for the bench/soak harness)."),
    # --- scheduler fairness (ISSUE 15) -----------------------------------
    _v("DEPPY_TPU_SCHED_FAIR", "str", "on", "deppy_tpu.sched.scheduler",
       "Weighted-fair per-tenant admission + priority lanes: 'on' "
       "sheds each tenant at its weighted share of the queue and "
       "orders flush heads by tenant priority class; 'off' restores "
       "the global-depth 503 and strict FIFO byte for byte (also "
       "--sched-fair).",
       flag="--sched-fair", config_key="schedFair"),
    _v("DEPPY_TPU_SCHED_TENANT_WEIGHTS", "str", None,
       "deppy_tpu.sched.scheduler",
       "Declarative tenant weights/priorities for the fair gate: "
       "inline JSON, @FILE, or a path mapping tenant -> weight number "
       "or {weight, priority} ('default' covers unlisted tenants; "
       "also --sched-tenant-weights).",
       flag="--sched-tenant-weights", config_key="schedTenantWeights"),
    # --- observability plane (ISSUE 16) ----------------------------------
    _v("DEPPY_TPU_OBS_STREAM", "str", None, "deppy_tpu.obs.stream",
       "Fleet telemetry streaming: aggregator address (host:port, "
       "normally the router) this replica batch-pushes its sink events "
       "to via POST /fleet/telemetry (also --obs-stream); unset keeps "
       "the local-sink-only pipeline byte for byte.",
       flag="--obs-stream", config_key="obsStream"),
    _v("DEPPY_TPU_OBS_FLUSH_MS", "float", 200.0, "deppy_tpu.obs.stream",
       "Max milliseconds a queued telemetry event waits before the "
       "streamer flushes a batch to the aggregator (also "
       "--obs-flush-ms).",
       flag="--obs-flush-ms", config_key="obsFlushMs"),
    _v("DEPPY_TPU_OBS_QUEUE", "int", 4096, "deppy_tpu.obs.stream",
       "Streamer queue capacity in events; a slow aggregator fills it "
       "and further events are DROPPED and counted "
       "(deppy_obs_stream_dropped_total) instead of stalling serving."),
    _v("DEPPY_TPU_OBS_BATCH", "int", 256, "deppy_tpu.obs.stream",
       "Max events per streamed POST /fleet/telemetry batch."),
    _v("DEPPY_TPU_OBS_BACKOFF_MAX_S", "float", 5.0,
       "deppy_tpu.obs.stream",
       "Ceiling in seconds on the streamer's bounded exponential "
       "hold-off after a failed telemetry POST (resumed streaks are "
       "counted on deppy_obs_stream_reconnects_total); the final "
       "close() flush bypasses the hold-off."),
    _v("DEPPY_TPU_OBS_SINK", "path", None, "deppy_tpu.obs.aggregate",
       "Router-side merged fleet sink: JSONL path the telemetry "
       "aggregator appends replica-stamped events to (also --obs-sink "
       "on `deppy route`); unset 404s POST /fleet/telemetry.",
       flag="--obs-sink"),
    _v("DEPPY_TPU_OBS_BASELINE", "path", None, "deppy_tpu.obs.drift",
       "Cost-model baseline artifact for the drift watchdog: a "
       "BENCH_rNN.json (or any JSON with a `costmodel` section) whose "
       "per-size-class µs/trip the live regression is compared "
       "against (also --obs-baseline); unset disarms the watchdog "
       "byte for byte.",
       flag="--obs-baseline", config_key="obsBaseline"),
    _v("DEPPY_TPU_OBS_DRIFT_BAND", "float", 0.5, "deppy_tpu.obs.drift",
       "Relative drift band for the cost-model watchdog: a live "
       "per-size-class µs/trip fit farther than this fraction from "
       "the baseline emits a costmodel_drift event and pushes "
       "deppy_costmodel_drift_ratio past the band."),
    _v("DEPPY_TPU_OBS_DRIFT_MIN", "int", 8, "deppy_tpu.obs.drift",
       "Minimum sampled device dispatches per size class before the "
       "drift watchdog trusts its regression enough to compare."),
    # --- route health ----------------------------------------------------
    _v("DEPPY_TPU_ROUTE_LEARN", "str", "off", "deppy_tpu.routes",
       "Route-health plane: 'off' (default) arms nothing — no regret "
       "ledger, no route_* metric families, responses byte-identical; "
       "'observe' runs the regret ledger, staleness watcher, and "
       "shadow probing; 'on' adds the online route registry that "
       "adopts learned portfolio rows onto the in-memory overlay "
       "(also --route-learn).  Audit with `deppy routes`.",
       flag="--route-learn", config_key="routeLearn"),
    _v("DEPPY_TPU_ROUTE_SHADOW_RATE", "float", 0.0625,
       "deppy_tpu.routes.shadow",
       "Fraction of a STALE-flagged class's flushes duplicated to one "
       "non-serving backend at idle priority (deterministic 1-in-N "
       "per class; 0 disables probing; also --route-shadow-rate).",
       flag="--route-shadow-rate", config_key="routeShadowRate"),
    _v("DEPPY_TPU_ROUTE_MAX_AGE_S", "float", 604800.0,
       "deppy_tpu.routes.staleness",
       "Measured-defaults provenance age past which a live-observed "
       "class's routing row is flagged stale (default 7 days)."),
    _v("DEPPY_TPU_ROUTE_MIN_SAMPLES", "int", 8, "deppy_tpu.routes.learn",
       "Uncensored live observations per (class, backend) before the "
       "online route registry trusts its decayed estimate enough to "
       "re-rank."),
    _v("DEPPY_TPU_ROUTE_DECAY", "float", 0.2, "deppy_tpu.routes.ledger",
       "EWMA weight of the newest observation in the regret ledger's "
       "per-(class, backend) wall estimates, in (0, 1]."),
    _v("DEPPY_TPU_ROUTE_REGISTRY", "path", None, "deppy_tpu.routes.learn",
       "Optional path where live-learned routing rows persist through "
       "the shared flock-guarded defaults store (also "
       "--route-registry); unset keeps adoptions in-memory only.",
       flag="--route-registry", config_key="routeRegistry"),
    # --- service ---------------------------------------------------------
    _v("DEPPY_TPU_REQUEST_DEADLINE_S", "float", None, "deppy_tpu.service",
       "Default wall-clock budget per /v1/resolve request (clients "
       "override via X-Deppy-Deadline-S; also --request-deadline).",
       flag="--request-deadline", config_key="requestDeadlineSeconds"),
    _v("DEPPY_TPU_DRAIN_S", "float", None, "deppy_tpu.service",
       "Graceful-shutdown bound on draining in-flight requests "
       "(default: the request deadline, else 10s)."),
    _v("DEPPY_TPU_REPROBE", "float", 600.0, "deppy_tpu.service",
       "Seconds between background accelerator re-probes while serving "
       "degraded (0 disables)."),
    # --- hostpool --------------------------------------------------------
    _v("DEPPY_TPU_HOST_WORKERS", "int", None, "deppy_tpu.hostpool.pool",
       "Host-engine worker pool size (default min(cpu_count, 8); 0 = "
       "inline serial engine; also --host-workers).",
       flag="--host-workers", config_key="hostWorkers"),
    _v("DEPPY_TPU_HOST_WORKER_RECYCLE", "int", 256,
       "deppy_tpu.hostpool.pool",
       "Solves per worker before it is retired and replaced (leak "
       "hygiene; 0 = never)."),
    _v("DEPPY_TPU_HOSTPOOL_SPAWN_TIMEOUT_S", "float", 30.0,
       "deppy_tpu.hostpool.pool",
       "Bound on a spawned worker's ready handshake; a sandbox that "
       "allows fork but hangs it must not hang the solve path."),
    _v("DEPPY_TPU_HOSTPOOL_START_METHOD", "str", "forkserver",
       "deppy_tpu.hostpool.pool",
       "multiprocessing start method for pool workers."),
    # --- mesh serving ----------------------------------------------------
    _v("DEPPY_TPU_MESH_DEVICES", "int", None, "deppy_tpu.parallel.mesh",
       "Shard each coalesced micro-batch across N devices ('all'/-1 = "
       "every local device; unset/0/1 = single-device dispatch; also "
       "--mesh-devices).",
       flag="--mesh-devices", config_key="meshDevices"),
    # --- engine ----------------------------------------------------------
    _v("DEPPY_TPU_MAX_LANES", "int", 512, "deppy_tpu.engine.driver",
       "Per-dispatch lane cap: batches chunk to this width (not yet "
       "measured on the chip)."),
    _v("DEPPY_TPU_PROBE_LANES", "int", 512, "deppy_tpu.engine.driver",
       "Lane width of one speculative core-probe dispatch (not yet "
       "measured on the chip)."),
    _v("DEPPY_TPU_HOST_CORE_NCONS", "int", 768, "deppy_tpu.engine.driver",
       "Constraint count above which UNSAT-core extraction routes to "
       "the host engine (not yet measured on the chip)."),
    _v("DEPPY_TPU_SPEC_CORE", "str", "auto", "deppy_tpu.engine.driver",
       "Speculative phase-3 core extraction: auto/on/off."),
    _v("DEPPY_TPU_SPEC_CORE_CAP", "int", 32768, "deppy_tpu.engine.driver",
       "Cost-proxy cap above which speculative core extraction is "
       "skipped."),
    _v("DEPPY_TPU_STAGE1_STEPS", "int", 0, "deppy_tpu.engine.driver",
       "Stage-1 step budget of the escalation ladder (0 = measured "
       "default)."),
    _v("DEPPY_TPU_BCP", "str", "auto", "deppy_tpu.engine.core",
       "BCP propagation implementation: auto/gather/bits/pallas/"
       "blockwise/watched ('watched' = the compressed-clause-bank "
       "implication-driven path; 'auto' resolves through the "
       "measured-defaults registry, falling back to 'bits'; also "
       "--bcp).",
       flag="--bcp", config_key="bcp"),
    _v("DEPPY_TPU_BANK_OCC_CAP", "int", 0, "deppy_tpu.engine.driver",
       "Watched-bank occurrence-width cap: a dispatch whose max "
       "per-literal clause count exceeds the cap ships dummy banks and "
       "runs the dense propagation program instead (0 = the dispatch's "
       "size-class OCC cap from deppy_tpu.size_classes)."),
    _v("DEPPY_TPU_SIZE_LADDER", "str", "on", "deppy_tpu.engine.driver",
       "Size-class partitioner: 'on' = the shared ladder "
       "(deppy_tpu.size_classes), 'off' = the legacy adjacent-jump "
       "splitter (A/B only)."),
    _v("DEPPY_TPU_BCP_UNROLL", "int", 1, "deppy_tpu.engine.core",
       "Propagation-loop unroll factor (trip-overhead amortization)."),
    _v("DEPPY_TPU_DPLL_UNROLL", "int", 1, "deppy_tpu.engine.core",
       "DPLL decision-loop unroll factor."),
    _v("DEPPY_TPU_CTL_UNROLL", "int", 1, "deppy_tpu.engine.core",
       "Control-loop unroll factor."),
    _v("DEPPY_TPU_SEARCH", "str", "auto", "deppy_tpu.engine.core",
       "Search-phase implementation: auto/xla/fused (fused = the "
       "whole-search Pallas kernel)."),
    _v("DEPPY_TPU_MEASURED_DEFAULTS", "path", None, "deppy_tpu.engine.core",
       "Override path of the measured-defaults registry JSON (default: "
       "the package-local engine/measured_defaults.json)."),
    _v("DEPPY_TPU_BLOCK_ROWS", "int", 2048,
       "deppy_tpu.engine.pallas_blockwise",
       "Clause-row block height of the blockwise BCP kernel."),
    # --- platform / tooling ---------------------------------------------
    _v("DEPPY_TPU_COMPILE_CACHE", "str", None,
       "deppy_tpu.utils.platform_env",
       "'off' disables the persistent XLA compile cache; otherwise it "
       "lives in JAX_COMPILATION_CACHE_DIR when set, else in "
       "<checkout>/.jax_cache unless JAX_PLATFORMS leaves out tpu."),
    _v("DEPPY_TPU_REVAL_LOG", "path", None, "scripts.tpu_revalidate",
       "JSONL record log shared by the revalidation ladder and "
       "bench.py's accelerator records."),
    # --- analysis --------------------------------------------------------
    _v("DEPPY_TPU_LOCKDEP", "bool", False, "deppy_tpu.analysis.lockdep",
       "Runtime lock-order assertion mode: named locks track "
       "acquisition order per thread, raise on lock-order inversions "
       "and self-deadlocks, and emit `lockdep` telemetry events."),
    _v("DEPPY_TPU_COMPILE_GUARD", "bool", False,
       "deppy_tpu.analysis.compileguard",
       "Runtime compile-guard mode: every registered jit entry's "
       "trace/compile is emitted as a `compileguard` telemetry event, "
       "and retracing one abstract signature past the entry's budget "
       "raises CompileGuardError (summarize with `deppy compiles`)."),
    _v("DEPPY_TPU_COMPILE_BUDGET", "int", None,
       "deppy_tpu.analysis.compileguard",
       "Per-signature trace budget for compile-guarded jit entries "
       "(default: 2 x local device count — per-device placement keys "
       "jit's cache once per device)."),
]

REGISTRY: "dict[str, EnvVar]" = {v.name: v for v in _DECLARATIONS}
assert len(REGISTRY) == len(_DECLARATIONS), "duplicate EnvVar declaration"


def declared(name: str) -> bool:
    return name in REGISTRY


def require(name: str) -> Optional[EnvVar]:
    """Assert ``name`` is a declared knob.  Only ``DEPPY_TPU_*`` names
    are enforced — the defensive parse helpers are shared with
    non-namespaced knobs (tests, DEPPY_BENCH_*) that this registry does
    not own.  Returns the declaration (None for foreign names)."""
    if not name.startswith(_PREFIX):
        return None
    try:
        return REGISTRY[name]
    except KeyError:
        raise UndeclaredEnvVar(
            f"{name} is not declared in deppy_tpu.config.REGISTRY — "
            f"declare it (name, type, default, consumer, help) so "
            f"docs/configuration.md and `deppy lint` stay in sync"
        ) from None


def env_raw(name: str, default: Optional[str] = None) -> Optional[str]:
    """``os.environ.get`` through the registry: the declaration is
    asserted, the value comes back verbatim (callers keep their own
    parse-or-degrade semantics)."""
    require(name)
    return os.environ.get(name, default)


def env_str(name: str, default: Optional[str] = None) -> Optional[str]:
    raw = env_raw(name)
    return raw if raw is not None and raw.strip() else default


def env_int(name: str, default: Optional[int] = None,
            strict: bool = True) -> Optional[int]:
    """Typed int read.  ``strict`` raises on a malformed value (the
    engine's import-time knobs fail loud); ``strict=False`` degrades to
    the default like the fault layer's parsers."""
    raw = env_raw(name)
    if raw is None or not raw.strip():
        return default
    try:
        return int(raw)
    except ValueError:
        if strict:
            raise
        return default


def env_float(name: str, default: Optional[float] = None,
              strict: bool = True) -> Optional[float]:
    raw = env_raw(name)
    if raw is None or not raw.strip():
        return default
    try:
        return float(raw)
    except ValueError:
        if strict:
            raise
        return default


_TRUE = frozenset(("1", "true", "yes", "on"))
_FALSE = frozenset(("0", "false", "no", "off", ""))


def env_bool(name: str, default: bool = False) -> bool:
    raw = env_raw(name)
    if raw is None:
        return default
    token = raw.strip().lower()
    if token in _TRUE:
        return True
    if token in _FALSE:
        return False
    return default


# ------------------------------------------------------------------ docs


def _fmt_default(v: EnvVar) -> str:
    if v.default is None:
        return "(unset)"
    if v.type == "bool":
        return "on" if v.default else "off"
    return str(v.default)


def render_markdown() -> str:
    """The docs/configuration.md body, generated from the registry.
    ``python -m deppy_tpu.config`` regenerates the file;
    tests/test_doc_sync.py pins the checked-in copy against this."""
    lines = [
        "# Configuration",
        "",
        "<!-- GENERATED FILE — do not edit by hand.",
        "     Regenerate with: python -m deppy_tpu.config > "
        "docs/configuration.md",
        "     Source of truth: deppy_tpu/config.py (REGISTRY). -->",
        "",
        "Every `DEPPY_TPU_*` environment knob, generated from the typed",
        "registry in `deppy_tpu/config.py`.  The `registry-sync` checker",
        "(`deppy lint`) fails on any knob read in code but missing here,",
        "and `tests/test_doc_sync.py` pins this file against the",
        "registry both ways.  The Mirrors column names the knob's",
        "declared CLI-flag / ResolverConfig-key twins; `registry-sync`",
        "pins those against `deppy_tpu/cli.py` both ways too.",
        "",
        "| Name | Type | Default | Consumer | Mirrors | Description |",
        "| --- | --- | --- | --- | --- | --- |",
    ]
    for name in sorted(REGISTRY):
        v = REGISTRY[name]
        mirrors = " ".join(
            f"`{m}`" for m in (v.flag, v.config_key) if m) or "—"
        lines.append(
            f"| `{v.name}` | {v.type} | `{_fmt_default(v)}` | "
            f"`{v.consumer}` | {mirrors} | {v.help} |")
    lines.append("")
    return "\n".join(lines)


if __name__ == "__main__":
    import sys

    sys.stdout.write(render_markdown())
