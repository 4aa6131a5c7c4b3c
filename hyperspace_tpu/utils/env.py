"""Typed registry of every ``HYPERSPACE_*`` environment knob.

Before this module, knob reads were scattered ``os.environ.get`` calls with
the name, type, and default repeated at each site — a drifted default or a
typo'd name only surfaced as a knob that silently did nothing. This registry
is the single source of truth: every knob declares its name, type, default,
and docstring here, every read goes through the typed accessors below
(hslint HS301 enforces it), and the env-knob table in docs/performance.md is
generated from it (``python -m hyperspace_tpu.utils.env --update-docs``).

Read semantics are deliberately conservative: accessors parse the raw
string exactly the way the historical call sites did (``int(s)``,
``float(s)``, ``s == "1"``), so centralizing the reads cannot change any
observable behavior. Call-site-specific fallbacks (e.g. the IO pool's
"unparseable means serial") stay at the call site, built on ``read_raw``.
"""

from __future__ import annotations

import os
from dataclasses import dataclass, field


@dataclass(frozen=True)
class EnvKnob:
    """One environment knob: its contract, not its current value."""

    name: str
    kind: str  # "int" | "float" | "str" | "bool" | "mode"
    default: object  # default VALUE (None = unset); shown in the docs table
    doc: str
    owner: str  # module that consumes the knob (docs table column)
    choices: tuple = ()  # for kind="mode": the accepted values

    def raw(self, default: "str | None" = None):
        return os.environ.get(self.name, default)


# mutated only by the module-level _register calls below at import time;
# env.py sits under staticcheck/concurrency in the import graph, so it
# cannot use guarded_by without a cycle
_REGISTRY: dict[str, EnvKnob] = {}  # hslint: HS305 — import-time only


def _register(name, kind, default, doc, owner, choices=()) -> EnvKnob:
    knob = EnvKnob(name, kind, default, doc, owner, tuple(choices))
    _REGISTRY[name] = knob
    return knob


def knob(name: str) -> EnvKnob:
    """The registered knob — KeyError for unregistered names, because the
    registry IS the catalog (an unregistered read is a lint violation)."""
    return _REGISTRY[name]


def all_knobs() -> list[EnvKnob]:
    return [_REGISTRY[k] for k in sorted(_REGISTRY)]


# --- typed accessors (the only sanctioned os.environ read path) -------------
#
# A name NOT in the registry is accepted only when the caller supplies an
# explicit default (ad-hoc knobs: parameterized test caches). A registered
# name with no explicit default falls back to the registry default.

def _raw(name: str) -> "str | None":
    k = _REGISTRY.get(name)
    if k is not None:
        return k.raw()
    return os.environ.get(name)


def _default(name: str, explicit):
    if explicit is not None:
        return explicit
    return _REGISTRY[name].default  # KeyError: unregistered AND no default


def read_raw(name: str, default: "str | None" = None) -> "str | None":
    """Raw string read (sites with bespoke parsing/fallback semantics)."""
    v = _raw(name)
    return v if v is not None else default


def env_str(name: str, default: "str | None" = None) -> "str | None":
    v = _raw(name)
    return v if v is not None else _default(name, default)


def env_int(name: str, default: "int | None" = None) -> int:
    v = _raw(name)
    return int(v) if v is not None else _default(name, default)


def env_float(name: str, default: "float | None" = None) -> float:
    v = _raw(name)
    return float(v) if v is not None else _default(name, default)


def env_bool(name: str) -> bool:
    """Historical convention: only the literal string "1" enables."""
    return _raw(name) == "1"


# ---------------------------------------------------------------------------
# the catalog — grouped by subsystem, alphabetical within a group
# ---------------------------------------------------------------------------

# IO / caches (columnar/io.py, utils/device_cache.py, utils/workers.py)
_register(
    "HYPERSPACE_BUILD_CACHE_MB", "int", 2048,
    "Byte budget (MB) of the maintenance source-column cache.",
    "columnar/io.py",
)
_register(
    "HYPERSPACE_DEVICE_CACHE_MB", "float", 6144,
    "Byte budget (MB) of device-resident column arrays; 0 disables.",
    "utils/device_cache.py",
)
_register(
    "HYPERSPACE_HOST_DERIVED_CACHE_MB", "float", 512,
    "Byte budget (MB) of host-derived device arrays (group ids, masks).",
    "utils/device_cache.py",
)
_register(
    "HYPERSPACE_INDEX_CACHE_MB", "int", 1024,
    "Byte budget (MB) of the decoded index-chunk cache.",
    "columnar/io.py",
)
_register(
    "HYPERSPACE_IO_BUDGET_MB", "float", 512,
    "Read-ahead byte budget (MB) of the streaming readers (scan chunks and "
    "bucket-pair loads in flight).",
    "columnar/io.py",
)
_register(
    "HYPERSPACE_IO_THREADS", "int", None,
    "Width of every IO-bound thread pool (parallel parquet decode, bucket "
    "loaders, compaction). Default min(8, nproc); <=1 or unparseable means "
    "serial.",
    "utils/workers.py",
)
_register(
    "HYPERSPACE_SKETCH_CACHE_MB", "int", 64,
    "Byte budget (MB) of the decoded per-row-group sketch sidecar cache "
    "(cache.sketch.*); 0 disables caching (sidecars re-parse per query).",
    "models/dataskipping/sketch_store.py",
)
_register(
    "HYPERSPACE_STATS_CACHE_MB", "int", 64,
    "Byte budget (MB) of the parquet footer row-group stats cache.",
    "columnar/io.py",
)
_register(
    "HYPERSPACE_STREAM_CHUNK_MB", "float", 64,
    "Target chunk size (MB) of the pipelined scan streamer's file groups.",
    "columnar/io.py",
)

# execution (plan/tpu_exec.py, plan/device_join.py, plan/pruning.py)
_register(
    "HYPERSPACE_ADAPTIVE", "mode", "0",
    "Mid-query adaptive re-optimization: 0 = off (default; bit-identical "
    "static plans), 1 = on (per-bucket join re-planning from observed "
    "build bytes, observed-selectivity conjunct reordering, scan "
    "abort-and-replan on pruning underdelivery), verify = adapt AND "
    "re-execute the static plan, raising on any result divergence (debug).",
    "plan/adaptive.py", choices=("0", "1", "verify"),
)
_register(
    "HYPERSPACE_ADAPTIVE_ABORT_FACTOR", "float", 4.0,
    "Actual-over-predicted kept-data ratio at which an under-delivering "
    "index scan aborts at a chunk boundary and re-enters the ranker "
    "(raw scan or next-best candidate) against the same pinned snapshot.",
    "plan/adaptive.py",
)
_register(
    "HYPERSPACE_ADAPTIVE_WARMUP_CHUNKS", "int", 2,
    "Chunks (scan abort) / observed bucket pairs (join re-plan) / chunk "
    "rows batches (conjunct reorder) the adaptive executor observes before "
    "it is allowed to switch anything.",
    "plan/adaptive.py",
)
_register(
    "HYPERSPACE_FORCE_PALLAS", "bool", False,
    "Force the Pallas kernel route off-TPU (interpret mode; testing).",
    "plan/tpu_exec.py",
)
_register(
    "HYPERSPACE_JOIN_BROADCAST_ROWS", "int", 4096,
    "Estimated build-side row count at or below which a bucket pair takes "
    "the broadcast strategy (whole pair in one band item, never split).",
    "plan/join_memory.py",
)
_register(
    "HYPERSPACE_JOIN_SPLIT_ROWS", "int", 1 << 18,
    "Left-side row count above which a bucket splits into probe chunks "
    "(only where partials fold exactly). Explicitly set, it OVERRIDES the "
    "grant-derived adaptive split row count (docs/performance.md "
    "\"Bucketed joins\"); unset, the device-memory grant decides.",
    "plan/device_join.py",
)
_register(
    "HYPERSPACE_PARK_WAIT_MS", "float", 50,
    "Bounded wait (ms) a parked join wave spends on the device ledger's "
    "release condition — after its own waves are spilled — for OTHER "
    "queries' reservations to drain before taking the zero-holder force "
    "grant past the limit.",
    "plan/join_memory.py",
)
_register(
    "HYPERSPACE_PIPELINE", "mode", "1",
    "Streaming executor mode: 1 = pipelined (default), serial = staged "
    "without overlap (debug), 0 = monolithic barrier path.",
    "plan/tpu_exec.py", choices=("1", "serial", "0"),
)
_register(
    "HYPERSPACE_PIPELINE_DEPTH", "int", 2,
    "Dispatch window of the chunk streamer (uploads in flight ahead of the "
    "device).",
    "plan/tpu_exec.py",
)
_register(
    "HYPERSPACE_PRUNE", "mode", "1",
    "Predicate-driven index pruning: 1 = on (default), 0 = off, verify = "
    "prune AND read full, raise on post-filter divergence (debug).",
    "plan/pruning.py", choices=("1", "0", "verify"),
)
_register(
    "HYPERSPACE_APPROX", "mode", "0",
    "Approximate query tier: 0 = off (default; exact execution, "
    "bit-identical results), 1 = on (sample twins written at index build / "
    "append / compact; eligible Count/Sum aggregates may execute against "
    "sampled runs with CLT confidence intervals when requested or when QoS "
    "degrades a predicted deadline miss), verify = sample AND run exact "
    "alongside, raising if any reported 95% CI fails to cover the exact "
    "answer (debug).",
    "plan/sampling.py", choices=("0", "1", "verify"),
)
_register(
    "HYPERSPACE_APPROX_FRACTIONS", "str", "0.01,0.1",
    "Comma list of sampling fractions (strata tiers) maintained as sample "
    "twin files next to index data and available to the sampled execution "
    "tier. Changing this only affects newly written index versions.",
    "models/sample_store.py",
)
_register(
    "HYPERSPACE_APPROX_CI_SAFETY", "float", 2.0,
    "Multiplier applied to CLT 95% half-widths from the sampled tier. "
    "The variance estimate is cluster-level (universe sampling keeps "
    "whole keys) but still sample-based; the safety factor absorbs "
    "small-sample effects, keeping reported intervals conservative.",
    "plan/sampling.py",
)
_register(
    "HYPERSPACE_APPROX_MAX_KEY_SHARE", "float", 0.05,
    "Skew guard for the sampled tier: if a single key owns at least this "
    "share of an index's rows (from the heavy-cluster entries in the "
    "per-file sample metas) AND the universe hash drops that key at the "
    "requested fraction, the planner declines the tier "
    "(approx.ineligible.hot-key) and falls back to exact — a sample that "
    "never sees a dominant cluster cannot honestly bound it. The write "
    "side derives its per-file heavy-cluster recording floor from this "
    "knob (half the threshold, capped at 1% of the file's rows, at least "
    "8 rows), so lower how-hot-counts-as-hot settings take effect on "
    "index versions written after the change.",
    "plan/sampling.py",
)
_register(
    "HYPERSPACE_APPROX_MIN_KEYS", "int", 8,
    "Minimum expected distinct sampled keys (fraction x sidecar NDV) for a "
    "sampling tier to be considered viable for an index scan; below it the "
    "planner declines the tier and falls back to a coarser fraction or "
    "exact execution.",
    "plan/sampling.py",
)
_register(
    "HYPERSPACE_SKETCHES", "str", None,
    "Per-row-group sketch store for covering indexes: unset/0 = off (the "
    "default; no sidecars, prune path unchanged), 1/all = every kind, or "
    "a comma list of bloom,valuelist,zregion. Enabled, index writes emit "
    "per-row-group sketch sidecars and Eq/In/range predicates on NON-sort "
    "columns skip row groups at scan time.",
    "models/dataskipping/sketch_store.py",
)
_register(
    "HYPERSPACE_SKETCH_BLOOM_FPP", "float", 0.01,
    "Target false-positive probability of per-row-group bloom filter "
    "sketches (sizing only; false positives keep extra groups, never drop).",
    "models/dataskipping/sketch_store.py",
)
_register(
    "HYPERSPACE_SKETCH_BLOOM_NDV", "int", 8192,
    "Cap on the expected-distinct-count a per-row-group bloom filter is "
    "sized for (bounds sidecar bytes on very-high-NDV columns).",
    "models/dataskipping/sketch_store.py",
)

# mesh scale-out (parallel/placement.py, parallel/mesh.py)
_register(
    "HYPERSPACE_MESH", "bool", False,
    "Mesh-sharded scale-out execution: bucketed-join band waves and "
    "streaming scan/agg chunks fan out across every visible device via the "
    "skew-aware placer (largest-first bin packing by predicted decoded "
    "bytes; round-robin when footer stats are missing). Results stay "
    "bit-identical to single-device execution; off (default) keeps every "
    "dispatch on the default device.",
    "parallel/placement.py",
)
_register(
    "HYPERSPACE_MESH_DEVICES", "int", 0,
    "Cap on the devices the mesh placer targets (0 = all visible; values "
    "above the visible count clamp down).",
    "parallel/placement.py",
)

# result cache / incremental views (cache/)
_register(
    "HYPERSPACE_RESULT_CACHE", "mode", "0",
    "Cross-query result cache keyed by (plan fingerprint, pinned snapshot "
    "version): 1 = on, 0 = off (default; correctness gates pin per-run "
    "execution effects, so serving deployments opt in), verify = on AND "
    "every hit/fold recomputes from scratch, raising on divergence.",
    "cache/result_cache.py", choices=("1", "0", "verify"),
)
_register(
    "HYPERSPACE_RESULT_CACHE_FOLD_DEPTH", "int", 32,
    "Successive delta folds a cached aggregate may accumulate before the "
    "next miss recomputes from scratch to re-anchor the entry.",
    "cache/view_maintenance.py",
)
_register(
    "HYPERSPACE_RESULT_CACHE_MB", "float", 256,
    "Byte budget (MB) of the cross-query result cache (LRU past it).",
    "cache/result_cache.py",
)

# serving (serve/)
_register(
    "HYPERSPACE_QOS_COST_MBPS", "float", 256,
    "Byte-cost normalization of the weighted-fair virtual clock: a "
    "finished query's attributed bytes (scan io + device transfers) are "
    "charged as bytes / (this many MB per second) on top of its run wall "
    "time.",
    "serve/qos.py",
)
_register(
    "HYPERSPACE_SERVE_AGING_MS", "float", 0,
    "Queue-wait aging interval (ms): a queued query's effective priority "
    "grows by one level per interval waited, bounded by "
    "HYPERSPACE_SERVE_AGING_CAP, so priority-0 queries cannot starve "
    "under a sustained high-priority flood. 0 (default) disables aging "
    "and preserves exact static-priority dispatch order.",
    "serve/qos.py",
)
_register(
    "HYPERSPACE_SERVE_AGING_CAP", "int", 100,
    "Upper bound on the aging priority boost (levels) a queued query can "
    "accumulate when HYPERSPACE_SERVE_AGING_MS is enabled.",
    "serve/qos.py",
)
_register(
    "HYPERSPACE_TENANTS", "str", None,
    "Tenant QoS bootstrap spec parsed at registry construction: "
    "name:key=value,...;name2:... with keys weight, rate_qps, burst, "
    "max_in_flight, max_active, budget_fraction (e.g. "
    "gold:weight=4,rate_qps=50;bulk:weight=1,max_active=1). Malformed "
    "specs raise TenantSpecError.",
    "serve/tenant.py",
)
_register(
    "HYPERSPACE_DEVICE_BUDGET_MB", "float", 4096,
    "Byte budget (MB) of the DEVICE-resident ledger bucketed-join band "
    "waves reserve their padded upload footprint through before dispatch; "
    "over-budget waves park/spill instead of declining to the host tier. "
    "0 disables the ledger (fixed-threshold pre-adaptive behavior).",
    "serve/budget.py",
)
_register(
    "HYPERSPACE_GLOBAL_BUDGET_MB", "float", 1024,
    "Byte budget (MB) of the GLOBAL read-ahead ledger every streaming "
    "consumer (scan chunks, join pair loads, across all concurrent "
    "queries) reserves through. Unset, an explicitly-set legacy "
    "HYPERSPACE_IO_BUDGET_MB carries over as the global limit.",
    "serve/budget.py",
)
_register(
    "HYPERSPACE_MAX_CONCURRENT_QUERIES", "int", 4,
    "Queries the scheduler runs concurrently (admission-controlled; the "
    "rest wait in the bounded run queue).",
    "serve/scheduler.py",
)
_register(
    "HYPERSPACE_SERVE_DEFAULT_PRIORITY", "int", 0,
    "Priority of queries submitted without an explicit one (higher runs "
    "first; FIFO within a priority).",
    "serve/scheduler.py",
)
_register(
    "HYPERSPACE_SERVE_QUEUE_DEPTH", "int", 32,
    "Bound of the scheduler's run queue; submissions past it are rejected "
    "at admission (load shedding) instead of queueing unboundedly.",
    "serve/scheduler.py",
)

# backend / device tier (utils/backend.py)
_register(
    "HYPERSPACE_BREAKER_COOLDOWN", "float", 30,
    "Seconds the device breaker stays open after a transient device "
    "failure before a half-open recovery probe is allowed (doubles per "
    "consecutive reopen, capped at 16x).",
    "utils/backend.py",
)
_register(
    "HYPERSPACE_DEVICE_STRICT", "bool", False,
    "Device failures raise instead of falling back to the host tier "
    "(CI/differential gates).",
    "utils/backend.py",
)

# ingestion / index maintenance (ingest/)
_register(
    "HYPERSPACE_COMPACT_RUNS", "int", 8,
    "Delta runs (files) a bucket accumulates before it becomes a "
    "compaction candidate; appends past the threshold schedule a "
    "background compaction on the shared IO pool.",
    "ingest/compaction.py",
)
_register(
    "HYPERSPACE_VACUUM_GRACE_S", "float", 0,
    "Seconds a superseded (unreferenced-by-latest) index data version must "
    "stay observed before vacuum may retire it, on top of its snapshot "
    "refcount draining; 0 = refcount-only.",
    "ingest/compaction.py",
)

# robustness / fault tolerance (utils/faults.py, utils/retry.py, actions/)
_register(
    "HYPERSPACE_ACTION_RETRIES", "int", 3,
    "Total attempts an index-mutating action makes when it loses the "
    "optimistic-concurrency race (ConcurrentWriteError re-reads the log "
    "and re-runs the transaction).",
    "actions/base.py",
)
_register(
    "HYPERSPACE_FAULTS", "str", None,
    "Deterministic fault-injection spec (point:kind:trigger[;...]) armed "
    "at import; unset = disarmed, zero overhead. Grammar in "
    "docs/robustness.md.",
    "utils/faults.py",
)
_register(
    "HYPERSPACE_IO_RETRIES", "int", 3,
    "Total attempts per per-file decode / footer-stats read unit for "
    "transient IO errors (bounded exponential backoff, deterministic "
    "jitter); 1 disables retrying.",
    "utils/retry.py",
)
_register(
    "HYPERSPACE_STALE_TX_S", "float", 3600,
    "Age (seconds) past which a transient log entry counts as a dead "
    "transaction: the auto recovery pass rolls back/fixes forward only "
    "entries older than this (explicit recover(force=True) ignores age).",
    "index_manager.py",
)

# telemetry (telemetry/trace.py, telemetry/exporter.py, telemetry/attribution.py)
_register(
    "HYPERSPACE_ESTIMATOR_FEEDBACK", "bool", False,
    "Estimator feedback: FilterIndexRanker and the join memory planner "
    "multiply their estimates by the accuracy ledger's observed "
    "correction factor per (index, predicate shape). Off (default) the "
    "ledger is observe-only and planning is bit-identical.",
    "telemetry/plan_stats.py",
)
_register(
    "HYPERSPACE_PLAN_STATS", "bool", False,
    "Collect per-plan-node runtime statistics (rows/wall/route/bytes + "
    "estimator q-errors) on every collect(), not just under "
    "explain_analyze; annotations ride exec spans when tracing is on "
    "(tools/trace_report.py --plan-stats).",
    "telemetry/plan_stats.py",
)
_register(
    "HYPERSPACE_METRICS_PORT", "int", None,
    "TCP port of the opt-in metrics exporter (Prometheus /metrics, JSON "
    "/snapshot, /healthz) started with the first query scheduler; 0 binds "
    "an ephemeral port (tests); unset = no exporter thread, no socket.",
    "telemetry/exporter.py",
)
_register(
    "HYPERSPACE_QUERY_LOG_WINDOW", "int", 256,
    "Finished serving queries kept in the rolling in-memory query log "
    "(hs.profile, /snapshot, tools/hs_top.py).",
    "telemetry/attribution.py",
)
_register(
    "HYPERSPACE_SLOW_QUERY_FILE", "str", None,
    "JSONL path the slow-query log appends finished query records to; "
    "unset disables the log.",
    "telemetry/attribution.py",
)
_register(
    "HYPERSPACE_SLOW_QUERY_MS", "float", 0,
    "Minimum total latency (ms) a finished serving query must exceed to "
    "enter the slow-query log (0 = log every query once the file is set).",
    "telemetry/attribution.py",
)
_register(
    "HYPERSPACE_SNAPSHOT_FILE", "str", None,
    "JSONL path the periodic snapshot sink appends full registry + "
    "serving-state snapshots to (headless runs); unset disables the sink.",
    "telemetry/exporter.py",
)
_register(
    "HYPERSPACE_SNAPSHOT_INTERVAL_S", "float", 10,
    "Seconds between periodic JSONL snapshots when the snapshot sink is "
    "enabled.",
    "telemetry/exporter.py",
)
_register(
    "HYPERSPACE_WORKLOAD_DIR", "str", None,
    "Directory for the durable workload-intelligence plane: the size-"
    "rotated JSONL query journal plus the persisted per-index utility "
    "ledger. Unset (default) the whole plane is off — zero writes, zero "
    "notes, bit-identical results.",
    "telemetry/workload.py",
)
_register(
    "HYPERSPACE_WORKLOAD_ROTATE_MB", "float", 64,
    "Workload-journal rotation bound (MB): the current workload.jsonl "
    "rotates to a numbered segment once it reaches this size.",
    "telemetry/workload.py",
)
_register(
    "HYPERSPACE_WORKLOAD_RETAIN", "int", 8,
    "Rotated workload-journal segments kept; older segments are deleted "
    "at rotation (the current file is always kept on top).",
    "telemetry/workload.py",
)
_register(
    "HYPERSPACE_WORKLOAD_WINDOW", "int", 64,
    "Rolling-window size (samples) the drift detector compares against "
    "the frozen baseline, per query label and per estimator.",
    "telemetry/workload.py",
)
_register(
    "HYPERSPACE_WORKLOAD_BASELINE", "int", 64,
    "Samples frozen as the drift baseline: the FIRST N observations of "
    "each series; everything after feeds the rolling window.",
    "telemetry/workload.py",
)
_register(
    "HYPERSPACE_WORKLOAD_DRIFT_FACTOR", "float", 2.0,
    "Drift threshold: a regression fires when the rolling window's median "
    "latency (or geomean q-error) exceeds the baseline by this factor.",
    "telemetry/workload.py",
)
_register(
    "HYPERSPACE_WORKLOAD_DRIFT_MIN", "int", 8,
    "Minimum samples required on BOTH sides (baseline and window) before "
    "the drift detector will compare a series.",
    "telemetry/workload.py",
)
_register(
    "HYPERSPACE_WORKLOAD_DRIFT_ABS_MS", "float", 1.0,
    "Absolute floor for latency drift: on top of the ratio, the window "
    "median must exceed the baseline median by at least this many "
    "milliseconds (guards microsecond-scale series against scheduler "
    "jitter).",
    "telemetry/workload.py",
)
_register(
    "HYPERSPACE_TRACE", "bool", False,
    "Force-enable query tracing at import (the traced tier-1 run).",
    "telemetry/trace.py",
)
_register(
    "HYPERSPACE_TRACE_FILE", "str", None,
    "JSONL sink path attached when tracing is force-enabled.",
    "telemetry/trace.py",
)

# static analysis (staticcheck/)
_register(
    "HYPERSPACE_LOCK_AUDIT", "bool", False,
    "Audit every TrackedLock acquisition: record per-thread held-sets into "
    "the global acquisition-order graph and raise LockOrderError (naming "
    "the cycle and both stack sites) when a nesting closes a cycle.",
    "staticcheck/concurrency.py",
)
_register(
    "HYPERSPACE_KERNEL_AUDIT", "bool", False,
    "Audit every kernel-cache miss: trace the jaxpr on the kernel's first "
    "call and scan it for hazards (host callbacks, implicit f64 promotion, "
    "non-deterministic primitives).",
    "staticcheck/kernel_audit.py",
)
_register(
    "HYPERSPACE_RETRACE_WARN", "int", 8,
    "Retrace watchdog threshold: distinct fingerprints of one kernel kind "
    "with identical dtype signatures before a churn warning fires.",
    "staticcheck/kernel_audit.py",
)
_register(
    "HYPERSPACE_VERIFY_PLAN", "bool", False,
    "Run the plan invariant verifier on every optimized plan (raises "
    "PlanInvariantError naming the node path on violation).",
    "staticcheck/plan_verifier.py",
)
_register(
    "HYPERSPACE_LIFECYCLE_AUDIT", "bool", False,
    "Audit resource lifecycles: record owner + acquire call chain for "
    "every live handle (snapshot pins, budget streams, ledger waves, "
    "attribution scopes, cache in-flight markers) so check_quiescent() "
    "can raise ResourceLeakError naming every leaked handle.",
    "staticcheck/lifecycle.py",
)


# ---------------------------------------------------------------------------
# docs table generation
# ---------------------------------------------------------------------------

_DOCS_BEGIN = "<!-- env-knob-table:begin (generated by hyperspace_tpu.utils.env; do not edit by hand) -->"
_DOCS_END = "<!-- env-knob-table:end -->"


def markdown_table() -> str:
    """The docs/performance.md env-knob table, generated from the registry."""
    rows = [
        "| Variable | Type | Default | Owner | Effect |",
        "|---|---|---|---|---|",
    ]
    for k in all_knobs():
        if k.kind == "bool":
            default = "1" if k.default else "unset"
        elif k.default is None:
            default = "unset"
        else:
            default = str(k.default)
        kind = k.kind if not k.choices else "/".join(k.choices)
        rows.append(
            f"| `{k.name}` | {kind} | {default} | `{k.owner}` | {k.doc} |"
        )
    return "\n".join(rows)


def render_docs_section() -> str:
    return f"{_DOCS_BEGIN}\n\n{markdown_table()}\n\n{_DOCS_END}"


def update_docs(path: str, check_only: bool = False) -> bool:
    """Replace the marked table section in ``path`` with the generated one.
    Returns True when the file already matched (or was updated)."""
    with open(path, encoding="utf-8") as f:
        text = f.read()
    start = text.find(_DOCS_BEGIN)
    end = text.find(_DOCS_END)
    if start < 0 or end < 0:
        raise ValueError(f"{path} has no env-knob-table markers")
    new = text[:start] + render_docs_section() + text[end + len(_DOCS_END):]
    if new == text:
        return True
    if check_only:
        return False
    with open(path, "w", encoding="utf-8") as f:
        f.write(new)
    return True


if __name__ == "__main__":  # pragma: no cover - tooling entry
    import argparse

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--update-docs", metavar="PATH", nargs="?",
                    const="docs/performance.md")
    ap.add_argument("--check", action="store_true",
                    help="with --update-docs: fail instead of rewriting")
    args = ap.parse_args()
    if args.update_docs:
        ok = update_docs(args.update_docs, check_only=args.check)
        if not ok:
            print(f"{args.update_docs}: env-knob table is stale "
                  f"(run python -m hyperspace_tpu.utils.env --update-docs)")
            raise SystemExit(1)
        print(f"{args.update_docs}: env-knob table up to date")
    else:
        print(markdown_table())
