"""Snapshot-keyed cross-query result cache.

Repeated dashboard-style queries are the dominant serving pattern the north
star targets, and before this module every repeat re-scanned, re-uploaded,
and re-dispatched from scratch. The cache closes that gap with EXACT (never
heuristic) invalidation, because both halves of its key already exist in
the engine:

    key = (plan structure fingerprint, plan files fingerprint,
           pinned snapshot ids)

- the structure/files fingerprints (plan/kernel_cache.py) canonicalize the
  whole optimized plan — node kinds, expression reprs, prune decisions,
  and the resolved (path, size, mtime) identity of every scanned file;
- the snapshot ids are the (index_path, entry_id) pins the query's
  pin scope collected at plan time (ingest/snapshots.py) — the immutable
  data versions PR 10 publishes atomically.

A hit therefore returns a stored result that is *guaranteed* bit-identical
to re-execution: same plan, same immutable bytes. Only plans that pinned at
least one index snapshot are cached (raw source scans have no version
authority; in-memory scans have no stable identity at all).

Incremental view maintenance (view_maintenance.py): an ``hs.append``
publishes a new snapshot whose content is old ∪ delta, so the exact key
misses — but entries over exactly-foldable fragments (global
count/min/max/int-sum aggregates) are not recomputed from scratch. The miss
path finds a same-structure entry whose file set is a subset of the new
plan's, executes the fragment over ONLY the delta files, and folds:
``result_vM = result_vN ⊕ agg(delta)``. Hot aggregates stay warm across sustained ingest at delta cost.

Modes (``HYPERSPACE_RESULT_CACHE``): ``0`` off (default — the repo's
correctness gates pin per-run execution effects, so caching is an explicit
serving-deployment opt-in), ``1`` on, ``verify`` on + every hit and every
fold recomputes from scratch and raises on any divergence (the
``HYPERSPACE_PRUNE=verify`` debug discipline).

Population is single-flight (the ``BoundedLRU.get_or_put`` semantics): N
concurrent identical queries compute once, the rest wait and read. A query
cancelled mid-compute (``QueryCancelledError`` is a BaseException) never
leaves the in-flight marker latched — a waiter wakes and takes over.

The store lock is a LEAF: factories (query execution!) and metric emission
always run outside it.
"""

from __future__ import annotations

import hashlib
import threading
import time
import weakref
from collections import OrderedDict
from typing import Optional

from ..exceptions import HyperspaceError
from ..staticcheck.concurrency import TrackedLock
from ..staticcheck.lifecycle import release_resource, tracked_resource
from ..utils import env


def _mode() -> str:
    return env.env_str("HYPERSPACE_RESULT_CACHE") or "0"


def enabled() -> bool:
    return _mode() != "0"


def is_verify() -> bool:
    return _mode() == "verify"


def _max_bytes() -> int:
    return int(env.env_float("HYPERSPACE_RESULT_CACHE_MB") * 1024 * 1024)


def _fold_depth_cap() -> int:
    return env.env_int("HYPERSPACE_RESULT_CACHE_FOLD_DEPTH")


def _digest(obj) -> str:
    return hashlib.blake2b(repr(obj).encode(), digest_size=16).hexdigest()


def batch_nbytes(batch) -> int:
    """Byte footprint of a ColumnBatch for the cache budget: data +
    validity + a conservative per-entry estimate for string vocabularies."""
    total = 0
    for c in batch.columns.values():
        total += c.data.nbytes
        if c.validity is not None:
            total += c.validity.nbytes
        if c.dictionary is not None:
            total += sum(len(s) for s in c.dictionary) + 8 * len(c.dictionary)
    return total


def _file_ids(scan) -> frozenset:
    return frozenset((f.name, f.size, f.modified_time) for f in scan.files)


class CachedResult:
    """One stored query result plus everything a later probe needs: the
    snapshots it is exact for, the per-scan file identity (the fold path's
    subset test), the fold spec when the fragment folds exactly, and the
    pre-optimization plan + owning session (weakly) so a background refresh
    can re-run the query template after a version advance."""

    __slots__ = (
        "key", "structure_key", "result", "nbytes", "snapshots",
        "scan_files", "fold_spec", "fold_depth", "raw_plan", "session_ref",
        "created_s", "hits",
    )

    def __init__(self, key, structure_key, result, snapshots, scan_files,
                 fold_spec, fold_depth, raw_plan, session):
        self.key = key
        self.structure_key = structure_key
        self.result = result
        self.nbytes = batch_nbytes(result)
        self.snapshots = tuple(snapshots)
        self.scan_files = tuple(scan_files)  # per-scan frozensets, preorder
        self.fold_spec = fold_spec
        self.fold_depth = fold_depth
        self.raw_plan = raw_plan
        self.session_ref = weakref.ref(session) if session is not None else None
        self.created_s = time.time()
        self.hits = 0


class ResultCache:
    """Byte-bounded LRU of CachedResults with a secondary structure index
    (template -> entries) for fold-candidate lookup, and single-flight
    population. Thread-safe; the lock is a leaf."""

    def __init__(self, name: str = "result"):
        self.name = name
        self._lock = TrackedLock(f"cache.{name}")
        self._d: OrderedDict = OrderedDict()  # key -> CachedResult
        self._by_structure: dict = {}  # structure_key -> OrderedDict[key, None]
        self._bytes = 0
        self._inflight: dict = {}
        self._inflight_lc: dict = {}  # key -> lifecycle-audit handle

    # --- metrics (outside the lock) ---------------------------------------

    def _count(self, event: str, n: int = 1) -> None:
        from ..telemetry.metrics import REGISTRY

        REGISTRY.counter(f"cache.{self.name}.{event}").inc(n)

    def _publish_bytes(self) -> None:
        from ..telemetry.metrics import REGISTRY

        with self._lock:
            b = self._bytes
        REGISTRY.gauge(f"cache.{self.name}.bytes").set(b)

    # --- store ------------------------------------------------------------

    def _unlink(self, entry: CachedResult) -> None:
        """Remove ``entry`` from both maps. Caller holds the lock."""
        self._d.pop(entry.key, None)
        self._bytes -= entry.nbytes
        sk = self._by_structure.get(entry.structure_key)
        if sk is not None:
            sk.pop(entry.key, None)
            if not sk:
                self._by_structure.pop(entry.structure_key, None)

    def put(self, entry: CachedResult) -> None:
        evicted = 0
        limit = _max_bytes()
        with self._lock:
            old = self._d.get(entry.key)
            if old is not None:
                self._unlink(old)
            self._d[entry.key] = entry
            self._d.move_to_end(entry.key)
            self._bytes += entry.nbytes
            self._by_structure.setdefault(entry.structure_key, OrderedDict())[
                entry.key
            ] = None
            while self._bytes > limit and len(self._d) > 1:
                _k, victim = next(iter(self._d.items()))
                self._unlink(victim)
                evicted += 1
            # a single over-budget entry is not worth keeping either
            if self._bytes > limit and entry.key in self._d:
                self._unlink(entry)
                evicted += 1
        if evicted:
            self._count("evictions", evicted)
        self._publish_bytes()

    def get(self, key) -> Optional[CachedResult]:
        with self._lock:
            entry = self._d.get(key)
            if entry is not None:
                self._d.move_to_end(key)
                entry.hits += 1
        return entry

    def get_or_compute(self, key, build):
        """(entry, hit: bool) — the ``BoundedLRU.get_or_put`` single-flight
        discipline: the first missing caller runs ``build()`` (which
        executes the query — always outside the lock) while the key is
        marked in flight; concurrent probes of the same key wait on its
        event and read the stored entry. A failed or CANCELLED build
        (QueryCancelledError is a BaseException) clears the marker and
        wakes the waiters so one of them takes over — an abandoned
        in-flight entry can never latch."""
        while True:
            with self._lock:
                entry = self._d.get(key)
                if entry is not None:
                    self._d.move_to_end(key)
                    entry.hits += 1
                    return entry, True
                event = self._inflight.get(key)
                if event is None:
                    event = self._inflight[key] = threading.Event()
                    self._inflight_lc[key] = tracked_resource(
                        "cache.inflight", self.name
                    )
                    building = True
                else:
                    building = False
            if not building:
                event.wait()
                continue
            try:
                entry = build()
            except BaseException:
                with self._lock:
                    self._inflight.pop(key, None)
                    lc = self._inflight_lc.pop(key, 0)
                event.set()
                release_resource(lc)
                raise
            try:
                if entry is not None:
                    self.put(entry)
            finally:
                with self._lock:
                    self._inflight.pop(key, None)
                    lc = self._inflight_lc.pop(key, 0)
                event.set()
                release_resource(lc)
            return entry, False

    # --- fold-candidate / maintenance reads -------------------------------

    def fold_candidates(self, structure_key) -> list:
        """Same-template entries, newest first (the most recently stored
        entry is closest to the new snapshot, so its delta is smallest)."""
        with self._lock:
            keys = list(self._by_structure.get(structure_key, ()))
            out = [self._d[k] for k in reversed(keys) if k in self._d]
        return out

    def entries_for_index(self, index_path: str) -> list:
        with self._lock:
            return [
                e
                for e in self._d.values()
                if any(s.index_path == index_path for s in e.snapshots)
            ]

    def invalidate_version(self, index_path: str, version: int) -> int:
        """Drop every entry pinned to (index_path, version) — called when
        vacuum physically retires the version. Exact keys already make such
        entries unreachable for direct hits; dropping them also removes
        them from the fold-candidate index and frees their bytes."""
        dropped = 0
        with self._lock:
            victims = [
                e
                for e in self._d.values()
                if any(
                    s.index_path == index_path and version in s.versions
                    for s in e.snapshots
                )
            ]
            for e in victims:
                self._unlink(e)
                dropped += 1
        if dropped:
            self._publish_bytes()
        return dropped

    # --- introspection / gates --------------------------------------------

    def check_consistency(self) -> bool:
        """Byte accounting + index coherence + no leaked in-flight markers
        (race/serve gates; call at quiescence)."""
        with self._lock:
            actual = sum(e.nbytes for e in self._d.values())
            indexed = {
                k for sk in self._by_structure.values() for k in sk
            }
            return (
                actual == self._bytes
                and self._bytes <= max(_max_bytes(), 0)
                and indexed == set(self._d)
                and not self._inflight
            )

    def state(self) -> dict:
        from ..telemetry.metrics import REGISTRY

        def val(n: str) -> int:
            m = REGISTRY.get(f"cache.{self.name}.{n}")
            return 0 if m is None else int(m.value)

        with self._lock:
            entries = len(self._d)
            byts = self._bytes
            foldable = sum(1 for e in self._d.values() if e.fold_spec)
        return {
            "mode": _mode(),
            "entries": entries,
            "foldable_entries": foldable,
            "bytes": byts,
            "max_bytes": _max_bytes(),
            "hits": val("hits"),
            "misses": val("misses"),
            "evictions": val("evictions"),
            "folds": val("folds"),
            "fold_rows": val("fold_rows"),
            "refreshes": val("refreshes"),
            "verified": val("verified"),
        }

    def clear(self) -> None:
        with self._lock:
            self._d.clear()
            self._by_structure.clear()
            self._bytes = 0
        self._publish_bytes()

    def __len__(self) -> int:
        with self._lock:
            return len(self._d)


RESULT_CACHE = ResultCache()


# ---------------------------------------------------------------------------
# the collect() integration
# ---------------------------------------------------------------------------

def _canonical_bits(batch) -> tuple:
    """Bit-exact comparable form of a result batch (verify mode): schema,
    dtypes, values with floats at .hex() precision, NULLs explicit."""
    out = []
    for name, c in batch.columns.items():
        vals = [
            x.hex() if isinstance(x, float) else x for x in c.decode().tolist()
        ]
        out.append((name, c.dtype, vals))
    return tuple(out)


def _build_key(plan, pins):
    from ..plan.kernel_cache import (
        plan_files_fingerprint,
        plan_structure_fingerprint,
    )

    structure_key = _digest(plan_structure_fingerprint(plan))
    files_key = _digest(plan_files_fingerprint(plan))
    snap_key = tuple(sorted((s.index_path, s.entry_id) for s in pins))
    return (structure_key, files_key, snap_key), structure_key


def _cacheable(plan, pins) -> bool:
    from ..plan.nodes import InMemoryScan

    if not pins:
        return False  # no snapshot authority: raw/in-memory-only plans
    return not any(isinstance(n, InMemoryScan) for n in plan.preorder())


def _verify_or_raise(session, plan, result, origin: str) -> None:
    """verify mode: recompute from scratch and compare bit-for-bit."""
    from ..plan.executor import execute_plan
    from ..telemetry.metrics import REGISTRY

    fresh = execute_plan(plan, session)
    if _canonical_bits(fresh) != _canonical_bits(result):
        raise HyperspaceError(
            f"result-cache verify divergence on {origin}: cached result "
            f"does not match recomputation (plan:\n{plan.pretty()})"
        )
    REGISTRY.counter("cache.result.verified").inc()


def serve_collect(session, raw_plan, plan):
    """The ``DataFrame.collect`` chokepoint: probe the result cache, serve
    a hit with zero scan/upload/dispatch, fold from a same-template older
    snapshot on an additive miss, or execute and populate. Falls through
    to plain execution whenever the cache is off or the plan is not
    cacheable (no pins / in-memory leaves)."""
    from ..plan.executor import execute_plan
    from ..telemetry import trace
    from ..telemetry.metrics import REGISTRY

    if not enabled():
        return execute_plan(plan, session)
    from ..ingest.snapshots import current_pins

    pins = current_pins()
    if not _cacheable(plan, pins):
        return execute_plan(plan, session)

    with trace.span("cache:probe"):
        key, structure_key = _build_key(plan, pins)
    outcome = {"via": "full"}

    def build() -> CachedResult:
        from .view_maintenance import classify_plan, try_fold
        from ..plan.nodes import FileScan

        REGISTRY.counter("cache.result.misses").inc()
        fold_spec = classify_plan(plan)
        result = None
        depth = 0
        if fold_spec is not None:
            folded = try_fold(
                session, plan, fold_spec,
                RESULT_CACHE.fold_candidates(structure_key),
            )
            if folded is not None:
                result, depth = folded
                outcome["via"] = "fold"
        if result is None:
            result = execute_plan(plan, session)
        return CachedResult(
            key, structure_key, result, pins,
            [_file_ids(n) for n in plan.preorder() if isinstance(n, FileScan)],
            fold_spec, depth, raw_plan, session,
        )

    entry, hit = RESULT_CACHE.get_or_compute(key, build)
    from ..telemetry import plan_stats

    if hit:
        REGISTRY.counter("cache.result.hits").inc()
        plan_stats.note_route(plan.plan_id, "cached")
        _log_cache_index_usage(session, plan, "ResultCacheHit")
        if is_verify():
            _verify_or_raise(session, plan, entry.result, "hit")
    elif outcome["via"] == "fold":
        plan_stats.note_route(plan.plan_id, "folded")
        _log_cache_index_usage(session, plan, "ResultCacheFold")
        if is_verify():
            _verify_or_raise(session, plan, entry.result, "fold")
    return entry.result


def _log_cache_index_usage(session, plan, rule: str) -> None:
    """Cache serves bypass the rule layer entirely, so without this the
    indexes baked into the cached plan are invisible to per-index
    attribution: emit the same ``IndexUsageEvent`` chokepoint the rewrite
    rules use, and credit the avoided index scan to the workload plane."""
    from ..plan.nodes import FileScan
    from ..rules.rule_utils import log_index_usage
    from ..telemetry import workload

    index_bytes: dict[str, int] = {}
    for n in plan.preorder():
        if isinstance(n, FileScan) and n.index_info is not None:
            index_bytes[n.index_info.index_name] = (
                index_bytes.get(n.index_info.index_name, 0)
                + sum(f.size for f in n.files)
            )
    if not index_bytes:
        return
    names = sorted(index_bytes)
    log_index_usage(
        session, rule, names,
        f"Result cache served plan using indexes: {', '.join(names)}",
    )
    for name in names:
        workload.note_index_applied(name, index_bytes[name], rule=rule)


def result_cache_state_string() -> str:
    """The hs.profile Result-cache block."""
    s = RESULT_CACHE.state()
    lines = ["== Result cache =="]
    if s["mode"] == "0":
        lines.append("disabled (HYPERSPACE_RESULT_CACHE=0)")
        return "\n".join(lines)
    looked = s["hits"] + s["misses"]
    ratio = f"{s['hits'] / looked:.2%}" if looked else "n/a"
    lines.append(
        f"mode={s['mode']} entries={s['entries']} "
        f"(foldable={s['foldable_entries']}) "
        f"bytes={s['bytes']}/{s['max_bytes']}"
    )
    lines.append(
        f"hits={s['hits']} misses={s['misses']} hit_ratio={ratio} "
        f"evictions={s['evictions']}"
    )
    lines.append(
        f"folds={s['folds']} fold_rows={s['fold_rows']} "
        f"refreshes={s['refreshes']} verified={s['verified']}"
    )
    return "\n".join(lines)
