"""Cross-query compiled-kernel cache.

Device kernels are jitted closures built from a plan fragment; tracing one
costs tens of milliseconds on CPU and up to seconds on a TPU — easily the
whole budget of a warm sub-second query. This module owns ONE process-wide
cache per kernel family, keyed by a canonical plan fingerprint:

    (kind/route flags, predicate expr repr, projection exprs, aggregate
     exprs, dtype signature of the device inputs, shape constants baked
     into the kernel body)

so a repeated query template (the TPC-H bench loop, a dashboard refresh)
skips retrace entirely — across queries, sessions, and both the monolithic
and the pipelined streaming executors (which share fingerprints by
construction, so a chunk kernel warmed by one path serves the other).

Size-class polymorphism is jax.jit's job: the cached object is the jitted
callable, which re-specializes per concrete input shape internally. Shape
constants that change the *traced body* (seg_pad, k, word count) are part
of the fingerprint instead.

Observability: `cache.kernel.{hits,misses,evictions}` counters in the
metrics registry, a `kernel.retrace` counter, and a `compile:<kind>` span
around every build — a warm query's trace carries no compile span at all,
which is the bench's "zero retraces" check.
"""

from __future__ import annotations

import threading
from collections import OrderedDict
from typing import Callable

from ..staticcheck.concurrency import TrackedLock


def _dev_dtype_label(v) -> str:
    """Stable dtype label for a device array or a Wide64 (hi, lo) pair."""
    return "wide64" if isinstance(v, tuple) else str(v.dtype)


def dtype_signature(dev_cols: dict) -> tuple:
    """Canonical (name, dtype) signature of an upload dict — order-free."""
    return tuple(sorted((n, _dev_dtype_label(a)) for n, a in dev_cols.items()))


class KernelCache:
    """Bounded LRU of compiled kernels with hit/miss/evict counters.

    Recency updates on both get and set so the hottest template survives
    churn; thread-safe (pipeline consumers and per-bucket executors hit it
    from pool workers)."""

    def __init__(self, name: str, maxlen: int):
        self.name = name
        self.maxlen = maxlen
        self._d: OrderedDict = OrderedDict()
        self._lock = TrackedLock(f"kernel_cache.{name}")
        self._inflight: dict = {}

    def _count(self, event: str, n: int = 1) -> None:
        from ..telemetry.metrics import REGISTRY

        REGISTRY.counter(f"cache.{self.name}.{event}").inc(n)

    def get(self, key, default=None):
        with self._lock:
            try:
                value = self._d[key]
            except KeyError:
                self._count("misses")
                return default
            self._d.move_to_end(key)
        self._count("hits")
        return value

    def set(self, key, value) -> None:
        evicted = 0
        with self._lock:
            self._d[key] = value
            self._d.move_to_end(key)
            while len(self._d) > self.maxlen:
                self._d.popitem(last=False)
                evicted += 1
        if evicted:
            self._count("evictions", evicted)

    def get_or_build(self, key, builder: Callable, kind: str):
        """The cached kernel for ``key``, building (and tracing) on miss
        under a ``compile:<kind>`` span. Single-flight: concurrent misses
        on one fingerprint trace ONCE — the first thread builds while the
        key is marked in-flight, the rest wait on its event and read the
        cached result (a failed build wakes them to take over). The build
        runs outside the cache lock so tracing one kernel never serializes
        unrelated kinds. Every actual build feeds the static-analysis
        layer (retrace watchdog always; jaxpr hazard audit under
        ``HYPERSPACE_KERNEL_AUDIT=1``) before caching."""
        while True:
            with self._lock:
                try:
                    kernel = self._d[key]
                    self._d.move_to_end(key)
                    hit = True
                except KeyError:
                    hit = False
                    event = self._inflight.get(key)
                    if event is None:
                        event = self._inflight[key] = threading.Event()
                        building = True
                    else:
                        building = False
            if hit:
                self._count("hits")
                return kernel
            if not building:
                event.wait()
                continue
            break
        from ..staticcheck.kernel_audit import observe_compile
        from ..telemetry import trace
        from ..telemetry.metrics import REGISTRY
        from ..utils import faults

        self._count("misses")
        try:
            with trace.span(f"compile:{kind}"):
                # `kernel.compile` injection point: fires only on actual
                # builds (a warm cache never compiles, so never faults here)
                faults.fire("kernel.compile", kind=kind)
                kernel = builder()
            REGISTRY.counter("kernel.retrace").inc()
            kernel = observe_compile(self.name, kind, key, kernel)
            self.set(key, kernel)
        finally:
            with self._lock:
                self._inflight.pop(key, None)
            event.set()
        return kernel

    def check_consistency(self) -> bool:
        """Bound + no leaked in-flight markers (race-stress gate; call at
        quiescence)."""
        with self._lock:
            return len(self._d) <= self.maxlen and not self._inflight

    def clear(self) -> None:
        with self._lock:
            self._d.clear()

    def __len__(self) -> int:
        return len(self._d)

    def __contains__(self, key) -> bool:
        with self._lock:
            return key in self._d

    def __iter__(self):
        with self._lock:
            return iter(list(self._d))


# --- canonical fingerprints -------------------------------------------------
#
# These MUST be the single source of the key tuples: the monolithic executor
# and the streaming executor share compiled kernels only because they build
# keys through the same functions.
#
# Contract: every fingerprint tuple ENDS with its dtype/column signature —
# the retrace watchdog (staticcheck/kernel_audit.py) groups fingerprints by
# that last element to detect one kind churning distinct keys over
# identical abstract shapes. A new fingerprint function must keep the
# signature last.

def fused_fingerprint(pallas_route: bool, pred_expr, proj_exprs, agg_list,
                      dev_cols: dict) -> tuple:
    """Global filter-aggregate kernel (kernel body is shape-polymorphic)."""
    return (
        pallas_route,
        repr(pred_expr),
        tuple((n, repr(e)) for n, e in proj_exprs),
        tuple((k, repr(c)) for k, c in agg_list),
        dtype_signature(dev_cols),
    )


def grouped_fingerprint(pallas_route: bool, seg_pad: int, pred_expr,
                        proj_exprs, agg_list, dev_cols: dict) -> tuple:
    """Grouped segment-reduction kernel (seg_pad is baked into the body)."""
    return (
        "grouped",
        pallas_route,
        seg_pad,
        repr(pred_expr),
        tuple((nm, repr(e)) for nm, e in proj_exprs),
        tuple((k, repr(c)) for k, c in agg_list),
        dtype_signature(dev_cols),
    )


def mesh_fingerprint(d: int, topology: tuple, seg_pad: int, pred_expr,
                     proj_exprs, agg_list, dev_cols: dict) -> tuple:
    """Distributed grouped kernel: full topology (axis names AND per-axis
    sizes) — a meshSlices change between factorizations of the same device
    count must rebuild, not reuse the stale slice mapping."""
    return (
        "mesh",
        d,
        topology,
        seg_pad,
        repr(pred_expr),
        tuple((nm, repr(e)) for nm, e in proj_exprs),
        tuple((k, repr(c)) for k, c in agg_list),
        dtype_signature(dev_cols),
    )


def mesh_probe_fingerprint(mesh_id: int, axis, l_shape: tuple, r_shape: tuple,
                           key_dtype: str) -> tuple:
    """Distributed co-partitioned probe (parallel/dist_join): the wave
    shapes are baked into the shard_map body, and a rebuilt mesh must not
    reuse closures over a dead one, hence the mesh identity."""
    return ("mesh_probe", mesh_id, axis, l_shape, r_shape, (("key", key_dtype),))


def join_fingerprint(kind: str, pads: tuple, key_dtype: str, agg_list=(),
                     residual=(), lfilters=(), rfilters=(), col_sig=()) -> tuple:
    """Bucketed-join kernels (plan/device_join): keyed on the kernel kind,
    the band pads baked into the traced body, the join-key dtype, the
    aggregate/residual/side-filter expression shapes, and the shipped-column
    signature. The band's bucket count (the leading vmap axis) is
    deliberately NOT part of the key: the cached object is the jitted
    callable, which re-specializes per leading-axis size internally, so a
    repeated join with identical band shapes provably never retraces —
    that's the warm-join "zero compile spans" contract.

    Under the memory-adaptive planner (plan/join_memory) the band pads are
    GRANT-DEPENDENT: split chunk sizes derive from
    ``HYPERSPACE_DEVICE_BUDGET_MB``, so a changed grant can land a bucket
    in a different pad class and trace a new kernel — once. The derived
    chunk sizes are quantized to powers of two on the same pad grid, so
    every repeat AT a given grant (and any nearby grant mapping to the
    same pad class) hits this cache; the warm "zero compile spans"
    contract holds per grant size, which tests pin across several."""
    return (
        "join",
        kind,
        tuple(pads),
        key_dtype,
        tuple((k, repr(c)) for k, c in agg_list),
        tuple(repr(r) for r in residual),
        tuple(repr(f) for f in lfilters),
        tuple(repr(f) for f in rfilters),
        tuple(col_sig),
    )


# --- full-plan fingerprints (cache/result_cache.py keys) --------------------
#
# The result cache extends the kernel-cache contract from plan FRAGMENTS to
# whole optimized plans: two queries share a cached result only when their
# plans are canonically identical. The fingerprint splits in two so the
# incremental-view path can recognize "same query template, grown file set":
#
#   plan_structure_fingerprint — every semantic property of the plan EXCEPT
#     the concrete leaf file lists (node kinds + arities in preorder,
#     expression reprs, scan schema/columns/pushed filters/prune decisions,
#     index identity). Equal structure = same query template.
#   plan_files_fingerprint — the per-scan (path, size, mtime) identity of
#     every resolved file, in preorder scan order. Equal files (with equal
#     structure) = bit-identical result, because execution is deterministic
#     over the resolved file set.
#
# Both are plain tuples; the result cache digests them (the file component
# of a wide scan is large) before keying.

def _scan_structure(n) -> tuple:
    """Structural identity of one FileScan, file list excluded. The prune
    spec's derived half (kept buckets, row-group conjuncts) is included:
    it is a deterministic function of predicate + layout, so old- and
    new-snapshot plans of one template agree on it — while a changed
    HYPERSPACE_PRUNE mode correctly changes the key."""
    ps = n.prune_spec
    prune = None
    if ps is not None:
        prune = (
            ps.index_name,
            ps.num_buckets,
            tuple(ps.key_columns),
            tuple(ps.sort_columns),
            tuple(sorted(ps.bucket_keep)) if ps.bucket_keep is not None else None,
            tuple(repr(c) for c in ps.rowgroup_conjuncts),
            tuple(repr(c) for c in ps.sketch_conjuncts),
            repr(ps.pred),
        )
    return (
        "FileScan",
        n.fmt,
        # an index scan's root is the commonpath of its files (cosmetic —
        # it drifts when an append adds the first extra v__=N dir); a raw
        # scan's roots are semantic (partition values derive from them)
        None if n.index_info is not None else tuple(n.root_paths),
        tuple(n.required_columns or ()),
        tuple((f.name, f.dtype) for f in n.full_schema),
        repr(n.pushed_filter),
        tuple(n.lineage_filter_ids or ()),
        (n.index_info.index_name, n.index_info.index_kind_abbr)
        if n.index_info
        else None,
        (
            n.bucket_spec.num_buckets,
            n.bucket_spec.bucket_columns,
            n.bucket_spec.sort_columns,
        )
        if n.bucket_spec
        else None,
        tuple(n.partition_columns),
        tuple(sorted(n.options.items())),
        prune,
        # approximate tier: a sampled scan must never share a key with its
        # exact twin (sampled plans also bypass the result cache outright —
        # this keeps any other structural consumer honest)
        n.sample_spec.structure_key() if n.sample_spec is not None else None,
    )


def plan_structure_fingerprint(plan) -> tuple:
    """Canonical structure of a whole optimized plan, leaf file lists
    excluded (see block comment above). Node arity rides along so preorder
    flattening cannot confuse two tree shapes; Project fingerprints its
    full expression reprs (its describe() only names outputs)."""
    from .nodes import FileScan, Project

    parts = []
    for n in plan.preorder():
        if isinstance(n, FileScan):
            parts.append(_scan_structure(n))
        elif isinstance(n, Project):
            parts.append(("Project", 1, tuple(repr(e) for e in n.exprs)))
        else:
            parts.append((n.kind, len(n.children()), n.describe()))
    return tuple(parts)


def plan_files_fingerprint(plan) -> tuple:
    """Per-scan resolved-file identity tuples ((path, size, mtime_ms),
    sorted within each scan), in preorder scan order."""
    from .nodes import FileScan

    out = []
    for n in plan.preorder():
        if isinstance(n, FileScan):
            out.append(
                tuple(sorted((f.name, f.size, f.modified_time) for f in n.files))
            )
    return tuple(out)


# process-wide caches: compiled XLA executables are the most expensive
# host-side artifact the engine builds — they outlive every query
KERNEL_CACHE = KernelCache("kernel", 256)
TOPK_CACHE = KernelCache("kernel_topk", 64)
SORT_CACHE = KernelCache("kernel_sort", 64)
JOIN_CACHE = KernelCache("kernel_join", 128)
MESH_CACHE = KernelCache("kernel_mesh", 32)
