"""Co-partitioned bucketed merge join execution.

The physical payoff of JoinIndexRule's rewrite (ref: the Exchange-free
sort-merge join Spark runs after covering/JoinIndexRule.scala:635-687, and
BucketUnionExec's 1:1 partition zip execution/BucketUnionExec.scala:52-121):
both sides arrive hash-bucketed on the join keys with identical bucket
counts, so bucket b joins only bucket b — no shuffle, no global hash table.

Execution per bucket: read only that bucket's files (bucket id parsed from
the filename), fold in hybrid-scan appended rows re-bucketed on the fly
(RepartitionByExpr marker), apply the side's residual filter/projection,
then a sorted merge join (rows are sorted within buckets by the bucket
columns at write time). Buckets run concurrently on a thread pool — the
analogue of the reference's driver-side `.par` concurrency
(zordercovering/ZOrderCoveringIndex.scala:90-94) — and pyarrow releases the
GIL during reads.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional, Sequence

import numpy as np

from .expr import Expr
from .nodes import (
    BucketSpec,
    BucketUnion,
    FileScan,
    Filter,
    Join,
    LogicalPlan,
    Project,
    RepartitionByExpr,
)
from ..columnar.table import ColumnBatch, STRING
from ..models.covering import bucket_id_from_filename
from ..ops.bucketize import bucket_ids_for_batch
from ..ops.join import host_merge_join_indices
from ..telemetry import attribution as _attr
from ..telemetry import trace
from ..telemetry.metrics import REGISTRY
from ..utils.workers import io_pool, io_worker_count


def _join_pipeline_enabled() -> bool:
    """Joins share the executor's pipeline switch: ``HYPERSPACE_PIPELINE=0``
    keeps the load-all barrier + global-pad behavior (which the streamed +
    banded path must match bit for bit)."""
    from .tpu_exec import _pipeline_enabled

    return _pipeline_enabled()


def _join_pipeline_overlap() -> bool:
    from .tpu_exec import _pipeline_overlap

    return _pipeline_overlap()


class _PlainJoinIneligible(Exception):
    """A streamed bucket pair turned out device-ineligible (string/null/
    unkeyable keys): the whole batched plain join declines to the
    per-bucket path, which reuses the already-loaded pairs."""


@dataclass
class BucketedSide:
    """One join side decomposed into bucket-addressable pieces. `ops` are the
    Filter/Project nodes between the scan and the join, ordered bottom-up
    (nearest the scan first) so per-bucket execution replays them exactly."""

    scan: FileScan  # the bucketed index scan
    spec: BucketSpec
    appended: Optional[LogicalPlan]  # subplan under RepartitionByExpr, if any
    ops: list[LogicalPlan]  # Filter/Project nodes, bottom-up

    @property
    def filters(self) -> list[Expr]:
        return [op.condition for op in self.ops if isinstance(op, Filter)]

    @property
    def project(self) -> Optional[Project]:
        for op in self.ops:
            if isinstance(op, Project):
                return op
        return None

    def __post_init__(self):
        # bucket id -> files, parsed once (hot path indexes this per bucket)
        self._files_by_bucket: dict[int, list] = {}
        for f in self.scan.files:
            b = bucket_id_from_filename(f.name)
            self._files_by_bucket.setdefault(b, []).append(f)

    def files_for_bucket(self, b: int) -> list:
        return self._files_by_bucket.get(b, [])

    def key_is_identity(self, name: str) -> bool:
        """True iff output column `name` is the scan column `name` unchanged
        (an aliased/derived projection would decouple the join values from
        the on-disk hash placement)."""
        if self.project is None:
            return True
        from .expr import Alias, Col, expr_output_name

        for e in self.project.exprs:
            if expr_output_name(e) == name:
                inner = e.child if isinstance(e, Alias) else e
                return isinstance(inner, Col) and inner.name == name
        return False


def _decompose_side(plan: LogicalPlan) -> Optional[BucketedSide]:
    """Match any stack of Filter/Project (at most one Project) over
    (bucketed FileScan | BucketUnion(bucketed FileScan,
    RepartitionByExpr(subplan)))."""
    node = plan
    ops_topdown: list[LogicalPlan] = []
    n_projects = 0
    while isinstance(node, (Project, Filter)):
        if isinstance(node, Project):
            n_projects += 1
            if n_projects > 1:
                return None
        ops_topdown.append(node)
        node = node.child
    appended = None
    if isinstance(node, BucketUnion):
        children = node.children()
        scans = [c for c in children if isinstance(c, FileScan)]
        reparts = [c for c in children if isinstance(c, RepartitionByExpr)]
        if len(scans) != 1 or len(reparts) != 1 or len(children) != 2:
            return None
        appended = reparts[0].child
        node = scans[0]
    if not isinstance(node, FileScan) or node.bucket_spec is None:
        return None
    # every index file must carry a parseable bucket id
    if any(bucket_id_from_filename(f.name) is None for f in node.files):
        return None
    return BucketedSide(node, node.bucket_spec, appended, list(reversed(ops_topdown)))


def try_bucketed_scan_aggregate(agg_plan, session) -> Optional[ColumnBatch]:
    """Aggregate(group_by ⊇ bucket columns)(bucketed scan stack): every group
    lives in exactly one bucket, so buckets aggregate independently on a
    thread pool and results concatenate (the grouped form of an index-only
    scan — e.g. per-key averages over a covering index)."""
    from .nodes import Aggregate, InMemoryScan
    from .expr import Col

    if not agg_plan.group_exprs:
        return None
    side = _decompose_side(agg_plan.child)
    if side is None or side.appended is not None:
        return None
    group_cols = set()
    for e in agg_plan.group_exprs:
        if not isinstance(e, Col):
            return None
        group_cols.add(e.name.lower())
    bucket_cols = {c.lower() for c in side.spec.bucket_columns}
    if not bucket_cols <= group_cols:
        return None  # a group could span buckets
    if not all(side.key_is_identity(c) for c in side.spec.bucket_columns):
        return None

    def agg_bucket(b: int) -> Optional[ColumnBatch]:
        from .executor import _exec_aggregate

        batch = _load_side_bucket(side, b, None, session)
        if batch is None or batch.num_rows == 0:
            return None
        sub = Aggregate(agg_plan.group_exprs, agg_plan.agg_exprs, InMemoryScan(batch))
        return _exec_aggregate(sub, session)

    n = side.spec.num_buckets
    with io_pool(io_worker_count(n), "hs-join") as pool:
        parts = [
            p for p in pool.map(_attr.bound(agg_bucket), range(n))
            if p is not None
        ]
    if not parts:
        # every bucket filtered to nothing: produce the empty grouped shape
        # without re-scanning (the data was already read once above)
        from .executor import _exec_aggregate, execute_plan
        from .nodes import InMemoryScan

        empty_side = BucketedSide(
            side.scan.copy(files=[]), side.spec, None, side.ops
        )
        empty_batch = _load_side_bucket(empty_side, 0, None, session)
        sub = Aggregate(
            agg_plan.group_exprs, agg_plan.agg_exprs, InMemoryScan(empty_batch)
        )
        return _exec_aggregate(sub, session)
    return ColumnBatch.concat(parts)


def try_bucketed_join_aggregate(agg_plan, session) -> Optional[ColumnBatch]:
    """Aggregate(group_by ⊇ join key)(Join(co-bucketed sides)): groups are
    disjoint across buckets, so each bucket joins AND aggregates locally and
    results simply concatenate — the join output never materializes (the
    partial-aggregation-over-SMJ shape of TPC-H Q3)."""
    from .nodes import Aggregate
    from .executor import extract_equi_keys
    from .expr import Col

    child = agg_plan.child
    if not isinstance(child, Join) or not agg_plan.group_exprs:
        return None
    group_cols = []
    for e in agg_plan.group_exprs:
        if not isinstance(e, Col):
            return None
        group_cols.append(e.name)
    lkeys, rkeys, _res = extract_equi_keys(
        child.condition, child.left.schema, child.right.schema
    ) if child.condition is not None else ([], [], [])
    # Buckets hash the FULL composite key tuple, so a group is guaranteed
    # bucket-local only when the grouping determines every key component:
    # each (lk, rk) pair (equal in the join output) must appear in the
    # group columns. Grouping by a strict subset of a multi-column key
    # would concatenate unmerged per-bucket partials.
    group_set = {c.lower() for c in group_cols}
    if not lkeys:
        return None
    if not all(
        lk.lower() in group_set or rk.lower() in group_set
        for lk, rk in zip(lkeys, rkeys)
    ):
        return None  # groups may span buckets: cannot aggregate per bucket

    def per_bucket(batch: ColumnBatch) -> ColumnBatch:
        from .executor import _exec_aggregate
        from .nodes import InMemoryScan

        sub = Aggregate(agg_plan.group_exprs, agg_plan.agg_exprs, InMemoryScan(batch))
        return _exec_aggregate(sub, session)

    return try_bucketed_merge_join(
        child, session, per_bucket=per_bucket, agg_plan=agg_plan
    )


def try_bucketed_merge_join(
    plan: Join, session, per_bucket=None, agg_plan=None
) -> Optional[ColumnBatch]:
    """Execute an equi join of two co-bucketed sides; None if the plan does
    not have the co-partitioned shape. `per_bucket` post-processes each
    bucket's joined rows before concatenation (used by the fused aggregate);
    when `agg_plan` is also given and TPU exec is enabled, eligible buckets
    run the fused join+aggregate ON DEVICE (plan.device_join) without ever
    materializing the join output — the host path is the fallback."""
    from .executor import execute_plan, extract_equi_keys

    if plan.how != "inner" or plan.condition is None:
        return None
    left = _decompose_side(plan.left)
    right = _decompose_side(plan.right)
    if left is None or right is None:
        return None
    if left.spec.num_buckets != right.spec.num_buckets:
        return None
    lkeys, rkeys, residual = extract_equi_keys(
        plan.condition, plan.left.schema, plan.right.schema
    )
    # join keys must be identity pass-throughs of the bucketed scan columns —
    # the name check below is meaningless if a projection rebinds the name
    if not all(left.key_is_identity(k) for k in lkeys):
        return None
    if not all(right.key_is_identity(k) for k in rkeys):
        return None
    # bucket columns must be exactly the join keys, pairwise aligned
    pairs = list(zip(lkeys, rkeys))
    if list(left.spec.bucket_columns) != lkeys or list(right.spec.bucket_columns) != rkeys:
        # allow order-permuted equality as long as the pairing matches
        if len(left.spec.bucket_columns) != len(lkeys):
            return None
        lmap = {a.lower(): b.lower() for a, b in pairs}
        for a, b in zip(left.spec.bucket_columns, right.spec.bucket_columns):
            if lmap.get(a.lower()) != b.lower():
                return None
    plan.schema  # ambiguity check before doing any work

    import time as _time

    n = left.spec.num_buckets
    appended_parts = _bucketize_appended(left, n, session), _bucketize_appended(right, n, session)
    t0 = _time.perf_counter()

    # per-bucket-pair memory plan (broadcast/banded/split + grant-derived
    # split row counts) from the cached footer stats — None when the device
    # ledger is disabled or the device tier is off; planning surprises must
    # never kill the join, only fall back to the fixed threshold
    strategy = None
    if session is not None and session.conf.exec_tpu_enabled:
        from .join_memory import plan_join_memory

        try:
            strategy = plan_join_memory(left, right, session)
        except Exception:
            strategy = None

    def _done(out, path):
        # uniform index-usage event + pipeline counters for EVERY execution
        # path (satellite: the device paths used to emit nothing)
        _log_join_exec(session, left, right, path)
        from ..telemetry import plan_stats

        # the whole-join device paths route "device"; the per-bucket loop
        # (host merge, or per-bucket device kernels) stays "bucketed"
        plan_stats.note_route(
            (agg_plan or plan).plan_id,
            "bucketed" if path == "per_bucket" else "device",
        )
        if path != "per_bucket":
            REGISTRY.counter("pipeline.join.queries").inc()
            REGISTRY.histogram("pipeline.join.query_ms").observe(
                (_time.perf_counter() - t0) * 1000
            )
        return out

    preloaded = None
    if agg_plan is None and per_bucket is None:
        # device execution of the whole join: across the mesh when one is
        # active (co-partitioning makes each shard's join local, zero
        # collectives), else the band-stacked single-device probe + run
        # expansion with two fetches total. Bucket pairs STREAM through the
        # read-ahead loader; a decline hands the already-loaded pairs to
        # the per-bucket path below, so nothing re-reads.
        dev_out, loaded, path = _try_device_join_paths(
            left, right, lkeys, rkeys, residual, appended_parts, session,
            strategy=strategy,
        )
        if dev_out is not None:
            return _done(dev_out, path)
        if loaded is not None:
            REGISTRY.counter("pipeline.join.aborted").inc()
            preloaded = loaded
    if agg_plan is not None and per_bucket is not None and _fused_device_possible(
        session, left, right, lkeys, rkeys
    ) and _stacked_plan_screen(
        session, agg_plan, left, right, lkeys, rkeys, residual
    ):
        # fused join+aggregate with band-stacked device dispatches + ONE
        # fetch (plan.device_join.try_stacked_join_agg) — every fetch is a
        # blocking device->host round trip, so the whole join pays 1, not
        # num_buckets. Buckets load RAW (side filters
        # evaluate IN-KERNEL over stable index-chunk buffers, so
        # steady-state repeats upload nothing) and STREAM: a band wave
        # dispatches while later pairs still decode. The plan screen above
        # keeps structurally-ineligible queries on the pushed-filter load;
        # a data-dependent decline below (dup keys, nulls, int ranges)
        # replays the side ops on the raw batches — the read cost is sunk,
        # so reuse beats a second scan.
        from .device_join import try_stacked_join_agg

        raw_loaded: list = [None] * n
        pipelined = _join_pipeline_enabled()
        if pipelined:
            gen = _iter_bucket_pairs(
                left, right, appended_parts, session, raw=True,
                overlap=_join_pipeline_overlap(),
            )
        else:
            gen = iter(
                [
                    (b,) + t
                    for b, t in enumerate(
                        _load_all_bucket_pairs(
                            left, right, appended_parts, session, raw=True
                        )
                    )
                ]
            )

        def raw_pairs():
            for b, lb, rb, ls, rs in gen:
                raw_loaded[b] = (lb, rb, ls, rs)
                yield b, lb, rb, ls, rs

        try:
            dev_out = try_stacked_join_agg(
                raw_pairs(),
                lkeys,
                rkeys,
                residual,
                session,
                agg_plan,
                lfilters=tuple(left.filters),
                rfilters=tuple(right.filters),
                lcols_avail=set(plan.left.schema.names),
                rcols_avail=set(plan.right.schema.names),
                banded=pipelined,
                strategy=strategy,
            )
            if dev_out is not None:
                return _done(dev_out, "stacked_agg")
            for b, lb, rb, ls, rs in gen:  # drain: fallback reuses every pair
                raw_loaded[b] = (lb, rb, ls, rs)
        finally:
            # the stacked path can return early (device success) or raise
            # (cancellation, device fault) with pairs still undelivered;
            # close the streaming generator explicitly instead of leaving
            # its BudgetStream to GC-driven GeneratorExit
            if pipelined:
                gen.close()
        REGISTRY.counter("pipeline.join.aborted").inc()
        preloaded = [
            None
            if t is None
            else (
                None if t[0] is None else _apply_side_ops(left, t[0]),
                None if t[1] is None else _apply_side_ops(right, t[1]),
                t[2],
                t[3],
            )
            for t in raw_loaded
        ]

    def join_bucket(b: int) -> Optional[ColumnBatch]:
        # filters and projections preserve row order, so a bucket loaded from
        # ONE index file keeps its on-disk sort by the bucket columns; a
        # multi-file bucket (incremental refresh in MERGE mode) or a
        # hybrid-scan append produces an unsorted concatenation
        if preloaded is not None and preloaded[b] is not None:
            lb, rb, l_sorted, r_sorted = preloaded[b]
        else:
            l_sorted = appended_parts[0] is None and len(left.files_for_bucket(b)) <= 1
            r_sorted = appended_parts[1] is None and len(right.files_for_bucket(b)) <= 1
            lb = _load_side_bucket(left, b, appended_parts[0], session)
            rb = _load_side_bucket(right, b, appended_parts[1], session)
        if lb is None or rb is None or lb.num_rows == 0 or rb.num_rows == 0:
            return None
        if agg_plan is not None:
            from .device_join import try_device_join_agg, try_host_join_agg

            fused = try_device_join_agg(
                agg_plan, lb, rb, lkeys, rkeys, residual, session, r_sorted
            )
            if fused is None:
                # numpy twin of the fused kernel: the join output does not
                # materialize on the host path either
                fused = try_host_join_agg(
                    agg_plan, lb, rb, lkeys, rkeys, residual, session, r_sorted
                )
            if fused is not None:
                return fused
        # plain (non-aggregated, or fused-declined) join: the probe phase
        # runs on device when the tier is up; output is bit-identical to the
        # host merge join, so downstream operators are none the wiser
        from .device_join import try_device_plain_join

        joined = try_device_plain_join(
            lb, rb, lkeys, rkeys, session, l_sorted, r_sorted
        )
        if joined is None:
            joined = _merge_join_batches(lb, rb, lkeys, rkeys, l_sorted, r_sorted)
        for r in residual:
            joined = joined.filter(np.asarray(r.eval(joined).data, dtype=bool))
        if per_bucket is not None:
            joined = per_bucket(joined)
        return joined

    with io_pool(io_worker_count(n), "hs-join") as pool:
        parts = [
            p for p in pool.map(_attr.bound(join_bucket), range(n))
            if p is not None
        ]
    if not parts:
        if per_bucket is not None:
            return _done(per_bucket(_empty_like(plan)), "per_bucket")
        return _done(_empty_like(plan), "per_bucket")
    return _done(ColumnBatch.concat(parts), "per_bucket")


def _log_join_exec(session, left: "BucketedSide", right: "BucketedSide",
                   path: str) -> None:
    """Index-usage event for the bucketed-join EXECUTION tiers. The rewrite
    event (JoinIndexRule) fires at plan time, but which physical path ran —
    mesh, band-stacked device probe, stacked fused aggregate, or the
    per-bucket flow — was invisible on the device tiers. Routed through
    rule_utils.log_index_usage so join executions appear in telemetry
    uniformly with the five rewrite rules (event + rules.usage counter +
    trace event). Manually-built bucketed scans without index_info stay
    silent."""
    if session is None:
        return
    names = sorted(
        {
            s.scan.index_info.index_name
            for s in (left, right)
            if s.scan.index_info is not None
        }
    )
    if not names:
        return
    from ..rules.rule_utils import log_index_usage

    log_index_usage(
        session,
        "BucketedJoinExec",
        names,
        f"Bucketed join executed ({path}): {', '.join(names)}",
    )


class _SchemaCols:
    """Duck-typed stand-in for a ColumnBatch in plan-level eligibility
    screens: exposes `.columns` membership and `.column(name).dtype` from a
    scan schema, so structural checks run WITHOUT loading a byte."""

    def __init__(self, schema):
        self.columns = {f.name: f for f in schema}

    def column(self, name):
        return self.columns[name]


def _no_derived_rebinding(side: BucketedSide, names) -> bool:
    """True iff no referenced name is a DERIVED projection output on this
    side: the stacked device path reads raw scan columns by name, so a
    Project that derives an expression under an existing raw column name
    (e.g. (price*(1-disc)).alias('price')) would silently bind the raw
    column instead of the derivation. Names absent from the projection's
    outputs are scan-level references (filters below the project) and bind
    raw columns on the host path too — those are fine."""
    project = side.project
    if project is None:
        return True
    from .expr import Alias, Col, expr_output_name

    for e in project.exprs:
        out = expr_output_name(e)
        if out in names:
            inner = e.child if isinstance(e, Alias) else e
            if not (isinstance(inner, Col) and inner.name == out):
                return False
    return True


def _stacked_plan_screen(
    session, agg_plan, left, right, lkeys, rkeys, residual
) -> bool:
    """Structural (data-independent) eligibility for the stacked fused
    join+aggregate, evaluated BEFORE the raw bucket load: a query that can
    never take the device path must keep its pushed-filter (row-group
    pruned) load instead of paying an unpruned raw scan for nothing."""
    from .device_join import _stacked_eligibility
    from .expr import Col as _Col

    try:
        lschema = _SchemaCols(left.scan.full_schema)
        rschema = _SchemaCols(right.scan.full_schema)
        elig = _stacked_eligibility(
            agg_plan,
            lschema,
            rschema,
            lkeys,
            rkeys,
            residual,
            tuple(left.filters),
            tuple(right.filters),
            set(agg_plan.child.left.schema.names),
            set(agg_plan.child.right.schema.names),
            exact_f64=session.conf.exec_exact_f64_aggregates,
        )
        if elig is None:
            return False
        # every column the kernel touches must reach the raw scan unchanged
        refs: set[str] = set(lkeys) | set(rkeys)
        for g in agg_plan.group_exprs:
            if isinstance(g, _Col):
                refs.add(g.name)
        for e in list(agg_plan.agg_exprs) + list(residual):
            refs |= e.references()
        for f in list(left.filters) + list(right.filters):
            refs |= f.references()
        return _no_derived_rebinding(left, refs) and _no_derived_rebinding(
            right, refs
        )
    except Exception:
        return False  # any screening surprise: pushed load + host path


def _plain_join_plan_screen(left, right, lkeys, rkeys, session) -> Optional[bool]:
    """Plan-level device-join eligibility BEFORE any bucket loads: single
    key, non-string dtype (data-dependent checks — nulls, int32 range —
    still run per bucket). None = ineligible."""
    if session is None or not session.conf.exec_tpu_enabled:
        return None
    if len(lkeys) != 1:
        return None
    for side, key in ((left, lkeys[0]), (right, rkeys[0])):
        try:
            f = side.scan.full_schema.field(key)
        except Exception:
            f = None
        if f is not None and f.dtype == "string":
            return None
    return True


_INELIGIBLE = object()  # sentinel: bucket pair can never take the device path


def _prep_plain_work(b, lb, rb, lkeys, rkeys, l_sorted, r_sorted):
    """One bucket pair -> the 9-tuple work item the batched device join
    consumes, ``None`` for an empty pair, or ``_INELIGIBLE`` (string/null/
    unkeyable keys). The argsorts cache on the source key buffer's identity
    (repeat queries skip the sort)."""
    from ..ops.join import exact_key32
    from ..utils.device_cache import HOST_DERIVED_CACHE

    if lb is None or rb is None or lb.num_rows == 0 or rb.num_rows == 0:
        return None
    lk_col, rk_col = lb.column(lkeys[0]), rb.column(rkeys[0])
    if lk_col.dtype == STRING or rk_col.dtype == STRING:
        return _INELIGIBLE
    if lk_col.validity is not None or rk_col.validity is not None:
        return _INELIGIBLE
    lk32, rk32 = exact_key32(lk_col.data), exact_key32(rk_col.data)
    if lk32 is None or rk32 is None or lk32.dtype != rk32.dtype:
        return _INELIGIBLE
    lorder = rorder = None
    if not l_sorted:
        lorder = HOST_DERIVED_CACHE.get_or_put(
            lk_col.data, ("jorder",), lambda a=lk32: np.argsort(a, kind="stable")
        )
        lk32 = lk32[lorder]
    if not r_sorted:
        rorder = HOST_DERIVED_CACHE.get_or_put(
            rk_col.data, ("jorder",), lambda a=rk32: np.argsort(a, kind="stable")
        )
        rk32 = rk32[rorder]
    return (b, lb, rb, lk32, rk32, lorder, rorder, lk_col.data, rk_col.data)


def _collect_plain_join_work(left, right, lkeys, rkeys, appended_parts, session):
    """Barrier form (mesh path + HYPERSPACE_PIPELINE=0): load every bucket
    pair on the pool, prep probe keys, screen totals/dtypes up front.
    Returns (work, loaded); work is None when any bucket is
    device-ineligible or the join is too small for the device probe."""
    from .device_join import _PLAIN_MIN_ROWS

    loaded = _load_all_bucket_pairs(left, right, appended_parts, session)
    work = []
    total_rows = 0
    for b, (lb, rb, l_sorted, r_sorted) in enumerate(loaded):
        w = _prep_plain_work(b, lb, rb, lkeys, rkeys, l_sorted, r_sorted)
        if w is _INELIGIBLE:
            return None, loaded
        if w is None:
            continue
        total_rows += lb.num_rows
        work.append(w)
    if not work or total_rows < _PLAIN_MIN_ROWS:
        return None, loaded
    dt = work[0][3].dtype
    if any(w[3].dtype != dt for w in work):
        return None, loaded
    return work, loaded


def _load_all_bucket_pairs(left, right, appended_parts, session, raw=False):
    """Barrier loader (mesh path + HYPERSPACE_PIPELINE=0): every bucket pair
    on a thread pool, ALL pairs materialized before any device work. Returns
    [(lb, rb, l_sorted, r_sorted)] indexed by bucket. raw=True skips the
    side ops and pushed filters (device paths evaluate them in-kernel so
    uploads derive from stable, cacheable index-chunk buffers). The
    pipelined executors use _iter_bucket_pairs instead."""
    n = left.spec.num_buckets

    def load(b):
        l_sorted = appended_parts[0] is None and len(left.files_for_bucket(b)) <= 1
        r_sorted = appended_parts[1] is None and len(right.files_for_bucket(b)) <= 1
        lb = _load_side_bucket(left, b, appended_parts[0], session, raw=raw)
        rb = _load_side_bucket(right, b, appended_parts[1], session, raw=raw)
        return lb, rb, l_sorted, r_sorted

    with io_pool(io_worker_count(n), "hs-join") as pool:
        return list(pool.map(_attr.bound(load), range(n)))


def _iter_bucket_pairs(left, right, appended_parts, session, raw=False,
                       overlap=True):
    """Ordered ``(bucket, lb, rb, l_sorted, r_sorted)`` stream replacing the
    load-all barrier: pair loads run ahead on the IO pool with at most
    ``width + 2`` pairs in flight, reserving estimated decoded bytes
    through the GLOBAL budget ledger (serve/budget.py) shared with the
    scan streamer and every concurrent query — so the device
    probe/dispatch work the consumer does for bucket N overlaps bucket
    N+1's parquet decode without ballooning host memory, and a query that
    both streams a scan and loads join pairs no longer double-counts its
    entitlement. Each pair is produced by the same
    ``_load_side_bucket`` calls the barrier loader makes, so the stream is
    bit-identical to it pair for pair. ``overlap=False``
    (``HYPERSPACE_PIPELINE=serial``) decodes on the caller's thread, one
    pair per request — the staged-but-no-overlap debug mode."""
    from ..serve import budget as serve_budget
    from ..serve import context as serve_ctx

    n = left.spec.num_buckets

    def load(b):
        import time as _time

        t0 = _time.perf_counter()
        l_sorted = appended_parts[0] is None and len(left.files_for_bucket(b)) <= 1
        r_sorted = appended_parts[1] is None and len(right.files_for_bucket(b)) <= 1
        lb = _load_side_bucket(left, b, appended_parts[0], session, raw=raw)
        rb = _load_side_bucket(right, b, appended_parts[1], session, raw=raw)
        # pair decode is the join's io phase (pool-thread time charged to
        # the submitting query's attribution target via bound())
        _attr.charge_phase("io", _time.perf_counter() - t0)
        return lb, rb, l_sorted, r_sorted

    width = io_worker_count(n)
    if not overlap or width <= 1 or n < 2:
        for b in range(n):
            serve_ctx.check_cancelled()
            with trace.span("join:load", bucket=b) as sp:
                out = load(b)
                sp.set_attr("rows_l", 0 if out[0] is None else out[0].num_rows)
                sp.set_attr("rows_r", 0 if out[1] is None else out[1].num_rows)
            REGISTRY.counter("pipeline.join.pairs").inc()
            yield (b,) + out
        return

    # estimated decoded bytes per pair: both sides' file bytes x2 (columnar
    # compression ratios vary; the budget is a backstop, not accounting)
    ests = [
        max(
            1,
            sum(
                f.size
                for side in (left, right)
                for f in side.files_for_bucket(b)
            ),
        )
        * 2
        for b in range(n)
    ]
    max_inflight = width + 2
    if serve_ctx.current_query() is not None:
        # serving layer: pair loads are tasks on the shared engine pool so
        # total decode parallelism stays bounded across concurrent queries
        from ..utils.workers import shared_io_pool

        pool, owned = shared_io_pool(), False
    else:
        pool, owned = io_pool(width, "hs-join-io"), True
    bstream = serve_budget.global_budget().stream("join")
    futures: dict = {}
    state = {"next": 0}

    def _pump() -> None:
        while (
            state["next"] < n
            and len(futures) < max_inflight
            and bstream.try_reserve(ests[state["next"]])
        ):
            b = state["next"]
            futures[b] = pool.submit(_attr.bound(load), b)
            state["next"] += 1

    try:
        _pump()
        for b in range(n):
            serve_ctx.check_cancelled()
            with trace.span("join:load", bucket=b) as sp:
                out = futures.pop(b).result()
                sp.set_attr("rows_l", 0 if out[0] is None else out[0].num_rows)
                sp.set_attr("rows_r", 0 if out[1] is None else out[1].num_rows)
            bstream.release(ests[b])
            _pump()
            REGISTRY.counter("pipeline.join.pairs").inc()
            yield (b,) + out
    finally:
        try:
            for f in futures.values():
                f.cancel()
            if owned:
                pool.shutdown(wait=False)
        finally:
            # returns outstanding reservations (cancel path); must run
            # even if a cancel/shutdown above raises
            bstream.close()


def _apply_side_ops(side: BucketedSide, batch: ColumnBatch) -> ColumnBatch:
    """Replay a side's Filter/Project ops on a raw-loaded bucket (exactly
    what _load_side_bucket does post-scan) — recovers the filtered batch
    when a device path that loaded raw declines."""
    for op in side.ops:
        if isinstance(op, Filter):
            batch = batch.filter(
                np.asarray(op.condition.eval(batch).data, dtype=bool)
            )
        else:
            from .expr import expr_output_name

            batch = ColumnBatch(
                {expr_output_name(e): e.eval(batch) for e in op.exprs}
            )
    return batch


def _fused_device_possible(session, left, right, lkeys, rkeys) -> bool:
    """Gate for the all-bucket fused path: backend up, plan-level key
    eligibility (single non-string, non-f64 key — knowable from the
    schema without loading a byte). Joins beyond the in-memory budget
    stay on the fused path when it can run memory-adaptively (pipelined
    pair streaming under the host ledger + band waves parking/spilling
    under the device ledger); only the barrier mode — or a disabled
    device ledger — still declines oversized builds to the per-bucket
    flow, the pre-adaptive behavior."""
    from ..utils.backend import device_healthy

    if session is None or not session.conf.exec_tpu_enabled:
        return False
    if _plain_join_plan_screen(left, right, lkeys, rkeys, session) is None:
        return False
    for side, key in ((left, lkeys[0]), (right, rkeys[0])):
        try:
            f = side.scan.full_schema.field(key)
        except Exception:
            f = None
        if f is not None and f.dtype == "float64":
            return False  # f64 join keys never ship (match structure)
    total_bytes = sum(
        f.size for side in (left, right) for f in side.scan.files
    )
    if total_bytes > session.conf.build_max_bytes_in_memory:
        from ..serve.budget import device_budget

        if not (_join_pipeline_enabled() and device_budget().max_bytes > 0):
            return False
    return device_healthy()


def _empty_join_output(lb: ColumnBatch, rb: ColumnBatch) -> ColumnBatch:
    """Zero-row joined batch with the correct output schema (built from any
    occupied bucket pair's columns) — a disjoint-keys join is a RESULT, not
    a reason to redo the whole join on the host."""
    empty = np.empty(0, dtype=np.int64)
    out = {nm: c.take(empty) for nm, c in lb.columns.items()}
    out.update({nm: c.take(empty) for nm, c in rb.columns.items()})
    return ColumnBatch(out)


def _try_device_join_paths(
    left, right, lkeys, rkeys, residual, appended_parts, session,
    strategy=None,
):
    """Device execution of the full co-partitioned join. Returns
    ``(result, loaded, path)``: result None -> the caller's per-bucket path,
    which reuses ``loaded`` ([(lb, rb, l_sorted, r_sorted)] indexed by
    bucket, possibly None when the screens declined before loading).

    The mesh path (when a mesh is active) collects every pair up front —
    its shard waves need the full set — and gets first shot. Otherwise
    bucket pairs STREAM through _iter_bucket_pairs into the band-stacked
    probe (device_join.try_batched_plain_join), whose waves dispatch while
    later pairs still decode; HYPERSPACE_PIPELINE=0 keeps the barrier +
    one-global-wave behavior."""
    from ..parallel.mesh import active_mesh
    from ..utils.backend import device_healthy

    if _plain_join_plan_screen(left, right, lkeys, rkeys, session) is None:
        return None, None, None
    if not device_healthy():
        return None, None, None
    from ..parallel.mesh import is_hierarchical

    mesh = active_mesh(session)
    if mesh is not None and is_hierarchical(mesh):
        # the co-partitioned probe moves bucket rows: intra-slice only by
        # design (same rationale as the build exchange) — on a hierarchical
        # mesh fall through to the single-device / host tiers
        mesh = None
    from .device_join import try_batched_plain_join

    if mesh is not None or not _join_pipeline_enabled():
        work, loaded = _collect_plain_join_work(
            left, right, lkeys, rkeys, appended_parts, session
        )
        if work is None:
            return None, loaded, None
        if mesh is not None:
            out = _mesh_join_work(mesh, work, residual, session, left, right)
            if out is not None:
                return out, loaded, "mesh"
        parts = try_batched_plain_join(work, residual, session, banded=False,
                                       strategy=strategy)
        if parts is None:
            return None, loaded, None
        ordered = [parts[b] for b in sorted(parts)]
        out = (
            ColumnBatch.concat(ordered)
            if ordered
            else _empty_join_output(work[0][1], work[0][2])
        )
        return out, loaded, "batched"

    # ---- streamed + banded: prep each pair as it arrives -----------------
    n = left.spec.num_buckets
    loaded: list = [None] * n
    gen = _iter_bucket_pairs(
        left, right, appended_parts, session,
        overlap=_join_pipeline_overlap(),
    )

    def work_items():
        for b, lb, rb, ls, rs in gen:
            loaded[b] = (lb, rb, ls, rs)
            w = _prep_plain_work(b, lb, rb, lkeys, rkeys, ls, rs)
            if w is _INELIGIBLE:
                raise _PlainJoinIneligible()
            if w is not None:
                yield w

    try:
        try:
            parts = try_batched_plain_join(work_items(), residual, session,
                                           banded=True, strategy=strategy)
        except _PlainJoinIneligible:
            parts = None
        for b, lb, rb, ls, rs in gen:  # drain: the fallback reuses every pair
            loaded[b] = (lb, rb, ls, rs)
    finally:
        # a raise out of the batched join (cancellation, device fault)
        # abandons the streaming generator mid-flight; without an explicit
        # close its BudgetStream would wait on GC-driven GeneratorExit to
        # return its read-ahead bytes
        gen.close()
    if parts is None:
        return None, loaded, None
    ordered = [parts[b] for b in sorted(parts)]
    if ordered:
        return ColumnBatch.concat(ordered), loaded, "batched"
    occupied = next(
        (
            t
            for t in loaded
            if t is not None
            and t[0] is not None
            and t[1] is not None
            and t[0].num_rows
            and t[1].num_rows
        ),
        None,
    )
    if occupied is None:
        return None, loaded, None  # nothing occupied: per-bucket empty shape
    return _empty_join_output(occupied[0], occupied[1]), loaded, "batched"


def _mesh_join_work(mesh, work, residual, session=None, left=None,
                    right=None) -> Optional[ColumnBatch]:
    """Join pre-collected bucket work across the device mesh: the probe
    phase runs one shard_map wave per `mesh_devices` buckets
    (parallel.dist_join — shard-local, zero collectives by co-partitioning);
    run expansion and column gathers stay on the host, so the output is
    bit-identical to the per-bucket host merge join including bucket order.
    None -> next device path."""
    from ..utils.backend import record_device_failure

    from ..ops.join import expand_runs
    from ..parallel.mesh import num_shards
    from ..parallel.dist_join import mesh_join_probe
    from .device_join import _pow2

    S = num_shards(mesh)
    pad_l = _pow2(max(len(w[3]) for w in work))
    pad_r = _pow2(max(len(w[4]) for w in work))
    dt = work[0][3].dtype
    pad_val = np.iinfo(dt).max if dt.kind == "i" else np.float32(np.inf)

    parts: dict[int, ColumnBatch] = {}
    for wave_start in range(0, len(work), S):
        wave = work[wave_start : wave_start + S]
        lk_stack = np.full((S, pad_l), pad_val, dtype=dt)
        rk_stack = np.full((S, pad_r), pad_val, dtype=dt)
        n_r = np.zeros(S, dtype=np.int64)
        for i, (_b, _lb, _rb, lk32, rk32, _lo, _ro, _ls, _rs) in enumerate(wave):
            lk_stack[i, : len(lk32)] = lk32
            rk_stack[i, : len(rk32)] = rk32
            n_r[i] = len(rk32)
        try:
            # only the DEVICE step may trip the circuit breaker — a host
            # bug in gather/residual code must not latch the tier off
            starts_all, counts_all = mesh_join_probe(mesh, lk_stack, rk_stack, n_r)
        except Exception as e:
            record_device_failure(e)
            return None
        for i, (b, lb, rb, lk32, rk32, lorder, rorder, _ls, _rs) in enumerate(wave):
            n_l = len(lk32)
            starts = starts_all[i, :n_l]
            counts = counts_all[i, :n_l]
            li = np.repeat(np.arange(n_l, dtype=np.int64), counts)
            ri = expand_runs(starts, counts)
            if lorder is not None:
                li = lorder[li]
            if rorder is not None:
                ri = rorder[ri]
            out = {nm: c.take(li) for nm, c in lb.columns.items()}
            out.update({nm: c.take(ri) for nm, c in rb.columns.items()})
            joined = ColumnBatch(out)
            for r in residual:
                joined = joined.filter(np.asarray(r.eval(joined).data, dtype=bool))
            parts[b] = joined
    from ..utils.backend import record_device_success

    record_device_success()  # every wave dispatched and fetched cleanly
    if session is not None:
        names = sorted(
            {
                s.scan.index_info.index_name
                for s in (left, right)
                if s is not None and s.scan.index_info is not None
            }
        )
        if names:
            from ..rules.rule_utils import log_index_usage

            log_index_usage(
                session,
                "MeshBucketedExec",
                names,
                f"Mesh bucketed join: {len(work)} buckets in waves of "
                f"{S} shards ({', '.join(names)})",
            )
    ordered = [parts[b] for b in sorted(parts)]
    return (
        ColumnBatch.concat(ordered)
        if ordered
        else _empty_join_output(work[0][1], work[0][2])
    )


def _bucketize_appended(
    side: BucketedSide, num_buckets: int, session
) -> Optional[list[ColumnBatch]]:
    """Evaluate the appended-data subplan once and split it by bucket — the
    'shuffle only the appended rows' half of hybrid scan."""
    if side.appended is None:
        return None
    from .executor import execute_plan

    batch = execute_plan(side.appended, session)
    ids = bucket_ids_for_batch(batch, list(side.spec.bucket_columns), num_buckets)
    return [batch.filter(ids == b) for b in range(num_buckets)]


def _load_side_bucket(
    side: BucketedSide,
    b: int,
    appended: Optional[list[ColumnBatch]],
    session,
    raw: bool = False,
) -> Optional[ColumnBatch]:
    from .executor import execute_plan
    from .expr import And

    files = side.files_for_bucket(b)
    if raw:
        # RAW load for device paths: no pushed filter (pruned/masked reads
        # produce fresh buffers; unfiltered reads come straight from the
        # index chunk cache with STABLE buffer identities the device cache
        # keys on) and no op replay (filters run in-kernel)
        sub_scan = side.scan.copy(files=files, pushed_filter=None)
        batch = execute_plan(sub_scan, session)
        if appended is not None and appended[b].num_rows:
            extra = appended[b].select(batch.schema.names)
            batch = ColumnBatch.concat([batch, extra])
        return batch
    pushed = side.scan.pushed_filter
    if pushed is None and side.scan.fmt == "parquet":
        # push_predicates usually set pushed_filter already; synthesize one
        # from filter conjuncts that reference scan columns directly
        scan_cols = set(side.scan.full_schema.names)
        # conservative: every referenced name must be a scan column that any
        # project passes through unchanged (aliased/derived names don't push)
        pushable = [
            f
            for f in side.filters
            if f.references()
            and all(c in scan_cols and side.key_is_identity(c) for c in f.references())
        ]
        for f in pushable:
            pushed = f if pushed is None else And(pushed, f)
    sub_scan = side.scan.copy(files=files, pushed_filter=pushed)
    batch = execute_plan(sub_scan, session)
    if appended is not None and appended[b].num_rows:
        extra = appended[b].select(batch.schema.names)
        batch = ColumnBatch.concat([batch, extra])
    # replay the side's ops bottom-up, exactly as the plan ordered them
    for op in side.ops:
        if isinstance(op, Filter):
            batch = batch.filter(np.asarray(op.condition.eval(batch).data, dtype=bool))
        else:
            from .expr import expr_output_name

            batch = ColumnBatch(
                {expr_output_name(e): e.eval(batch) for e in op.exprs}
            )
    return batch


def _merge_join_batches(
    lb: ColumnBatch,
    rb: ColumnBatch,
    lkeys: Sequence[str],
    rkeys: Sequence[str],
    l_sorted: bool = False,
    r_sorted: bool = False,
) -> ColumnBatch:
    from .executor import join_indices

    if len(lkeys) == 1:
        lcol = lb.column(lkeys[0])
        rcol = rb.column(rkeys[0])
        if (
            lcol.dtype not in ("string",)
            and rcol.dtype not in ("string",)
            and lcol.validity is None
            and rcol.validity is None
        ):
            # single numeric key: pure searchsorted merge on the on-disk sort
            # order; only perturbed (appended) sides pay an argsort
            if l_sorted:
                lsorted_keys, lorder = lcol.data, None
            else:
                lorder = np.argsort(lcol.data, kind="stable")
                lsorted_keys = lcol.data[lorder]
            if r_sorted:
                rsorted_keys, rorder = rcol.data, None
            else:
                rorder = np.argsort(rcol.data, kind="stable")
                rsorted_keys = rcol.data[rorder]
            li, ri = host_merge_join_indices(lsorted_keys, rsorted_keys)
            if lorder is not None:
                li = lorder[li]
            if rorder is not None:
                ri = rorder[ri]
            out = {n: c.take(li) for n, c in lb.columns.items()}
            out.update({n: c.take(ri) for n, c in rb.columns.items()})
            return ColumnBatch(out)
    li, ri = join_indices(lb, rb, list(lkeys), list(rkeys))
    out = {n: c.take(li) for n, c in lb.columns.items()}
    out.update({n: c.take(ri) for n, c in rb.columns.items()})
    return ColumnBatch(out)


def _empty_like(plan: Join) -> ColumnBatch:
    from ..columnar.table import Column, STRING, numpy_dtype

    cols = {}
    for f in plan.schema:
        if f.dtype == STRING:
            cols[f.name] = Column(np.empty(0, np.int32), STRING, None, [""])
        else:
            cols[f.name] = Column(np.empty(0, numpy_dtype(f.dtype)), f.dtype)
    return ColumnBatch(cols)
