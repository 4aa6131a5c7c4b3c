"""Plan execution.

Two tiers, mirroring how the reference splits work between Spark's codegen and
its own operators:

- This module: host-side columnar execution over numpy — the always-correct
  reference path for every node (the analogue of Spark's row pipeline).
- ops/ + parallel/: jitted XLA/Pallas kernels the executor dispatches to for
  the hot patterns (filter+aggregate pipelines, co-partitioned merge join,
  bucketize/sort index builds) when a device mesh is available.

Joins here are equi hash joins on factorized keys; the index-accelerated path
replaces them with the shuffle-free bucketed merge join (ops/join.py).
"""

from __future__ import annotations

from typing import Sequence

import numpy as np

from . import expr as X
from .expr import AggExpr, Alias, Expr, expr_output_name, split_conjunction
from .nodes import (
    Aggregate,
    BucketUnion,
    FileScan,
    Filter,
    InMemoryScan,
    Join,
    Limit,
    LogicalPlan,
    Project,
    RepartitionByExpr,
    Sort,
    Union,
)
from ..columnar.table import Column, ColumnBatch, STRING
from ..columnar import io as cio
from ..exceptions import HyperspaceError
from .. import constants as C


def execute_plan(plan: LogicalPlan, session=None) -> ColumnBatch:
    """Execute one plan node (recursing into children). When tracing is on,
    every node gets an `exec:<op>` span carrying output rows and the RPC
    deltas of everything beneath it; when off this is a single bool check.

    Plan statistics (telemetry/plan_stats.py): when a collector is active
    (EXPLAIN ANALYZE / HYPERSPACE_PLAN_STATS=1) every node additionally
    records its output rows and inclusive wall time — observe-only, so an
    analyze run stays bit-identical to a plain collect. Disabled cost is
    one contextvar read.

    Cancellation boundary: a query cancelled through the serving layer
    (serve/scheduler.py) unwinds here between plan nodes — plus at every
    chunk/pair boundary inside the streamers — so no new node starts work
    after the cancel flag flips."""
    import time

    from ..serve.context import check_cancelled
    from ..telemetry import plan_stats, trace

    check_cancelled()
    col = plan_stats.current()
    if col is None and not trace.enabled():
        if trace.recording():  # a profiler session: the annotation only
            with trace.span(f"exec:{plan.kind}"):
                return _execute_node(plan, session)
        return _execute_node(plan, session)
    t0 = time.perf_counter() if col is not None else 0.0
    if not trace.enabled():
        out = _execute_node(plan, session)
        col.record_node(plan, out.num_rows, time.perf_counter() - t0)
        return out
    with trace.span(f"exec:{plan.kind}", plan_id=plan.plan_id) as sp:
        out = _execute_node(plan, session)
        sp.set_attr("rows_out", out.num_rows)
        if col is not None:
            ns = col.record_node(plan, out.num_rows, time.perf_counter() - t0)
            # annotate the exec span too so a trace JSONL alone can render
            # the analyzed tree (tools/trace_report.py --plan-stats)
            if ns.route != "host":
                sp.set_attr("route", ns.route)
            if ns.bytes_scanned is not None:
                sp.set_attr("bytes_scanned", ns.bytes_scanned)
        return out


def _execute_node(plan: LogicalPlan, session=None) -> ColumnBatch:
    if (
        session is not None
        and isinstance(plan, (Aggregate, Filter))
        and session.conf.exec_tpu_enabled
    ):
        from .tpu_exec import try_execute_select, try_execute_tpu

        if isinstance(plan, Aggregate):
            result = try_execute_tpu(plan, session)
        else:
            result = try_execute_select(plan, session)
        if result is not None:
            return result
    if isinstance(plan, InMemoryScan):
        return plan.batch
    if isinstance(plan, FileScan):
        return _exec_file_scan(plan)
    if isinstance(plan, Filter):
        child = execute_plan(plan.child, session)
        # observed-selectivity conjunct reordering (HYPERSPACE_ADAPTIVE):
        # None = static path; a returned mask is bit-identical to the
        # static eval by construction (AND commutes, data ⊆ valid)
        from . import adaptive

        mask = adaptive.conjunct_mask(plan.condition, child)
        if mask is None:
            mask = np.asarray(plan.condition.eval(child).data, dtype=bool)
        return child.filter(mask)
    if isinstance(plan, Project):
        plan.schema  # raises on duplicate output names
        child = execute_plan(plan.child, session)
        cols = {}
        for e in plan.exprs:
            cols[expr_output_name(e)] = e.eval(child)
        return ColumnBatch(cols)
    if isinstance(plan, Join):
        return _exec_join(plan, session)
    if isinstance(plan, Aggregate):
        return _exec_aggregate(plan, session)
    if isinstance(plan, Sort):
        child = execute_plan(plan.child, session)
        return _exec_sort(plan, child, session)
    if isinstance(plan, Limit):
        if isinstance(plan.child, Sort):
            # execute the sort's child ONCE; top-k or exact sort both reuse it
            sort_plan = plan.child
            child = execute_plan(sort_plan.child, session)
            if session is not None and session.conf.exec_tpu_enabled:
                from .tpu_exec import try_device_topk

                topk = try_device_topk(sort_plan, plan.n, child, session)
                if topk is not None:
                    from ..telemetry import plan_stats

                    plan_stats.note_route(plan.plan_id, "device")
                    return topk
            topk = _try_topk_batch(sort_plan, plan.n, child)
            if topk is not None:
                return topk
            # multi-key / f64 / heavy-tie shapes: the general device sort
            # serves the full ordering before the host lexsort does
            full = _exec_sort(sort_plan, child, session)
            return full.take(np.arange(min(plan.n, full.num_rows)))
        child = execute_plan(plan.child, session)
        idx = np.arange(min(plan.n, child.num_rows))
        return child.take(idx)
    if isinstance(plan, (Union, BucketUnion)):
        batches = [execute_plan(c, session) for c in plan.children()]
        aligned = [b.select(batches[0].schema.names) for b in batches]
        return ColumnBatch.concat(aligned)
    if isinstance(plan, RepartitionByExpr):
        # Pure marker on the host path; the device path uses it to drive the
        # small-side all_to_all (parallel/exchange.py).
        return execute_plan(plan.child, session)
    raise HyperspaceError(f"Cannot execute node {plan.kind}")


# ---------------------------------------------------------------------------
# scans
# ---------------------------------------------------------------------------

def _maybe_verify_pruning(scan: FileScan, out: ColumnBatch) -> ColumnBatch:
    """HYPERSPACE_PRUNE=verify: compare the pruned scan against the full
    read (hash/stats contract guard). Covers the pruned-to-empty paths too —
    a diverged bucket hash shows up exactly as a wrongly-empty scan."""
    if scan.prune_spec is not None:
        from . import pruning

        if pruning.is_verify(scan):
            pruning.verify_against_full(scan, out)
    return out


def _empty_scan_batch(scan: FileScan, want: list[str]) -> ColumnBatch:
    empty = {
        f.name: Column(
            np.empty(0, dtype=np.int32 if f.dtype in (STRING, "date32") else np.dtype(f.dtype)),
            f.dtype,
            None,
            [""] if f.dtype == STRING else None,
        )
        for f in scan.full_schema.select(want)
    }
    return ColumnBatch(empty)


def _constant_column(dtype: str, value: str, n: int) -> Column:
    if dtype == STRING:
        return Column(np.zeros(n, dtype=np.int32), STRING, None, [value])
    return Column(np.full(n, int(value), dtype=np.int64).astype(np.dtype(dtype)), dtype)


def _exec_file_scan(scan: FileScan) -> ColumnBatch:
    from ..utils.partitions import partition_key

    want = list(scan.required_columns or scan.full_schema.names)
    part_names = [c for c in scan.partition_columns if c in scan.full_schema]
    physical_want = [c for c in want if c not in part_names]
    read_cols = list(physical_want)
    need_lineage_filter = scan.lineage_filter_ids is not None
    if need_lineage_filter and C.DATA_FILE_NAME_ID not in read_cols:
        read_cols.append(C.DATA_FILE_NAME_ID)
    physical_schema = scan.full_schema.select(
        [n for n in scan.full_schema.names if n not in part_names]
    )
    arrow_filter = None
    if scan.pushed_filter is not None and scan.fmt == "parquet":
        from .passes import to_arrow_filter

        arrow_filter = to_arrow_filter(scan.pushed_filter, physical_schema)
    if not scan.files:
        return _maybe_verify_pruning(scan, _empty_scan_batch(scan, want))

    # predicate-driven row-group skipping for covering-index scans: sorted
    # buckets + footer stats narrow each file to the matching runs (files
    # whose every group is skipped drop out entirely); sidecar sketches
    # (bloom/value-list/z-region) do the same for non-sort-column conjuncts
    row_groups = None
    scan_files = scan.files
    if (
        scan.prune_spec is not None
        and (
            scan.prune_spec.rowgroup_conjuncts
            or scan.prune_spec.sketch_conjuncts
        )
        and not part_names
        and read_cols
    ):
        from . import pruning

        row_groups, scan_files = pruning.rowgroup_selection(scan)
        if not scan_files:
            return _maybe_verify_pruning(scan, _empty_scan_batch(scan, want))

    def read(paths: list[str]) -> ColumnBatch:
        if not read_cols and scan.fmt == "parquet" and arrow_filter is None:
            # only partition columns requested: row counts come from file
            # metadata, no data pages are read
            n = sum(cio.file_num_rows(p) for p in paths)
            return ColumnBatch({"__rows__": Column(np.zeros(n, np.int8), "int8")})
        if scan.fmt == "parquet":
            # index files are the engine-owned resident working set: decoded
            # chunks cache across queries (HBM-resident on device; host
            # memory here). Raw source scans never cache.
            return cio.read_parquet(
                paths, read_cols, arrow_filter,
                cache=scan.index_info is not None,
                row_groups=row_groups,
            )
        return cio.read_files(scan.fmt, paths, read_cols)

    if not part_names:
        batch = read([f.name for f in scan_files])
    else:
        # group files by partition values; prune groups the pushed filter's
        # partition-only conjuncts rule out, then attach constant columns
        groups: dict[tuple, list[str]] = {}
        for f in scan.files:
            groups.setdefault(
                partition_key(f.name, part_names, scan.root_paths), []
            ).append(f.name)
        prunable = _partition_conjuncts(scan, part_names)
        parts = []
        for key, paths in groups.items():
            pv_batch = ColumnBatch(
                {
                    c: _constant_column(scan.full_schema.field(c).dtype, v, 1)
                    for c, v in zip(part_names, key)
                }
            )
            if any(not bool(p.eval(pv_batch).data[0]) for p in prunable):
                continue
            b = read(paths)
            for c, v in zip(part_names, key):
                if c in want:
                    b = b.with_column(
                        c, _constant_column(scan.full_schema.field(c).dtype, v, b.num_rows)
                    )
            parts.append(b)
        if not parts:
            return _empty_scan_batch(scan, want)
        batch = ColumnBatch.concat([p.select(parts[0].schema.names) for p in parts])

    if need_lineage_filter:
        ids = np.asarray(scan.lineage_filter_ids, dtype=np.int64)
        lineage = batch.column(C.DATA_FILE_NAME_ID).data
        mask = ~np.isin(lineage, ids)
        batch = batch.filter(mask)
        if C.DATA_FILE_NAME_ID not in want:
            batch = batch.select(want)
    out = batch.select(want) if batch.schema.names != want else batch
    return _maybe_verify_pruning(scan, out)


def scan_streamable(scan: FileScan) -> bool:
    """True when the scan can execute as an ordered stream of per-file-group
    chunks whose concatenation reproduces the monolithic read exactly: plain
    parquet/arrow layout, no partition-value columns to attach, no lineage
    filter, no pushed arrow filter (the device tier strips it anyway), and
    at least two files to overlap."""
    if scan.fmt != "parquet" or len(scan.files) < 2:
        return False
    if scan.pushed_filter is not None or scan.lineage_filter_ids is not None:
        return False
    if any(c in scan.full_schema for c in scan.partition_columns):
        return False
    if scan.prune_spec is not None:
        from . import pruning

        if pruning.is_verify(scan):
            # the pruned-vs-full comparison runs in _exec_file_scan
            return False
    want = list(scan.required_columns or scan.full_schema.names)
    return bool(want)


def resolve_scan_pruning(scan: FileScan):
    """(row_groups, kept_files) for the scan's prune spec — the shared
    resolution the monolithic reader and the chunk streamer both consume,
    so they enumerate the same files and row groups (bit-identical fold).
    (None, scan.files) when row-group pruning does not apply."""
    if scan.prune_spec is None or not (
        scan.prune_spec.rowgroup_conjuncts or scan.prune_spec.sketch_conjuncts
    ):
        return None, list(scan.files)
    from . import pruning

    return pruning.rowgroup_selection(scan)


def iter_scan_chunks(scan: FileScan, overlap: bool = True, selection=None):
    """Chunk stream for a `scan_streamable` FileScan: same column set and
    per-file read calls as `_exec_file_scan`, yielded per file group with
    bounded read-ahead (columnar.io.iter_chunks). Index-file scans serve and
    populate the decoded-chunk cache per group, which keeps the chunk
    Columns' buffer identities stable across repeat queries — the device
    upload cache keys on exactly that. Pass a pre-resolved ``selection``
    (from `resolve_scan_pruning`) to share one row-group resolution with
    the caller's row-count planning."""
    want = list(scan.required_columns or scan.full_schema.names)
    if selection is None:
        selection = resolve_scan_pruning(scan)
    row_groups, files = selection
    return cio.iter_chunks(
        [f.name for f in files],
        want,
        cache=scan.index_info is not None,
        overlap=overlap,
        row_groups=row_groups,
    )


def _partition_conjuncts(scan: FileScan, part_names: list[str]):
    """Pushed-filter conjuncts referencing only partition columns — safe to
    evaluate per group before reading any data."""
    if scan.pushed_filter is None:
        return []
    part_set = set(part_names)
    return [
        c
        for c in split_conjunction(scan.pushed_filter)
        if c.references() and c.references() <= part_set
    ]


# ---------------------------------------------------------------------------
# join
# ---------------------------------------------------------------------------

def extract_equi_keys(
    condition: Expr, left_schema, right_schema
) -> tuple[list[str], list[str], list[Expr]]:
    """Split a join condition into equi column pairs + residual predicates
    (ref: JoinIndexRule.isJoinConditionSupported — CNF of Col = Col)."""
    left_keys: list[str] = []
    right_keys: list[str] = []
    residual: list[Expr] = []
    for conj in split_conjunction(condition):
        if isinstance(conj, X.Eq) and isinstance(conj.left, X.Col) and isinstance(
            conj.right, X.Col
        ):
            a, b = conj.left.name, conj.right.name
            if a in left_schema and b in right_schema:
                left_keys.append(a)
                right_keys.append(b)
                continue
            if b in left_schema and a in right_schema:
                left_keys.append(b)
                right_keys.append(a)
                continue
        residual.append(conj)
    return left_keys, right_keys, residual


def _comparable_values(c: Column) -> np.ndarray:
    """Order-correct raw values for factorization (strings decoded)."""
    if c.dtype == STRING:
        vals = np.asarray(c.decode(), dtype=object)
        if c.validity is not None:
            vals = vals.copy()
            vals[~c.validity] = ""  # placeholder; callers handle nulls via validity
        return vals.astype(str)
    return c.data


def _factorize_pair(a: Column, b: Column) -> tuple[np.ndarray, np.ndarray]:
    """Joint factorization of two key columns into comparable int codes."""
    if (a.dtype == STRING) != (b.dtype == STRING):
        raise HyperspaceError(
            f"Cannot join string key with non-string key ({a.dtype} vs {b.dtype})"
        )
    av = _comparable_values(a)
    bv = _comparable_values(b)
    allv = np.concatenate([av, bv])
    _, codes = np.unique(allv, return_inverse=True)
    return codes[: len(av)], codes[len(av):]


def _combine_codes(code_list: list[np.ndarray], other_list: list[np.ndarray]):
    combined_a = code_list[0].astype(np.int64)
    combined_b = other_list[0].astype(np.int64)
    for ca, cb in zip(code_list[1:], other_list[1:]):
        n = int(max(ca.max(initial=0), cb.max(initial=0))) + 1
        combined_a = combined_a * n + ca
        combined_b = combined_b * n + cb
    return combined_a, combined_b


def _any_null_mask(batch: ColumnBatch, keys: Sequence[str]) -> np.ndarray | None:
    masks = [batch.column(k).validity for k in keys]
    if all(m is None for m in masks):
        return None
    invalid = np.zeros(batch.num_rows, dtype=bool)
    for m in masks:
        if m is not None:
            invalid |= ~m
    return invalid


def join_indices(
    left: ColumnBatch,
    right: ColumnBatch,
    left_keys: Sequence[str],
    right_keys: Sequence[str],
) -> tuple[np.ndarray, np.ndarray]:
    """Inner-join row indices via sort + searchsorted on factorized keys.
    SQL semantics: a NULL key never matches anything, including another NULL."""
    la, lb = [], []
    for lk, rk in zip(left_keys, right_keys):
        ca, cb = _factorize_pair(left.column(lk), right.column(rk))
        la.append(ca)
        lb.append(cb)
    lcodes, rcodes = _combine_codes(la, lb)
    lnull = _any_null_mask(left, left_keys)
    rnull = _any_null_mask(right, right_keys)
    if lnull is not None:
        lcodes = np.where(lnull, np.int64(-1), lcodes)
    if rnull is not None:
        rcodes = np.where(rnull, np.int64(-2), rcodes)
    if len(lcodes) >= 4096:
        from .. import native

        nat = native.join_i64(lcodes, rcodes)
        if nat is not None:
            return nat
    from ..ops.join import expand_runs

    order = np.argsort(rcodes, kind="stable")
    sorted_r = rcodes[order]
    starts = np.searchsorted(sorted_r, lcodes, side="left")
    ends = np.searchsorted(sorted_r, lcodes, side="right")
    counts = ends - starts
    li = np.repeat(np.arange(len(lcodes)), counts)
    ri = order[expand_runs(starts, counts)]
    return li, ri


def _exec_join(plan: Join, session) -> ColumnBatch:
    if plan.how != "inner":
        raise HyperspaceError(f"Join type not yet supported: {plan.how}")
    # co-partitioned fast path: both sides bucketed on the join keys (the
    # shape JoinIndexRule produces) joins bucket-by-bucket with no global
    # hash table or shuffle
    from .bucket_join import try_bucketed_merge_join

    bucketed = try_bucketed_merge_join(plan, session)  # notes its own route
    if bucketed is not None:
        return bucketed
    plan.schema  # raises on ambiguous output columns before any work runs
    left = execute_plan(plan.left, session)
    right = execute_plan(plan.right, session)
    if plan.condition is None:
        raise HyperspaceError("Cross join not supported")
    lk, rk, residual = extract_equi_keys(
        plan.condition, plan.left.schema, plan.right.schema
    )
    if not lk:
        raise HyperspaceError(f"No equi keys in join condition: {plan.condition!r}")
    li, ri = join_indices(left, right, lk, rk)
    out_cols = {}
    for n, c in left.columns.items():
        out_cols[n] = c.take(li)
    for n, c in right.columns.items():
        out_cols[n] = c.take(ri)
    out = ColumnBatch(out_cols)
    for r in residual:
        mask = np.asarray(r.eval(out).data, dtype=bool)
        out = out.filter(mask)
    return out


# ---------------------------------------------------------------------------
# aggregate
# ---------------------------------------------------------------------------

def _unwrap_agg(e: Expr) -> tuple[str, AggExpr]:
    if isinstance(e, Alias):
        return e.name, _unwrap_agg(e.child)[1]
    if isinstance(e, AggExpr):
        return expr_output_name(e), e
    raise HyperspaceError(f"Not an aggregate expression: {e!r}")


def _agg_values(agg: AggExpr, batch: ColumnBatch) -> tuple[np.ndarray, np.ndarray, Column | None]:
    """Returns (values, valid_mask, source_column). For string columns the
    values are codes re-factorized against a *sorted* vocabulary so their
    order matches lexicographic string order (min/max sketches depend on it)."""
    if isinstance(agg, X.Count) and isinstance(agg.child, X.Lit):
        vals = np.ones(batch.num_rows, dtype=np.int64)
        return vals, np.ones(batch.num_rows, dtype=bool), None
    c = agg.child.eval(batch)
    valid = c.validity if c.validity is not None else np.ones(len(c), dtype=bool)
    if c.dtype == STRING:
        if not isinstance(agg, (X.Min, X.Max, X.Count)):
            raise HyperspaceError(f"{agg.func} not supported on string column")
        vals = np.asarray(c.decode(), dtype=object)
        vals[~valid] = ""
        vocab, codes = np.unique(vals.astype(str), return_inverse=True)
        sorted_col = Column(codes.astype(np.int32), STRING, c.validity, list(vocab))
        return codes.astype(np.int64), valid, sorted_col
    return c.data, valid, c


def _exec_aggregate(plan: Aggregate, session) -> ColumnBatch:
    from ..telemetry import plan_stats

    if isinstance(plan.child, Join):
        from .bucket_join import try_bucketed_join_aggregate

        fused = try_bucketed_join_aggregate(plan, session)  # notes its route
        if fused is not None:
            return fused
    elif plan.group_exprs and not isinstance(plan.child, InMemoryScan):
        from .bucket_join import try_bucketed_scan_aggregate

        fused = try_bucketed_scan_aggregate(plan, session)
        if fused is not None:
            plan_stats.note_route(plan.plan_id, "bucketed")
            return fused
    child = execute_plan(plan.child, session)
    n = child.num_rows

    if not plan.group_exprs:
        # global aggregate -> single row
        out = {}
        for e in plan.agg_exprs:
            name, agg = _unwrap_agg(e)
            out[name] = _global_agg(agg, child)
        return ColumnBatch(out)

    key_cols = [e.eval(child) for e in plan.group_exprs]
    group_ids, num_groups, first_idx = factorize_group_keys(key_cols)

    out_cols: dict[str, Column] = {}
    for e, kc in zip(plan.group_exprs, key_cols):
        out_cols[expr_output_name(e)] = kc.take(first_idx)

    for e in plan.agg_exprs:
        name, agg = _unwrap_agg(e)
        vals, valid, src = _agg_values(agg, child)
        out_cols[name] = _grouped_agg(agg, vals, valid, src, group_ids, num_groups)
    return ColumnBatch(out_cols)


def factorize_group_keys(
    key_cols: list[Column],
) -> tuple[np.ndarray, int, np.ndarray]:
    """(group_ids, num_groups, first_occurrence_idx) for one or more key
    columns. SQL GROUP BY treats NULL keys as one distinct group, so NULL
    maps to a fresh code rather than colliding with the storage fill value."""
    codes_list = []
    for kc in key_cols:
        codes = _dense_int_codes(kc)
        if codes is None:
            vals = _comparable_values(kc)
            _, codes = np.unique(vals, return_inverse=True)
            codes = codes.astype(np.int64)
        if kc.validity is not None:
            codes = np.where(kc.validity, codes, np.int64(codes.max(initial=-1) + 1))
        codes_list.append(codes)
    # guard the combined-code domain: dense (uncompacted) codes can push the
    # product past int64 with several keys — re-compact each first if so
    domain = 1
    for c in codes_list:
        domain *= int(c.max(initial=0)) + 1
        if domain > 2**62:
            codes_list = [
                np.unique(c, return_inverse=True)[1].astype(np.int64) for c in codes_list
            ]
            break
    combined = codes_list[0]
    for c in codes_list[1:]:
        combined = combined * (int(c.max(initial=0)) + 1) + c
    uniq, group_ids = _compact_group_ids(combined)
    num_groups = len(uniq)
    # first occurrence index per group for key output (validity rides along)
    seen_order = np.argsort(group_ids, kind="stable")
    boundaries = np.searchsorted(group_ids[seen_order], np.arange(num_groups))
    first_idx = seen_order[boundaries]
    return group_ids, num_groups, first_idx


def _dense_int_codes(kc: Column) -> np.ndarray | None:
    """Direct group codes without the O(n log n) np.unique sort. Two cases:
    string columns group by dictionary code (code order is NOT value order —
    grouping doesn't care; only valid when the vocabulary has no duplicate
    values, which is checked), and dense non-negative int keys group by value
    when max(key) is within 8x the row count (e.g. join keys)."""
    if kc.dtype == STRING:
        if kc.dictionary_is_unique:  # checked once, cached on the column
            return kc.data.astype(np.int64)
        return None  # duplicate values under different codes: decode path
    if kc.data.dtype.kind not in ("i", "u"):
        return None
    n = len(kc.data)
    if n == 0:
        return None
    mn = int(kc.data.min())
    mx = int(kc.data.max())
    if mn < 0 or mx > max(1024, 8 * n):
        return None
    return kc.data.astype(np.int64)


def _compact_group_ids(combined: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """(unique codes, group ids) — bincount-based compaction for small
    non-negative domains, np.unique otherwise."""
    n = len(combined)
    if n and combined.min() >= 0:
        domain = int(combined.max()) + 1
        if domain <= max(1024, 8 * n):
            present = np.zeros(domain, dtype=bool)
            present[combined] = True
            uniq = np.nonzero(present)[0].astype(np.int64)
            remap = np.zeros(domain, dtype=np.int64)
            remap[uniq] = np.arange(len(uniq))
            return uniq, remap[combined]
    return np.unique(combined, return_inverse=True)


def _global_agg(agg: AggExpr, batch: ColumnBatch) -> Column:
    vals, valid, src = _agg_values(agg, batch)
    v = vals[valid]
    if isinstance(agg, X.Count):
        return Column(np.array([len(v)], dtype=np.int64), "int64")
    if len(v) == 0:
        # SQL: aggregate over zero (non-NULL) rows is NULL
        return Column(np.array([0.0]), "float64", np.array([False]))
    if isinstance(agg, (X.Min, X.Max)) and src is not None and src.dtype == STRING:
        code = v.min() if isinstance(agg, X.Min) else v.max()
        return Column(np.array([code], dtype=np.int32), STRING, None, src.dictionary)
    if isinstance(agg, X.Sum):
        r = v.sum()
    elif isinstance(agg, X.Min):
        r = v.min()
    elif isinstance(agg, X.Max):
        r = v.max()
    elif isinstance(agg, X.Avg):
        r = v.astype(np.float64).mean()
    else:
        raise HyperspaceError(f"Unknown aggregate {agg!r}")
    arr = np.asarray([r])
    dtype = str(arr.dtype)
    return Column(arr, dtype if dtype in ("int64", "float64", "int32", "float32") else "float64")


def _grouped_agg(
    agg: AggExpr,
    vals: np.ndarray,
    valid: np.ndarray,
    src: Column | None,
    group_ids: np.ndarray,
    num_groups: int,
) -> Column:
    counts = np.bincount(
        group_ids, weights=valid.astype(np.float64), minlength=num_groups
    ).astype(np.int64)
    if isinstance(agg, X.Count):
        return Column(counts, "int64")
    # SQL: a group with zero non-NULL inputs aggregates to NULL
    group_validity = None if (counts > 0).all() else counts > 0
    fvals = np.where(valid, vals, 0)
    if isinstance(agg, X.Sum):
        s = np.bincount(group_ids, weights=fvals.astype(np.float64), minlength=num_groups)
        if vals.dtype.kind == "i":
            return Column(s.astype(np.int64), "int64", group_validity)
        return Column(s, "float64", group_validity)
    if isinstance(agg, X.Avg):
        s = np.bincount(group_ids, weights=fvals.astype(np.float64), minlength=num_groups)
        with np.errstate(invalid="ignore", divide="ignore"):
            return Column(
                np.where(counts > 0, s / np.maximum(counts, 1), 0.0),
                "float64",
                group_validity,
            )
    if isinstance(agg, (X.Min, X.Max)):
        is_min = isinstance(agg, X.Min)
        if vals.dtype.kind == "f":
            init = np.inf if is_min else -np.inf
        else:
            info = np.iinfo(vals.dtype)
            init = info.max if is_min else info.min
        out = np.full(num_groups, init, dtype=vals.dtype)
        ufunc = np.minimum if is_min else np.maximum
        ufunc.at(out, group_ids[valid], vals[valid])
        out = np.where(counts > 0, out, 0)
        if src is not None and src.dtype == STRING:
            return Column(out.astype(np.int32), STRING, group_validity, src.dictionary)
        return Column(out, str(out.dtype), group_validity)
    raise HyperspaceError(f"Unknown aggregate {agg!r}")


# ---------------------------------------------------------------------------
# sort
# ---------------------------------------------------------------------------

def _try_topk_batch(sort_plan: Sort, k: int, child: ColumnBatch) -> ColumnBatch | None:
    """Limit(Sort) -> argpartition top-k + small final sort instead of a full
    O(n log n) sort (the ORDER BY ... LIMIT shape of Q3-like queries).
    Operates on the already-executed child batch; None = use the exact sort."""
    from ..columnar.table import sort_key_values

    n = child.num_rows
    if n <= max(k * 4, 1024) or not sort_plan.orders:
        return None  # full sort is fine at this size
    keys = [sort_key_values(e.eval(child), asc) for e, asc in reversed(sort_plan.orders)]
    primary = keys[-1]  # lexsort's last key is the primary
    if primary.dtype.kind not in ("i", "u", "f"):
        return None
    # over-select to k*4 candidates on the primary key (ties spill into the
    # buffer; exact for k rows unless > 3k ties share the boundary value —
    # guarded below)
    cand_size = min(n, max(4 * k, 64))
    cand = np.argpartition(primary, cand_size - 1)[:cand_size]
    boundary = primary[cand].max()
    if (primary <= boundary).sum() > cand_size:
        # heavy boundary ties: fall back to the exact full sort
        return None
    sub = child.take(cand)
    sub_keys = [kk[cand] for kk in keys]
    order = np.lexsort(sub_keys)[:k]
    return sub.take(order)


def _exec_sort(plan: Sort, child: ColumnBatch, session=None) -> ColumnBatch:
    """Multi-key sort; key encoding (exactness, NULL placement, descending)
    is shared with the index write path via sort_key_values. When the device
    tier is up, the general device sort (order-preserving uint32 word
    encoding + lax.sort) serves first — bit-identical output."""
    if session is not None and session.conf.exec_tpu_enabled:
        from .tpu_exec import try_device_sort

        out = try_device_sort(plan, child, session)
        if out is not None:
            return out
    from ..columnar.table import sort_key_values

    keys = [
        sort_key_values(e.eval(child), asc) for e, asc in reversed(plan.orders)
    ]
    order = np.lexsort(keys) if keys else np.arange(child.num_rows)
    return child.take(order)
