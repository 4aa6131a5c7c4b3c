"""Lazy DataFrame frontend.

The reference intercepts queries inside Spark's optimizer; since there is no
Catalyst here, the frontend owns the plan: every DataFrame op builds logical
nodes lazily, and collect() runs the session's extra optimizations (the
ApplyHyperspace rewrite when enabled, ref package.scala:82-93) before lowering
to the executor.
"""

from __future__ import annotations

import os
from typing import Any, Iterable, Sequence

import numpy as np

from .expr import Avg, Col, Count, Expr, Lit, Max, Min, Sum, col
from .nodes import (
    Aggregate,
    FileScan,
    Filter,
    InMemoryScan,
    Join,
    Limit,
    LogicalPlan,
    Project,
    Sort,
    Union,
)
from .executor import execute_plan
from ..columnar import io as cio
from ..columnar.table import ColumnBatch, Schema
from ..exceptions import HyperspaceError
from ..meta.entry import FileInfo


def _has_magic(path: str) -> bool:
    import glob as _glob

    return _glob.has_magic(path)


def _first_unmatched(paths: list[str], patterns: list[str]) -> str | None:
    """First path no pattern covers, or None when all match."""
    for p in paths:
        if not any(
            _glob_segments_match(os.path.abspath(p), os.path.abspath(g))
            for g in patterns
        ):
            return p
    return None


def _glob_segments_match(path: str, pattern: str) -> bool:
    """Per-segment fnmatch: '*' matches within one path component only
    (the reference's glob semantics, not fnmatch's separator-crossing '*')."""
    import fnmatch

    p_segs = path.split(os.sep)
    g_segs = pattern.split(os.sep)
    if len(p_segs) != len(g_segs):
        return False
    return all(fnmatch.fnmatch(p, g) for p, g in zip(p_segs, g_segs))


def _to_expr(c) -> Expr:
    if isinstance(c, Expr):
        return c
    if isinstance(c, str):
        return col(c)
    return Lit(c)


def resolve_nested_refs(e: Expr, schema: Schema, alias_bare: bool = False) -> Expr:
    """Resolve bare dotted references to flattened nested columns: a user's
    col("a.b.c") binds to the schema column "__hs_nested.a.b.c" when present
    (ref: ResolverUtils.ResolvedColumn normalization). With alias_bare, a
    rewritten top-level Col keeps the user's dotted name as its output name."""
    from .. import constants as C
    from .expr import Alias, map_cols

    names = set(schema.names)
    if not any(n.startswith(C.NESTED_FIELD_PREFIX) for n in names):
        return e
    lower = {n.lower(): n for n in names}

    def fix(c: Col) -> Col:
        if c.name in names:
            return c
        cand = lower.get((C.NESTED_FIELD_PREFIX + c.name).lower())
        return Col(cand) if cand is not None else c

    out = map_cols(e, fix)
    if (
        alias_bare
        and isinstance(e, Col)
        and isinstance(out, Col)
        and out.name != e.name
    ):
        return Alias(out, e.name)
    return out


class DataFrame:
    def __init__(self, session, plan: LogicalPlan):
        self.session = session
        self.plan = plan

    # --- transformations ---
    def _r(self, e: Expr, alias_bare: bool = False) -> Expr:
        return resolve_nested_refs(e, self.plan.schema, alias_bare)

    def filter(self, condition: Expr) -> "DataFrame":
        return DataFrame(self.session, Filter(self._r(condition), self.plan))

    where = filter

    def select(self, *cols) -> "DataFrame":
        exprs = [self._r(_to_expr(c), alias_bare=True) for c in cols]
        return DataFrame(self.session, Project(exprs, self.plan))

    def with_column(self, name: str, e: Expr) -> "DataFrame":
        exprs: list[Expr] = [col(n) for n in self.schema.names if n != name]
        exprs.append(_to_expr(e).alias(name))
        return DataFrame(self.session, Project(exprs, self.plan))

    def with_column_renamed(self, old: str, new: str) -> "DataFrame":
        exprs = [
            col(n).alias(new) if n == old else col(n) for n in self.schema.names
        ]
        return DataFrame(self.session, Project(exprs, self.plan))

    def join(self, other: "DataFrame", condition: Expr, how: str = "inner") -> "DataFrame":
        both = Schema(list(self.plan.schema) + list(other.plan.schema))
        return DataFrame(
            self.session,
            Join(self.plan, other.plan, resolve_nested_refs(condition, both), how),
        )

    def group_by(self, *cols) -> "GroupedData":
        # group keys stay bare Cols (the fused/device paths match on Col);
        # a resolved nested key surfaces under its full __hs_nested. name
        return GroupedData(self, [self._r(_to_expr(c)) for c in cols])

    groupBy = group_by

    def agg(self, *aggs: Expr) -> "DataFrame":
        return DataFrame(
            self.session,
            Aggregate([], [self._r(a) for a in aggs], self.plan),
        )

    def sort(self, *cols, ascending: bool | Sequence[bool] = True) -> "DataFrame":
        exprs = [self._r(_to_expr(c)) for c in cols]
        if isinstance(ascending, bool):
            orders = [(e, ascending) for e in exprs]
        else:
            orders = list(zip(exprs, ascending))
        return DataFrame(self.session, Sort(orders, self.plan))

    order_by = sort

    def limit(self, n: int) -> "DataFrame":
        return DataFrame(self.session, Limit(n, self.plan))

    def union(self, other: "DataFrame") -> "DataFrame":
        return DataFrame(self.session, Union([self.plan, other.plan]))

    # --- schema / plan ---
    @property
    def schema(self) -> Schema:
        return self.plan.schema

    @property
    def columns(self) -> list[str]:
        return self.schema.names

    def __getitem__(self, name: str) -> Col:
        self.schema.field(name)  # validate
        return col(name)

    def optimized_plan(self) -> LogicalPlan:
        from .passes import (
            pre_rewrite_plan,
            prune_columns,
            push_predicates,
        )

        from ..telemetry import trace

        from .pruning import apply_pruning

        # main-batch passes first (join pushdown + column pruning), exactly
        # as Catalyst runs before extraOptimizations — the rules must see
        # pruned scans or covering indexes are wrongly rejected
        from ..telemetry import attribution

        with trace.span("plan", annotate=False), attribution.phase("plan"):
            plan = pre_rewrite_plan(self.plan)
            for rule in self.session.extra_optimizations:
                plan = rule(plan)
            # scan-level passes run again after the index rewrite so
            # pruned/pushed scans include index relations
            plan = push_predicates(plan)
            plan = prune_columns(plan)
            # predicate-driven index pruning LAST: it consumes the pushed
            # filters the passes above just attached to index scans
            plan = apply_pruning(plan, self.session)
            # HYPERSPACE_VERIFY_PLAN=1: enforce the structural invariants
            # of the final plan (read-only walk; raises PlanInvariantError)
            from ..staticcheck.plan_verifier import maybe_verify_plan

            maybe_verify_plan(plan, self.session)
            return plan

    def explain_plan(self, optimized: bool = True) -> str:
        return (self.optimized_plan() if optimized else self.plan).pretty()

    def explain(self, analyze: bool = False, redirect=None):
        """``df.explain()`` — the optimized plan tree; ``analyze=True``
        executes the query ONCE with the plan-statistics collector
        installed and returns the tree annotated with per-node actual
        rows/wall/route/bytes and estimator q-errors (bit-identical to a
        plain ``collect``; see docs/observability.md)."""
        if not analyze:
            s = self.explain_plan()
        else:
            from ..analysis.explain import explain_analyze_string

            s = explain_analyze_string(self.session, self)
        if redirect is not None:
            redirect(s)
            return None
        return s

    # --- actions ---
    def collect(self) -> ColumnBatch:
        from ..telemetry import attribution

        # query-log completeness (docs/observability.md "Query log"):
        # a direct collect() outside the scheduler opens its own lightweight
        # ledger record, so hs.profile's Query log block and the slow-query
        # JSONL cover ad-hoc queries too. Served queries (an attribution
        # scope is already installed) keep their scheduler-owned record.
        if attribution.current_stats() is not None:
            return self._collect_served()
        from ..serve.context import QueryCancelledError, QueryContext
        from ..telemetry.attribution import LEDGER

        ctx = QueryContext(label=f"collect:{self.plan.kind}")
        stats = LEDGER.begin(ctx)
        outcome, error = "done", None
        try:
            with attribution.scope(stats):
                return self._collect_inner()
        except QueryCancelledError as e:
            outcome, error = "cancelled", e
            raise
        except BaseException as e:
            outcome, error = "failed", e
            raise
        finally:
            # after the scope exited: the rollups are not charged back
            LEDGER.finish(stats, outcome, error)

    def _collect_served(self) -> ColumnBatch:
        """A served query: on its home chip when its session deploys on
        several (``serve.context.query_home``), as on one chip otherwise.
        The kernel cache makes what it compiles there ready on the other
        chips (``plan/kernel_cache.ChipKernel``)."""
        from ..serve.context import query_home
        from ..utils.backend import on_home

        with on_home(query_home(self.session)):
            return self._collect_inner()

    def _collect_inner(self) -> ColumnBatch:
        from ..cache.result_cache import serve_collect
        from ..ingest.snapshots import pin_scope
        from ..telemetry import plan_stats, trace

        # pin scope: every index snapshot the rewrite resolves inside this
        # execution stays on disk (refcounted against compaction/vacuum)
        # until the query drains — released on success, failure, AND
        # cancellation (QueryCancelledError unwinds through the with).
        # serve_collect is the result-cache chokepoint: with
        # HYPERSPACE_RESULT_CACHE on, a plan whose (fingerprint, pinned
        # snapshots) key repeats is served from the cache with zero
        # scan/upload/dispatch; otherwise it executes exactly as before.
        # plan_stats.maybe_scope installs a per-node statistics collector
        # only under HYPERSPACE_PLAN_STATS=1 (explain_analyze installs its
        # own scope outside); observe-only either way.
        def run() -> ColumnBatch:
            from ..telemetry import workload
            from . import adaptive, sampling

            optimized = self.optimized_plan()
            plan_stats.note_plan(optimized)
            # workload plane: shapes / join keys / columns of the optimized
            # plan ride the query's journal record (no-op when disabled)
            workload.note_plan(optimized)
            # approximate tier (HYPERSPACE_APPROX + a requested fraction —
            # QoS degrade or an explicit approx_scope): eligible aggregates
            # execute against sample twins and come back scaled with CIs;
            # ineligible or off, the exact path below is untouched
            approx = sampling.maybe_execute_sampled(self.session, optimized)
            if approx is not None:
                return approx
            # adaptive.execute_collect IS serve_collect when
            # HYPERSPACE_ADAPTIVE=0 (the default); otherwise it installs
            # the replan scope (scan abort-and-replan re-optimizes against
            # the same pinned snapshot) and, in verify mode, re-executes
            # the final plan statically and raises on divergence
            return adaptive.execute_collect(
                self.session, self.plan, optimized, self.optimized_plan
            )

        if not trace.enabled():
            with plan_stats.maybe_scope(), pin_scope():
                return run()
        with plan_stats.maybe_scope(), trace.span("query") as sp, pin_scope():
            out = run()
            sp.set_attr("rows_out", out.num_rows)
            return out

    def to_pydict(self) -> dict[str, list]:
        return self.collect().to_pydict()

    def to_pandas(self):
        import pandas as pd

        return pd.DataFrame(self.to_pydict())

    def count(self) -> int:
        return self.collect().num_rows

    def write_parquet(self, path: str, filename: str = "part-0.parquet") -> None:
        batch = self.collect()
        cio.write_parquet(batch, os.path.join(path, filename))


class GroupedData:
    def __init__(self, df: DataFrame, group_exprs: list[Expr]):
        self._df = df
        self._group_exprs = group_exprs

    def agg(self, *aggs: Expr) -> DataFrame:
        return DataFrame(
            self._df.session,
            Aggregate(
                self._group_exprs,
                [self._df._r(a) for a in aggs],
                self._df.plan,
            ),
        )


class DataFrameReader:
    """session.read.parquet/csv/json — builds a FileScan with resolved files
    (the leaf the rewrite rules and hybrid scan reason over)."""

    def __init__(self, session):
        self.session = session
        self._options: dict[str, str] = {}

    def option(self, key: str, value) -> "DataFrameReader":
        self._options[key] = str(value)
        return self

    def _load(self, fmt: str, path: str | Sequence[str]) -> DataFrame:
        roots = [path] if isinstance(path, str) else list(path)
        # glob expansion (ref: globbing-pattern handling in
        # DefaultFileBasedRelation:129-192): wildcard roots expand to the
        # matching directories/files; a declared `globbingPattern` option is
        # validated against the roots so indexes record the right pattern
        from ..sources.interfaces import expand_glob_roots

        had_glob = any(_has_magic(r) for r in roots)
        expanded = expand_glob_roots(roots)
        from .. import constants as C

        declared = self._options.get(C.GLOBBING_PATTERN_KEY) or self._options.get(
            "globbingPattern"
        )
        patterns: list[str] = []
        if declared:
            # the whole string is tried first (paths may legally contain
            # commas), then the reference's comma-separated interpretation
            whole = [str(declared)]
            parts = [p.strip() for p in str(declared).split(",") if p.strip()]
            candidates = whole if _first_unmatched(expanded, whole) is None else parts
            bad = _first_unmatched(expanded, candidates)
            if bad is not None:
                raise HyperspaceError(
                    f"Path {bad!r} does not match the declared globbing "
                    f"pattern {declared!r}"
                )
            patterns = candidates
        from ..sources.interfaces import encode_glob_paths

        if declared:
            # the declared pattern IS the relation's scope: refresh expands
            # it (and only it) so later-matching directories are covered
            # while out-of-scope data stays excluded
            self._options[C.OPT_GLOB_PATHS] = encode_glob_paths(patterns)
        elif had_glob:
            # no declaration: record the raw glob roots as the scope
            # (ref: the relation records glob paths as rootPaths,
            # DefaultFileBasedRelation.scala:159-187)
            self._options[C.OPT_GLOB_PATHS] = encode_glob_paths(roots)
        else:
            # never inherit a previous load's pattern on reader reuse
            self._options.pop(C.OPT_GLOB_PATHS, None)
        roots = expanded
        files: list[FileInfo] = []
        for root in roots:
            root = os.path.abspath(root)
            if os.path.isfile(root):
                files.append(FileInfo.from_path(root))
            elif os.path.isdir(root):
                for dirpath, _dirs, names in os.walk(root):
                    # skip hidden/metadata dirs (e.g. _hyperspace_log)
                    parts = os.path.relpath(dirpath, root).split(os.sep)
                    if any(p.startswith(("_", ".")) for p in parts if p != "."):
                        continue
                    for fn in sorted(names):
                        if fn.startswith(("_", ".")):
                            continue
                        files.append(FileInfo.from_path(os.path.join(dirpath, fn)))
            else:
                raise HyperspaceError(f"Path not found: {root}")
        if not files:
            raise HyperspaceError(f"No data files under {roots}")
        schema = cio.read_schema(fmt, files[0].name)
        # hive-style partition columns from key=value path components
        from ..utils.partitions import infer_partition_fields

        abs_roots = [os.path.abspath(r) for r in roots]
        part_fields = [
            f for f in infer_partition_fields([fi.name for fi in files], abs_roots)
            if f.name not in schema
        ]
        if part_fields:
            from ..columnar.table import Schema

            schema = Schema(list(schema.fields) + part_fields)
        scan = FileScan(
            [os.path.abspath(r) for r in roots],
            fmt,
            schema,
            files,
            options=self._options,
            partition_columns=[f.name for f in part_fields],
        )
        return DataFrame(self.session, scan)

    def parquet(self, path) -> DataFrame:
        return self._load("parquet", path)

    def csv(self, path) -> DataFrame:
        return self._load("csv", path)

    def json(self, path) -> DataFrame:
        return self._load("json", path)

    def orc(self, path) -> DataFrame:
        return self._load("orc", path)

    def text(self, path) -> DataFrame:
        return self._load("text", path)

    def format(self, fmt: str):
        reader = self
        class _Bound:
            def load(self, path):
                return reader._load(fmt, path)
        return _Bound()
