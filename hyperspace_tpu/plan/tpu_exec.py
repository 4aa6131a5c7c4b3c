"""TPU execution path: compile plan fragments to fused XLA kernels.

The reference's hot query loop is Spark's JVM whole-stage codegen; here the
equivalent is tracing the expression tree straight into one jitted XLA
computation per (plan shape, chunk size): scan columns land in HBM once,
filter + projection + aggregation fuse into a single pass (XLA fuses the
elementwise chain into the reduce), and nothing round-trips to the host until
the scalar results.

Static-shape contract: columns are padded to the next power-of-two chunk and
masked, so one compiled kernel serves any file/row count of the same size
class (no recompiles per file).

Supported fragment today — the filter-aggregate pipeline:
    Aggregate(no groups | grouped) ← [Project] ← [Filter] ← FileScan
with numeric/date columns. Anything else falls back to the host executor.
"""

from __future__ import annotations

import time
from collections import deque
from functools import partial
from typing import Optional

import numpy as np

import jax
import jax.numpy as jnp

from . import expr as X
from .expr import Alias, Expr
from .kernel_cache import (
    KERNEL_CACHE as _KERNEL_CACHE,
    SORT_CACHE as _SORT_CACHE,
    TOPK_CACHE as _TOPK_CACHE,
    _dev_dtype_label,
    fused_fingerprint,
    grouped_fingerprint,
    mesh_fingerprint,
)
from .nodes import Aggregate, FileScan, Filter, LogicalPlan, Project
from ..columnar.table import Column, ColumnBatch, STRING
from ..exceptions import HyperspaceError
from ..serve.context import check_cancelled as _serve_check_cancelled
from ..telemetry import attribution as _attr
from ..telemetry import trace
from ..telemetry.metrics import REGISTRY
from ..utils import env


def _observe_dispatch(kernel_name: str, t0: float) -> None:
    """Per-kernel dispatch-latency histograms (always on; two clock reads
    against milliseconds-scale device work). Doubles as the serving
    query's "dispatch" phase chokepoint."""
    dt = time.perf_counter() - t0
    ms = dt * 1000
    REGISTRY.histogram("kernel.dispatch_ms").observe(ms)
    REGISTRY.histogram(f"kernel.{kernel_name}.dispatch_ms").observe(ms)
    _attr.charge_phase("dispatch", dt)

# ---------------------------------------------------------------------------
# Expr -> jnp tracing
# ---------------------------------------------------------------------------

_CMP = {
    X.Eq: jnp.equal,
    X.Ne: jnp.not_equal,
    X.Lt: jnp.less,
    X.Le: jnp.less_equal,
    X.Gt: jnp.greater,
    X.Ge: jnp.greater_equal,
}
_ARITH = {X.Add: jnp.add, X.Sub: jnp.subtract, X.Mul: jnp.multiply, X.Div: jnp.true_divide}


class Wide64:
    """Device representation of a full-range int64 column on a 32-bit
    device: signed high word + unsigned-compared low word. Only comparison
    predicates against int literals are defined over it (two-word
    lexicographic compare); anything else falls back to the host."""

    def __init__(self, hi, lo_u):
        self.hi = hi  # int32 (signed high word)
        self.lo_u = lo_u  # uint32 view of the low word

    def compare(self, kind, value: int):
        v64 = np.int64(value)
        l_hi = jnp.int32(np.int32(v64 >> np.int64(32)))  # signed high word
        l_lo = jnp.uint32(np.uint64(v64) & np.uint64(0xFFFFFFFF))
        hi_eq = self.hi == l_hi
        if kind is X.Eq:
            return hi_eq & (self.lo_u == l_lo)
        if kind is X.Ne:
            return ~(hi_eq & (self.lo_u == l_lo))
        if kind is X.Lt:
            return (self.hi < l_hi) | (hi_eq & (self.lo_u < l_lo))
        if kind is X.Le:
            return (self.hi < l_hi) | (hi_eq & (self.lo_u <= l_lo))
        if kind is X.Gt:
            return (self.hi > l_hi) | (hi_eq & (self.lo_u > l_lo))
        if kind is X.Ge:
            return (self.hi > l_hi) | (hi_eq & (self.lo_u >= l_lo))
        raise HyperspaceError(f"Wide64 comparison unsupported: {kind}")


def _wide_compare(e: Expr, cols):
    """Two-word compare when one side is a Wide64 column and the other an
    int literal; None when the pattern does not apply."""
    flipped = {X.Lt: X.Gt, X.Le: X.Ge, X.Gt: X.Lt, X.Ge: X.Le, X.Eq: X.Eq, X.Ne: X.Ne}
    for a, b, kind in (
        (e.left, e.right, type(e)),
        (e.right, e.left, flipped[type(e)]),
    ):
        if (
            isinstance(a, X.Col)
            and isinstance(cols.get(a.name), Wide64)
            and isinstance(b, X.Lit)
            and isinstance(b.value, (int, np.integer))
            and not isinstance(b.value, bool)
        ):
            return cols[a.name].compare(kind, int(b.value))
    return None


def compile_expr(e: Expr, cols: dict[str, jnp.ndarray]):
    """Trace an expression over device column arrays. Caller guarantees the
    involved columns are non-null numerics (checked in _plan_supported)."""
    if isinstance(e, Alias):
        return compile_expr(e.child, cols)
    if isinstance(e, X.Col):
        v = cols[e.name]
        if isinstance(v, Wide64):
            raise HyperspaceError(
                f"Wide int64 column {e.name} only supports literal comparisons"
            )
        return v
    if isinstance(e, X.Lit):
        return e.value
    for klass, op in _CMP.items():
        if type(e) is klass:
            wide = _wide_compare(e, cols)
            if wide is not None:
                return wide
            return op(compile_expr(e.left, cols), compile_expr(e.right, cols))
    for klass, op in _ARITH.items():
        if type(e) is klass:
            return op(compile_expr(e.left, cols), compile_expr(e.right, cols))
    if isinstance(e, X.And):
        return compile_expr(e.left, cols) & compile_expr(e.right, cols)
    if isinstance(e, X.Or):
        return compile_expr(e.left, cols) | compile_expr(e.right, cols)
    if isinstance(e, X.Not):
        return ~compile_expr(e.child, cols)
    if isinstance(e, X.In):
        c = compile_expr(e.child, cols)
        out = jnp.zeros(c.shape, dtype=bool)
        for v in e.values:
            out = out | (c == v)
        return out
    raise HyperspaceError(f"Expression not supported on device: {e!r}")


def _expr_device_ok(e: Expr, string_ok: frozenset = frozenset()) -> bool:
    try:
        _check_expr(e, string_ok)
        return True
    except HyperspaceError:
        return False


def _int_lit_fits(v) -> bool:
    if isinstance(v, (int, np.integer)) and not isinstance(v, bool):
        return -(2**31) <= int(v) < 2**31
    return True


def _literals_fit(e: Expr, wide_ok: frozenset = frozenset()) -> bool:
    """False when an integer literal outside the 32-bit device range appears
    anywhere but a Wide64 comparison: tracing such an expression against a
    downcast column overflows at jnp conversion. That is an unsupported
    shape, not a backend failure — it must decline to the host path BEFORE
    the circuit breaker can latch the device tier off on it."""
    if type(e) in _CMP:
        for a, b in ((e.left, e.right), (e.right, e.left)):
            if (
                isinstance(a, X.Col)
                and a.name in wide_ok
                and isinstance(b, X.Lit)
            ):
                return True  # Wide64 compares any int literal magnitude
    if isinstance(e, X.Lit):
        return _int_lit_fits(e.value)
    if isinstance(e, X.In) and not all(_int_lit_fits(v) for v in e.values):
        return False
    return all(_literals_fit(c, wide_ok) for c in e.children())


def _string_eq_pattern(e: Expr):
    """(col_name, lit_value, is_eq) when e is Eq/Ne(Col, Lit(str)) in either
    order; None otherwise."""
    if isinstance(e, (X.Eq, X.Ne)):
        for a, b in ((e.left, e.right), (e.right, e.left)):
            if (
                isinstance(a, X.Col)
                and isinstance(b, X.Lit)
                and isinstance(b.value, str)
            ):
                return a.name, b.value, isinstance(e, X.Eq)
    return None


def _check_expr(e: Expr, string_ok: frozenset = frozenset()) -> None:
    if isinstance(e, (X.IsNull, X.IsNotNull)):
        raise HyperspaceError("null tests need host path")
    pat = _string_eq_pattern(e)
    if pat is not None and pat[0] in string_ok:
        return  # rewritable to a dictionary-code comparison at exec time
    if (
        isinstance(e, X.In)
        and isinstance(e.child, X.Col)
        and e.child.name in string_ok
        and all(isinstance(v, str) for v in e.values)
    ):
        return
    if isinstance(e, X.Lit) and isinstance(e.value, str):
        raise HyperspaceError("string literal needs host path")
    for c in e.children():
        _check_expr(c, string_ok)


def _encode_string_predicates(e: Expr, batch: ColumnBatch, scols: set[str]):
    """Rewrite string-column comparisons against string literals into
    dictionary-code comparisons for the batch at hand (codes are int32 and
    ship to device; the strings themselves never do). Values absent from
    the dictionary fold to boolean literals. Returns None when a string
    reference survives in a non-rewritable position."""
    pat = _string_eq_pattern(e)
    if pat is not None and pat[0] in scols:
        name, value, is_eq = pat
        lut = {s: i for i, s in enumerate(batch.column(name).dictionary or [])}
        code = lut.get(value)
        if code is None:
            return X.Lit(is_eq is False)  # Eq -> never; Ne -> always (no NULLs)
        klass = X.Eq if is_eq else X.Ne
        return klass(X.Col(name), X.Lit(int(code)))
    if (
        isinstance(e, X.In)
        and isinstance(e.child, X.Col)
        and e.child.name in scols
        and all(isinstance(v, str) for v in e.values)
    ):
        lut = {s: i for i, s in enumerate(batch.column(e.child.name).dictionary or [])}
        codes = [int(lut[v]) for v in e.values if v in lut]
        if not codes:
            return X.Lit(False)
        return X.In(X.Col(e.child.name), codes)
    if isinstance(e, X.Col) and e.name in scols:
        return None  # bare string reference cannot ship
    if isinstance(e, (X.And, X.Or, *_CMP.keys(), *_ARITH.keys())):
        left = _encode_string_predicates(e.left, batch, scols)
        right = _encode_string_predicates(e.right, batch, scols)
        if left is None or right is None:
            return None
        return type(e)(left, right)
    if isinstance(e, X.Not):
        child = _encode_string_predicates(e.child, batch, scols)
        return None if child is None else X.Not(child)
    if isinstance(e, X.In):
        child = _encode_string_predicates(e.child, batch, scols)
        return None if child is None else X.In(child, e.values)
    return e  # Lit / Col(non-string) / anything without string refs below


# ---------------------------------------------------------------------------
# fragment matching
# ---------------------------------------------------------------------------

class _Fragment:
    def __init__(self, agg: Aggregate, project: Optional[Project], filt: Optional[Filter], scan: FileScan):
        self.agg = agg
        self.project = project
        self.filter = filt
        self.scan = scan
        # the predicate the kernels compile: starts as the filter condition,
        # replaced by its dictionary-code rewrite when strings are involved
        self.pred: Optional[Expr] = filt.condition if filt is not None else None


def _match_fragment(plan: LogicalPlan) -> Optional[_Fragment]:
    """Aggregate ← [Project] ← [Filter] ← FileScan. A Filter *above* a
    Project is not matched: its predicate may reference projected aliases,
    which the kernel compiles against raw scan columns."""
    if not isinstance(plan, Aggregate):
        return None
    node = plan.child
    project = None
    filt = None
    if isinstance(node, Project):
        project = node
        node = node.child
    if isinstance(node, Filter):
        filt = node
        node = node.child
    if not isinstance(node, FileScan):
        return None
    return _Fragment(plan, project, filt, node)


def _group_key_names(f: _Fragment) -> set[str]:
    return {e.name for e in f.agg.group_exprs if isinstance(e, X.Col)}


def _project_identity(project: Project, name: str) -> bool:
    """True iff the projection outputs `name` as the unchanged column."""
    for e in project.exprs:
        if X.expr_output_name(e) == name:
            inner = e.child if isinstance(e, Alias) else e
            return isinstance(inner, X.Col) and inner.name == name
    return False


def _upload_columns(batch: ColumnBatch, names, padded: int, wide_ok: frozenset = frozenset(),
                    device=None):
    """Zero-padded device upload of the named columns; None when any column
    is nullable or exceeds the device's 32-bit integer range (host path).
    Columns in `wide_ok` (full-range int64 referenced only in literal
    comparisons) ship as (hi int32, lo uint32) word pairs instead.

    Device copies are cached by source-buffer identity (utils/device_cache)
    so repeated queries over the same index chunks skip the host->device
    transfer entirely. ``device`` commits the upload to a placed mesh
    device under its own cache entry; None keeps the historical
    uncommitted default-device path and its exact cache keys."""
    from ..ops.hashing import split64_np
    from ..utils.device_cache import DEVICE_CACHE

    def _commit(x):
        return jnp.asarray(x) if device is None else jax.device_put(x, device)

    def _dtag(t: tuple) -> tuple:
        return t if device is None else t + (f"d{device.id}",)

    n = batch.num_rows
    dev_cols = {}
    for name in sorted(names):
        col = batch.column(name)
        if col.validity is not None:
            return None
        if col.dtype == "int64" and (
            col.data.min(initial=0) < -(2**31) or col.data.max(initial=0) >= 2**31
        ):
            if name not in wide_ok:
                return None

            def _build_wide(data=col.data):
                lo, hi = split64_np(data)
                hi_p = np.zeros(padded, np.int32)
                hi_p[:n] = hi
                lo_p = np.zeros(padded, np.uint32)
                lo_p[:n] = lo.view(np.uint32)
                return (_commit(hi_p), _commit(lo_p))

            dev_cols[name] = DEVICE_CACHE.get_or_put(
                col.data, _dtag(("wide", padded)), _build_wide
            )
            continue

        def _build(data=col.data):
            arr = np.zeros(padded, dtype=_device_dtype(data.dtype))
            arr[:n] = data.astype(arr.dtype)
            return _commit(arr)

        dev_cols[name] = DEVICE_CACHE.get_or_put(
            col.data, _dtag(("pad", padded)), _build
        )
    return dev_cols


def _padded_mask(padded: int, n: int, device=None):
    """Device copy of the valid-rows mask [0..n) within [0..padded): a fresh
    upload per query costs a host->device round trip, and the
    arrays are `padded` device bytes each — so they live in the budgeted
    device LRU, not an unbounded side cache."""
    from ..utils.device_cache import DEVICE_CACHE

    if device is None:
        return DEVICE_CACHE.get_or_put_keyed(
            ("mask", padded, n), lambda: jnp.asarray(np.arange(padded) < n)
        )
    return DEVICE_CACHE.get_or_put_keyed(
        ("mask", padded, n, f"d{device.id}"),
        lambda: jax.device_put(np.arange(padded) < n, device),
    )


def _wrap_wide(cols: dict):
    """Re-wrap transported (hi, lo) word pairs into Wide64 inside kernels
    (Wide64 itself is not a pytree, so tuples cross the jit boundary)."""
    return {
        k: Wide64(v[0], v[1]) if isinstance(v, tuple) else v
        for k, v in cols.items()
    }


def _wide_pattern_ok(e: Expr, name: str) -> bool:
    """Every reference to `name` inside e must be a direct comparison
    against an integer literal (the only operation Wide64 defines)."""
    if isinstance(e, X.Col):
        return e.name != name
    if type(e) in _CMP:
        for a, b in ((e.left, e.right), (e.right, e.left)):
            if isinstance(a, X.Col) and a.name == name:
                return (
                    isinstance(b, X.Lit)
                    and isinstance(b.value, (int, np.integer))
                    and not isinstance(b.value, bool)
                )
    return all(_wide_pattern_ok(c, name) for c in e.children())


def _wide_predicate_cols(frag: "_Fragment", batch: ColumnBatch) -> frozenset:
    """int64 columns exceeding the 32-bit device range that may still ship
    as word pairs: non-null, referenced ONLY by the filter predicate, and
    there only in comparisons against integer literals."""
    pred = frag.pred
    if pred is None:
        return frozenset()
    cand = set()
    for name in pred.references():
        if name not in batch.columns:
            continue
        col = batch.column(name)
        if col.validity is not None or col.data.dtype != np.int64:
            continue
        if len(col.data) and (
            col.data.min() < -(2**31) or col.data.max() >= 2**31
        ):
            cand.add(name)
    if not cand:
        return frozenset()
    pred_orig = frag.filter.condition if frag.filter is not None else None
    for e in _device_exprs(frag):
        if e is pred_orig:
            continue
        cand -= e.references()
    return frozenset(c for c in cand if _wide_pattern_ok(pred, c))


def _fragment_literals_fit(frag: "_Fragment", wide_ok: frozenset = frozenset()) -> bool:
    """Literal-magnitude screen over everything the kernels will trace.
    Only the filter predicate may lean on Wide64 comparisons."""
    if frag.pred is not None and not _literals_fit(frag.pred, wide_ok):
        return False
    for e in _device_projections(frag):
        if not _literals_fit(e):
            return False
    for e in frag.agg.agg_exprs:
        if not _literals_fit(e):
            return False
    return True


def _agg_list_names(frag: _Fragment):
    from .executor import _unwrap_agg

    agg_list, names = [], []
    for e in frag.agg.agg_exprs:
        name, agg = _unwrap_agg(e)
        names.append(name)
        agg_list.append(
            ("count", None) if isinstance(agg, X.Count) else (agg.func, agg.child)
        )
    return agg_list, names


def _device_projections(f: _Fragment) -> list[Expr]:
    """Projection outputs the device must compute: identity pass-throughs of
    group keys are excluded (keys factorize host-side and never ship)."""
    if f.project is None:
        return []
    keys = _group_key_names(f)
    out = []
    for e in f.project.exprs:
        inner = e.child if isinstance(e, Alias) else e
        if isinstance(inner, X.Col) and X.expr_output_name(e) in keys and inner.name == X.expr_output_name(e):
            continue
        out.append(e)
    return out


def _device_exprs(f: _Fragment) -> list[Expr]:
    exprs: list[Expr] = list(f.agg.agg_exprs)
    if f.filter is not None:
        exprs.append(f.filter.condition)
    exprs.extend(_device_projections(f))
    return exprs


def _device_refs(f: "_Fragment") -> set[str]:
    """Source columns device kernels may read: every expression reference
    (the filter condition is part of _device_exprs; its dictionary-code
    rewrite preserves column names, so frag.pred adds nothing)."""
    refs: set[str] = set()
    for e in _device_exprs(f):
        refs |= e.references()
    return refs


def _fragment_supported(f: _Fragment) -> bool:
    """Structural + dtype screen that needs no data read (validity is checked
    after the scan; everything else is knowable from schema + expressions)."""
    if f.agg.group_exprs:
        # grouped fragments run on device via segment reductions when every
        # group key is a bare scan column passed through untouched by any
        # projection (keys factorize host-side from the scan batch)
        keys = _group_key_names(f)
        if len(keys) != len(f.agg.group_exprs):
            return False
        scan_cols = set(f.scan.schema.names)
        for k in keys:
            if k not in scan_cols:
                return False
            if f.project is not None and not _project_identity(f.project, k):
                return False
    exprs = _device_exprs(f)
    string_cols = frozenset(
        fld.name for fld in f.scan.schema if fld.dtype == STRING
    )
    pred = f.filter.condition if f.filter is not None else None
    for e in exprs:
        # the filter predicate may compare string columns against string
        # literals (rewritten to dictionary codes at exec time); aggregates
        # and projections may not touch strings at all
        if not _expr_device_ok(e, string_cols if e is pred else frozenset()):
            return False
    # string columns may serve as group keys (factorized host-side, never
    # shipped) or appear in rewritable filter patterns (shipped as codes),
    # but must not feed other device expressions
    device_refs: set[str] = set()
    for e in exprs:
        if e is pred:
            continue
        device_refs |= e.references()
    for field in f.scan.schema:
        if field.dtype == STRING and field.name in device_refs:
            return False
    return True


# ---------------------------------------------------------------------------
# kernel
# ---------------------------------------------------------------------------

def _pad_pow2(n: int) -> int:
    return 1 << max(10, int(np.ceil(np.log2(max(1, n)))))


# Compiled kernels cache cross-query by canonical plan fingerprint — shared
# between the monolithic and pipelined executors (plan/kernel_cache.py owns
# the instances, the fingerprint format, and the hit/miss/evict metrics).


def _extreme(dtype, want_max: bool):
    if jnp.issubdtype(dtype, jnp.integer):
        info = jnp.iinfo(dtype)
        return info.max if want_max else info.min
    return jnp.inf if want_max else -jnp.inf


# Exact integer SUM/AVG accumulation (see ops/intsum.py for the scheme and
# the row-cap rationale).
from ..ops.intsum import (  # noqa: E402
    _INT_SUM_ROW_CAP,
    blocked_segment_sum,
    combine_int_chunks as _combine_int_chunks,
    int_chunk_sums as _int_chunk_sums,
)


def _combine_chunks_maybe_avg(v, kind: str, counts):
    """Host side of a device aggregate (per group, or global with a scalar
    ``counts``): exact int chunks fold to int64, and an Avg (the device
    returns its sum) divides by the counts in f64."""
    s = _combine_int_chunks(v) if isinstance(v, tuple) else np.asarray(v)
    if kind == "avg":
        return np.asarray(s, np.float64) / np.maximum(counts, 1)
    return s


def _parquet_row_count(scan) -> Optional[int]:
    """Total rows from file metadata (no data pages); None for other
    formats or unreadable footers. Index scans carry fmt='parquet' even
    when the files are .arrow — cio.file_num_rows dispatches per
    extension, and ANY metadata failure must decline to the host path,
    not crash the query (ArrowInvalid is not an OSError)."""
    from ..columnar import io as cio

    if scan.fmt != "parquet":
        return None
    try:
        return sum(cio.file_num_rows(f.name) for f in scan.files)
    except Exception:
        return None


def _pruned_row_count(scan, selection) -> Optional[int]:
    """Row count of the scan AFTER row-group pruning (footer metadata only):
    files read whole count via `file_num_rows`, partially-selected files sum
    their kept groups' row counts from the cached stats."""
    from ..columnar import io as cio

    row_groups, files = selection
    if scan.fmt != "parquet":
        return None
    try:
        total = 0
        for f in files:
            sel = row_groups.get(f.name) if row_groups else None
            if sel is None:
                total += cio.file_num_rows(f.name)
            else:
                stats = cio.read_rowgroup_stats(f.name, [])
                if stats is None:
                    return None
                total += sum(stats[g]["num_rows"] for g in sel)
        return total
    except Exception:
        return None


def _maybe_int_expr(e: Expr, frag: "_Fragment") -> bool:
    """Conservative integer-dtype inference (False only when e provably
    traces to float). Drives the exact chunked accumulation row cap for Avg;
    a false True merely applies the cap to a float expression (the kernel
    branches on the actual traced dtype), while a false False would let
    chunk sums overflow — so unknowns resolve to True."""
    if isinstance(e, Alias):
        return _maybe_int_expr(e.child, frag)
    if isinstance(e, X.Div):
        return False  # true_divide always yields float
    if isinstance(e, X.Lit):
        return not isinstance(e.value, float)
    if isinstance(e, X.Col):
        sch = frag.scan.schema
        if e.name in sch.names:
            return not sch.field(e.name).dtype.startswith("float")
        if frag.project is not None:
            for p in frag.project.exprs:
                if X.expr_output_name(p) == e.name:
                    return _maybe_int_expr(p, frag)
        return True
    children = e.children()
    if not children:
        return True
    # arithmetic promotes to float when ANY operand is float
    return all(_maybe_int_expr(c, frag) for c in children)


def _has_int_sum(frag: "_Fragment", plan) -> bool:
    """True when an aggregate needs the exact chunked int accumulation (and
    therefore its row cap): int-typed SUM, or AVG over a (possibly) integer
    input — an f32 sum of large-magnitude ints would deviate visibly from
    the host's f64 accumulation."""
    from .executor import _unwrap_agg

    schema = plan.schema
    for e in frag.agg.agg_exprs:
        nm, agg = _unwrap_agg(e)
        if isinstance(agg, X.Sum) and schema.field(nm).dtype.startswith("int"):
            return True
        if isinstance(agg, X.Avg) and _maybe_int_expr(agg.child, frag):
            return True
    return False


def _pallas_shape(pred_expr, proj_exprs, agg_list):
    """When the fragment is exactly filter -> sum(a*b)+count or
    filter -> sum(a)+count, the hand-rolled Pallas reductions
    (ops/pallas_kernels.filter_weighted_sum / filter_sum) take over on TPU.
    Returns (a_expr, b_expr|None, sum_pos, cnt_pos) or None."""
    if pred_expr is None or proj_exprs:
        return None
    if len(agg_list) != 2:
        return None
    kinds = [k for k, _ in agg_list]
    if sorted(kinds) != ["count", "sum"]:
        return None
    sum_pos = kinds.index("sum")
    child = agg_list[sum_pos][1]
    cnt_pos = kinds.index("count")
    if type(child) is X.Mul and isinstance(child.left, X.Col) and isinstance(child.right, X.Col):
        return child.left, child.right, sum_pos, cnt_pos
    if isinstance(child, X.Col):
        return child, None, sum_pos, cnt_pos
    return None


def _build_pallas_kernel(pred_expr, proj_exprs, agg_list, a_expr, b_expr, sum_pos):
    from ..ops.pallas_kernels import filter_sum, filter_weighted_sum

    def kernel(cols, mask):
        cols = _wrap_wide(cols)
        a = compile_expr(a_expr, cols)
        b = None if b_expr is None else compile_expr(b_expr, cols)
        if jnp.issubdtype(a.dtype, jnp.integer) or (
            b is not None and jnp.issubdtype(b.dtype, jnp.integer)
        ):
            # integer sums need the exact chunked accumulation; the f32
            # Pallas reduction would round — generic body takes over
            return _generic_agg_compute(pred_expr, proj_exprs, agg_list, cols, mask)
        pred = mask & compile_expr(pred_expr, cols)
        if b is None:
            rev, cnt = filter_sum(pred, a)
        else:
            rev, cnt = filter_weighted_sum(pred, a, b)
        matched = cnt.astype(jnp.int32)
        out = (rev, matched) if sum_pos == 0 else (matched, rev)
        return matched, out

    return jax.jit(kernel)  # hslint: HS201 — builder runs via KernelCache.get_or_build


def _generic_agg_compute(pred_expr, proj_exprs, agg_list, cols, mask):
    """Traced body of the generic fused kernel (shared so the Pallas kernel
    can fall back to it at trace time for integer-sum exactness)."""
    if pred_expr is not None:
        mask = mask & compile_expr(pred_expr, cols)
    matched = mask.sum()
    proj_cols = dict(cols)
    for name, e in proj_exprs:
        proj_cols[name] = compile_expr(e, cols)
    out = []
    for kind, child in agg_list:
        if kind == "count":
            out.append(matched)
            continue
        vals = compile_expr(child, proj_cols)
        # fill values stay in the column dtype (no float promotion that
        # would round ints >= 2**24)
        if kind == "sum":
            if jnp.issubdtype(vals.dtype, jnp.integer):
                out.append(_int_chunk_sums(jnp.where(mask, vals, 0)))
            else:
                out.append(jnp.where(mask, vals, 0).sum())
        elif kind == "min":
            out.append(jnp.where(mask, vals, _extreme(vals.dtype, True)).min())
        elif kind == "max":
            out.append(jnp.where(mask, vals, _extreme(vals.dtype, False)).max())
        elif kind == "avg":
            # the sum only: the HOST divides by the count in f64 (an f32
            # sum of large-magnitude ints deviates from the host's f64, and
            # the TPU's f32 division is not correctly rounded)
            if jnp.issubdtype(vals.dtype, jnp.integer):
                out.append(_int_chunk_sums(jnp.where(mask, vals, 0)))
            else:
                out.append(jnp.where(mask, vals, 0).sum())
    return matched, tuple(out)


def _pallas_route() -> bool:
    """Whether kernel builds take the Pallas route — part of the kernel
    cache key, since the decision is made at build time."""
    from ..utils.backend import platform

    return platform() == "tpu" or env.env_bool("HYPERSPACE_FORCE_PALLAS")


def _build_kernel(pred_expr, proj_exprs, agg_list):
    use_pallas = _pallas_route()
    if use_pallas:
        shape = _pallas_shape(pred_expr, proj_exprs, agg_list)
        if shape is not None:
            a, b, sum_pos, _cnt_pos = shape
            return _build_pallas_kernel(pred_expr, proj_exprs, agg_list, a, b, sum_pos)

    def kernel(cols, mask):
        cols = _wrap_wide(cols)
        return _generic_agg_compute(pred_expr, proj_exprs, agg_list, cols, mask)

    return jax.jit(kernel)  # hslint: HS201 — builder runs via KernelCache.get_or_build


def _device_dtype(np_dtype) -> np.dtype:
    # x64 is disabled on device: widest native types are 32-bit; float64
    # accumulation happens in the final host combine
    d = np.dtype(np_dtype)
    if d == np.int64:
        return np.dtype(np.int32)  # caller verified value range
    if d == np.float64:
        return np.dtype(np.float32)
    return d




def _assemble_grouped_output(plan, frag, key_cols, first_idx, counts, results, agg_list_spec, names, num_groups, first_masked=None):
    """Shared grouped-result assembly (single-device and mesh paths must not
    diverge): drop empty groups, emit key columns from first occurrences,
    coerce aggregate dtypes per the plan schema. `first_masked` (per-group
    index of the first row passing the predicate, from the kernel) orders
    the output rows exactly like the host tier, which groups the FILTERED
    batch — without it the order would follow pre-filter first occurrence
    when the device scanned unfiltered chunks."""
    keep = counts > 0
    order = None
    if first_masked is not None and keep.any():
        fm = np.asarray(first_masked)[:num_groups][keep]
        order = np.argsort(fm, kind="stable")
    out_cols: dict[str, Column] = {}
    for e, kc in zip(frag.agg.group_exprs, key_cols):
        kept = kc.take(first_idx[keep])
        out_cols[X.expr_output_name(e)] = kept if order is None else kept.take(order)
    schema = plan.schema
    for (name, val), (kind, _c) in zip(zip(names, results), agg_list_spec):
        f = schema.field(name)
        np_val = np.asarray(val)[:num_groups][keep]
        if order is not None:
            np_val = np_val[order]
        if kind == "count":
            out_cols[name] = Column(np_val.astype(np.int64), "int64")
        elif f.dtype in ("int64", "int32", "int16", "int8"):
            out_cols[name] = Column(np_val.astype(np.dtype(f.dtype)), f.dtype)
        else:
            out_cols[name] = Column(np_val.astype(np.float64), "float64")
    return ColumnBatch(out_cols)


def _assemble_global_output(plan, matched, scalar_values, agg_list_spec, names):
    """Shared global-result assembly: zero matches -> SQL NULL for non-count
    aggregates (host-executor semantics)."""
    out_cols: dict[str, Column] = {}
    schema = plan.schema
    for (name, val), (kind, _c) in zip(zip(names, scalar_values), agg_list_spec):
        f = schema.field(name)
        if kind == "count":
            out_cols[name] = Column(np.array([matched], dtype=np.int64), "int64")
        elif matched == 0:
            out_cols[name] = Column(np.zeros(1, np.float64), "float64", np.array([False]))
        else:
            if f.dtype in ("int64", "int32", "int16", "int8"):
                out_cols[name] = Column(np.array([int(val)], dtype=np.dtype(f.dtype)), f.dtype)
            else:
                out_cols[name] = Column(np.array([float(val)]), "float64")
    return ColumnBatch(out_cols)


def _fragment_touches_f64(frag: "_Fragment") -> bool:
    """True when any device expression (predicate, projection, aggregate
    input) references a float64 scan column — under exactF64Aggregates the
    fragment must decline so device and host tiers agree bit-for-bit."""
    f64_cols = {
        fld.name for fld in frag.scan.schema if fld.dtype == "float64"
    }
    if not f64_cols:
        return False
    for e in _device_exprs(frag):
        if e.references() & f64_cols:
            return True
    return False


def try_execute_tpu(plan: LogicalPlan, session) -> Optional[ColumnBatch]:
    """Execute a supported fragment as one fused device kernel; None if the
    plan shape or data is unsupported (host executor takes over). Device
    failures mid-query degrade to the host path through the circuit
    breaker (fail-open execution, the reference's rewrite philosophy
    extended to the kernels); HYPERSPACE_DEVICE_STRICT=1 raises them."""
    from ..utils.backend import (
        device_healthy,
        record_device_failure,
        record_device_success,
    )

    frag = _match_fragment(plan)
    if frag is None:
        return None
    # screen on schema + expressions BEFORE reading anything, so unsupported
    # queries do not pay a duplicate scan when the host path takes over
    if not _fragment_supported(frag):
        return None
    if (
        session is not None
        and session.conf.exec_exact_f64_aggregates
        and _fragment_touches_f64(frag)
    ):
        # strict mode: f64 predicates/sums evaluate in f32 on device and
        # could differ from the exact host tier — decline the whole fragment
        return None
    # everything below this point touches the device
    if not device_healthy():
        return None
    from .executor import _exec_file_scan

    if _has_int_sum(frag, plan):
        # screen the int-sum row cap BEFORE reading: a post-read fallback
        # would pay a duplicate full scan. Parquet footers give row counts
        # for ~free; other formats fall back to the post-read check below.
        est = _parquet_row_count(frag.scan)
        if est is not None and _pad_pow2(est) > _INT_SUM_ROW_CAP:
            return None

    # the scan read happens OUTSIDE the breaker: a transient host IO error
    # must propagate like any host failure, not latch the device tier off.
    # The device path reads WITHOUT the pushed filter: the kernel compiles
    # the full predicate anyway, and an unfiltered read serves stable
    # chunk-cache buffers, so the device-resident column cache makes repeat
    # queries upload nothing regardless of the predicate values (file-level
    # pruning upstream in the rules still applies — only row-group
    # masking moves onto the device)
    scan = frag.scan
    if scan.pushed_filter is not None:
        scan = scan.copy(pushed_filter=None)

    # pipelined tier: stream scan→upload→dispatch per file-group chunk when
    # the scan and fragment shapes allow it (bit-identical to the monolithic
    # path by construction); any abort falls through to the full read below
    if _pipeline_enabled() and _mesh_for(session) is None:
        from .executor import scan_streamable

        if scan_streamable(scan):
            from . import adaptive
            from ..columnar.io import ChunkReadError

            try:
                out = _execute_streaming(frag, scan, plan, session)
            except ChunkReadError:
                raise  # host IO failure: propagate like any scan error
            except adaptive.ScanAbortAndReplan:
                # mid-query abort-and-replan: the collect loop re-plans
                # and re-enters — NOT a device failure, never latch the
                # breaker for it
                raise
            except Exception as e:  # device failure mid-stream
                # returning None here (never a partial fold) hands the WHOLE
                # plan to the host executor, which re-reads and recomputes
                # from scratch — the clean-degradation contract the chaos
                # gate verifies bit-for-bit. The breaker decides whether the
                # next query may try the device again.
                record_device_failure(e)
                return None
            if out is not None:
                record_device_success()
                from ..telemetry import plan_stats

                plan_stats.note_route(plan.plan_id, "pipelined")
                plan_stats.note_scan(
                    frag.scan.plan_id, len(scan.files),
                    sum(f.size for f in scan.files),
                )
                return out

    batch = _exec_file_scan(scan)
    try:
        result = _try_execute_tpu_inner(frag, batch, plan, session)
    except Exception as e:  # device failure: host executor takes over
        record_device_failure(e)
        return None
    if result is not None:
        record_device_success()
        from ..telemetry import plan_stats

        plan_stats.note_route(plan.plan_id, "device")
        plan_stats.note_scan(
            frag.scan.plan_id, len(scan.files),
            sum(f.size for f in scan.files), rows=batch.num_rows,
        )
    return result


def _try_execute_tpu_inner(
    frag: "_Fragment", batch: ColumnBatch, plan, session
) -> Optional[ColumnBatch]:
    n = batch.num_rows
    if n == 0:
        return None
    if frag.pred is not None:
        scols = {
            fld.name for fld in frag.scan.schema if fld.dtype == STRING
        } & frag.pred.references()
        if scols:
            rewritten = _encode_string_predicates(frag.pred, batch, scols)
            if rewritten is None:
                return None
            frag.pred = rewritten
    if _has_int_sum(frag, plan) and _pad_pow2(n) > _INT_SUM_ROW_CAP:
        return None  # chunked int accumulation is exact only to 2^23 rows
    mesh = _mesh_for(session)
    if mesh is not None:
        out = _execute_on_mesh(frag, batch, plan, session, mesh)
        if out is not None:
            return out
    if frag.agg.group_exprs:
        return _execute_grouped(frag, batch, plan)
    padded = _pad_pow2(n)
    device_refs = _device_refs(frag)
    wide_ok = _wide_predicate_cols(frag, batch)
    if not _fragment_literals_fit(frag, wide_ok):
        return None  # out-of-range literal vs downcast column: host path
    # the kernel span opens BEFORE the upload so its RpcMeter delta carries
    # the full device cost of this dispatch: uploads + dispatch + fetch
    with trace.span("kernel:fused_agg", rows=n, padded=padded) as sp:
        dev_cols = _upload_columns(
            batch, device_refs & set(batch.columns), padded, wide_ok
        )
        if dev_cols is None:
            sp.set_attr("declined", "nullable_or_out_of_range")
            return None  # nullable/out-of-range data: host path (re-read)
        mask = _padded_mask(padded, n)

        pred_expr = frag.pred
        proj_exprs = (
            tuple((X.expr_output_name(e), e) for e in frag.project.exprs)
            if frag.project is not None
            else ()
        )
        agg_list, names = _agg_list_names(frag)

        key = fused_fingerprint(
            _pallas_route(), pred_expr, proj_exprs, agg_list, dev_cols
        )
        kernel = _KERNEL_CACHE.get_or_build(
            key, lambda: _build_kernel(pred_expr, proj_exprs, agg_list),
            "fused_agg",
        )
        # ONE batched transfer for the whole result tree: per-array fetches
        # pay a device->host round trip each
        from ..utils.rpc_meter import METER, device_get as metered_get

        METER.record_dispatch()
        t0 = time.perf_counter()
        matched, results = metered_get(kernel(dev_cols, mask))
        _observe_dispatch("fused_agg", t0)
    matched = int(matched)
    scalar_values = [
        _combine_chunks_maybe_avg(v, kind, matched)
        for v, (kind, _c) in zip(results, agg_list)
    ]
    return _assemble_global_output(plan, matched, scalar_values, agg_list, names)


def _pallas_grouped_shape(pred_expr, agg_list, seg_pad):
    """When the grouped fragment is sums/averages/counts over a small group
    domain, the Pallas streaming histogram
    (ops/pallas_kernels.filter_grouped_multi_sum) takes over on TPU: its
    per-lane f32 partials keep a float sum over tens of millions of rows
    within ~1e-5 relative, where one running segment sum would not.
    Returns [(kind, child|None)] == agg_list on match, else None."""
    from ..ops.pallas_kernels import _MAX_PALLAS_GROUPS

    if seg_pad > _MAX_PALLAS_GROUPS:
        return None
    for kind, _child in agg_list:
        if kind not in ("sum", "avg", "count"):
            return None
    return list(agg_list)


def _build_grouped_pallas_kernel(pred_expr, proj_exprs, agg_list, seg_pad):
    from ..ops.pallas_kernels import filter_grouped_multi_sum

    def kernel(cols, gids, mask):
        cols = _wrap_wide(cols)
        if pred_expr is not None:
            mask = mask & compile_expr(pred_expr, cols)
        proj_cols = dict(cols)
        for name, e in proj_exprs:
            proj_cols[name] = compile_expr(e, cols)
        sum_vals = []
        for kind, child in agg_list:
            if kind == "count":
                continue
            vals = compile_expr(child, proj_cols)
            if jnp.issubdtype(vals.dtype, jnp.integer):
                # exact chunked accumulation owns int sums/avgs — generic body
                return _generic_grouped_compute(
                    pred_expr, proj_exprs, agg_list, seg_pad, cols, gids, mask
                )
            sum_vals.append(vals)
        # every measure + the count in ONE streaming pass over pred/gids
        sums, counts = filter_grouped_multi_sum(mask, gids, sum_vals, seg_pad)
        gids_m = jnp.where(mask, gids, seg_pad - 1)
        first_masked = _first_masked_rows(mask, gids_m, seg_pad)
        out = []
        i = 0
        for kind, _child in agg_list:
            if kind == "count":
                out.append(counts)
            else:  # sum, or an avg's sum (the host divides)
                out.append(sums[i])
                i += 1
        return counts, first_masked, tuple(out)

    return jax.jit(kernel)  # hslint: HS201 — builder runs via KernelCache.get_or_build


def _first_masked_rows(mask, gids, seg_pad):
    """Per-group index of the first row PASSING the predicate: the host
    tier orders grouped output by first post-filter occurrence, and the
    device assembly reorders by this vector so both tiers emit identical
    row order even when the device scanned unfiltered (cache-stable)
    chunks."""
    idx = jnp.arange(gids.shape[0], dtype=jnp.int32)
    return jax.ops.segment_min(
        jnp.where(mask, idx, jnp.int32(2**31 - 1)), gids, num_segments=seg_pad
    )


def _generic_grouped_compute(pred_expr, proj_exprs, agg_list, seg_pad, cols, gids, mask):
    """Traced body of the generic grouped kernel (shared so the Pallas route
    can fall back at trace time for integer-sum exactness)."""
    if pred_expr is not None:
        mask = mask & compile_expr(pred_expr, cols)
    gids = jnp.where(mask, gids, seg_pad - 1)
    first_masked = _first_masked_rows(mask, gids, seg_pad)
    proj_cols = dict(cols)
    for name, e in proj_exprs:
        proj_cols[name] = compile_expr(e, cols)
    counts = jax.ops.segment_sum(
        jnp.ones_like(gids, dtype=jnp.int32), gids, num_segments=seg_pad
    )
    out = []
    for kind, child in agg_list:
        if kind == "count":
            out.append(counts)
            continue
        vals = compile_expr(child, proj_cols)
        if kind == "sum":
            if jnp.issubdtype(vals.dtype, jnp.integer):
                out.append(_int_chunk_sums(vals, gids, seg_pad))
            else:
                out.append(blocked_segment_sum(vals, gids, seg_pad))
        elif kind == "min":
            out.append(jax.ops.segment_min(vals, gids, num_segments=seg_pad))
        elif kind == "max":
            out.append(jax.ops.segment_max(vals, gids, num_segments=seg_pad))
        elif kind == "avg":  # the sum only: the host divides
            if jnp.issubdtype(vals.dtype, jnp.integer):
                out.append(_int_chunk_sums(vals, gids, seg_pad))
            else:
                out.append(blocked_segment_sum(vals, gids, seg_pad))
    return counts, first_masked, tuple(out)


def _build_grouped_kernel(pred_expr, proj_exprs, agg_list, seg_pad):
    """Grouped fragment: predicate + per-group segment reductions in one
    jitted pass; rows failing the mask land in the dump segment seg_pad-1.
    On TPU, small-group sum/count fragments stream through the Pallas
    histogram kernel instead."""
    if _pallas_route() and _pallas_grouped_shape(pred_expr, agg_list, seg_pad) is not None:
        return _build_grouped_pallas_kernel(pred_expr, proj_exprs, agg_list, seg_pad)

    def kernel(cols, gids, mask):
        cols = _wrap_wide(cols)
        return _generic_grouped_compute(
            pred_expr, proj_exprs, agg_list, seg_pad, cols, gids, mask
        )

    return jax.jit(kernel)  # hslint: HS201 — builder runs via KernelCache.get_or_build


def _execute_grouped(frag: _Fragment, batch: ColumnBatch, plan) -> Optional[ColumnBatch]:
    """Grouped fragment: keys factorize host-side (string keys never ship);
    masked segment reductions run on device."""
    from .executor import factorize_group_keys

    n = batch.num_rows
    device_refs = _device_refs(frag)

    from ..utils.device_cache import DEVICE_CACHE, HOST_DERIVED_CACHE

    key_cols = [batch.column(e.name) for e in frag.agg.group_exprs]
    # single-key grouping factorizes once per chunk: the host factorize pass
    # and the device gid upload both cache on the key buffer's identity
    cache_key_buf = (
        key_cols[0].data
        if len(key_cols) == 1 and key_cols[0].validity is None
        else None
    )
    if cache_key_buf is not None:
        group_ids, num_groups, first_idx = HOST_DERIVED_CACHE.get_or_put(
            cache_key_buf, ("factorize",), lambda: factorize_group_keys(key_cols)
        )
    else:
        group_ids, num_groups, first_idx = factorize_group_keys(key_cols)
    seg_pad = 1 << max(4, int(np.ceil(np.log2(num_groups + 1))))

    padded = _pad_pow2(n)
    wide_ok = _wide_predicate_cols(frag, batch)
    if not _fragment_literals_fit(frag, wide_ok):
        return None
    with trace.span(
        "kernel:grouped_agg", rows=n, padded=padded, groups=num_groups
    ) as sp:
        dev_cols = _upload_columns(
            batch, device_refs & set(batch.columns), padded, wide_ok
        )
        if dev_cols is None:
            sp.set_attr("declined", "nullable_or_out_of_range")
            return None

        def _build_gids(g=group_ids):
            arr = np.full(padded, seg_pad - 1, dtype=np.int32)
            arr[:n] = g.astype(np.int32)
            return jnp.asarray(arr)

        if cache_key_buf is not None:
            gids_d = DEVICE_CACHE.get_or_put(
                cache_key_buf, ("gids", padded, seg_pad), _build_gids
            )
        else:
            gids_d = _build_gids()
        mask = _padded_mask(padded, n)

        pred_expr = frag.pred
        proj_exprs = tuple(
            (X.expr_output_name(e), e) for e in _device_projections(frag)
        )
        agg_list, names = _agg_list_names(frag)
        key = grouped_fingerprint(
            _pallas_route(), seg_pad, pred_expr, proj_exprs, agg_list, dev_cols
        )
        kernel = _KERNEL_CACHE.get_or_build(
            key,
            lambda: _build_grouped_kernel(pred_expr, proj_exprs, agg_list, seg_pad),
            "grouped_agg",
        )
        from ..utils.rpc_meter import METER, device_get as metered_get

        METER.record_dispatch()
        t0 = time.perf_counter()
        counts_dev, first_masked, results = metered_get(
            kernel(dev_cols, gids_d, mask)
        )
        _observe_dispatch("grouped_agg", t0)
    counts_full = np.asarray(counts_dev)
    counts = counts_full[:num_groups]
    results = [
        _combine_chunks_maybe_avg(v, kind, counts_full)
        for v, (kind, _c) in zip(results, agg_list)
    ]
    return _assemble_grouped_output(
        plan, frag, key_cols, first_idx, counts, results, agg_list, names,
        num_groups, first_masked,
    )


# ---------------------------------------------------------------------------
# pipelined chunk streaming (scan ∥ upload ∥ dispatch)
# ---------------------------------------------------------------------------
#
# Multi-file scans execute as an ordered stream of file-group chunks: the IO
# pool decodes chunk N+2 while chunk N+1's columns upload and chunk N's
# kernel runs (jax dispatch is async; a bounded deque of in-flight results
# is the double buffer). Two routes, both bit-identical to the monolithic
# path by construction:
#
#   partial — every aggregate folds exactly across chunks (count, min, max,
#     int sum, provably-int avg): each chunk runs the SAME fused kernel the
#     monolithic path would build (shared fingerprint → shared executable)
#     and the host folds the exact partials. The full batch never exists,
#     on host or device.
#   concat — float sums/avgs, whose f32 partial sums would not be
#     decomposition-invariant: chunks upload individually and concatenate
#     device-side into the exact array the monolithic upload would have
#     produced, then the monolithic kernel runs once. The full batch exists
#     only in device memory; host memory stays chunk-bounded.
#
# `HYPERSPACE_PIPELINE=0` disables the streamer (legacy monolithic path);
# `HYPERSPACE_PIPELINE=serial` keeps the staged executor but removes every
# overlap (the debug mode for isolating pipelining effects). Any abort —
# nullable chunk, out-of-32-bit-range int64, cross-file dtype drift,
# non-rewritable string predicate — falls back to the monolithic path.

def _pipeline_enabled() -> bool:
    return env.env_str("HYPERSPACE_PIPELINE") != "0"


def _pipeline_overlap() -> bool:
    return env.env_str("HYPERSPACE_PIPELINE") != "serial"


def _pipeline_depth() -> int:
    """In-flight chunk dispatches before the consumer blocks on a fetch
    (``HYPERSPACE_PIPELINE_DEPTH``, default 2 = double buffering)."""
    try:
        return max(1, env.env_int("HYPERSPACE_PIPELINE_DEPTH"))
    except ValueError:
        return 2


def _provably_int_expr(e: Expr, frag: "_Fragment") -> bool:
    """True only when e certainly traces to an integer on device (the
    strict dual of _maybe_int_expr): drives the partial route's exact-fold
    screen, where a float mistaken for int would break bit-identity."""
    if isinstance(e, Alias):
        return _provably_int_expr(e.child, frag)
    if isinstance(e, X.Div):
        return False
    if isinstance(e, X.Lit):
        return isinstance(e.value, (int, np.integer)) and not isinstance(
            e.value, bool
        )
    if isinstance(e, X.Col):
        sch = frag.scan.schema
        if e.name in sch.names:
            dt = sch.field(e.name).dtype
            return dt.startswith("int") or dt == "date32"
        if frag.project is not None:
            for p in frag.project.exprs:
                if X.expr_output_name(p) == e.name:
                    return _provably_int_expr(p, frag)
        return False
    children = e.children()
    if not children or not isinstance(e, (X.Add, X.Sub, X.Mul)):
        return False
    return all(_provably_int_expr(c, frag) for c in children)


def _stream_route(frag: "_Fragment", plan) -> Optional[str]:
    """'partial' | 'concat' | None (decline streaming, monolithic path)."""
    from .executor import _unwrap_agg

    if not _fragment_literals_fit(frag):  # Wide64 never streams
        return None
    schema = plan.schema
    exact = True
    for e in frag.agg.agg_exprs:
        nm, agg = _unwrap_agg(e)
        if isinstance(agg, (X.Count, X.Min, X.Max)):
            continue
        if isinstance(agg, X.Sum) and schema.field(nm).dtype.startswith("int"):
            continue
        if isinstance(agg, X.Avg) and _provably_int_expr(agg.child, frag):
            continue
        exact = False
        break
    if exact:
        return "partial"
    # the concat route ships predicate columns as one device array, which a
    # per-chunk string-code rewrite cannot produce (dictionaries differ)
    if frag.pred is not None:
        scols = {f.name for f in frag.scan.schema if f.dtype == STRING}
        if frag.pred.references() & scols:
            return None
    return "concat"


def _execute_streaming(frag: "_Fragment", scan, plan, session) -> Optional[ColumnBatch]:
    """Streamed execution of a supported fragment over a streamable scan;
    None = fall back to the monolithic read (which re-screens and may still
    run on device, with Wide64, or decline to the host tier)."""
    route = _stream_route(frag, plan)
    if route is None:
        REGISTRY.counter("pipeline.declined").inc()
        return None
    from .executor import iter_scan_chunks, resolve_scan_pruning

    # one row-group resolution shared by the row-count plan and the chunk
    # stream, so the streamed chunks concatenate to exactly n_total rows
    selection = resolve_scan_pruning(scan)
    n_total = _pruned_row_count(scan, selection)
    if not n_total:
        return None
    # identical decline decisions to the monolithic path: over-cap int sums
    # go to the host tier either way
    if _has_int_sum(frag, plan) and _pad_pow2(n_total) > _INT_SUM_ROW_CAP:
        return None
    overlap = _pipeline_overlap()
    chunks = iter_scan_chunks(scan, overlap=overlap, selection=selection)
    # abort-and-replan monitor: pass-through unless HYPERSPACE_ADAPTIVE is
    # on AND this scan's prune stage underdelivered its prediction
    from . import adaptive

    chunks = adaptive.monitor_scan_chunks(chunks, scan, selection)
    t0 = time.perf_counter()
    with trace.span(
        f"pipeline:{route}", rows=n_total, files=len(scan.files),
        grouped=bool(frag.agg.group_exprs),
    ) as sp:
        try:
            if route == "partial":
                if frag.agg.group_exprs:
                    out = _stream_grouped_partial(frag, plan, chunks, overlap)
                else:
                    out = _stream_global_partial(frag, plan, chunks, overlap)
            else:
                out = _stream_concat(frag, plan, chunks, n_total)
        finally:
            chunks.close()  # stop IO read-ahead on abort
        if out is None:
            sp.set_attr("aborted", True)
            REGISTRY.counter("pipeline.aborted").inc()
        else:
            REGISTRY.counter("pipeline.queries").inc()
            REGISTRY.histogram("pipeline.query_ms").observe(
                (time.perf_counter() - t0) * 1000
            )
    return out


def _chunk_pred(frag: "_Fragment", batch: ColumnBatch) -> tuple[Optional[Expr], bool]:
    """(predicate for this chunk, ok): string comparisons re-encode against
    THIS chunk's dictionaries; ok=False means a string reference survives in
    a non-rewritable position (abort the stream)."""
    pred = frag.pred
    if pred is None:
        return None, True
    scols = {
        f.name for f in frag.scan.schema if f.dtype == STRING
    } & pred.references()
    if not scols:
        return pred, True
    rewritten = _encode_string_predicates(pred, batch, scols)
    return rewritten, rewritten is not None


def _stream_global_partial(frag, plan, chunks, overlap) -> Optional[ColumnBatch]:
    """Per-chunk fused kernels + exact host folds for a global aggregate."""
    from ..utils.rpc_meter import METER, device_get as metered_get

    agg_list, names = _agg_list_names(frag)
    proj_exprs = (
        tuple((X.expr_output_name(e), e) for e in frag.project.exprs)
        if frag.project is not None
        else ()
    )
    device_refs = _device_refs(frag)
    depth = _pipeline_depth() if overlap else 0
    pending: deque = deque()
    state = {"matched": 0}
    accs: list = [None] * len(agg_list)

    def fold(res) -> None:
        with trace.span("pipeline:fetch"), _attr.phase("fold"):
            matched, results = metered_get(res)
        state["matched"] += int(matched)
        for i, (v, (kind, _c)) in enumerate(zip(results, agg_list)):
            if kind == "count":
                continue
            if isinstance(v, tuple):  # exact int chunk sums
                s = _combine_int_chunks(v)
                accs[i] = s if accs[i] is None else accs[i] + s
            elif kind == "min":
                v = np.asarray(v)
                accs[i] = v if accs[i] is None else np.minimum(accs[i], v)
            elif kind == "max":
                v = np.asarray(v)
                accs[i] = v if accs[i] is None else np.maximum(accs[i], v)
            else:  # unreachable on this route (floats take the concat route)
                raise HyperspaceError(f"non-foldable {kind} on partial route")

    expect_dtypes: dict = {}
    from ..parallel import placement as mesh_placement

    placer = mesh_placement.chunk_placer()
    for chunk in chunks:
        batch = chunk.batch
        n = batch.num_rows
        if n == 0:
            continue
        with trace.span(
            "pipeline:chunk", index=chunk.index, rows=n,
            decode_ms=round(chunk.decode_s * 1000, 3),
        ):
            if not _chunk_dtypes_ok(batch, device_refs, expect_dtypes):
                return None
            pred, ok = _chunk_pred(frag, batch)
            if not ok:
                return None
            padded = _pad_pow2(n)
            device = None
            if placer is not None:
                ordinal, device = placer.next(padded * max(len(device_refs), 1) * 8)
                with trace.span("mesh:dispatch", device=ordinal, rows=n):
                    pass  # zero-width marker: where this chunk was placed
            dev_cols = _upload_columns(
                batch, device_refs & set(batch.columns), padded, device=device
            )
            if dev_cols is None:
                return None  # nullable / out-of-range chunk: monolithic path
            mask = _padded_mask(padded, n, device=device)
            key = fused_fingerprint(
                _pallas_route(), pred, proj_exprs, agg_list, dev_cols
            )
            kernel = _KERNEL_CACHE.get_or_build(
                key, lambda: _build_kernel(pred, proj_exprs, agg_list),
                "fused_agg",
            )
            METER.record_dispatch()
            pending.append(kernel(dev_cols, mask))
            REGISTRY.counter("pipeline.chunks").inc()
        while len(pending) > depth:
            fold(pending.popleft())
    while pending:
        # a cancel mid-drain stops fetching the remaining in-flight
        # device results (serving-layer cancellation contract)
        _serve_check_cancelled()
        fold(pending.popleft())

    matched = state["matched"]
    scalar_values = []
    for acc, (kind, _c) in zip(accs, agg_list):
        if kind == "count":
            scalar_values.append(np.int64(matched))
        elif kind == "avg":
            scalar_values.append(acc / max(matched, 1))
        else:
            scalar_values.append(np.asarray(acc) if acc is not None else np.float64(0))
    return _assemble_global_output(plan, matched, scalar_values, agg_list, names)


_FIRST_SENTINEL = 2**31 - 1


def _key_tuple_rows(key_cols: list[Column], idxs: np.ndarray) -> list[tuple]:
    """Hashable group-key value tuples for the given rows (NULL -> None);
    the cross-chunk group identity the partial route folds on."""
    out = []
    for i in idxs:
        t = []
        for kc in key_cols:
            if kc.validity is not None and not kc.validity[i]:
                t.append(None)
            elif kc.dtype == STRING:
                t.append(kc.dictionary[int(kc.data[i])] if kc.dictionary else "")
            else:
                t.append(kc.data[i].item())
        out.append(tuple(t))
    return out


def _grown(arr: Optional[np.ndarray], size: int, fill, dtype) -> np.ndarray:
    if arr is None:
        return np.full(size, fill, dtype=dtype)
    if len(arr) >= size:
        return arr
    out = np.full(size, fill, dtype=arr.dtype)
    out[: len(arr)] = arr
    return out


def _stream_grouped_partial(frag, plan, chunks, overlap) -> Optional[ColumnBatch]:
    """Per-chunk grouped kernels + exact host folds. Each chunk factorizes
    its own keys (local gids, local seg_pad); the host maintains the global
    group table in first-appearance order and folds per-group partials
    through it. Output ordering follows the global first-passing-row index,
    exactly like the monolithic assembly."""
    from .executor import factorize_group_keys
    from ..utils.device_cache import DEVICE_CACHE
    from ..utils.rpc_meter import METER, device_get as metered_get

    agg_list, names = _agg_list_names(frag)
    proj_exprs = tuple(
        (X.expr_output_name(e), e) for e in _device_projections(frag)
    )
    key_names = [e.name for e in frag.agg.group_exprs]
    device_refs = _device_refs(frag)
    depth = _pipeline_depth() if overlap else 0
    pending: deque = deque()

    key_index: dict = {}
    key_slices: list[ColumnBatch] = []
    counts_g: Optional[np.ndarray] = None
    first_g: Optional[np.ndarray] = None
    accs: list = [None] * len(agg_list)

    def fold(entry) -> None:
        nonlocal counts_g, first_g
        gmap, num_l, offset, res = entry
        with trace.span("pipeline:fetch"), _attr.phase("fold"):
            counts_l, first_l, results = metered_get(res)
        size = len(key_index)
        counts_g = _grown(counts_g, size, 0, np.int64)
        first_g = _grown(first_g, size, np.iinfo(np.int64).max, np.int64)
        counts_l = np.asarray(counts_l)[:num_l].astype(np.int64)
        np.add.at(counts_g, gmap, counts_l)
        fl = np.asarray(first_l)[:num_l].astype(np.int64)
        valid = fl < _FIRST_SENTINEL
        if valid.any():
            np.minimum.at(first_g, gmap[valid], fl[valid] + offset)
        for i, (v, (kind, _c)) in enumerate(zip(results, agg_list)):
            if kind == "count":
                continue
            if isinstance(v, tuple):  # exact int chunk sums per group
                s = _combine_int_chunks(v)[:num_l]
                accs[i] = _grown(accs[i], size, 0, np.int64)
                np.add.at(accs[i], gmap, s)
            else:
                v = np.asarray(v)[:num_l]
                if kind == "min":
                    accs[i] = _grown(accs[i], size, _np_extreme(v.dtype, True), v.dtype)
                    np.minimum.at(accs[i], gmap, v)
                elif kind == "max":
                    accs[i] = _grown(accs[i], size, _np_extreme(v.dtype, False), v.dtype)
                    np.maximum.at(accs[i], gmap, v)
                else:
                    raise HyperspaceError(f"non-foldable {kind} on partial route")
        # groups discovered after this chunk dispatched: extend with identities
        for i, (kind, _c) in enumerate(agg_list):
            if accs[i] is not None and len(accs[i]) < size:
                fill = (
                    _np_extreme(accs[i].dtype, kind == "min")
                    if kind in ("min", "max")
                    else 0
                )
                accs[i] = _grown(accs[i], size, fill, accs[i].dtype)

    expect_dtypes: dict = {}
    row_offset = 0
    from ..parallel import placement as mesh_placement

    placer = mesh_placement.chunk_placer()
    for chunk in chunks:
        batch = chunk.batch
        n = batch.num_rows
        if n == 0:
            continue
        with trace.span(
            "pipeline:chunk", index=chunk.index, rows=n,
            decode_ms=round(chunk.decode_s * 1000, 3),
        ):
            if not _chunk_dtypes_ok(batch, device_refs, expect_dtypes):
                return None
            pred, ok = _chunk_pred(frag, batch)
            if not ok:
                return None
            key_cols = [batch.column(nm) for nm in key_names]
            gids_l, num_l, first_idx_l = factorize_group_keys(key_cols)
            tuples = _key_tuple_rows(key_cols, first_idx_l)
            gmap = np.empty(num_l, dtype=np.int64)
            new_rows = []
            for j, t in enumerate(tuples):
                g = key_index.get(t)
                if g is None:
                    g = len(key_index)
                    key_index[t] = g
                    new_rows.append(first_idx_l[j])
                gmap[j] = g
            if new_rows:
                key_slices.append(
                    ColumnBatch(
                        {
                            nm: kc.take(np.asarray(new_rows, dtype=np.int64))
                            for nm, kc in zip(key_names, key_cols)
                        }
                    )
                )
            seg_pad = 1 << max(4, int(np.ceil(np.log2(num_l + 1))))
            padded = _pad_pow2(n)
            device = None
            if placer is not None:
                ordinal, device = placer.next(padded * max(len(device_refs), 1) * 8)
                with trace.span("mesh:dispatch", device=ordinal, rows=n):
                    pass  # zero-width marker: where this chunk was placed
            dev_cols = _upload_columns(
                batch, device_refs & set(batch.columns), padded, device=device
            )
            if dev_cols is None:
                return None
            gids_arr = np.full(padded, seg_pad - 1, dtype=np.int32)
            gids_arr[:n] = gids_l.astype(np.int32)
            if len(key_cols) == 1 and key_cols[0].validity is None:
                # cache-stable chunk key buffer: repeat queries reuse the
                # device gids upload (same contract as the monolithic path)
                gids_tag = ("gids", padded, seg_pad) if device is None else \
                    ("gids", padded, seg_pad, f"d{device.id}")
                gids_d = DEVICE_CACHE.get_or_put(
                    key_cols[0].data, gids_tag,
                    lambda: jnp.asarray(gids_arr) if device is None
                    else jax.device_put(gids_arr, device),
                )
            else:
                gids_d = jnp.asarray(gids_arr) if device is None else \
                    jax.device_put(gids_arr, device)
            mask = _padded_mask(padded, n, device=device)
            key = grouped_fingerprint(
                _pallas_route(), seg_pad, pred, proj_exprs, agg_list, dev_cols
            )
            kernel = _KERNEL_CACHE.get_or_build(
                key,
                lambda: _build_grouped_kernel(pred, proj_exprs, agg_list, seg_pad),
                "grouped_agg",
            )
            METER.record_dispatch()
            pending.append((gmap, num_l, row_offset, kernel(dev_cols, gids_d, mask)))
            REGISTRY.counter("pipeline.chunks").inc()
        row_offset += n
        while len(pending) > depth:
            fold(pending.popleft())
    while pending:
        # a cancel mid-drain stops fetching the remaining in-flight
        # device results (serving-layer cancellation contract)
        _serve_check_cancelled()
        fold(pending.popleft())
    if not key_index:
        return None  # every chunk was empty: let the monolithic path decide

    num_groups = len(key_index)
    counts_g = _grown(counts_g, num_groups, 0, np.int64)
    first_g = _grown(first_g, num_groups, np.iinfo(np.int64).max, np.int64)
    keys_batch = ColumnBatch.concat(key_slices)
    keep = counts_g > 0
    idx = np.nonzero(keep)[0]
    order = np.argsort(first_g[keep], kind="stable")
    out_cols: dict[str, Column] = {}
    for e, nm in zip(frag.agg.group_exprs, key_names):
        kept = keys_batch.column(nm).take(idx)
        out_cols[X.expr_output_name(e)] = kept.take(order)
    schema = plan.schema
    for (name, acc), (kind, _c) in zip(zip(names, accs), agg_list):
        f = schema.field(name)
        if kind == "count":
            vals = counts_g
        elif kind == "avg":
            vals = acc / np.maximum(counts_g, 1)
        else:
            vals = _grown(
                acc, num_groups,
                _np_extreme(acc.dtype, kind == "min") if acc is not None and kind in ("min", "max") else 0,
                np.int64 if acc is None else acc.dtype,
            )
        np_val = np.asarray(vals)[keep][order]
        if kind == "count":
            out_cols[name] = Column(np_val.astype(np.int64), "int64")
        elif f.dtype in ("int64", "int32", "int16", "int8"):
            out_cols[name] = Column(np_val.astype(np.dtype(f.dtype)), f.dtype)
        else:
            out_cols[name] = Column(np_val.astype(np.float64), "float64")
    return ColumnBatch(out_cols)


def _np_extreme(dtype, want_max: bool):
    d = np.dtype(dtype)
    if np.issubdtype(d, np.integer):
        info = np.iinfo(d)
        return info.max if want_max else info.min
    return np.inf if want_max else -np.inf


def _chunk_dtypes_ok(batch: ColumnBatch, refs, expect: dict) -> bool:
    """Guard against cross-file dtype drift (permissive promotion would have
    unified it in the monolithic read): the first chunk pins each referenced
    column's numpy dtype; any later mismatch aborts the stream."""
    for name in refs:
        if name not in batch.columns:
            continue
        dt = batch.column(name).data.dtype
        prev = expect.setdefault(name, dt)
        if prev != dt:
            return False
    return True


def _stream_concat(frag, plan, chunks, n_total) -> Optional[ColumnBatch]:
    """Upload chunks as they decode, concatenate device-side into exactly
    the array the monolithic upload would have produced, then run the
    monolithic kernel once: bit-identical results with host memory bounded
    by the chunk size, and decode ∥ upload overlap."""
    from .executor import factorize_group_keys
    from ..utils.device_cache import DEVICE_CACHE
    from ..utils.rpc_meter import METER, device_get as metered_get

    device_refs = sorted(_device_refs(frag))
    key_names = [e.name for e in frag.agg.group_exprs]
    dev_parts: dict[str, list] = {}
    src_parts: dict[str, list] = {}
    key_parts: list[ColumnBatch] = []
    expect_dtypes: dict = {}
    n_seen = 0
    for chunk in chunks:
        batch = chunk.batch
        n = batch.num_rows
        if n == 0:
            continue
        with trace.span(
            "pipeline:chunk", index=chunk.index, rows=n,
            decode_ms=round(chunk.decode_s * 1000, 3),
        ):
            if not _chunk_dtypes_ok(batch, device_refs, expect_dtypes):
                return None
            for name in device_refs:
                if name not in batch.columns:
                    continue
                col = batch.column(name)
                if col.validity is not None:
                    return None
                d = col.data
                if d.dtype == np.int64 and len(d) and (
                    d.min() < -(2**31) or d.max() >= 2**31
                ):
                    return None  # Wide64 territory: monolithic path decides
                dev = DEVICE_CACHE.get_or_put(
                    d, ("chunk",),
                    lambda data=d: jnp.asarray(
                        data.astype(_device_dtype(data.dtype))
                    ),
                )
                dev_parts.setdefault(name, []).append(dev)
                src_parts.setdefault(name, []).append(d)
            if key_names:
                key_parts.append(batch.select(key_names))
            REGISTRY.counter("pipeline.chunks").inc()
        n_seen += n
    if n_seen == 0:
        return None
    padded = _pad_pow2(n_seen)
    dev_cols = {}
    for name, parts in dev_parts.items():
        def _cat(parts=parts):
            tail = padded - n_seen
            arrs = list(parts)
            if tail:
                arrs.append(jnp.zeros(tail, dtype=parts[0].dtype))
            return jnp.concatenate(arrs)

        # keyed on every chunk buffer: a repeat query over cache-stable index
        # chunks reuses the concatenated device column outright
        dev_cols[name] = DEVICE_CACHE.get_or_put_multi(
            tuple(src_parts[name]), ("cat", padded), _cat, meter=False
        )
    mask = _padded_mask(padded, n_seen)
    pred_expr = frag.pred
    agg_list, names = _agg_list_names(frag)

    if not key_names:
        proj_exprs = (
            tuple((X.expr_output_name(e), e) for e in frag.project.exprs)
            if frag.project is not None
            else ()
        )
        with trace.span("kernel:fused_agg", rows=n_seen, padded=padded):
            key = fused_fingerprint(
                _pallas_route(), pred_expr, proj_exprs, agg_list, dev_cols
            )
            kernel = _KERNEL_CACHE.get_or_build(
                key, lambda: _build_kernel(pred_expr, proj_exprs, agg_list),
                "fused_agg",
            )
            METER.record_dispatch()
            t0 = time.perf_counter()
            matched, results = metered_get(kernel(dev_cols, mask))
            _observe_dispatch("fused_agg", t0)
        matched = int(matched)
        scalar_values = [
            _combine_chunks_maybe_avg(v, kind, matched)
            for v, (kind, _c) in zip(results, agg_list)
        ]
        return _assemble_global_output(plan, matched, scalar_values, agg_list, names)

    # grouped: keys were collected host-side per chunk (they never ship);
    # factorize the concatenation exactly like the monolithic path
    keys_host = ColumnBatch.concat(key_parts)
    key_cols = [keys_host.column(nm) for nm in key_names]
    group_ids, num_groups, first_idx = factorize_group_keys(key_cols)
    seg_pad = 1 << max(4, int(np.ceil(np.log2(num_groups + 1))))
    proj_exprs = tuple(
        (X.expr_output_name(e), e) for e in _device_projections(frag)
    )
    gids_arr = np.full(padded, seg_pad - 1, dtype=np.int32)
    gids_arr[:n_seen] = group_ids.astype(np.int32)
    gids_d = jnp.asarray(gids_arr)
    with trace.span(
        "kernel:grouped_agg", rows=n_seen, padded=padded, groups=num_groups
    ):
        key = grouped_fingerprint(
            _pallas_route(), seg_pad, pred_expr, proj_exprs, agg_list, dev_cols
        )
        kernel = _KERNEL_CACHE.get_or_build(
            key,
            lambda: _build_grouped_kernel(pred_expr, proj_exprs, agg_list, seg_pad),
            "grouped_agg",
        )
        METER.record_dispatch()
        t0 = time.perf_counter()
        counts_dev, first_masked, results = metered_get(
            kernel(dev_cols, gids_d, mask)
        )
        _observe_dispatch("grouped_agg", t0)
    counts_full = np.asarray(counts_dev)
    counts = counts_full[:num_groups]
    results = [
        _combine_chunks_maybe_avg(v, kind, counts_full)
        for v, (kind, _c) in zip(results, agg_list)
    ]
    return _assemble_grouped_output(
        plan, frag, key_cols, first_idx, counts, results, agg_list, names,
        num_groups, first_masked,
    )


# ---------------------------------------------------------------------------
# top-k fragment (ORDER BY ... LIMIT)
# ---------------------------------------------------------------------------

def _build_topk_kernel(k: int, asc: bool, padded: int):
    """lax.top_k over an order-preserving uint32 encoding of the sort key
    (sign-flip for ints, sign-magnitude fold for floats). Padding encodes to
    the minimum, and top_k's lower-index-first tie rule keeps real rows ahead
    of pads — matching the host sort's stable tie order."""

    def kernel(x, n):
        if jnp.issubdtype(x.dtype, jnp.integer):
            u = jax.lax.bitcast_convert_type(
                x.astype(jnp.int32), jnp.uint32
            ) ^ jnp.uint32(0x80000000)
        else:
            bits = jax.lax.bitcast_convert_type(x, jnp.uint32)
            u = jnp.where(bits >> 31, ~bits, bits | jnp.uint32(0x80000000))
        e = ~u if asc else u
        real = jnp.arange(padded) < n
        e = jnp.where(real, e, jnp.uint32(0))
        _vals, idx = jax.lax.top_k(e, k)
        return idx

    return jax.jit(kernel)  # hslint: HS201 — builder runs via KernelCache.get_or_build


def try_device_topk(sort_plan, k: int, batch: ColumnBatch, session) -> Optional[ColumnBatch]:
    """Limit(Sort) fragment on device: the single numeric sort key ships,
    lax.top_k picks the winners, the host gathers k rows (the
    TakeOrderedAndProject analogue of ORDER BY ... LIMIT tails)."""
    if session is None or not session.conf.exec_tpu_enabled or k <= 0:
        return None
    if len(sort_plan.orders) != 1:
        return None
    e, asc = sort_plan.orders[0]
    if not isinstance(e, X.Col) or e.name not in batch.columns:
        return None
    col = batch.column(e.name)
    if col.validity is not None or col.dtype == STRING:
        return None
    n = batch.num_rows
    if n < 4096 or k >= n:
        return None  # the host argpartition path is cheaper at small sizes
    from ..ops.join import exact_key32

    data = exact_key32(col.data)  # sort keys decide order: no lossy downcast
    if data is None:
        return None
    from ..utils.backend import device_healthy, record_device_failure

    if not device_healthy():
        return None
    padded = _pad_pow2(n)
    arr = np.zeros(padded, dtype=data.dtype)
    arr[:n] = data
    try:
        with trace.span("kernel:topk", rows=n, k=int(k)):
            from ..utils.rpc_meter import METER as _M

            _M.record_upload(arr.nbytes)
            key = ("topk", padded, int(k), str(data.dtype), bool(asc))
            kernel = _TOPK_CACHE.get_or_build(
                key, lambda: _build_topk_kernel(int(k), bool(asc), padded),
                "topk",
            )
            _M.record_dispatch()
            t0 = time.perf_counter()
            idx = np.asarray(kernel(jnp.asarray(arr), jnp.int32(n)))
            _observe_dispatch("topk", t0)
    except Exception as e:  # device failure: host top-k takes over
        record_device_failure(e)
        return None
    from ..utils.backend import record_device_success

    record_device_success()
    return batch.take(idx.astype(np.int64))


# ---------------------------------------------------------------------------
# general device sort (ORDER BY without LIMIT, multi-key, f64 keys)
# ---------------------------------------------------------------------------

_SORT_MIN_ROWS = 4096  # host lexsort is cheaper below this


def _enc_i32_words(a: np.ndarray) -> np.ndarray:
    """Order-preserving uint32 encoding of an int32 array (sign-bit flip)."""
    return a.view(np.uint32) ^ np.uint32(0x80000000)


def _enc_f32_words(a: np.ndarray) -> np.ndarray:
    """Order-preserving uint32 encoding of a float32 array (sign-magnitude
    fold; -0.0 canonicalizes to +0.0 so tie order matches the host)."""
    bits = (a + np.float32(0.0)).view(np.uint32)
    return np.where(bits >> 31 != 0, ~bits, bits | np.uint32(0x80000000))


def _encode_sort_words(col: Column, asc: bool):
    """One sort key column as 1-3 order-preserving uint32 words whose
    lexicographic order equals the column's exact order; None when the
    dtype cannot encode exactly (strings/nulls: host factorization path).

    - int64 splits Wide64-style: encoded signed high word, raw low word.
    - f64 splits into three f32 words (hi = f32(x), mid = f32(x - hi),
      lo = f32(x - hi - mid)); each residual subtraction is exact in f64,
      rounding is monotonic, and a host-side exactness check
      (hi + mid + lo == x) guarantees distinct keys keep distinct words —
      so lex order over the encoded words IS the f64 order, bit for bit.
    - descending flips every word (lexicographic reversal).
    """
    if col.validity is not None or col.dtype == STRING:
        return None
    d = col.data
    if d.dtype == np.int64:
        hi = (d >> 32).astype(np.int32)
        lo = (d & np.int64(0xFFFFFFFF)).astype(np.uint32)
        words = [_enc_i32_words(hi), lo]
    elif d.dtype in (np.int32, np.int16, np.int8):
        words = [_enc_i32_words(d.astype(np.int32))]
    elif d.dtype == np.bool_:
        words = [_enc_i32_words(d.astype(np.int32))]
    elif d.dtype == np.float32:
        if np.isnan(d).any():
            return None
        words = [_enc_f32_words(d)]
    elif d.dtype == np.float64:
        if not np.isfinite(d).all():
            return None  # inf residuals turn NaN; NaN order is host-defined
        with np.errstate(over="ignore", invalid="ignore"):
            hi = d.astype(np.float32)
            if not np.isfinite(hi).all():
                return None  # beyond f32 range: host path
            r = d - hi.astype(np.float64)
            mid = r.astype(np.float32)
            lo = (r - mid.astype(np.float64)).astype(np.float32)
            exact = (
                hi.astype(np.float64) + mid.astype(np.float64) + lo.astype(np.float64)
            ) == d
        if not exact.all():
            return None  # this data needs >76 bits: host path
        words = [_enc_f32_words(hi), _enc_f32_words(mid), _enc_f32_words(lo)]
    else:
        return None
    if not asc:
        words = [~w for w in words]
    return words


def _build_sort_kernel(n_words: int, padded: int):
    """lax.sort over the encoded key words plus the row index as the final
    key: stable multi-key sort whose returned index column IS the exact
    host-stable permutation (pads carry all-ones words and the largest
    indices, so they sort last)."""

    def kernel(*ops):
        out = jax.lax.sort(ops, num_keys=n_words + 1)
        return out[-1]

    return jax.jit(kernel)  # hslint: HS201 — builder runs via KernelCache.get_or_build


def try_device_sort(sort_plan, batch: ColumnBatch, session) -> Optional[ColumnBatch]:
    """Full ORDER BY on device (no LIMIT required): every key column encodes
    into order-preserving uint32 words (multi-key and exact f64 included),
    one lax.sort returns the permutation, and the host gathers rows in their
    original dtypes — output bit-identical to the host lexsort, including
    tie order. None -> host sort.

    Reference parity: sort is intrinsic to every bucketed write and SMJ
    (index/DataFrameWriterExtensions.scala:50-68); this is the query-side
    ORDER BY analogue (SURVEY §7 kernel layer (d)/(e))."""
    from ..utils.backend import device_healthy, record_device_failure

    if session is None or not session.conf.exec_tpu_enabled:
        return None
    if not sort_plan.orders:
        return None
    n = batch.num_rows
    if n < _SORT_MIN_ROWS:
        return None
    words: list[np.ndarray] = []
    for e, asc in sort_plan.orders:
        if not isinstance(e, X.Col) or e.name not in batch.columns:
            return None
        w = _encode_sort_words(batch.column(e.name), asc)
        if w is None:
            return None
        words.extend(w)
    if not device_healthy():
        return None
    padded = _pad_pow2(n)
    try:
        with trace.span("kernel:sort", rows=n, n_words=len(words)):
            key = ("sort", padded, len(words))
            kernel = _SORT_CACHE.get_or_build(
                key, lambda: _build_sort_kernel(len(words), padded), "sort"
            )
            ops = []
            from ..utils.rpc_meter import METER as _M

            for w in words:
                arr = np.full(padded, 0xFFFFFFFF, dtype=np.uint32)
                arr[:n] = w
                _M.record_upload(arr.nbytes)
                ops.append(jnp.asarray(arr))
            ops.append(jnp.arange(padded, dtype=np.int32))
            from ..utils.rpc_meter import METER, device_get as metered_get

            METER.record_dispatch()
            t0 = time.perf_counter()
            perm = np.asarray(metered_get(kernel(*ops)))[:n]
            _observe_dispatch("sort", t0)
    except Exception as e:  # device failure: host sort takes over
        record_device_failure(e)
        return None
    from ..utils.backend import record_device_success

    record_device_success()
    return batch.take(perm.astype(np.int64))


def _mesh_for(session):
    """Active execution mesh when conf requests one (see
    parallel.mesh.active_mesh)."""
    from ..parallel.mesh import active_mesh

    return active_mesh(session)


def _execute_on_mesh(frag: _Fragment, batch: ColumnBatch, plan, session, mesh) -> Optional[ColumnBatch]:
    """Global or grouped fragment over a device mesh: rows shard across
    devices, each shard runs the fused predicate + segment reductions, and
    psum/pmin/pmax trees combine per-group partials (a global aggregate is
    the one-group special case). Only [seg_pad]-sized vectors cross ICI/DCN."""
    from .executor import factorize_group_keys
    from ..parallel.dist_agg import build_distributed_grouped_kernel

    # int sums/avgs run chunked (ops/intsum.py): the caller's global row cap
    # already screened n <= 2^23, which keeps every chunk psum within int32

    n = batch.num_rows
    device_refs = _device_refs(frag)
    if not _fragment_literals_fit(frag):  # mesh shards never ship Wide64
        return None

    if frag.agg.group_exprs:
        key_cols = [batch.column(e.name) for e in frag.agg.group_exprs]
        group_ids, num_groups, first_idx = factorize_group_keys(key_cols)
    else:
        key_cols, first_idx = [], None
        group_ids, num_groups = np.zeros(n, dtype=np.int64), 1
    seg_pad = 1 << max(4, int(np.ceil(np.log2(num_groups + 1))))

    from ..parallel.mesh import num_shards, shard_rows

    d = num_shards(mesh)  # flat or hierarchical (dcn x ici) topology
    padded = _pad_pow2(n)
    if padded % d:
        padded = ((padded + d - 1) // d) * d
    dev_cols = _upload_columns(batch, device_refs & set(batch.columns), padded)
    if dev_cols is None:
        return None
    sharding = shard_rows(mesh)
    from ..utils.rpc_meter import METER as _M

    dev_cols = {k: jax.device_put(v, sharding) for k, v in dev_cols.items()}
    gids = np.full(padded, seg_pad - 1, dtype=np.int32)
    gids[:n] = group_ids.astype(np.int32)
    gids_d = jax.device_put(jnp.asarray(gids), sharding)
    mask_d = jax.device_put(jnp.asarray(np.arange(padded) < n), sharding)
    _M.record_upload(
        sum(v[0].nbytes + v[1].nbytes if isinstance(v, tuple) else v.nbytes
            for v in dev_cols.values())
        + gids_d.nbytes
        + mask_d.nbytes,
        n=len(dev_cols) + 2,
    )

    pred_expr = frag.pred
    proj_exprs = tuple((X.expr_output_name(e), e) for e in _device_projections(frag))
    agg_list_spec, names = _agg_list_names(frag)

    def make_valfn(child):
        def fn(cols):
            proj_cols = dict(cols)
            for nm, e in proj_exprs:
                proj_cols[nm] = compile_expr(e, cols)
            return compile_expr(child, proj_cols)

        return fn

    agg_list = [
        (kind, make_valfn(child) if child is not None else None)
        for kind, child in agg_list_spec
    ]
    pred_fn = (lambda cols: compile_expr(pred_expr, cols)) if pred_expr is not None else None

    key = mesh_fingerprint(
        d, tuple(zip(mesh.axis_names, mesh.devices.shape)), seg_pad,
        pred_expr, proj_exprs, agg_list_spec, dev_cols,
    )
    kernel = _KERNEL_CACHE.get_or_build(
        key,
        lambda: build_distributed_grouped_kernel(mesh, pred_fn, agg_list, seg_pad),
        "mesh_agg",
    )
    from ..utils.rpc_meter import METER, device_get as metered_get

    with trace.span(
        "kernel:mesh_agg", rows=n, shards=d, groups=num_groups
    ):
        METER.record_dispatch()
        t0 = time.perf_counter()
        counts_dev, first_masked, results = metered_get(
            kernel(dev_cols, gids_d, mask_d)
        )
        _observe_dispatch("mesh_agg", t0)
    info = getattr(frag.scan, "index_info", None)
    if info is not None:
        from ..rules.rule_utils import log_index_usage

        log_index_usage(
            session,
            "MeshBucketedExec",
            [info.index_name],
            f"Mesh grouped aggregate: rows sharded over {d} devices "
            f"({info.index_name})",
        )
    counts_full = np.asarray(counts_dev)
    counts = counts_full[:num_groups]
    results = [
        _combine_chunks_maybe_avg(v, kind, counts_full)
        for v, (kind, _c) in zip(results, agg_list_spec)
    ]
    if frag.agg.group_exprs:
        return _assemble_grouped_output(
            plan, frag, key_cols, first_idx, counts, results, agg_list_spec,
            names, num_groups, first_masked,
        )
    matched = int(counts[0])
    scalar_values = [np.asarray(v)[0] for v in results]
    return _assemble_global_output(plan, matched, scalar_values, agg_list_spec, names)
