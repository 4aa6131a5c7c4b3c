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

Supported fragments — the filter-aggregate pipeline and the selection:
    Aggregate(no groups | grouped) ← [Project] ← [Filter] ← FileScan
    Filter ← index FileScan  (rows out: positions on the device, rows on the host)
with numeric/date columns. Anything else falls back to the host executor.
Literals are kernel arguments (``slot_literals``): one kernel per shape.
"""

from __future__ import annotations

import time
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
    select_fingerprint,
)
from .nodes import Aggregate, FileScan, Filter, LogicalPlan, Project
from ..columnar.table import Column, ColumnBatch, STRING
from ..exceptions import HyperspaceError
from ..telemetry import attribution as _attr
from ..telemetry import trace
from ..telemetry.metrics import REGISTRY
from ..utils import env

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

    def compare(self, kind, words):
        """Against an int64 literal given as its (hi int32, lo uint32)
        words (a ``w`` slot)."""
        l_hi, l_lo = words
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


def _int64_words(value) -> tuple:
    """(signed high int32, low uint32) words of an int64 value."""
    v64 = np.int64(value)
    return (np.int32(v64 >> np.int64(32)),
            np.uint32(np.uint64(v64) & np.uint64(0xFFFFFFFF)))


def _is_int_value(v) -> bool:
    return isinstance(v, (int, np.integer)) and not isinstance(v, (bool, np.bool_))


def _wide_compare(e: Expr, cols, lits=()):
    """Two-word compare when one side is a Wide64 column and the other the
    words of an int literal (a ``w`` slot); None when the pattern does not
    apply."""
    flipped = {X.Lt: X.Gt, X.Le: X.Ge, X.Gt: X.Lt, X.Ge: X.Le, X.Eq: X.Eq, X.Ne: X.Ne}
    for a, b, kind in (
        (e.left, e.right, type(e)),
        (e.right, e.left, flipped[type(e)]),
    ):
        if (
            isinstance(a, X.Col)
            and isinstance(cols.get(a.name), Wide64)
            and isinstance(b, X.Lit)
            and isinstance(b.value, Slot)
            and b.value.tag == "w"
        ):
            return cols[a.name].compare(kind, _slot_value(lits, b.value))
    return None


def compile_expr(e: Expr, cols: dict[str, jnp.ndarray], lits=()):
    """Trace an expression over device column arrays. Caller guarantees the
    involved columns are non-null numerics (checked in _plan_supported).
    A literal lowered to a ``Slot`` reads its word of the kernel argument
    ``lits``; any other literal is a trace constant."""
    if isinstance(e, Alias):
        return compile_expr(e.child, cols, lits)
    if isinstance(e, X.Col):
        v = cols[e.name]
        if isinstance(v, Wide64):
            raise HyperspaceError(
                f"Wide int64 column {e.name} only supports literal comparisons"
            )
        return v
    if isinstance(e, X.Lit):
        return _slot_value(lits, e.value) if isinstance(e.value, Slot) else e.value
    for klass, op in _CMP.items():
        if type(e) is klass:
            wide = _wide_compare(e, cols, lits)
            if wide is not None:
                return wide
            return op(compile_expr(e.left, cols, lits),
                      compile_expr(e.right, cols, lits))
    for klass, op in _ARITH.items():
        if type(e) is klass:
            return op(compile_expr(e.left, cols, lits),
                      compile_expr(e.right, cols, lits))
    if isinstance(e, X.And):
        return compile_expr(e.left, cols, lits) & compile_expr(e.right, cols, lits)
    if isinstance(e, X.Or):
        return compile_expr(e.left, cols, lits) | compile_expr(e.right, cols, lits)
    if isinstance(e, X.Not):
        return ~compile_expr(e.child, cols, lits)
    if isinstance(e, X.In):
        c = compile_expr(e.child, cols, lits)
        out = jnp.zeros(c.shape, dtype=bool)
        for v in e.values:
            out = out | (c == (_slot_value(lits, v) if isinstance(v, Slot) else v))
        return out
    raise HyperspaceError(f"Expression not supported on device: {e!r}")


# ---------------------------------------------------------------------------
# literal slots: literals reach the kernels as arguments
# ---------------------------------------------------------------------------
#
# A kernel closes over the expressions of the query that built it and is
# cached by their fingerprint (plan/kernel_cache.py). Were literals trace
# constants, every new order key, year or dictionary code would key, trace
# and compile a kernel of its own. Instead each literal becomes a Slot: the
# expression keeps the literal's type and place, and the value travels in
# the kernel's ``lits`` argument, one int32 vector for all of a kernel's
# literals (one host-to-device copy a dispatch; a float as its float32 bits).
# The fingerprint reads the slotted expressions, whose repr is their shape,
# so one predicate shape builds one kernel whatever its values.

class Slot:
    """A literal lowered to word ``i`` of a kernel's int32 ``lits`` vector.
    ``tag`` is ``i``/``f``/``b`` for an int32/float32/bool value (a float
    held as its bits), ``w`` for the (hi int32, lo uint32) words of an int64
    compared against a Wide64 column, at ``i`` and ``i + 1``. The value
    takes the type the literal meets on the device (an int compared with a
    float32 column is read as float32, as jnp converts a constant), and the
    tag keeps the literal's own kind besides: ``i>f``. The repr is the tag
    alone."""

    __slots__ = ("i", "tag")

    def __init__(self, i: int, tag: str):
        self.i = i
        self.tag = tag

    def __repr__(self):
        return "?" + self.tag


def _slot_word(value, kind: str) -> int:
    """The int32 word that carries a literal of device kind ``kind``."""
    if kind == "f":
        return int(np.float32(value).view(np.int32))
    return int(np.int32(value))


def _slot_value(lits, slot: Slot):
    """A slot's traced value, read from the kernel's ``lits`` words."""
    word = lits[slot.i]
    kind = slot.tag[-1]
    if kind == "w":
        return word, jax.lax.bitcast_convert_type(lits[slot.i + 1], jnp.uint32)
    if kind == "f":
        return jax.lax.bitcast_convert_type(word, jnp.float32)
    if kind == "b":
        return word != 0
    return word


def _value_kind(v) -> Optional[str]:
    """``b``/``i``/``f`` of a literal that can be slotted; None keeps it a
    trace constant (None, strings, ints beyond 32 bits outside a Wide64
    compare — screened out before any kernel is built)."""
    if isinstance(v, (bool, np.bool_)):
        return "b"
    if _is_int_value(v):
        return "i" if _int_lit_fits(v) else None
    if isinstance(v, (float, np.floating)):
        return "f"
    return None


def _dtype_kind(dt) -> Optional[str]:
    dt = np.dtype(dt)
    if dt == np.bool_:
        return "b"
    if dt == np.int32:
        return "i"
    if dt == np.float32:
        return "f"
    return None


class _Slotter:
    """Rewrites expressions with their literals as slots and collects the
    slot values, in a traversal fixed by the expression's shape."""

    def __init__(self, col_kinds: dict, wide: frozenset):
        self.kinds = dict(col_kinds)  # column -> device kind, where known
        self.wide = wide
        self.words: list[int] = []

    def _new(self, words: list[int], tag: str) -> Slot:
        self.words.extend(words)
        return Slot(len(self.words) - len(words), tag)

    def _lit(self, v, context: Optional[str]):
        own = _value_kind(v)
        if own is None:
            return None
        # an int meeting a float32 operand is read as float32, an int
        # meeting a bool operand stays int (jnp promotes as for a constant)
        kind = context if own == "i" and context == "f" else own
        tag = own if kind == own else f"{own}>{kind}"
        return self._new([_slot_word(v, kind)], tag)

    def kind(self, e: Expr) -> Optional[str]:
        """The device kind an expression traces to, where it is plain."""
        if isinstance(e, Alias):
            return self.kind(e.child)
        if isinstance(e, X.Col):
            return self.kinds.get(e.name)
        if isinstance(e, X.Lit):
            return _value_kind(e.value)
        if isinstance(e, (X.And, X.Or, X.Not, X.In, *_CMP)):
            return "b"
        if isinstance(e, X.Div):
            return "f"
        if type(e) in _ARITH:
            a, b = self.kind(e.left), self.kind(e.right)
            return "f" if "f" in (a, b) else (a if a == b else None)
        return None

    def slot(self, e: Expr, context: Optional[str] = None) -> Expr:
        if isinstance(e, X.Lit):
            s = self._lit(e.value, context)
            return e if s is None else X.Lit(s)
        if isinstance(e, Alias):
            return Alias(self.slot(e.child, context), e.name)
        if type(e) in _CMP or type(e) in _ARITH:
            for a, b in ((e.left, e.right), (e.right, e.left)):
                if (type(e) in _CMP and isinstance(a, X.Col)
                        and a.name in self.wide and isinstance(b, X.Lit)
                        and _is_int_value(b.value)):
                    words = [int(w.view(np.int32)) for w in _int64_words(b.value)]
                    lit = X.Lit(self._new(words, "w"))
                    return (type(e)(a, lit) if a is e.left
                            else type(e)(lit, a))
            # each operand is typed by the other one, as jnp promotes it
            left = self.slot(e.left, self.kind(e.right))
            right = self.slot(e.right, self.kind(e.left))
            return type(e)(left, right)
        if isinstance(e, (X.And, X.Or)):
            return type(e)(self.slot(e.left), self.slot(e.right))
        if isinstance(e, X.Not):
            return X.Not(self.slot(e.child))
        if isinstance(e, X.In):
            ctx = self.kind(e.child)
            vals = [self._lit(v, ctx) for v in e.values]
            if any(v is None for v in vals):
                return e
            return X.In(self.slot(e.child), vals)
        return e


def slot_literals(pred_expr, proj_exprs=(), agg_list=(), col_kinds=None,
                  wide: frozenset = frozenset()):
    """``(pred, proj_exprs, agg_list, lits)``: the kernel's expressions with
    every literal as a ``Slot``, and the int32 vector of slot words the
    kernel takes as its ``lits`` argument. ``col_kinds`` maps a column to the kind
    of its device array (``dev_cols``); ``wide`` names the Wide64 columns.
    Projections are typed as they come, so aggregates over their outputs
    see the same kinds the kernel will."""
    s = _Slotter(col_kinds or {}, wide)
    pred = None if pred_expr is None else s.slot(pred_expr)
    proj = []
    for name, e in proj_exprs:
        proj.append((name, s.slot(e)))
        s.kinds[name] = s.kind(e)
    aggs = [(kind, None if c is None else s.slot(c)) for kind, c in agg_list]
    return pred, tuple(proj), aggs, np.asarray(s.words, dtype=np.int32)


def _slot_for(dev_cols: dict, pred_expr, proj_exprs=(), agg_list=()):
    """``slot_literals`` typed by the uploaded columns: each column's device
    kind, and Wide64 for the columns uploaded as word pairs."""
    kinds = {
        n: "i" if isinstance(a, tuple) else _dtype_kind(a.dtype)
        for n, a in dev_cols.items()
    }
    wide = frozenset(n for n, a in dev_cols.items() if isinstance(a, tuple))
    return slot_literals(pred_expr, proj_exprs, agg_list, kinds, wide)


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


def _upload_columns(batch: ColumnBatch, names, padded: int, wide_ok: frozenset = frozenset()):
    """Zero-padded device upload of the named columns; None when any column
    is nullable or exceeds the device's 32-bit integer range (host path).
    Columns in `wide_ok` (full-range int64 referenced only in literal
    comparisons) ship as (hi int32, lo uint32) word pairs instead.

    Device copies are cached by source-buffer identity (utils/device_cache)
    so repeated queries over the same index chunks skip the host->device
    transfer entirely."""
    from ..ops.hashing import split64_np
    from ..utils.device_cache import DEVICE_CACHE

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
                return (jnp.asarray(hi_p), jnp.asarray(lo_p))

            dev_cols[name] = DEVICE_CACHE.get_or_put(
                col.data, ("wide", padded), _build_wide
            )
            continue

        def _build(data=col.data):
            arr = np.zeros(padded, dtype=_device_dtype(data.dtype))
            arr[:n] = data.astype(arr.dtype)
            return jnp.asarray(arr)

        dev_cols[name] = DEVICE_CACHE.get_or_put(
            col.data, ("pad", padded), _build
        )
    return dev_cols


def _padded_mask(padded: int, n: int):
    """Device copy of the valid-rows mask [0..n) within [0..padded): a fresh
    upload per query costs a host->device round trip, and the
    arrays are `padded` device bytes each — so they live in the budgeted
    device LRU, not an unbounded side cache."""
    from ..utils.device_cache import DEVICE_CACHE

    return DEVICE_CACHE.get_or_put_keyed(
        ("mask", padded, n), lambda: jnp.asarray(np.arange(padded) < n)
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
    if frag.pred is None:
        return frozenset()
    pred_orig = frag.filter.condition if frag.filter is not None else None
    others: set[str] = set()
    for e in _device_exprs(frag):
        if e is not pred_orig:
            others |= e.references()
    return _wide_cols(frag.pred, batch) - others


def _wide_cols(pred: Expr, batch: ColumnBatch) -> frozenset:
    """Columns of ``pred`` that ship as Wide64 word pairs: non-null int64
    beyond the 32-bit device range, referenced only in comparisons against
    integer literals."""
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


def _truth_valued(e: Expr, schema) -> bool:
    """True when ``e`` yields booleans: comparisons and IN, bool columns and
    literals, joined by And/Or/Not. The device's ``&``, ``|`` and ``~`` of
    ints are bitwise where the host reads their truth values, so a
    predicate of any other shape stays on the host."""
    if isinstance(e, (X.And, X.Or, X.Not)):
        return all(_truth_valued(c, schema) for c in e.children())
    if isinstance(e, X.In) or type(e) in _CMP:
        return True
    if isinstance(e, X.Col):
        return e.name in schema.names and schema.field(e.name).dtype == "bool"
    return isinstance(e, X.Lit) and isinstance(e.value, (bool, np.bool_))


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
    if pred is not None and not _truth_valued(pred, f.scan.full_schema):
        return False
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


def _concat_padded(parts: list, tail: int):
    """``parts`` end to end on the device, then ``tail`` zeros: one jitted
    program (specialized per part shapes), not eager operations, so the
    other chips of a deployment find it compiled
    (``kernel_cache.ChipKernel``)."""
    from .kernel_cache import concat_kernel

    return concat_kernel()(*parts, np.zeros(tail, parts[0].dtype))


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
from ..ops.runs import run_reduce, run_starts  # noqa: E402


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

    def kernel(cols, mask, lits):
        cols = _wrap_wide(cols)
        a = compile_expr(a_expr, cols, lits)
        b = None if b_expr is None else compile_expr(b_expr, cols, lits)
        if jnp.issubdtype(a.dtype, jnp.integer) or (
            b is not None and jnp.issubdtype(b.dtype, jnp.integer)
        ):
            # integer sums need the exact chunked accumulation; the f32
            # Pallas reduction would round — generic body takes over
            return _generic_agg_compute(
                pred_expr, proj_exprs, agg_list, cols, mask, lits
            )
        pred = mask & compile_expr(pred_expr, cols, lits)
        if b is None:
            rev, cnt = filter_sum(pred, a)
        else:
            rev, cnt = filter_weighted_sum(pred, a, b)
        matched = cnt.astype(jnp.int32)
        out = (rev, matched) if sum_pos == 0 else (matched, rev)
        return matched, out

    return jax.jit(kernel)  # hslint: HS201 — builder runs via KernelCache.get_or_build


def _generic_agg_compute(pred_expr, proj_exprs, agg_list, cols, mask, lits):
    """Traced body of the generic fused kernel (shared so the Pallas kernel
    can fall back to it at trace time for integer-sum exactness)."""
    if pred_expr is not None:
        mask = mask & compile_expr(pred_expr, cols, lits)
    matched = mask.sum()
    proj_cols = dict(cols)
    for name, e in proj_exprs:
        proj_cols[name] = compile_expr(e, cols, lits)
    out = []
    for kind, child in agg_list:
        if kind == "count":
            out.append(matched)
            continue
        vals = compile_expr(child, proj_cols, lits)
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

    def kernel(cols, mask, lits):
        cols = _wrap_wide(cols)
        return _generic_agg_compute(
            pred_expr, proj_exprs, agg_list, cols, mask, lits
        )

    return jax.jit(kernel)  # hslint: HS201 — builder runs via KernelCache.get_or_build


def _fused_kernel(pred_expr, proj_exprs, agg_list, dev_cols):
    """The cached fused kernel of the fragment's shape, and its literal
    arguments."""
    pred, proj, aggs, lits = _slot_for(dev_cols, pred_expr, proj_exprs, agg_list)
    key = fused_fingerprint(_pallas_route(), pred, proj, aggs, dev_cols)
    kernel = _KERNEL_CACHE.get_or_build(
        key, lambda: _build_kernel(pred, proj, aggs), "fused_agg"
    )
    return kernel, lits


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

        kernel, lits = _fused_kernel(pred_expr, proj_exprs, agg_list, dev_cols)
        # ONE batched transfer for the whole result tree: per-array fetches
        # pay a device->host round trip each
        from ..utils.rpc_meter import METER, device_get as metered_get

        METER.record_dispatch()
        with _attr.phase("enqueue"):
            out = kernel(dev_cols, mask, lits)
        matched, results = metered_get(out)
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

    def kernel(cols, gids, mask, lits):
        cols = _wrap_wide(cols)
        if pred_expr is not None:
            mask = mask & compile_expr(pred_expr, cols, lits)
        proj_cols = dict(cols)
        for name, e in proj_exprs:
            proj_cols[name] = compile_expr(e, cols, lits)
        sum_vals = []
        for kind, child in agg_list:
            if kind == "count":
                continue
            vals = compile_expr(child, proj_cols, lits)
            if jnp.issubdtype(vals.dtype, jnp.integer):
                # exact chunked accumulation owns int sums/avgs — generic body
                return _generic_grouped_compute(
                    pred_expr, proj_exprs, agg_list, seg_pad, cols, gids, mask,
                    lits,
                )
            sum_vals.append(vals)
        # every measure, the count and each group's first passing row in
        # ONE streaming pass over pred/gids: no XLA scatter
        sums, counts, first_masked = filter_grouped_multi_sum(
            mask, gids, sum_vals, seg_pad
        )
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


_NO_ROW = 2**31 - 1  # the first passing row of a group with none


def _first_masked_rows(mask, gids, seg_pad):
    """Per-group index of the first row PASSING the predicate: the host
    tier orders grouped output by first post-filter occurrence, and the
    device assembly reorders by this vector so both tiers emit identical
    row order even when the device scanned unfiltered (cache-stable)
    chunks."""
    idx = jnp.arange(gids.shape[0], dtype=jnp.int32)
    return jax.ops.segment_min(
        jnp.where(mask, idx, jnp.int32(_NO_ROW)), gids, num_segments=seg_pad
    )


def _generic_grouped_compute(pred_expr, proj_exprs, agg_list, seg_pad, cols,
                             gids, mask, lits):
    """Traced body of the generic grouped kernel (shared so the Pallas route
    can fall back at trace time for integer-sum exactness)."""
    if pred_expr is not None:
        mask = mask & compile_expr(pred_expr, cols, lits)
    gids = jnp.where(mask, gids, seg_pad - 1)
    first_masked = _first_masked_rows(mask, gids, seg_pad)
    proj_cols = dict(cols)
    for name, e in proj_exprs:
        proj_cols[name] = compile_expr(e, cols, lits)
    counts = jax.ops.segment_sum(
        jnp.ones_like(gids, dtype=jnp.int32), gids, num_segments=seg_pad
    )
    out = []
    for kind, child in agg_list:
        if kind == "count":
            out.append(counts)
            continue
        vals = compile_expr(child, proj_cols, lits)
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


def _run_grouped_compute(pred_expr, proj_exprs, agg_list, cols, gids, mask,
                         lits, last_rows, seg_pad):
    """Traced body of the run-route grouped kernel: every group is one run
    of rows, ``last_rows`` each group's last row (negative for a group with
    none), so each reduction is a segmented scan read at the runs' last rows
    (``ops/runs.py``), with no scatter. Failing rows stay in their run and
    add the identity. Int sums keep their exact chunked scatters."""
    if pred_expr is not None:
        mask = mask & compile_expr(pred_expr, cols, lits)
    starts = run_starts(gids)

    def per_run(v, op, identity):
        return run_reduce(v, starts, last_rows, op, identity)

    idx = jnp.arange(gids.shape[0], dtype=jnp.int32)
    first_masked = per_run(jnp.where(mask, idx, _NO_ROW), jnp.minimum, _NO_ROW)
    counts = per_run(mask.astype(jnp.int32), jnp.add, 0)
    proj_cols = dict(cols)
    for name, e in proj_exprs:
        proj_cols[name] = compile_expr(e, cols, lits)
    out = []
    for kind, child in agg_list:
        if kind == "count":
            out.append(counts)
            continue
        vals = compile_expr(child, proj_cols, lits)
        if kind in ("sum", "avg"):  # an avg's sum: the host divides
            vals = jnp.where(mask, vals, 0)
            if jnp.issubdtype(vals.dtype, jnp.integer):
                out.append(_int_chunk_sums(vals, gids, seg_pad))
            else:
                out.append(per_run(vals, jnp.add, 0))
        else:  # min, max
            fill = _extreme(vals.dtype, kind == "min")
            op = jnp.minimum if kind == "min" else jnp.maximum
            out.append(per_run(jnp.where(mask, vals, fill), op, fill))
    return counts, first_masked, tuple(out)


def _run_route(pred_expr, agg_list, seg_pad, runs: bool) -> bool:
    """Whether a grouped kernel takes the run route: its grouping is one run
    of rows a group (``runs``), and the Pallas histogram, which keeps its
    precedence, does not take it."""
    return runs and not (
        _pallas_route()
        and _pallas_grouped_shape(pred_expr, agg_list, seg_pad) is not None
    )


def _build_grouped_kernel(pred_expr, proj_exprs, agg_list, seg_pad,
                          runs: bool = False):
    """Grouped fragment: predicate + per-group segment reductions in one
    jitted pass; rows failing the mask land in the dump segment seg_pad-1.
    On TPU, small-group sum/count fragments stream through the Pallas
    histogram kernel instead; otherwise a grouping of one run of rows a
    group (``runs``) reduces over its runs, its kernel taking each group's
    last row as a fifth argument."""
    if _pallas_route() and _pallas_grouped_shape(pred_expr, agg_list, seg_pad) is not None:
        return _build_grouped_pallas_kernel(pred_expr, proj_exprs, agg_list, seg_pad)
    if runs:
        def run_kernel(cols, gids, mask, lits, last_rows):
            return _run_grouped_compute(
                pred_expr, proj_exprs, agg_list, _wrap_wide(cols), gids, mask,
                lits, last_rows, seg_pad,
            )

        return jax.jit(run_kernel)  # hslint: HS201 — builder runs via KernelCache.get_or_build

    def kernel(cols, gids, mask, lits):
        cols = _wrap_wide(cols)
        return _generic_grouped_compute(
            pred_expr, proj_exprs, agg_list, seg_pad, cols, gids, mask, lits
        )

    return jax.jit(kernel)  # hslint: HS201 — builder runs via KernelCache.get_or_build


def _grouped_kernel(seg_pad, pred_expr, proj_exprs, agg_list, dev_cols,
                    runs: bool):
    """The cached grouped kernel of the fragment's shape, its literal
    arguments, and whether it takes the run route (``runs``: the grouping
    is one run of rows a group)."""
    pred, proj, aggs, lits = _slot_for(dev_cols, pred_expr, proj_exprs, agg_list)
    runs = _run_route(pred, aggs, seg_pad, runs)
    key = grouped_fingerprint(
        _pallas_route(), seg_pad, runs, pred, proj, aggs, dev_cols
    )
    kernel = _KERNEL_CACHE.get_or_build(
        key, lambda: _build_grouped_kernel(pred, proj, aggs, seg_pad, runs),
        "grouped_agg",
    )
    return kernel, lits, runs


def _grouped_scatters(kernel, args) -> int:
    """The XLA scatters in a grouped kernel's program: the Pallas route has
    none, the generic one a segment scatter for the first rows, the counts
    and each float sum, avg, min or max, four for an int sum; the run route
    only an int sum's four. Its cache key
    fixes the count, so it is counted once, from the program traced at the
    kernel's first dispatch, and kept on the cached kernel."""
    n = getattr(kernel, "scatters", None)
    if n is None:
        from ..staticcheck.kernel_audit import _iter_eqns

        n = kernel.scatters = sum(
            eqn.primitive.name.startswith("scatter")
            for eqn in _iter_eqns(jax.make_jaxpr(kernel)(*args))
        )
    return n


def _dispatch_grouped(kernel, dev_cols, gids_d, mask, lits, last_rows=None):
    """One dispatch of a cached grouped kernel and the fetch of its result;
    adds the kernel's scatters to ``kernel.grouped.scatters``. A run-route
    kernel takes ``last_rows`` and counts in ``kernel.grouped.runs``."""
    from ..utils.rpc_meter import METER, device_get as metered_get

    args = (dev_cols, gids_d, mask, lits)
    if last_rows is not None:
        args += (last_rows,)
        REGISTRY.counter("kernel.grouped.runs").inc()
    METER.record_dispatch()
    with _attr.phase("enqueue"):
        out = kernel(*args)
    REGISTRY.counter("kernel.grouped.scatters").inc(_grouped_scatters(kernel, args))
    return metered_get(out)


def _last_rows(ids: np.ndarray, seg_pad: int) -> np.ndarray:
    """Each group's last row, where every group is one run of rows: a
    ``seg_pad`` int32 vector, -1 for a group with no rows."""
    ends = np.append(np.flatnonzero(np.diff(ids)), len(ids) - 1)
    out = np.full(seg_pad, -1, np.int32)
    out[ids[ends]] = ends
    return out


def _grouping(key_parts: list[ColumnBatch], key_names: list[str], padded: int):
    """Grouping of the key rows of ``key_parts`` (one batch of the key
    columns per chunk, in row order): ``(num_groups, seg_pad, first_keys,
    gids_d, last_rows_d)``, where ``first_keys`` holds each group's key
    values at its first occurrence (``num_groups`` rows), ``gids_d`` the
    padded int32 device group ids, pad rows in the dump segment
    ``seg_pad - 1``, and ``last_rows_d``, where every group is one
    contiguous run of rows (a covering index's bucketed, sorted key), each
    group's last row on the device (``_last_rows``), else None.

    It derives from the key buffers alone, never from the predicate,
    literals or aggregates, so it caches under every key chunk's data and
    validity buffer in chunk order, as the ``("cat", padded)`` device
    columns do: the O(groups) host part and whether the groups are runs in
    HOST_DERIVED_CACHE, the gids and last rows in DEVICE_CACHE. A hit needs
    each buffer to be the same live object, so a refreshed, appended or
    evicted chunk misses; a hit does no O(rows) host work. A miss builds
    what the uncached route built: the concatenated keys,
    ``factorize_group_keys``, the padded fill and its upload, and the
    O(rows) test for runs."""
    from .executor import factorize_group_keys
    from ..utils.device_cache import DEVICE_CACHE, HOST_DERIVED_CACHE

    key_cols = [[part.column(nm) for nm in key_names] for part in key_parts]
    srcs = tuple(
        buf
        for cols in key_cols
        for c in cols
        for buf in (c.data, c.validity)
        if buf is not None
    )
    n = sum(part.num_rows for part in key_parts)
    group_ids = []  # filled by a build: the per-row ids are never cached

    def factorize():
        keys = (
            key_parts[0] if len(key_parts) == 1 else ColumnBatch.concat(key_parts)
        )
        cols = [keys.column(nm) for nm in key_names]
        ids, num_groups, first_idx = factorize_group_keys(cols)
        group_ids.append(ids)
        runs = n > 0 and 1 + int(np.count_nonzero(np.diff(ids))) == num_groups
        return num_groups, [c.take(first_idx) for c in cols], runs

    with trace.span("grouping", rows=n, padded=padded):
        num_groups, first_keys, runs = HOST_DERIVED_CACHE.get_or_put_multi(
            srcs, ("grouping", tuple(key_names), padded), factorize
        )
        seg_pad = 1 << max(4, int(np.ceil(np.log2(num_groups + 1))))

        def ids():
            if not group_ids:  # host part cached, device copy gone
                factorize()
            return group_ids[0]

        def upload():
            arr = np.full(padded, seg_pad - 1, dtype=np.int32)
            arr[:n] = ids().astype(np.int32)
            return jnp.asarray(arr)

        gids_d = DEVICE_CACHE.get_or_put_multi(
            srcs, ("gids", padded, seg_pad), upload
        )
        last_rows_d = None
        if runs:
            last_rows_d = DEVICE_CACHE.get_or_put_multi(
                srcs, ("last_rows", padded, seg_pad),
                lambda: jnp.asarray(_last_rows(ids(), seg_pad)),
            )
    REGISTRY.counter(
        "pipeline.grouping.builds" if group_ids else "pipeline.grouping.hits"
    ).inc()
    return num_groups, seg_pad, first_keys, gids_d, last_rows_d


def _execute_grouped(frag: _Fragment, batch: ColumnBatch, plan) -> Optional[ColumnBatch]:
    """Grouped fragment: keys factorize host-side (string keys never ship);
    masked segment reductions run on device."""
    n = batch.num_rows
    device_refs = _device_refs(frag)
    key_names = [e.name for e in frag.agg.group_exprs]
    padded = _pad_pow2(n)
    wide_ok = _wide_predicate_cols(frag, batch)
    if not _fragment_literals_fit(frag, wide_ok):
        return None
    with trace.span("kernel:grouped_agg", rows=n, padded=padded) as sp:
        dev_cols = _upload_columns(
            batch, device_refs & set(batch.columns), padded, wide_ok
        )
        if dev_cols is None:
            sp.set_attr("declined", "nullable_or_out_of_range")
            return None
        num_groups, seg_pad, first_keys, gids_d, last_rows_d = _grouping(
            [batch.select(key_names)], key_names, padded
        )
        sp.set_attr("groups", num_groups)
        mask = _padded_mask(padded, n)

        pred_expr = frag.pred
        proj_exprs = tuple(
            (X.expr_output_name(e), e) for e in _device_projections(frag)
        )
        agg_list, names = _agg_list_names(frag)
        kernel, lits, runs = _grouped_kernel(
            seg_pad, pred_expr, proj_exprs, agg_list, dev_cols,
            last_rows_d is not None,
        )
        counts_dev, first_masked, results = _dispatch_grouped(
            kernel, dev_cols, gids_d, mask, lits, last_rows_d if runs else None
        )
    counts_full = np.asarray(counts_dev)
    counts = counts_full[:num_groups]
    results = [
        _combine_chunks_maybe_avg(v, kind, counts_full)
        for v, (kind, _c) in zip(results, agg_list)
    ]
    return _assemble_grouped_output(
        plan, frag, first_keys, np.arange(num_groups), counts, results,
        agg_list, names, num_groups, first_masked,
    )


# ---------------------------------------------------------------------------
# selection route: Filter ← FileScan over an index (row-returning scans)
# ---------------------------------------------------------------------------
#
# FilterIndexRule rewrites [Project →] Filter → Scan onto a covering index
# and returns rows. The predicate runs on the device over the device-resident
# predicate columns (the same uploads and device cache as the aggregates),
# and the matching rows compact there to ascending positions, at most
# SELECT_CAP of them: one dispatch, and one fetch of a count and a few
# positions. The host takes exactly those rows from the batch it read, so
# rows, order, dtypes and float64 values are the host tier's; a Project above
# runs on the host over the taken rows, as it would over the host filter's.
# More matches than the cap run the kernel again at a cap that holds them
# all (``select.cap_overflow``): exact, never truncated. The route reads the
# scan monolithically; the chunk streamer takes no selections.

SELECT_CAP = 256  # positions one fetch brings back

# column types the device compares exactly as the host does (32-bit ints,
# int64 within or as Wide64 words, dictionary codes); floats, held in 32
# bits there, stay on the host
_EXACT_SELECT_TYPES = frozenset(
    {"int8", "int16", "int32", "int64", "date32", "bool", STRING}
)


def _select_pred_exact(e: Expr, schema) -> bool:
    """True when the device decides every row as the host would: a truth
    value (``_truth_valued``) whose comparisons and IN lists take integer,
    date, bool and string columns and int, bool and string literals. No
    floats, and no arithmetic, which wraps at 32 bits on the device and not
    on the host."""

    def operand(x) -> bool:
        if isinstance(x, X.Col):
            return (x.name in schema.names
                    and schema.field(x.name).dtype in _EXACT_SELECT_TYPES)
        return isinstance(x, X.Lit) and _exact_value(x.value)

    def walk(x) -> bool:
        if isinstance(x, (X.And, X.Or, X.Not)):
            return all(walk(c) for c in x.children())
        if isinstance(x, X.In):
            return operand(x.child) and all(_exact_value(v) for v in x.values)
        if type(x) in _CMP:
            return operand(x.left) and operand(x.right)
        return True  # a bool column or literal

    return _truth_valued(e, schema) and walk(e)


def _exact_value(v) -> bool:
    return v is not None and not isinstance(v, (float, np.floating))


def _build_select_kernel(pred_expr, cap: int):
    """Predicate over the padded rows, then the first ``cap`` matching
    positions in ascending order (the k-th match is where the running count
    first reaches k; positions past the count read ``padded``)."""

    def kernel(cols, mask, lits):
        cols = _wrap_wide(cols)
        keep = mask & compile_expr(pred_expr, cols, lits)
        running = jnp.cumsum(keep, dtype=jnp.int32)
        ranks = jnp.arange(1, cap + 1, dtype=jnp.int32)
        pos = jnp.searchsorted(
            running, ranks, side="left", method="scan_unrolled"
        ).astype(jnp.int32)
        return running[-1], pos

    return jax.jit(kernel)  # hslint: HS201 — builder runs via KernelCache.get_or_build


def _select_once(pred_expr, dev_cols, mask, cap: int) -> tuple[int, np.ndarray]:
    """One dispatch and one fetch: (matches, first ``cap`` positions)."""
    from ..utils.rpc_meter import METER, device_get as metered_get

    pred, _proj, _aggs, lits = _slot_for(dev_cols, pred_expr)
    kernel = _KERNEL_CACHE.get_or_build(
        select_fingerprint(cap, pred, dev_cols),
        lambda: _build_select_kernel(pred, cap), "select",
    )
    METER.record_dispatch()
    with _attr.phase("enqueue"):
        out = kernel(dev_cols, mask, lits)
    count, pos = metered_get(out)
    return int(count), np.asarray(pos)


def _select_positions(pred: Expr, scan: FileScan, batch: ColumnBatch) -> Optional[np.ndarray]:
    """Ascending positions of the rows of ``batch`` that pass ``pred``,
    computed on the device; None when the batch cannot ship (nullable or
    out-of-range columns, a string reference no code rewrite covers)."""
    n = batch.num_rows
    if n == 0:
        return None
    scols = {f.name for f in scan.full_schema if f.dtype == STRING} & pred.references()
    if scols:
        pred = _encode_string_predicates(pred, batch, scols)
        if pred is None:
            return None
    refs = pred.references() & set(batch.columns)
    padded = _pad_pow2(n)
    with trace.span("kernel:select", rows=n, padded=padded) as sp:
        dev_cols = _upload_columns(batch, refs, padded) if _literals_fit(pred) else None
        if dev_cols is None:  # full-range int64 columns ship as word pairs
            wide = _wide_cols(pred, batch)
            if wide and _literals_fit(pred, wide):
                dev_cols = _upload_columns(batch, refs, padded, wide)
        if dev_cols is None:
            sp.set_attr("declined", "nullable_or_out_of_range")
            return None
        mask = _padded_mask(padded, n)
        count, pos = _select_once(pred, dev_cols, mask, SELECT_CAP)
        if count > SELECT_CAP:
            REGISTRY.counter("select.cap_overflow").inc()
            count, pos = _select_once(pred, dev_cols, mask, _pad_pow2(count))
        sp.set_attr("matched", count)
    REGISTRY.counter("select.rows_scanned").inc(n)
    REGISTRY.counter("select.rows_matched").inc(count)
    return pos[:count].astype(np.int64)


def try_execute_select(plan: LogicalPlan, session) -> Optional[ColumnBatch]:
    """A ``Filter ← FileScan`` over an index as a device selection; None
    when the shape,
    the predicate or the data is unsupported, or the device failed (the host
    executor takes over; failures go through the breaker as in
    ``try_execute_tpu``)."""
    from ..utils.backend import (
        device_healthy,
        record_device_failure,
        record_device_success,
    )

    if not isinstance(plan, Filter) or not isinstance(plan.child, FileScan):
        return None
    scan = plan.child
    if scan.index_info is None:
        # only index data is the engine's resident set (cached chunks, so
        # device copies that persist); a source scan reads fresh buffers,
        # and the route would upload its columns and pay a round trip on
        # every query for a filter the host runs in place
        return None
    pred = plan.condition
    strings = frozenset(f.name for f in scan.full_schema if f.dtype == STRING)
    if (
        not pred.references()
        or not _expr_device_ok(pred, strings)
        or not _select_pred_exact(pred, scan.full_schema)
        or not device_healthy()
    ):
        return None
    from .executor import _exec_file_scan

    # as the aggregates do: read outside the breaker, without the pushed
    # filter, so the chunk and device caches hold stable buffers whatever
    # the literals (bucket and row-group pruning still apply)
    read = scan.copy(pushed_filter=None) if scan.pushed_filter is not None else scan
    batch = _exec_file_scan(read)
    try:
        pos = _select_positions(pred, scan, batch)
    except Exception as e:  # device failure: host executor takes over
        record_device_failure(e)
        return None
    if pos is None:
        return None
    record_device_success()
    from ..telemetry import plan_stats

    plan_stats.note_route(plan.plan_id, "device")
    plan_stats.note_scan(
        scan.plan_id, len(read.files), sum(f.size for f in read.files),
        rows=batch.num_rows,
    )
    return batch.take(pos)


# ---------------------------------------------------------------------------
# pipelined chunk streaming (scan ∥ upload, then one kernel)
# ---------------------------------------------------------------------------
#
# Multi-file scans execute as an ordered stream of file-group chunks: the IO
# pool decodes the next chunks while this one's columns upload. The chunks
# concatenate device-side into exactly the array the monolithic upload would
# have produced, and the monolithic kernel runs once on it: bit-identical to
# the monolithic path by construction, with host memory bounded by the chunk
# size. The chunk uploads and the concatenated copy both live in the device
# cache, so a warm repeat over cache-stable index chunks uploads nothing.
#
# The stream declines (`pipeline.declined`, monolithic read) when the
# fragment's literals need Wide64 or its predicate references a string
# column (chunk dictionaries differ, so no one code rewrite covers the
# concatenated array); an int sum over the exact-sum row cap goes to the
# host tier either way. It aborts to the monolithic path on a nullable
# chunk, an out-of-32-bit-range int64 chunk or cross-file dtype drift.
#
# `HYPERSPACE_PIPELINE=0` disables the streamer (legacy monolithic path);
# `HYPERSPACE_PIPELINE=serial` keeps the staged executor but removes the IO
# overlap (the debug mode for isolating pipelining effects).

def _pipeline_enabled() -> bool:
    return env.env_str("HYPERSPACE_PIPELINE") != "0"


def _pipeline_overlap() -> bool:
    return env.env_str("HYPERSPACE_PIPELINE") != "serial"


def _fragment_streams(frag: "_Fragment") -> bool:
    """False = decline streaming (monolithic path): Wide64 literals, or a
    predicate over a string column."""
    if not _fragment_literals_fit(frag):
        return False
    if frag.pred is None:
        return True
    scols = {f.name for f in frag.scan.schema if f.dtype == STRING}
    return frag.pred.references().isdisjoint(scols)


def _execute_streaming(frag: "_Fragment", scan, plan, session) -> Optional[ColumnBatch]:
    """Streamed execution of a supported fragment over a streamable scan;
    None = fall back to the monolithic read (which re-screens and may still
    run on device, with Wide64, or decline to the host tier)."""
    if not _fragment_streams(frag):
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
    chunks = iter_scan_chunks(
        scan, overlap=_pipeline_overlap(), selection=selection
    )
    # abort-and-replan monitor: pass-through unless HYPERSPACE_ADAPTIVE is
    # on AND this scan's prune stage underdelivered its prediction
    from . import adaptive

    chunks = adaptive.monitor_scan_chunks(chunks, scan, selection)
    t0 = time.perf_counter()
    with trace.span(
        "pipeline:concat", rows=n_total, files=len(scan.files),
        grouped=bool(frag.agg.group_exprs),
    ) as sp:
        try:
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
            return _concat_padded(parts, padded - n_seen)

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
            kernel, lits = _fused_kernel(
                pred_expr, proj_exprs, agg_list, dev_cols
            )
            METER.record_dispatch()
            with _attr.phase("enqueue"):
                out = kernel(dev_cols, mask, lits)
            matched, results = metered_get(out)
        matched = int(matched)
        scalar_values = [
            _combine_chunks_maybe_avg(v, kind, matched)
            for v, (kind, _c) in zip(results, agg_list)
        ]
        return _assemble_global_output(plan, matched, scalar_values, agg_list, names)

    # grouped: keys were collected host-side per chunk (they never ship)
    num_groups, seg_pad, first_keys, gids_d, last_rows_d = _grouping(
        key_parts, key_names, padded
    )
    proj_exprs = tuple(
        (X.expr_output_name(e), e) for e in _device_projections(frag)
    )
    with trace.span(
        "kernel:grouped_agg", rows=n_seen, padded=padded, groups=num_groups
    ):
        kernel, lits, runs = _grouped_kernel(
            seg_pad, pred_expr, proj_exprs, agg_list, dev_cols,
            last_rows_d is not None,
        )
        counts_dev, first_masked, results = _dispatch_grouped(
            kernel, dev_cols, gids_d, mask, lits, last_rows_d if runs else None
        )
    counts_full = np.asarray(counts_dev)
    counts = counts_full[:num_groups]
    results = [
        _combine_chunks_maybe_avg(v, kind, counts_full)
        for v, (kind, _c) in zip(results, agg_list)
    ]
    return _assemble_grouped_output(
        plan, frag, first_keys, np.arange(num_groups), counts, results,
        agg_list, names, num_groups, first_masked,
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
            from ..utils.rpc_meter import device_get as metered_get

            _M.record_dispatch()
            with _attr.phase("enqueue"):
                out = kernel(jnp.asarray(arr), jnp.int32(n))
            idx = metered_get(out)
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
            with _attr.phase("enqueue"):
                out = kernel(*ops)
            perm = np.asarray(metered_get(out))[:n]
    except Exception as e:  # device failure: host sort takes over
        record_device_failure(e)
        return None
    from ..utils.backend import record_device_success

    record_device_success()
    return batch.take(perm.astype(np.int64))


def _mesh_for(session):
    """Active execution mesh when conf requests one (see
    parallel.mesh.active_mesh); None for a query homed on one chip of the
    deployment, which runs there as on one chip."""
    from ..parallel.mesh import active_mesh
    from ..utils.backend import home

    return None if home() is not None else active_mesh(session)


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
        with _attr.phase("enqueue"):
            out = kernel(dev_cols, gids_d, mask_d)
        counts_dev, first_masked, results = metered_get(out)
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
