"""Device execution of the co-partitioned bucketed join + aggregate.

The physical payoff of JoinIndexRule on TPU (ref: the Exchange-free
sort-merge join arranged by covering/JoinIndexRule.scala:635-720 and executed
by BucketUnionExec.scala:52-121): per bucket, the right side arrives sorted
by the join key from the index file, every left row probes it with one
device rank probe (ops/join.probe_ranks: a sort-merge, or a binary search
for a small left side), right attributes gather back per left row, and the
aggregate reduces per right key with segment reductions — the join output
NEVER materializes. Only [n_right_keys]-sized aggregate vectors return to
the host (the Q3 hot shape: revenue per order over a lineitem x orders
bucket join).

Applicability (checked per bucket; anything else falls back to the host
merge join): single numeric equi-key; group columns drawn from the join key
and right-side columns; aggregates and residual predicates
device-expressible over left columns and gathered right columns. Duplicate
right keys are fine when aggregates/residuals are left-only and groups are
keyed by the join key (match-count weighting); otherwise a per-key gather
would drop rows and the bucket falls back. f64 Sum/Avg inputs always take
the host twin (exact f64 accumulation — tiers must agree).

The PLAIN (non-aggregated) join also runs here: try_device_plain_join
probes on device and gathers on the host in original dtypes, bit-identical
to the host merge join.
"""

from __future__ import annotations

from typing import Optional, Sequence

import numpy as np

import jax
import jax.numpy as jnp

from . import expr as X
from .expr import Alias, Expr, expr_output_name
from .kernel_cache import JOIN_CACHE, join_fingerprint
from ..columnar.table import Column, ColumnBatch, STRING
from ..ops.join import merge_probe, probe_ranks, probe_temp_bytes
from ..telemetry import attribution as _attr
from ..telemetry import trace
from ..telemetry.metrics import REGISTRY
from ..utils import env
from ..utils.backend import home, on_home


def _pow2(n: int, floor: int = 10) -> int:
    return 1 << max(floor, int(np.ceil(np.log2(max(1, n)))))


def join_split_rows() -> int:
    """Fallback split threshold when no memory plan is active: buckets
    whose left side exceeds this row count split into sub-bucket probe
    chunks (``HYPERSPACE_JOIN_SPLIT_ROWS``, default 262144; 0 disables
    splitting). With the device-memory ledger enabled the per-bucket
    strategy plan (plan/join_memory.plan_join_memory) decides instead —
    the knob then acts as an explicit OVERRIDE of the grant-derived split
    row count. Splitting engages only where chunk partials fold exactly:
    always for the plain probe (per-left-row results concatenate), and for
    the fused aggregate only when every aggregate is count/min/max — f32
    sum/avg partials are not decomposition-invariant, so those buckets run
    unsplit in their own band instead."""
    try:
        return env.env_int("HYPERSPACE_JOIN_SPLIT_ROWS")
    except ValueError:
        return 1 << 18


# buckets per stacked band dispatch: small enough that device work starts
# while later bucket pairs are still decoding on the IO pool, large enough
# that the default 8-bucket layout stays a single dispatch per band
_JOIN_WAVE = 8


def _band_pads(n_l: int, n_r: int) -> tuple:
    """The power-of-2 size band a bucket pair belongs to: its vmap pads."""
    return _pow2(n_l), _pow2(n_r)


def note_probe(pad_l: int, pad_r: int, rows: int) -> None:
    """Count the ``rows`` probe slots (pads included) of one dispatched
    kernel under the form ``probe_ranks`` takes at these pads."""
    if merge_probe(pad_l, pad_r):
        REGISTRY.counter("join.probe.merge").inc(rows)
    else:
        REGISTRY.counter("join.probe.search").inc(rows)


class _JoinDeclined(Exception):
    """The batched device join declines to the per-bucket path for a
    DATA-shaped reason (int32 pair-count overflow, the skew readback
    guard) — not a device failure, so it must never latch the breaker."""


class _Wave:
    """One dispatched band wave: its pads, items, device record, device-
    ledger reservation, and — once spilled (parked admission) or fetched
    (the normal batched finish) — its host-side results in ``done``."""

    __slots__ = ("pads", "items", "rec", "nbytes", "done", "ordinal")

    def __init__(self, pads, items, rec, nbytes: int = 0, ordinal: int = 0):
        self.pads = pads
        self.items = items
        self.rec = rec
        self.nbytes = nbytes
        self.done = None
        self.ordinal = ordinal  # the chip whose device ledger it charges


class _BandScheduler:
    """Groups per-bucket join work into power-of-2 ``(pad_l, pad_r)`` bands
    and dispatches a band's stacked kernel as soon as ``_JOIN_WAVE`` items
    are waiting — jax dispatch is asynchronous, so device work for earlier
    buckets overlaps the next pair's parquet decode. With ``banded=False``
    (the ``HYPERSPACE_PIPELINE=0`` contract) everything defers to
    ``finish()`` and runs as ONE wave at the global pads — the pre-banding
    behavior, which the banded path must match bit for bit.

    Device-memory ledger (``ledger``/``estimate``/``retire``): before a
    wave dispatches, its padded upload footprint (``estimate(pads,
    items)``) is reserved on the device-byte accountant. When the wave
    does not fit, the admission PARKS it: ``spill_one`` retires this
    join's oldest in-flight wave — ``retire(wave)`` fetches its results
    back to the host, freeing the device buffers — and releases its
    reservation, until the new wave fits (or, once nothing of ours is
    left, the zero-holder force grant admits it). Spilling changes only
    WHEN a wave's results come back, never what they are, so the adaptive
    path stays bit-identical to the unconstrained one.

    Only the dispatch/retire callbacks may touch the device: their
    failures latch the fail-open circuit breaker and kill the scheduler
    (``dead``); a ``_JoinDeclined`` from retire records a data-shaped
    decline (``declined``) without touching the breaker; consumption
    errors (host IO) propagate to the caller untouched."""

    def __init__(self, dispatch, banded: bool, wave: int = _JOIN_WAVE,
                 ledger=None, estimate=None, retire=None):
        self._dispatch = dispatch  # (pads, items) -> device record
        self.banded = banded
        self.wave = wave
        self._ledger = ledger  # plan/join_memory.DeviceLedger or None
        self._estimate = estimate  # (pads, items) -> wave footprint bytes
        self._retire = retire  # (_Wave) -> host results (the spill fetch)
        self._groups: dict = {}
        self.records: list[_Wave] = []
        self.dead: Optional[BaseException] = None
        self.declined: Optional[Exception] = None
        self._item_pads = 0
        self._max_l = self._max_r = 0
        self._n_items = 0

    def add(self, item, n_l: int, n_r: int, place=None) -> None:
        """``place`` is the chip the skew-aware placer chose for this item
        (``(ordinal, devices)`` from ``parallel.placement``) or None (the
        query's home chip, or the default device off a deployment).
        Placed items band by ``(pads, place)`` so each wave's single
        dispatch targets exactly one chip; unplaced behavior is unchanged
        to the byte."""
        self._max_l = max(self._max_l, n_l)
        self._max_r = max(self._max_r, n_r)
        self._n_items += 1
        if not self.banded:
            # ONE global wave: per-wave chip targeting is meaningless
            self._groups.setdefault(None, []).append(item)
            return
        band = (_band_pads(n_l, n_r), place)
        group = self._groups.setdefault(band, [])
        group.append(item)
        if len(group) >= self.wave:
            self._flush(band[0], group, place)
            self._groups[band] = []

    def spill_one(self) -> bool:
        """Retire (spill) this join's oldest in-flight wave: fetch its
        results to the host — the device buffers die with the record —
        and release its ledger reservation. False when every dispatched
        wave is already retired (nothing of ours left to free)."""
        for w in self.records:
            if w.done is None:
                with trace.span(
                    "join:spill", pad_l=w.pads[0], pad_r=w.pads[1],
                    buckets=len(w.items), bytes=w.nbytes,
                ):
                    w.done = self._retire(w)
                w.rec = None  # drop the device references
                REGISTRY.counter("join.spill.spills").inc()
                from ..telemetry import plan_stats

                plan_stats.note_flag("spilled_waves")
                if w.nbytes:
                    self._ledger.release(w.nbytes, device=w.ordinal)
                    w.nbytes = 0
                return True
        return False

    def release_reservations(self) -> None:
        """Return every outstanding wave reservation (after the final
        fetch has landed all results on the host)."""
        for w in self.records:
            if w.nbytes:
                self._ledger.release(w.nbytes, device=w.ordinal)
                w.nbytes = 0

    def _flush(self, pads, items, place=None) -> None:
        if self.dead is not None or self.declined is not None or not items:
            return
        # the placed chip, else the query's home chip (0 off a deployment):
        # the wave runs there and charges its device ledger
        h = home() if place is None else place
        ordinal = 0 if h is None else h[0]
        need = 0
        if self._ledger is not None and self._ledger.enabled and self._estimate:
            need = int(self._estimate(pads, items))
        reserved = False
        try:
            if need:
                # reserve the wave's device footprint; parks (spilling
                # in-flight waves) instead of declining when it won't fit
                self._ledger.admit(need, self.spill_one, device=ordinal)
                reserved = True
            with trace.span(
                "join:band", pad_l=pads[0], pad_r=pads[1], buckets=len(items)
            ):
                if place is None:
                    rec = self._dispatch(pads, items)
                else:
                    # the wave's uploads, cache entries and kernel land on
                    # the placed chip
                    with trace.span(
                        "mesh:dispatch", device=ordinal, pad_l=pads[0],
                        pad_r=pads[1], buckets=len(items),
                    ), on_home(place):
                        rec = self._dispatch(pads, items)
        except _JoinDeclined as e:
            if reserved:
                self._ledger.release(need, device=ordinal)
            self.declined = e
            return
        except Exception as e:
            from ..utils.backend import record_device_failure

            if reserved:
                self._ledger.release(need, device=ordinal)
            record_device_failure(e)
            self.dead = e
            return
        REGISTRY.counter("pipeline.join.bands").inc()
        self._item_pads += len(items) * (pads[0] + pads[1])
        self.records.append(
            _Wave(pads, items, rec, need if reserved else 0, ordinal)
        )

    def finish(self) -> list:
        if self.banded:
            for key in sorted(
                self._groups,
                key=lambda k: (k[0], -1 if k[1] is None else k[1][0]),
            ):
                self._flush(key[0], self._groups[key], key[1])
        elif self._groups.get(None):
            self._flush(
                _band_pads(self._max_l, self._max_r), self._groups[None]
            )
        self._groups = {}
        if self.banded and self._n_items:
            # padding rows the banding avoided vs one global pad — the
            # direct evidence that a skewed bucket no longer pads the batch
            global_pads = sum(_band_pads(self._max_l, self._max_r))
            saved = self._n_items * global_pads - self._item_pads
            if saved > 0:
                REGISTRY.counter("pipeline.join.pad_rows_saved").inc(saved)
        return self.records


def _shippable(col: Column) -> Optional[np.ndarray]:
    """Host array ready for device upload (32-bit), or None."""
    if col.dtype == STRING or col.validity is not None:
        return None
    d = col.data
    if d.dtype == np.int64:
        if len(d) and (d.min() < -(2**31) or d.max() >= 2**31):
            return None
        return d.astype(np.int32)
    if d.dtype == np.float64:
        return d.astype(np.float32)
    if d.dtype in (np.int32, np.float32, np.int16, np.int8, np.bool_):
        return d
    return None


def _batch_data_nbytes(batch: Optional[ColumnBatch]) -> int:
    """Decoded in-memory footprint of one loaded bucket side — the actual
    the footer-stats size estimate is scored against."""
    if batch is None:
        return 0
    total = 0
    for c in batch.columns.values():
        total += c.data.nbytes
        if c.validity is not None:
            total += c.validity.nbytes
    return total


def _unwrap(e: Expr):
    from .executor import _unwrap_agg

    return _unwrap_agg(e)


def _col_dtype(name: str, lb: ColumnBatch, rb: ColumnBatch) -> Optional[str]:
    if name in lb.columns:
        return str(lb.column(name).dtype)
    if name in rb.columns:
        return str(rb.column(name).dtype)
    return None


def try_device_join_agg(
    agg_plan,
    lb: ColumnBatch,
    rb: ColumnBatch,
    lkeys: Sequence[str],
    rkeys: Sequence[str],
    residual: Sequence[Expr],
    session,
    r_sorted: bool,
) -> Optional[ColumnBatch]:
    """One bucket's join+aggregate on device; None -> host path. Device
    failures record on the circuit breaker and fall back (fail-open)."""
    from ..utils.backend import record_device_failure

    prep = prepare_device_join_agg(
        agg_plan, lb, rb, lkeys, rkeys, residual, session, r_sorted
    )
    if prep is None:
        return None
    tree, assemble = prep
    try:
        # dispatch is async: execution errors surface at the blocking fetch
        from ..utils.rpc_meter import device_get as _metered_get

        fetched = _metered_get(tree)
    except Exception as e:
        record_device_failure(e)
        return None
    from ..utils.backend import record_device_success

    record_device_success()
    return assemble(fetched)


def prepare_device_join_agg(
    agg_plan,
    lb: ColumnBatch,
    rb: ColumnBatch,
    lkeys: Sequence[str],
    rkeys: Sequence[str],
    residual: Sequence[Expr],
    session,
    r_sorted: bool,
):
    """Eligibility checks + device dispatch of one bucket's fused
    join+aggregate, WITHOUT fetching: returns (device result tree,
    assemble(fetched) -> ColumnBatch) so callers with many buckets can
    batch every fetch into one transfer. None -> host path; dispatch
    failures record on the circuit breaker."""
    from ..utils.backend import device_healthy, record_device_failure

    if session is None or len(lkeys) != 1 or not session.conf.exec_tpu_enabled:
        return None
    if not device_healthy():
        return None  # breaker open: host merge join
    try:
        return _prepare_join_agg_inner(
            agg_plan, lb, rb, lkeys, rkeys, residual, session, r_sorted
        )
    except Exception as e:
        record_device_failure(e)
        return None


def _prepare_join_agg_inner(
    agg_plan,
    lb: ColumnBatch,
    rb: ColumnBatch,
    lkeys: Sequence[str],
    rkeys: Sequence[str],
    residual: Sequence[Expr],
    session,
    r_sorted: bool,
):
    # returns (device result tree, assemble(fetched) -> ColumnBatch) or None
    from .tpu_exec import _expr_device_ok, _literals_fit

    lk_name, rk_name = lkeys[0], rkeys[0]

    # --- group columns: join key or right-side columns -------------------
    group_cols = []  # (output_name, source) source: "key" | right col name
    for g in agg_plan.group_exprs:
        if not isinstance(g, X.Col):
            return None
        nm = g.name
        if nm.lower() in (lk_name.lower(), rk_name.lower()):
            group_cols.append((nm, "key"))
        elif nm in rb.columns:
            group_cols.append((nm, nm))
        else:
            return None
    if not any(src == "key" for _n, src in group_cols):
        return None  # right side unique per key makes key-groups bucket-local

    # --- aggregates ------------------------------------------------------
    agg_specs = []  # (name, kind, child_expr|None)
    schema = agg_plan.schema
    for e in agg_plan.agg_exprs:
        name, agg = _unwrap(e)
        if isinstance(agg, X.Count):
            # count(expr) counts non-NULL inputs on the host path; device
            # columns are non-null by the shippable contract, so counting
            # matched rows is equivalent — but only for shippable refs
            if not isinstance(agg.child, X.Lit) and not _expr_device_ok(agg.child):
                return None
            agg_specs.append((name, "count", None))
            continue
        if not isinstance(agg, (X.Sum, X.Avg, X.Min, X.Max)):
            return None
        if not _expr_device_ok(agg.child) or not _literals_fit(agg.child):
            return None
        if isinstance(agg, (X.Sum, X.Avg)):
            if schema.field(name).dtype not in ("float32", "float64"):
                return None  # int sums accumulate 32-bit on device and may wrap
            if session.conf.exec_exact_f64_aggregates and any(
                _col_dtype(c, lb, rb) == "float64"
                for c in agg.child.references()
            ):
                # exactF64Aggregates: f64 inputs would downcast to f32 and
                # segment-sum with accumulated rounding the host twin's
                # exact f64 bincount does not have — under the strict conf
                # the same query must not return different totals per tier,
                # so f64 Sum/Avg stays on the host twin. The default
                # accepts the f32 device accumulation (error analysis on
                # the conf constant). (Min/Max of f32-rounded values always
                # stays: rounding is monotonic, so the selected extreme
                # matches the host's to within one half-ulp of the value.)
                return None
        agg_specs.append((name, agg.func, agg.child))
    for r in residual:
        if not _expr_device_ok(r) or not _literals_fit(r):
            return None

    # --- referenced columns must ship ------------------------------------
    refs: set[str] = set()
    for _n, _k, c in agg_specs:
        if c is not None:
            refs |= c.references()
    for e in agg_plan.agg_exprs:
        _nm, agg = _unwrap(e)
        if isinstance(agg, X.Count) and not isinstance(agg.child, X.Lit):
            refs |= agg.child.references()
    for r in residual:
        refs |= r.references()
    left_refs = {c for c in refs if c in lb.columns}
    right_refs = {c for c in refs if c not in lb.columns}
    if not right_refs <= set(rb.columns):
        return None

    lk_col, rk_col = lb.column(lk_name), rb.column(rk_name)
    if lk_col.data.dtype == np.float64 or rk_col.data.dtype == np.float64:
        # join KEYS must not downcast: distinct f64 keys that collapse in
        # f32 would produce spurious matches (values tolerate f32; keys
        # decide match structure). The host fused path handles f64 exactly.
        return None
    lk_arr, rk_arr = _shippable(lk_col), _shippable(rk_col)
    if lk_arr is None or rk_arr is None:
        return None
    if lk_arr.dtype.kind != rk_arr.dtype.kind:
        return None
    ship_left = {}
    for c in left_refs:
        a = _shippable(lb.column(c))
        if a is None:
            return None
        ship_left[c] = a
    ship_right = {}
    for c in right_refs:
        a = _shippable(rb.column(c))
        if a is None:
            return None
        ship_right[c] = a

    # --- right side sorted; duplicates allowed for left-only aggregates --
    rorder = None
    if not r_sorted:
        rorder = np.argsort(rk_arr, kind="stable")
        rk_arr = rk_arr[rorder]
        ship_right = {c: a[rorder] for c, a in ship_right.items()}
    dup = bool(len(rk_arr) > 1 and (rk_arr[1:] == rk_arr[:-1]).any())
    if dup and (right_refs or any(src != "key" for _n, src in group_cols)):
        # duplicate right keys with right-side gathers would drop rows; but
        # when every aggregate input and residual is left-only and groups
        # are keyed by the join key, each left row's contribution is just
        # weighted by its match count — no expansion, no gather
        return None

    n_l, n_r = lb.num_rows, rb.num_rows
    pad_l, pad_r = _pow2(n_l), _pow2(n_r)

    def padded(a, pad, fill=0):
        out = np.full(pad, fill, dtype=a.dtype)
        out[: len(a)] = a
        return out

    # pad right keys with the dtype max so real keys stay a sorted prefix;
    # probes are additionally bounded by n_r below
    rk_pad_val = (
        np.iinfo(rk_arr.dtype).max
        if rk_arr.dtype.kind == "i"
        else np.float32(np.inf)
    )
    dev_in = {
        "lk": jnp.asarray(padded(lk_arr, pad_l)),
        "rk": jnp.asarray(padded(rk_arr, pad_r, rk_pad_val)),
        "mask": jnp.asarray(np.arange(pad_l) < n_l),
        "n_r": jnp.int32(n_r),
    }
    for c, a in ship_left.items():
        dev_in["l_" + c] = jnp.asarray(padded(a, pad_l))
    for c, a in ship_right.items():
        dev_in["r_" + c] = jnp.asarray(padded(a, pad_r))

    key = join_fingerprint(
        "bucket_agg_dup" if dup else "bucket_agg",
        (pad_l, pad_r),
        str(lk_arr.dtype),
        agg_list=[(k, c) for _n, k, c in agg_specs],
        residual=residual,
        col_sig=tuple(sorted(("l_" + c, str(a.dtype)) for c, a in ship_left.items()))
        + tuple(sorted(("r_" + c, str(a.dtype)) for c, a in ship_right.items())),
    )
    kernel = JOIN_CACHE.get_or_build(
        key,
        lambda: _build_kernel(
            [(k, c) for _n, k, c in agg_specs],
            list(residual),
            sorted(ship_left),
            sorted(ship_right),
            pad_r,
            dup,
        ),
        "join_agg",
    )
    from ..utils.rpc_meter import METER as _METER

    note_probe(pad_l, pad_r, pad_l)
    _METER.record_dispatch()
    with _attr.phase("enqueue"):
        tree = kernel(dev_in)  # dispatched async; caller batches the fetch

    def assemble(fetched) -> ColumnBatch:
        # host-side output (one row per surviving right key); runs OUTSIDE
        # the circuit-breaker scope
        counts_d, results = fetched
        counts = np.asarray(counts_d)[:n_r]
        keep = counts > 0
        out_cols: dict[str, Column] = {}
        for nm, src in group_cols:
            if src == "key":
                col = rb.column(rk_name)
            else:
                col = rb.column(src)
            if rorder is not None:
                col = col.take(rorder)
            out_cols[nm] = col.take(np.flatnonzero(keep))
        for (nm, kind, _c), vals in zip(agg_specs, results):
            np_val = np.asarray(vals)[:n_r][keep]
            f = schema.field(nm)
            if kind == "count":
                out_cols[nm] = Column(np_val.astype(np.int64), "int64")
            elif kind == "avg":  # the device returned the sum
                out_cols[nm] = Column(
                    np_val.astype(np.float64) / np.maximum(counts[keep], 1),
                    "float64",
                )
            elif f.dtype in ("int64", "int32", "int16", "int8"):
                out_cols[nm] = Column(np_val.astype(np.dtype(f.dtype)), f.dtype)
            else:
                out_cols[nm] = Column(np_val.astype(np.float64), "float64")
        return ColumnBatch(out_cols)

    return tree, assemble


# ---------------------------------------------------------------------------
# stacked fused join+aggregate: band-stacked dispatches, ONE fetch
# ---------------------------------------------------------------------------


def _stacked_eligibility(
    agg_plan,
    lb,
    rb,
    lkeys,
    rkeys,
    residual,
    lfilters=(),
    rfilters=(),
    lcols_avail=None,
    rcols_avail=None,
    exact_f64=True,
):
    """Bucket-independent screens for the fused join+aggregate, factored
    from the per-bucket prepare: group columns, aggregate specs, residuals,
    SIDE FILTERS (evaluated in-kernel over raw index columns so uploads stay
    cache-stable), schema-level dtype rules. Returns (group_cols, agg_specs,
    left_names, right_gather_names, right_filter_names) or None. `lb`/`rb`
    are ANY occupied bucket pair (dtypes are schema-wide); `l/rcols_avail`
    are the POST-OPS side schemas, used to attribute agg/residual refs to a
    side (raw batches may carry columns the projections drop)."""
    from .tpu_exec import _expr_device_ok, _literals_fit

    if lcols_avail is None:
        lcols_avail = set(lb.columns)
    if rcols_avail is None:
        rcols_avail = set(rb.columns)
    lk_name, rk_name = lkeys[0], rkeys[0]
    group_cols = []
    for g in agg_plan.group_exprs:
        if not isinstance(g, X.Col):
            return None
        nm = g.name
        if nm.lower() in (lk_name.lower(), rk_name.lower()):
            group_cols.append((nm, "key"))
        elif nm in rcols_avail and nm in rb.columns:
            group_cols.append((nm, nm))
        else:
            return None
    if not any(src == "key" for _n, src in group_cols):
        return None

    agg_specs = []
    schema = agg_plan.schema
    for e in agg_plan.agg_exprs:
        name, agg = _unwrap(e)
        if isinstance(agg, X.Count):
            if not isinstance(agg.child, X.Lit) and not _expr_device_ok(agg.child):
                return None
            agg_specs.append((name, "count", None))
            continue
        if not isinstance(agg, (X.Sum, X.Avg, X.Min, X.Max)):
            return None
        if not _expr_device_ok(agg.child) or not _literals_fit(agg.child):
            return None
        if isinstance(agg, (X.Sum, X.Avg)):
            if schema.field(name).dtype not in ("float32", "float64"):
                return None
            if exact_f64 and any(
                _col_dtype(c, lb, rb) == "float64" for c in agg.child.references()
            ):
                # exactF64Aggregates: f64 Sum/Avg inputs take the exact-f64
                # host twin so the tiers agree bit-for-bit; the default
                # accepts f32 device accumulation (error analysis on the
                # conf constant)
                return None
        agg_specs.append((name, agg.func, agg.child))
    for r in residual:
        if not _expr_device_ok(r) or not _literals_fit(r):
            return None
    # side filters compile over their OWN side's raw columns
    for f in lfilters:
        if not _expr_device_ok(f) or not _literals_fit(f):
            return None
        if not f.references() <= set(lb.columns):
            return None
    for f in rfilters:
        if not _expr_device_ok(f) or not _literals_fit(f):
            return None
        if not f.references() <= set(rb.columns):
            return None
    if exact_f64:
        # strict mode guarantees BIT agreement between tiers: predicates
        # over f64 columns evaluate in f32 on device and could flip a
        # boundary row's membership, so they decline too (not just sums)
        for e in list(residual) + list(lfilters) + list(rfilters):
            if any(
                _col_dtype(c, lb, rb) == "float64" for c in e.references()
            ):
                return None

    refs: set[str] = set()
    for _n, _k, c in agg_specs:
        if c is not None:
            refs |= c.references()
    for e in agg_plan.agg_exprs:
        _nm, agg = _unwrap(e)
        if isinstance(agg, X.Count) and not isinstance(agg.child, X.Lit):
            refs |= agg.child.references()
    for r in residual:
        refs |= r.references()
    left_refs = {c for c in refs if c in lcols_avail and c in lb.columns}
    right_refs = {c for c in refs if c not in left_refs}
    if not right_refs <= (rcols_avail & set(rb.columns)):
        return None
    lfilter_refs = set().union(*(f.references() for f in lfilters)) if lfilters else set()
    rfilter_refs = set().union(*(f.references() for f in rfilters)) if rfilters else set()
    return (
        group_cols,
        agg_specs,
        sorted(left_refs | lfilter_refs),
        sorted(right_refs),
        sorted(rfilter_refs),
    )


def _build_stacked_kernel(
    agg_specs, residual, lfilters, rfilters, right_gather, pad_l, pad_r
):
    """The per-bucket fused filter+probe+gather+segment-reduce body, vmapped
    over the bucket axis: an entire co-partitioned join+aggregate is ONE
    jitted call (every dispatch pays a host round trip, so the per-bucket
    form paid B dispatches where this pays 1).

    SIDE FILTERS evaluate in-kernel over the raw index columns: a left row
    failing its filter contributes weight 0; right-side filters fold into a
    prefix-sum so each left row's weight w = #(matching right rows passing
    the filter) — exact for duplicate right keys too (callers guarantee dup
    buckets are left-only/key-grouped). Shipping RAW columns is what lets
    the device-resident cache serve repeat queries with zero upload."""
    from .tpu_exec import _extreme, compile_expr

    def bucket_body(lk, rk, n_l, n_r, lcols, rcols):
        lmask = jnp.arange(pad_l) < n_l
        for f in lfilters:
            lmask = lmask & compile_expr(f, lcols)
        rmask = jnp.arange(pad_r) < n_r
        for f in rfilters:
            rmask = rmask & compile_expr(f, rcols)
        lo, hi = probe_ranks(rk, lk, n_r)
        posc = jnp.clip(lo, 0, pad_r - 1)
        if rfilters:
            # e[i] = #right rows passing the filter before position i:
            # w = e[hi] - e[lo] counts the PASSING matches per left row
            e = jnp.concatenate(
                [jnp.zeros(1, jnp.int32), jnp.cumsum(rmask.astype(jnp.int32))]
            )
            w = jnp.where(lmask, e[hi] - e[lo], 0).astype(jnp.int32)
        else:
            w = jnp.where(lmask, hi - lo, 0).astype(jnp.int32)
        env = dict(lcols)
        env.update({c: rcols[c][posc] for c in right_gather})
        for r in residual:
            w = w * compile_expr(r, env).astype(jnp.int32)
        found = w > 0
        seg = jnp.where(found, posc, pad_r)
        counts = jax.ops.segment_sum(w, seg, num_segments=pad_r + 1)[:pad_r]
        out = []
        for kind, child in agg_specs:
            if kind == "count":
                out.append(counts)
                continue
            vals = compile_expr(child, env)
            if kind == "sum":
                vals = jnp.where(found, vals * w, 0)
                out.append(
                    jax.ops.segment_sum(vals, seg, num_segments=pad_r + 1)[:pad_r]
                )
            elif kind == "avg":  # the sum only: the host divides
                vals = jnp.where(found, vals * w, 0)
                out.append(
                    jax.ops.segment_sum(vals, seg, num_segments=pad_r + 1)[:pad_r]
                )
            elif kind == "min":
                out.append(
                    jax.ops.segment_min(
                        jnp.where(found, vals, _extreme(vals.dtype, True)),
                        seg,
                        num_segments=pad_r + 1,
                    )[:pad_r]
                )
            elif kind == "max":
                out.append(
                    jax.ops.segment_max(
                        jnp.where(found, vals, _extreme(vals.dtype, False)),
                        seg,
                        num_segments=pad_r + 1,
                    )[:pad_r]
                )
        return counts, tuple(out)

    return jax.jit(jax.vmap(bucket_body))  # hslint: HS201 — builder runs via JOIN_CACHE.get_or_build


class _AggItem:
    """One stacked-agg band row: a whole bucket's prepared slabs, or one
    left-chunk of a split bucket (the right side repeats per chunk; chunk
    partials fold exactly on the host — the split gate only admits
    count/min/max aggregates)."""

    __slots__ = ("bucket", "lb", "rb", "lk_arr", "rk_arr", "rorder",
                 "ship_l", "ship_r", "lo_ofs", "n_chunks")

    def __init__(self, bucket, lb, rb, lk_arr, rk_arr, rorder, ship_l,
                 ship_r, lo_ofs=0, n_chunks=1):
        self.bucket = bucket
        self.lb = lb
        self.rb = rb
        self.lk_arr = lk_arr
        self.rk_arr = rk_arr
        self.rorder = rorder
        self.ship_l = ship_l
        self.ship_r = ship_r
        self.lo_ofs = lo_ofs
        self.n_chunks = n_chunks


def try_stacked_join_agg(
    pairs,
    lkeys,
    rkeys,
    residual,
    session,
    agg_plan,
    lfilters=(),
    rfilters=(),
    lcols_avail=None,
    rcols_avail=None,
    banded=True,
    strategy=None,
) -> Optional[ColumnBatch]:
    """Fused join+aggregate over every bucket via band-stacked device
    dispatches and (in the unconstrained case) ONE blocking fetch; band
    waves reserve their padded upload footprint on the device-memory
    ledger before dispatch and park/spill instead of declining when the
    build side exceeds the grant (see ``_BandScheduler``). ``strategy``
    (plan/join_memory.JoinMemoryPlan) carries the per-bucket
    broadcast/banded/split decisions and the grant-derived split row
    counts; None keeps the fixed ``HYPERSPACE_JOIN_SPLIT_ROWS`` threshold.
    ``pairs`` is an iterable of
    ``(bucket, lb, rb, l_sorted, r_sorted)`` consumed LAZILY: each occupied
    pair preps and joins its power-of-2 size band as it arrives, and a full
    band wave dispatches (asynchronously) while later pairs are still
    decoding on the IO pool — the load-all barrier is gone. Engages only
    when EVERY occupied bucket pair is device-eligible — otherwise None and
    the caller's per-bucket flow takes over (the caller retains the loaded
    pairs, so nothing re-reads).

    ``banded=False`` (the ``HYPERSPACE_PIPELINE=0`` contract) runs all
    buckets as ONE wave at the global pads — the pre-banding behavior the
    banded path reproduces bit for bit: padding rows never touch real
    segments (they land in the dump segment), so per-bucket results are
    independent of the pad. Buckets above ``HYPERSPACE_JOIN_SPLIT_ROWS``
    split into left-chunks only when every aggregate folds exactly
    (count/min/max); f32 sum/avg buckets run unsplit in their own band.

    Bucket pairs hold RAW batches (side filters NOT applied) and
    ``lfilters``/``rfilters`` carry the per-side Filter conjuncts,
    evaluated in-kernel: every upload derives from stable index-chunk
    buffers and caches on their identity, so steady-state repeat queries
    upload NOTHING (the int32 count vectors aside) regardless of the
    predicate values.

    Reference bar: the rewrite IS the speedup — one Exchange-free SMJ pass
    (covering/JoinIndexRule.scala:635-720, BucketUnionExec.scala:52-121);
    here additionally one fetch round trip."""
    from .join_memory import DeviceLedger

    ledger = DeviceLedger("join_agg")
    try:
        return _stacked_join_agg_impl(
            pairs, lkeys, rkeys, residual, session, agg_plan, lfilters,
            rfilters, lcols_avail, rcols_avail, banded, strategy, ledger,
        )
    finally:
        # the cancellation/decline unwind path: outstanding wave
        # reservations return to the shared device ledger here
        ledger.close()


def _log_mesh_exec(session, strategy, place, records, path: str) -> None:
    """MeshBucketedExec index-usage event for a PLACED execution — the
    placed twin of the ``BucketedJoinExec`` event the one-chip tiers emit,
    with the message naming the placement so telemetry shows which chips a
    query's waves actually landed on."""
    if session is None:
        return
    name = getattr(strategy, "index_name", "") if strategy is not None else ""
    if not name:
        return
    from ..rules.rule_utils import log_index_usage

    ordinals = sorted({w.ordinal for w in records})
    log_index_usage(
        session,
        "MeshBucketedExec",
        [name],
        f"Mesh bucketed exec ({path}): {len(records)} waves placed on "
        f"devices {ordinals} of {len(place.devices)}",
    )


def _stacked_join_agg_impl(
    pairs,
    lkeys,
    rkeys,
    residual,
    session,
    agg_plan,
    lfilters,
    rfilters,
    lcols_avail,
    rcols_avail,
    banded,
    strategy,
    ledger,
) -> Optional[ColumnBatch]:
    from ..utils.backend import record_device_failure
    from ..utils.device_cache import DEVICE_CACHE, HOST_DERIVED_CACHE
    from ..utils.rpc_meter import METER, device_get

    lk_name, rk_name = lkeys[0], rkeys[0]
    state: dict = {"elig": None, "dt": None, "first_rb": None,
                   "splittable": False}

    def _chunk_tags(items, right: bool) -> tuple:
        # per-item derivation tag: chunk offset + slab length + sort flag,
        # so a wave's stacked upload caches on (source buffers, derivation)
        return tuple(
            (it.lo_ofs, len(it.rk_arr if right else it.lk_arr),
             it.rorder is None)
            for it in items
        )

    def _dispatch_agg(pads, items):
        pad_l, pad_r = pads
        dt = state["dt"]
        (_gc, agg_specs, left_names, right_gather, _rf, right_names) = state["elig"]
        rk_pad_val = np.iinfo(dt).max if dt.kind == "i" else np.float32(np.inf)
        B = len(items)

        def _build_rk():
            stack = np.full((B, pad_r), rk_pad_val, dtype=dt)
            for i, it in enumerate(items):
                stack[i, : len(it.rk_arr)] = it.rk_arr
            return jnp.asarray(stack)

        rk_d = DEVICE_CACHE.get_or_put_multi(
            tuple(it.rb.column(rk_name).data for it in items),
            ("stackrk", pad_r, dt.str, _chunk_tags(items, True)),
            _build_rk,
        )

        def _stack_cols(names, ship_attr, batch_attr, pad, tag):
            # RAW index batches with stable buffers: every stacked column
            # upload caches on its constituent buffer identities
            out = {}
            for c in names:
                def _build(c=c):
                    first = getattr(items[0], ship_attr)[c]
                    stack = np.zeros((B, pad), dtype=first.dtype)
                    for i, it in enumerate(items):
                        a = getattr(it, ship_attr)[c]
                        stack[i, : len(a)] = a
                    return jnp.asarray(stack)

                srcs = tuple(
                    getattr(it, batch_attr).column(c).data for it in items
                )
                out[c] = DEVICE_CACHE.get_or_put_multi(
                    srcs,
                    (tag, pad, c, _chunk_tags(items, tag == "stackr")),
                    _build,
                )
            return out

        lcols_d = _stack_cols(left_names, "ship_l", "lb", pad_l, "stackl")
        rcols_d = _stack_cols(right_names, "ship_r", "rb", pad_r, "stackr")

        def _build_lk():
            stack = np.zeros((B, pad_l), dtype=dt)
            for i, it in enumerate(items):
                stack[i, : len(it.lk_arr)] = it.lk_arr
            return jnp.asarray(stack)

        lk_d = DEVICE_CACHE.get_or_put_multi(
            tuple(it.lb.column(lk_name).data for it in items),
            ("stacklk", pad_l, dt.str, _chunk_tags(items, False)),
            _build_lk,
        )
        n_l = jnp.asarray(np.array([len(it.lk_arr) for it in items], np.int32))
        n_r = jnp.asarray(np.array([len(it.rk_arr) for it in items], np.int32))

        kernel = JOIN_CACHE.get_or_build(
            join_fingerprint(
                "stacked_agg", pads, dt.str,
                agg_list=[(k, c) for _n, k, c in agg_specs],
                residual=residual, lfilters=lfilters, rfilters=rfilters,
                col_sig=(tuple(left_names), tuple(right_names),
                         tuple(right_gather)),
            ),
            lambda: _build_stacked_kernel(
                [(k, c) for _n, k, c in agg_specs], list(residual),
                list(lfilters), list(rfilters), right_gather, pad_l, pad_r,
            ),
            "join_stacked_agg",
        )
        note_probe(pad_l, pad_r, B * pad_l)
        METER.record_dispatch()
        with _attr.phase("enqueue"):
            return kernel(lk_d, rk_d, n_l, n_r, lcols_d, rcols_d)

    def _est_agg(pads, items):
        # one wave's device footprint: stacked 32-bit uploads (keys +
        # shipped columns), the kernel's per-bucket output vectors, and
        # the probe's temporaries
        elig = state["elig"]
        if elig is None:
            return 0
        (_gc, agg_specs, left_names, _rg, _rf, right_names) = elig
        pad_l, pad_r = pads
        return len(items) * (
            4 * (
                pad_l * (1 + len(left_names))
                + pad_r * (1 + len(right_names))
                + pad_r * (1 + len(agg_specs))
            )
            + probe_temp_bytes(pad_l, pad_r)
        )

    def _retire_agg(wave):
        # the spill fetch: one parked admission retires this wave's
        # results to the host (counts + aggregate vectors), freeing its
        # device buffers; folding is deferred to the common finish path,
        # so spilling cannot change what is folded — only when
        with _attr.phase("fold"):
            return device_get(wave.rec)

    sched = _BandScheduler(
        _dispatch_agg, banded, ledger=ledger, estimate=_est_agg,
        retire=_retire_agg,
    )
    split_default = join_split_rows() if banded else 0
    n_splits = 0
    n_buckets = 0
    place = None
    if banded:
        # skew-aware placement over the deployment's chips (None unless
        # the session spreads join bands): non-banded mode is ONE global
        # wave, nothing to place
        from ..parallel import placement as mesh_placement

        place = mesh_placement.plan_for_strategy(strategy, session)

    # ---- lazy consumption: prep + band + (maybe) dispatch per pair -------
    for b, lb, rb, _l_sorted, r_sorted in pairs:
        if lb is None or rb is None or not lb.num_rows or not rb.num_rows:
            continue
        if state["elig"] is None:
            elig = _stacked_eligibility(
                agg_plan, lb, rb, lkeys, rkeys, residual,
                lfilters, rfilters, lcols_avail, rcols_avail,
                exact_f64=session.conf.exec_exact_f64_aggregates,
            )
            if elig is None:
                return None
            group_cols, agg_specs, left_names, right_gather, rfn = elig
            right_names = sorted(set(right_gather) | set(rfn))
            state["elig"] = (group_cols, agg_specs, left_names, right_gather,
                             rfn, right_names)
            state["first_rb"] = rb
            state["splittable"] = all(
                k in ("count", "min", "max") for _n, k, _c in agg_specs
            )
        (group_cols, _specs, left_names, right_gather, _rf,
         right_names) = state["elig"]
        agg_specs = _specs

        lk_col, rk_col = lb.column(lk_name), rb.column(rk_name)
        if lk_col.data.dtype == np.float64 or rk_col.data.dtype == np.float64:
            return None  # join keys never downcast
        lk_arr, rk_arr = _shippable(lk_col), _shippable(rk_col)
        # EXACT dtype equality: stacking casts into one buffer dtype, and a
        # wider key written into a narrower stack would wrap and fabricate
        # matches (kind-equality is not enough: int16 vs int32 wraps)
        if lk_arr is None or rk_arr is None or lk_arr.dtype != rk_arr.dtype:
            return None
        if state["dt"] is None:
            state["dt"] = lk_arr.dtype
        elif lk_arr.dtype != state["dt"]:
            return None
        ship_l, ship_r = {}, {}
        for c in left_names:
            a = _shippable(lb.column(c))
            if a is None:
                return None
            ship_l[c] = a
        for c in right_names:
            a = _shippable(rb.column(c))
            if a is None:
                return None
            ship_r[c] = a
        rorder = None
        if not r_sorted:
            rorder = HOST_DERIVED_CACHE.get_or_put(
                rk_col.data, ("jorder",),
                lambda a=rk_arr: np.argsort(a, kind="stable"),
            )
            rk_arr = rk_arr[rorder]
            ship_r = {c: a[rorder] for c, a in ship_r.items()}
        dup = bool(len(rk_arr) > 1 and (rk_arr[1:] == rk_arr[:-1]).any())
        if dup and (right_gather or any(src != "key" for _n, src in group_cols)):
            return None  # per-key gather would drop rows for this bucket
        n_buckets += 1
        n_l_total = len(lk_arr)
        if strategy is not None:
            # feed the accuracy ledger the decoded truth the footer-stats
            # estimate priced this bucket at (estimator.qerror.join_build_bytes)
            strategy.observe_actual(b, n_l_total, _batch_data_nbytes(lb))
        # per-bucket split threshold: the memory plan's grant-derived (or
        # overridden) row count when one is active, else the fixed knob.
        # splittable rides along so an adaptive re-derivation never records
        # a strategy flip this aggregate shape could not act on
        split = (
            strategy.split_rows(b, splittable=state["splittable"])
            if strategy is not None and banded
            else split_default
        )
        if split and state["splittable"] and n_l_total > split:
            n_chunks = -(-n_l_total // split)
            n_splits += n_chunks - 1
            for ci, c0 in enumerate(range(0, n_l_total, split)):
                c1 = min(c0 + split, n_l_total)
                sched.add(
                    _AggItem(
                        b, lb, rb, lk_arr[c0:c1], rk_arr, rorder,
                        {c: a[c0:c1] for c, a in ship_l.items()}, ship_r,
                        lo_ofs=c0, n_chunks=n_chunks,
                    ),
                    c1 - c0, len(rk_arr),
                    place=place.slot_for(b, ci) if place else None,
                )
        else:
            sched.add(
                _AggItem(b, lb, rb, lk_arr, rk_arr, rorder, ship_l, ship_r),
                n_l_total, len(rk_arr),
                place=place.slot_for(b) if place else None,
            )

    if state["elig"] is None:
        return None  # no occupied bucket pair: caller emits the empty shape
    records = sched.finish()
    if sched.dead is not None or sched.declined is not None or not records:
        return None
    REGISTRY.counter("pipeline.join.buckets").inc(n_buckets)
    if n_splits:
        REGISTRY.counter("pipeline.join.splits").inc(n_splits)

    (group_cols, agg_specs, _ln, _rg, _rfn, _rn) = state["elig"]

    # ---- ONE blocking fetch over every un-spilled band -------------------
    # (parked admissions already retired their waves to the host; fetching
    # early vs late never changes a wave's results, so the adaptive path
    # folds exactly what the unconstrained one does)
    try:
        pending = [w for w in records if w.done is None]
        if pending:
            if place is not None:
                # the cross-chip gather: ONE fetch spanning every placed
                # wave (device_get pulls from each wave's own chip)
                with trace.span(
                    "mesh:gather", waves=len(pending),
                    devices=len({w.ordinal for w in pending}),
                ), trace.span("join:fold", annotate=False,
                              waves=len(pending)), _attr.phase("fold"):
                    fetched = device_get([w.rec for w in pending])
            else:
                with trace.span("join:fold", annotate=False,
                                waves=len(pending)), _attr.phase("fold"):
                    fetched = device_get([w.rec for w in pending])
            for w, f in zip(pending, fetched):
                w.done = f
                w.rec = None
    except Exception as e:
        record_device_failure(e)
        return None
    from ..utils.backend import record_device_success

    record_device_success()  # all band dispatches and the fold fetch landed
    sched.release_reservations()
    if place is not None:
        _log_mesh_exec(session, strategy, place, records, "stacked_agg")

    # ---- host: fold split chunks exactly, then assemble per bucket -------
    per_bucket: dict[int, dict] = {}
    for wave in records:
        items = wave.items
        counts_d, results_d = wave.done
        counts_np = np.asarray(counts_d)
        results_np = [np.asarray(r) for r in results_d]
        for i, it in enumerate(items):
            n_r_i = len(it.rk_arr)
            counts = counts_np[i, :n_r_i]
            vals = [r[i, :n_r_i] for r in results_np]
            slot = per_bucket.get(it.bucket)
            if slot is None:
                per_bucket[it.bucket] = {"item": it, "counts": counts,
                                         "vals": vals}
                continue
            # exact chunk folds (the split gate only admits count/min/max)
            slot["counts"] = slot["counts"] + counts
            folded = []
            for (_nm, kind, _c), a, bv in zip(agg_specs, slot["vals"], vals):
                if kind == "count":
                    folded.append(a + bv)
                elif kind == "min":
                    folded.append(np.minimum(a, bv))
                else:
                    folded.append(np.maximum(a, bv))
            slot["vals"] = folded

    schema = agg_plan.schema
    parts = []
    for b in sorted(per_bucket):
        slot = per_bucket[b]
        it = slot["item"]
        counts = slot["counts"]
        keep = counts > 0
        if not keep.any():
            continue
        out_cols: dict[str, Column] = {}
        for nm, src in group_cols:
            col = it.rb.column(rk_name if src == "key" else src)
            if it.rorder is not None:
                col = col.take(it.rorder)
            out_cols[nm] = col.take(np.flatnonzero(keep))
        for (nm, kind, _c), full in zip(agg_specs, slot["vals"]):
            np_val = full[keep]
            f = schema.field(nm)
            if kind == "count":
                out_cols[nm] = Column(np_val.astype(np.int64), "int64")
            elif kind == "avg":  # the device returned the sum
                out_cols[nm] = Column(
                    np_val.astype(np.float64) / np.maximum(counts[keep], 1),
                    "float64",
                )
            elif f.dtype in ("int64", "int32", "int16", "int8"):
                out_cols[nm] = Column(np_val.astype(np.dtype(f.dtype)), f.dtype)
            else:
                out_cols[nm] = Column(np_val.astype(np.float64), "float64")
        parts.append(ColumnBatch(out_cols))
    if not parts:
        # all groups empty: emit the grouped empty shape
        rb0 = state["first_rb"]
        empty = np.empty(0, dtype=np.int64)
        out_cols = {}
        for nm, src in group_cols:
            out_cols[nm] = rb0.column(rk_name if src == "key" else src).take(empty)
        for nm, kind, _c in agg_specs:
            f = schema.field(nm)
            dtype = "int64" if kind == "count" else (
                f.dtype if f.dtype.startswith("int") else "float64"
            )
            from ..columnar.table import numpy_dtype

            out_cols[nm] = Column(np.empty(0, numpy_dtype(dtype)), dtype)
        return ColumnBatch(out_cols)
    return ColumnBatch.concat(parts)


_PLAIN_MIN_ROWS = 4096  # below this the host searchsorted probe is cheaper


from ..ops.join import exact_key32 as _key32  # keys decide match structure


def _build_plain_probe_kernel():
    """Lower/upper-bound probe of the sorted right keys for every left key:
    (starts, counts) per left row. Pads in rk carry the dtype maximum so the
    real keys stay a sorted prefix; probes clamp to n_r. Shape-polymorphic:
    one cached callable per key dtype, re-specialized per size class by
    jax.jit internally."""

    def kernel(lk, rk, n_r):
        lo, hi = probe_ranks(rk, lk, n_r)
        return lo, hi - lo

    return jax.jit(kernel)  # hslint: HS201 — builder runs via JOIN_CACHE.get_or_build


def _build_stacked_probe_kernel(pad_l: int, pad_r: int):
    """Per-bucket probe + exclusive offsets + overflow check, vmapped over
    the bucket axis: the whole wave of buckets probes in ONE dispatch.
    offs[i] = number of pairs emitted before left row i (pads probe to an
    empty range, so they add nothing). int32 cumsum overflow is detectable:
    counts are non-negative, so ends must be nondecreasing and the total
    non-negative — any wrap breaks one of those."""

    def body(lk, rk, n_r, n_l):
        idx = jnp.arange(pad_l, dtype=jnp.int32)
        lo, hi = probe_ranks(rk, lk, n_r)
        cnt = jnp.where(idx < n_l, hi - lo, 0)
        ends = jnp.cumsum(cnt)
        ok = jnp.all(jnp.diff(ends) >= 0) & (ends[-1] >= 0)
        return lo.astype(jnp.int32), (ends - cnt).astype(jnp.int32), ends[-1], ok

    return jax.jit(jax.vmap(body))  # hslint: HS201 — builder runs via JOIN_CACHE.get_or_build


def _build_stacked_expand_kernel(out_pad: int):
    """Per-bucket run expansion vmapped over the bucket axis: pair j of
    bucket i maps to left row li = the run whose [offs[li], offs[li]+cnt)
    interval contains j (the right rank of j in the nondecreasing offs,
    minus 1; empty runs share their start offset with the next run, and
    walking back from a shared boundary lands on the non-empty one for
    j < total), and right row
    lo[li] + (j - offs[li]). Emitting (li, ri) directly means the host
    fetches ~2 * pairs int32 instead of 2 * pad_l — readback proportional to
    the JOIN OUTPUT, not the probe domain. out_pad is the max bucket's
    padded pair count (smaller buckets mask; the caller guards heavy skew)."""

    def body(lo, offs, total):
        j = jnp.arange(out_pad, dtype=jnp.int32)
        _, i = probe_ranks(offs, j, offs.shape[0])
        i = jnp.clip(i - 1, 0, lo.shape[0] - 1)
        li = i
        ri = lo[i] + (j - offs[i])
        valid = j < total
        return jnp.where(valid, li, 0), jnp.where(valid, ri, 0)

    return jax.jit(jax.vmap(body))  # hslint: HS201 — builder runs via JOIN_CACHE.get_or_build


class _ProbeItem:
    """One stacked-probe band row: a whole bucket's sorted left keys, or one
    left-chunk of an oversized (split) bucket. Per-left-row probe results
    are independent of the chunking, so chunk results concatenate into
    exactly the unsplit bucket's — the split fold is exact by construction.
    ``lo_ofs`` is the chunk's offset into the bucket's sorted left keys."""

    __slots__ = ("bucket", "lb", "rb", "lk32", "rk32", "lorder", "rorder",
                 "lk_src", "rk_src", "lo_ofs", "n_chunks")

    def __init__(self, bucket, lb, rb, lk32, rk32, lorder, rorder, lk_src,
                 rk_src, lo_ofs=0, n_chunks=1):
        self.bucket = bucket
        self.lb = lb
        self.rb = rb
        self.lk32 = lk32
        self.rk32 = rk32
        self.lorder = lorder
        self.rorder = rorder
        self.lk_src = lk_src
        self.rk_src = rk_src
        self.lo_ofs = lo_ofs
        self.n_chunks = n_chunks


def _split_probe_items(w, split: int):
    """Expand one work tuple into probe items: whole-bucket, or left-chunks
    of at most ``split`` rows when the bucket exceeds it (split=0 never
    splits). Yields at least one item for a non-empty pair."""
    b, lb, rb, lk32, rk32, lorder, rorder, lk_src, rk_src = w
    n_l = len(lk32)
    if split and n_l > split:
        n_chunks = -(-n_l // split)
        for c0 in range(0, n_l, split):
            c1 = min(c0 + split, n_l)
            yield _ProbeItem(b, lb, rb, lk32[c0:c1], rk32, lorder, rorder,
                             lk_src, rk_src, lo_ofs=c0, n_chunks=n_chunks)
    else:
        yield _ProbeItem(b, lb, rb, lk32, rk32, lorder, rorder, lk_src, rk_src)


def _stack_band_keys(items, arr_attr: str, src_attr: str, pad: int, dt,
                     pad_val):
    """Device copy of one band wave's stacked key slabs, cached by the
    ORIGINAL key buffers' identities + the per-item derivation (chunk
    offset, slab length, sort flag): sorted/sliced/padded stacks are
    deterministic per source set, so steady-state repeats upload nothing."""
    from ..utils.device_cache import DEVICE_CACHE

    srcs = tuple(getattr(it, src_attr) for it in items)
    left = arr_attr == "lk32"
    tag = (
        "jband", arr_attr, pad, dt.str,
        tuple(
            (it.lo_ofs, len(getattr(it, arr_attr)),
             (it.lorder is None) if left else (it.rorder is None))
            for it in items
        ),
    )

    def _build():
        stack = np.full((len(items), pad), pad_val, dtype=dt)
        for i, it in enumerate(items):
            a = getattr(it, arr_attr)
            stack[i, : len(a)] = a
        return jnp.asarray(stack)

    return DEVICE_CACHE.get_or_put_multi(srcs, tag, _build)


def try_batched_plain_join(work, residual, session, banded=None,
                           strategy=None):
    """Device plain join over MANY co-partitioned buckets: band-stacked
    probe dispatches, then band-stacked run expansions, with exactly TWO
    blocking fetches TOTAL in the unconstrained case — every fetch is a
    blocking device->host round trip, so the whole join still costs 2
    regardless of bucket count, and the pair readback
    is sized per band by the join output rather than one global probe
    domain. Every probe wave reserves its padded footprint on the
    device-memory ledger before dispatch; waves that do not fit park and
    spill earlier waves (probe-fetch + expand + host readback per spilled
    wave) instead of declining — per-wave results are independent of WHEN
    they are fetched, so the spilling path stays bit-identical.
    ``strategy`` (plan/join_memory.JoinMemoryPlan) supplies per-bucket
    grant-derived split row counts; None keeps the fixed
    ``HYPERSPACE_JOIN_SPLIT_ROWS`` threshold.

    ``work`` is an ITERABLE of ``(bucket, lb, rb, lk32_sorted, rk32_sorted,
    lorder, rorder, lk_src, rk_src)`` consumed lazily: each item joins its
    power-of-2 size band as it arrives and a full band wave dispatches its
    probe immediately (jax dispatch is asynchronous), so device probe work
    overlaps the caller's next pair decode. ``banded=None`` resolves from
    ``HYPERSPACE_PIPELINE``: ``0`` keeps the pre-banding behavior — one
    wave at the global pads, no splitting — which the banded path matches
    bit for bit (per-bucket probe results are independent of the pad and of
    the wave composition). Buckets above ``HYPERSPACE_JOIN_SPLIT_ROWS``
    split into left-chunk probe items whose results concatenate exactly.

    src arrays are the ORIGINAL key buffers, whose identity keys the device
    upload cache (sorted/padded/stacked derivations are deterministic per
    source set). Returns {bucket: joined ColumnBatch} or None (caller's
    per-bucket path)."""
    from .join_memory import DeviceLedger

    ledger = DeviceLedger("join_plain")
    try:
        return _batched_plain_join_impl(
            work, residual, session, banded, strategy, ledger
        )
    finally:
        # cancellation/decline unwind: outstanding wave reservations
        # return to the shared device ledger
        ledger.close()


def _batched_plain_join_impl(work, residual, session, banded, strategy,
                             ledger):
    from ..utils.backend import device_healthy, record_device_failure
    from ..utils.rpc_meter import METER, device_get

    if session is None or not session.conf.exec_tpu_enabled:
        return None
    if not device_healthy():
        return None
    if banded is None:
        from .tpu_exec import _pipeline_enabled

        banded = _pipeline_enabled()
    split_default = join_split_rows() if banded else 0
    state: dict = {"dt": None}

    def _dispatch_probe(pads, items):
        pad_l, pad_r = pads
        dt = state["dt"]
        pad_val = np.iinfo(dt).max if dt.kind == "i" else np.float32(np.inf)
        lk_d = _stack_band_keys(items, "lk32", "lk_src", pad_l, dt, pad_val)
        rk_d = _stack_band_keys(items, "rk32", "rk_src", pad_r, dt, pad_val)
        n_l = jnp.asarray(np.array([len(it.lk32) for it in items], np.int32))
        n_r = jnp.asarray(np.array([len(it.rk32) for it in items], np.int32))
        kernel = JOIN_CACHE.get_or_build(
            join_fingerprint("stacked_probe", pads, dt.str),
            lambda: _build_stacked_probe_kernel(pad_l, pad_r),
            "join_stacked_probe",
        )
        note_probe(pad_l, pad_r, len(items) * pad_l)
        METER.record_dispatch()
        with _attr.phase("enqueue"):
            return kernel(lk_d, rk_d, n_r, n_l)

    def _expansion_plan(wave, totals_np, ok_np):
        """Validate one wave's probe totals and dispatch its run
        expansion: (totals list, has_pairs, pair tree|None). Raises
        ``_JoinDeclined`` on int32 pair-count overflow or the skew
        readback guard — data-shaped declines, never breaker events."""
        if not all(bool(o) for o in np.asarray(ok_np)):
            raise _JoinDeclined("pair count overflowed int32")
        totals_arr = np.asarray(totals_np)
        totals = [int(t) for t in totals_arr]
        max_total = max(totals) if totals else 0
        if max_total == 0:
            return totals, False, None
        out_pad = _pow2(max_total)
        padded_bytes = len(wave.items) * out_pad * 8  # two int32 arrays
        actual_bytes = sum(totals) * 8
        if padded_bytes > 32 * 2**20 and padded_bytes > 4 * actual_bytes:
            # heavy skew within one wave: the [W, pow2(max_total)]
            # readback would dwarf the real join output — fall back
            # (banding + splitting make this far rarer than the old
            # global-pad form, where ONE hot bucket padded every bucket)
            raise _JoinDeclined("skewed expansion readback")
        lo_d, offs_d, _t, _ok = wave.rec
        kernel = JOIN_CACHE.get_or_build(
            join_fingerprint("expand", (out_pad,), "int32"),
            lambda out_pad=out_pad: _build_stacked_expand_kernel(out_pad),
            "join_expand",
        )
        note_probe(out_pad, wave.pads[0], len(wave.items) * out_pad)
        METER.record_dispatch()
        with _attr.phase("enqueue"):
            tree = kernel(lo_d, offs_d, jnp.asarray(totals_arr))
        return totals, True, tree

    def _est_probe(pads, items):
        # stacked key uploads, the probe's per-left-slot int32 outputs, and
        # its temporaries
        dt = state["dt"]
        isz = dt.itemsize if dt is not None else 4
        return len(items) * ((pads[0] + pads[1]) * isz + 2 * pads[0] * 4
                             + probe_temp_bytes(*pads))

    def _retire_probe(wave):
        # the spill fetch for one parked admission: probe totals + run
        # expansion for THIS wave only, results straight to the host —
        # per-wave results are independent of when they come back
        with _attr.phase("fold"):
            totals_np, ok_np = device_get((wave.rec[2], wave.rec[3]))
        totals, has_pairs, tree = _expansion_plan(wave, totals_np, ok_np)
        if not has_pairs:
            return totals, None, None
        with _attr.phase("fold"):
            li_np, ri_np = device_get(tree)
        return totals, li_np, ri_np

    sched = _BandScheduler(
        _dispatch_probe, banded, ledger=ledger, estimate=_est_probe,
        retire=_retire_probe,
    )
    total_left = 0
    n_buckets = 0
    n_splits = 0
    place = None
    if banded:
        # skew-aware placement over the deployment's chips (None unless
        # the session spreads join bands): non-banded mode is ONE global
        # wave, nothing to place
        from ..parallel import placement as mesh_placement

        place = mesh_placement.plan_for_strategy(strategy, session)
    # consumption runs OUTSIDE the breaker scope: a host IO error from a
    # streaming caller must propagate as a scan error, not latch the tier
    # off; device errors inside the dispatch are the scheduler's to record
    for w in work:
        dt = w[3].dtype
        if state["dt"] is None:
            state["dt"] = dt
        elif dt != state["dt"]:
            return None  # cross-bucket key-dtype drift: per-bucket path
        total_left += len(w[3])
        n_buckets += 1
        if strategy is not None:
            strategy.observe_actual(w[0], len(w[3]), _batch_data_nbytes(w[1]))
        # per-bucket split threshold: the memory plan's grant-derived (or
        # overridden) row count when one is active, else the fixed knob
        split = (
            strategy.split_rows(w[0])
            if strategy is not None and banded
            else split_default
        )
        for ci, item in enumerate(_split_probe_items(w, split)):
            if item.n_chunks > 1 and item.lo_ofs == 0:
                n_splits += item.n_chunks - 1
            sched.add(item, len(item.lk32), len(item.rk32),
                      place=place.slot_for(w[0], ci) if place else None)
    records = sched.finish()
    if sched.dead is not None or sched.declined is not None or not records:
        return None
    if total_left < _PLAIN_MIN_ROWS:
        return None  # the host searchsorted probe is cheaper at this size
    REGISTRY.counter("pipeline.join.buckets").inc(n_buckets)
    if n_splits:
        REGISTRY.counter("pipeline.join.splits").inc(n_splits)

    try:
        # ---- phase 1: un-spilled waves' totals in ONE blocking fetch ----
        pending = [w for w in records if w.done is None]
        if pending:
            if place is not None:
                # zero-width marker: the probe/expand fetches below gather
                # results from every placed chip in one pass
                with trace.span(
                    "mesh:gather", waves=len(pending),
                    devices=len({w.ordinal for w in pending}),
                ):
                    pass
            with trace.span("join:probe", annotate=False,
                            waves=len(pending)), _attr.phase("fold"):
                fetched = device_get(
                    [(w.rec[2], w.rec[3]) for w in pending]
                )
            # ---- phase 2: per-wave expansion dispatches, ONE fetch ------
            plans = [
                _expansion_plan(w, totals_np, ok_np)
                for w, (totals_np, ok_np) in zip(pending, fetched)
            ]
            pair_trees = [tree for _t, has, tree in plans if has]
            with trace.span("join:fold", annotate=False,
                            waves=len(pair_trees)), _attr.phase("fold"):
                fetched_pairs = device_get(pair_trees) if pair_trees else []
            pair_idx = 0
            for w, (totals, has_pairs, _tree) in zip(pending, plans):
                if has_pairs:
                    li_np, ri_np = fetched_pairs[pair_idx]
                    pair_idx += 1
                    w.done = (totals, li_np, ri_np)
                else:
                    w.done = (totals, None, None)
                w.rec = None
    except _JoinDeclined:
        return None  # overflow / skew readback: per-bucket path
    except Exception as e:
        record_device_failure(e)
        return None
    from ..utils.backend import record_device_success

    record_device_success()  # both fetches landed: probe + expansion clean
    sched.release_reservations()
    if place is not None:
        _log_mesh_exec(session, strategy, place, records, "batched_probe")

    # ---- host: gather columns per bucket (outside the breaker scope) ----
    chunks_by_bucket: dict[int, list] = {}
    info_by_bucket: dict[int, _ProbeItem] = {}
    for wave in records:
        totals, li_np, ri_np = wave.done
        for i, it in enumerate(wave.items):
            info_by_bucket.setdefault(it.bucket, it)
            t = totals[i]
            if t == 0:
                continue
            li = np.asarray(li_np[i, :t]).astype(np.int64) + it.lo_ofs
            ri = np.asarray(ri_np[i, :t]).astype(np.int64)
            chunks_by_bucket.setdefault(it.bucket, []).append(
                (it.lo_ofs, li, ri)
            )
    parts: dict[int, ColumnBatch] = {}
    for b, chunks in chunks_by_bucket.items():
        it = info_by_bucket[b]
        chunks.sort(key=lambda c: c[0])  # chunk order = sorted left order
        li = np.concatenate([c[1] for c in chunks])
        ri = np.concatenate([c[2] for c in chunks])
        if it.lorder is not None:
            li = it.lorder[li]
        if it.rorder is not None:
            ri = it.rorder[ri]
        out = {nm: c.take(li) for nm, c in it.lb.columns.items()}
        out.update({nm: c.take(ri) for nm, c in it.rb.columns.items()})
        joined = ColumnBatch(out)
        for r in residual:
            joined = joined.filter(np.asarray(r.eval(joined).data, dtype=bool))
        parts[b] = joined
    return parts


def try_device_plain_join(
    lb: ColumnBatch,
    rb: ColumnBatch,
    lkeys: Sequence[str],
    rkeys: Sequence[str],
    session,
    l_sorted: bool,
    r_sorted: bool,
) -> Optional[ColumnBatch]:
    """Device execution of the plain (non-aggregated) co-partitioned merge
    join: the probe phase — per-left-row lower/upper bounds over the sorted
    right keys — runs as one device kernel (duplicate right keys welcome);
    the host expands the [start, start+count) runs into pair indices and
    gathers BOTH sides' columns in their original dtypes, so the joined rows
    are bit-identical to the host merge join (including row order: the left
    side is processed in the same sorted order the host path uses).

    Reference parity: the Exchange-free SMJ itself
    (covering/JoinIndexRule.scala:635-720, execution/BucketUnionExec.scala:
    52-121) — the join output consumed by arbitrary downstream operators,
    not only the fused aggregate shape. None -> host merge join.
    """
    from ..utils.backend import device_healthy, record_device_failure

    if len(lkeys) != 1 or session is None or not session.conf.exec_tpu_enabled:
        return None
    if lb.num_rows < _PLAIN_MIN_ROWS or rb.num_rows == 0:
        return None
    lk_col, rk_col = lb.column(lkeys[0]), rb.column(rkeys[0])
    if lk_col.dtype == STRING or rk_col.dtype == STRING:
        return None
    if lk_col.validity is not None or rk_col.validity is not None:
        return None
    lk32, rk32 = _key32(lk_col.data), _key32(rk_col.data)
    if lk32 is None or rk32 is None or lk32.dtype != rk32.dtype:
        return None
    if not device_healthy():
        return None
    try:
        return _device_plain_join_inner(
            lb, rb, lk32, rk32, lk_col.data, rk_col.data, l_sorted, r_sorted
        )
    except Exception as e:
        record_device_failure(e)
        return None


def _sorted_padded_keys(k32: np.ndarray, src: np.ndarray, is_sorted: bool, pad: int):
    """(order|None, device copy of the sorted zero-pad-to-max keys). Both
    the host argsort and the device upload cache on the SOURCE column's
    buffer identity — repeated queries over the same index chunks skip the
    sort, the gather, and the transfer (utils/device_cache): a device hit
    pays O(1) host work."""
    from ..utils.device_cache import DEVICE_CACHE, HOST_DERIVED_CACHE

    pad_val = np.iinfo(k32.dtype).max if k32.dtype.kind == "i" else np.float32(np.inf)

    order = None
    if not is_sorted:
        # exact_key32 preserves order (exact int casts / NaN-free f32), so
        # the derived-key argsort is the source argsort — cacheable by the
        # source buffer's identity
        order = HOST_DERIVED_CACHE.get_or_put(
            src, ("jorder",), lambda: np.argsort(k32, kind="stable")
        )

    def _build():
        sorted_k = k32 if order is None else k32[order]
        out = np.full(pad, pad_val, dtype=k32.dtype)
        out[: len(sorted_k)] = sorted_k
        return jnp.asarray(out)

    keys_d = DEVICE_CACHE.get_or_put(src, ("jkey", pad, is_sorted), _build)
    return order, keys_d


def _device_plain_join_inner(
    lb: ColumnBatch,
    rb: ColumnBatch,
    lk32: np.ndarray,
    rk32: np.ndarray,
    lk_src: np.ndarray,
    rk_src: np.ndarray,
    l_sorted: bool,
    r_sorted: bool,
) -> ColumnBatch:
    from ..ops.join import expand_runs

    n_l, n_r = len(lk32), len(rk32)
    pad_l, pad_r = _pow2(n_l), _pow2(n_r)
    # probe in left-sorted order so the emitted pair order matches the
    # host merge join exactly (host sorts the left side first)
    lorder, lk_d = _sorted_padded_keys(lk32, lk_src, l_sorted, pad_l)
    rorder, rk_d = _sorted_padded_keys(rk32, rk_src, r_sorted, pad_r)

    # the probe body is shape-polymorphic (no baked pads): one fingerprint
    # per key dtype serves every (pad_l, pad_r) size class
    kernel = JOIN_CACHE.get_or_build(
        join_fingerprint("probe", (), str(lk32.dtype)),
        _build_plain_probe_kernel,
        "join_probe",
    )
    from ..utils.rpc_meter import METER as _METER, device_get as _metered_get

    note_probe(pad_l, pad_r, pad_l)
    _METER.record_dispatch()
    with _attr.phase("enqueue"):
        out = kernel(lk_d, rk_d, jnp.int32(n_r))
    lo_d, cnt_d = _metered_get(out)
    starts = np.asarray(lo_d)[:n_l].astype(np.int64)
    counts = np.asarray(cnt_d)[:n_l].astype(np.int64)

    li = np.repeat(np.arange(n_l, dtype=np.int64), counts)
    ri = expand_runs(starts, counts)
    if lorder is not None:
        li = lorder[li]
    if rorder is not None:
        ri = rorder[ri]
    out = {n: c.take(li) for n, c in lb.columns.items()}
    out.update({n: c.take(ri) for n, c in rb.columns.items()})
    return ColumnBatch(out)


def try_host_join_agg(
    agg_plan,
    lb: ColumnBatch,
    rb: ColumnBatch,
    lkeys: Sequence[str],
    rkeys: Sequence[str],
    residual: Sequence[Expr],
    session,
    r_sorted: bool,
) -> Optional[ColumnBatch]:
    """Numpy twin of the device kernel for the same fused shape: probe the
    sorted unique right side once per left row, gather only the referenced
    right columns, and reduce per right key with bincount — the join output
    never materializes on the host path either. Accepts any evaluable
    expression or dtype (except string join keys) but, unlike the device
    kernel's match-count weighting, still requires unique right keys — a
    dup bucket falls through to the full merge join + per_bucket aggregate.
    Used when the device path is off or declines."""
    from .executor import _unwrap_agg

    if len(lkeys) != 1:
        return None
    lk_name, rk_name = lkeys[0], rkeys[0]
    lk_col, rk_col = lb.column(lk_name), rb.column(rk_name)
    if lk_col.dtype == "string" or rk_col.dtype == "string":
        return None  # per-batch dictionary codes are not comparable across sides
    if lk_col.validity is not None or rk_col.validity is not None:
        return None

    group_cols = []
    for g in agg_plan.group_exprs:
        if not isinstance(g, X.Col):
            return None
        nm = g.name
        if nm.lower() in (lk_name.lower(), rk_name.lower()):
            group_cols.append((nm, "key"))
        elif nm in rb.columns:
            group_cols.append((nm, nm))
        else:
            return None
    if not any(src == "key" for _n, src in group_cols):
        return None
    agg_specs = []
    for e in agg_plan.agg_exprs:
        name, agg = _unwrap_agg(e)
        if not isinstance(agg, (X.Sum, X.Avg, X.Min, X.Max, X.Count)):
            return None
        agg_specs.append((name, agg))

    rk = rk_col.data
    rorder = None
    if not r_sorted:
        rorder = np.argsort(rk, kind="stable")
        rk = rk[rorder]
    if len(rk) > 1 and (rk[1:] == rk[:-1]).any():
        return None  # duplicate right keys: per-key gather would drop rows

    lk = lk_col.data

    # Single-pass native fast path for the Q3 hot shape: int64 key, no
    # residual, left-only Sum/Avg/Count inputs — probe + accumulation fuse
    # in C++ with no match-index or mask materialization.
    if not residual and lk.dtype == np.int64 and rk.dtype == np.int64:
        out = _native_probe_agg(agg_specs, agg_plan, lb, rb, rk_name, group_cols, lk, rk, rorder)
        if out is not None:
            return out

    n_r = len(rk)
    pos = np.searchsorted(rk, lk)
    posc = np.clip(pos, 0, n_r - 1)
    found = rk[posc] == lk

    refs: set[str] = set()
    for _nm, agg in agg_specs:
        if not (isinstance(agg, X.Count) and isinstance(agg.child, X.Lit)):
            refs |= agg.child.references()
    for r in residual:
        refs |= r.references()
    env_cols = dict(lb.columns)
    for c in refs - set(lb.columns):
        if c not in rb.columns:
            return None
        col = rb.column(c)
        if rorder is not None:
            col = col.take(rorder)
        env_cols[c] = col.take(posc)  # per-left-row gather (masked by found)
    env = ColumnBatch(env_cols)
    for r in residual:
        v = r.eval(env)
        arr = np.asarray(v.data, dtype=bool)
        if v.validity is not None:
            arr = arr & v.validity
        found = found & arr

    counts = np.bincount(posc[found], minlength=n_r).astype(np.int64)
    keep = counts > 0

    agg_cols: dict[str, Column] = {}
    for nm, agg in agg_specs:
        col = _host_grouped_agg(agg, env, posc, found, counts, n_r, keep)
        if col is None:
            return None  # e.g. min/max over a string column
        agg_cols[nm] = col

    out_cols: dict[str, Column] = {}
    for nm, src in group_cols:
        col = rb.column(rk_name if src == "key" else src)
        if rorder is not None:
            col = col.take(rorder)
        out_cols[nm] = col.take(np.flatnonzero(keep))
    out_cols.update(agg_cols)
    return ColumnBatch(out_cols)


def _native_probe_agg(
    agg_specs, agg_plan, lb, rb, rk_name, group_cols, lk, rk, rorder
) -> Optional[ColumnBatch]:
    """C++ fused probe+accumulate (native.probe_agg_i64) for Sum/Avg/Count
    aggregates whose inputs come from the left side only; None -> numpy."""
    from .. import native

    # validate the whole spec list cheaply BEFORE any full-column eval
    for _nm, agg in agg_specs:
        if isinstance(agg, X.Count) and isinstance(agg.child, X.Lit):
            continue
        if not isinstance(agg, (X.Sum, X.Avg)):
            return None
        if not agg.child.references() <= set(lb.columns):
            return None
    specs = []
    weights: list[np.ndarray] = []
    for nm, agg in agg_specs:
        if isinstance(agg, X.Count) and isinstance(agg.child, X.Lit):
            specs.append((nm, "count", -1))
            continue
        v = agg.child.eval(lb)
        if v.validity is not None or v.dtype == STRING:
            return None
        specs.append((nm, agg.func, len(weights)))
        weights.append(v.data.astype(np.float64, copy=False))
    out = native.probe_agg_i64(lk, rk, weights)
    if out is None:
        return None
    counts, sums = out
    keep = counts > 0
    out_cols: dict[str, Column] = {}
    for nm, src in group_cols:
        col = rb.column(rk_name if src == "key" else src)
        if rorder is not None:
            col = col.take(rorder)
        out_cols[nm] = col.take(np.flatnonzero(keep))
    schema = agg_plan.schema
    kept_counts = counts[keep]
    for nm, kind, wi in specs:
        if kind == "count":
            out_cols[nm] = Column(kept_counts, "int64")
        elif kind == "avg":
            out_cols[nm] = Column(
                sums[wi][keep] / np.maximum(kept_counts, 1), "float64"
            )
        else:
            s = sums[wi][keep]
            f = schema.field(nm)
            if f.dtype.startswith("int"):
                out_cols[nm] = Column(
                    s.astype(np.int64).astype(np.dtype(f.dtype)), f.dtype
                )
            else:
                out_cols[nm] = Column(s, "float64")
    return ColumnBatch(out_cols)


def _host_grouped_agg(agg, env, posc, found, counts, n_r, keep):
    """One aggregate over the fused probe (mirrors executor._grouped_agg
    semantics: Count counts non-NULL inputs, zero-valid groups are NULL)."""
    if isinstance(agg, X.Count) and isinstance(agg.child, X.Lit):
        return Column(counts[keep], "int64")
    vals = agg.child.eval(env)
    if vals.dtype == STRING:
        return None
    mask = found if vals.validity is None else (found & vals.validity)
    seg = posc[mask]
    counts_valid = np.bincount(seg, minlength=n_r).astype(np.int64)
    if isinstance(agg, X.Count):
        return Column(counts_valid[keep], "int64")
    kept_valid = counts_valid[keep]
    group_validity = None if (kept_valid > 0).all() else kept_valid > 0
    data = vals.data[mask]
    if isinstance(agg, X.Sum):
        s = np.bincount(seg, weights=data.astype(np.float64), minlength=n_r)
        if vals.data.dtype.kind == "i":
            return Column(s[keep].astype(np.int64), "int64", group_validity)
        return Column(s[keep], "float64", group_validity)
    if isinstance(agg, X.Avg):
        s = np.bincount(seg, weights=data.astype(np.float64), minlength=n_r)
        return Column(
            s[keep] / np.maximum(kept_valid, 1), "float64", group_validity
        )
    if isinstance(agg, (X.Min, X.Max)):
        is_min = isinstance(agg, X.Min)
        if data.dtype.kind == "f":
            init = np.inf if is_min else -np.inf
        else:
            info = np.iinfo(data.dtype)
            init = info.max if is_min else info.min
        out = np.full(n_r, init, dtype=data.dtype)
        (np.minimum if is_min else np.maximum).at(out, seg, data)
        return Column(out[keep], str(vals.dtype), group_validity)
    return None


def _build_kernel(agg_specs, residual, left_names, right_names, pad_r, dup=False):
    """jit kernel: probe + gather + masked segment reductions. Rows whose
    probe misses (or fails a residual) land in the dump segment pad_r.
    With dup=True (duplicate right keys, left-only aggregates) every left
    row's contribution is weighted by its match count — the upper-bound
    probe replaces the per-pair expansion entirely."""
    from .tpu_exec import _extreme, compile_expr

    def kernel(dev_in):
        lk, rk, mask, n_r = dev_in["lk"], dev_in["rk"], dev_in["mask"], dev_in["n_r"]
        lo, hi = probe_ranks(rk, lk, n_r)
        posc = jnp.clip(lo, 0, pad_r - 1)
        found = mask & (hi > lo)
        env = {c: dev_in["l_" + c] for c in left_names}
        env.update({c: dev_in["r_" + c][posc] for c in right_names})
        for r in residual:
            found = found & compile_expr(r, env)
        if dup:
            w = jnp.where(found, hi - lo, 0).astype(jnp.int32)
        else:
            w = found.astype(jnp.int32)
        seg = jnp.where(found, posc, pad_r)
        counts = jax.ops.segment_sum(w, seg, num_segments=pad_r + 1)[:pad_r]
        out = []
        for kind, child in agg_specs:
            if kind == "count":
                out.append(counts)
                continue
            vals = compile_expr(child, env)
            if kind == "sum":
                vals = jnp.where(found, vals * w, 0)
                out.append(
                    jax.ops.segment_sum(vals, seg, num_segments=pad_r + 1)[:pad_r]
                )
            elif kind == "avg":  # the sum only: the host divides
                vals = jnp.where(found, vals * w, 0)
                out.append(
                    jax.ops.segment_sum(vals, seg, num_segments=pad_r + 1)[:pad_r]
                )
            elif kind == "min":
                out.append(
                    jax.ops.segment_min(
                        jnp.where(found, vals, _extreme(vals.dtype, True)),
                        seg,
                        num_segments=pad_r + 1,
                    )[:pad_r]
                )
            elif kind == "max":
                out.append(
                    jax.ops.segment_max(
                        jnp.where(found, vals, _extreme(vals.dtype, False)),
                        seg,
                        num_segments=pad_r + 1,
                    )[:pad_r]
                )
        return counts, tuple(out)

    return jax.jit(kernel)  # hslint: HS201 — builder runs via JOIN_CACHE.get_or_build


# Back-compat aliases: the per-family BoundedLRUs merged into the one
# process-wide KernelCache (plan/kernel_cache.JOIN_CACHE) so join kernels
# show up in cache.kernel_join.* counters and compile:join_* spans like
# every other kernel family. Existing callers/tests that clear or len() the
# old names keep working against the shared cache.
_CACHE = JOIN_CACHE
_STACK_CACHE = JOIN_CACHE
_PLAIN_CACHE = JOIN_CACHE
