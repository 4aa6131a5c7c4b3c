"""The device selection route (``Filter ← FileScan``, rows out) against the
host executor, and literals as kernel arguments: one kernel build per
predicate shape for the selection, fused and grouped kernels."""

import os
import threading

import numpy as np
import pytest

from hyperspace_tpu import constants as C
from hyperspace_tpu import Hyperspace
from hyperspace_tpu.columnar import io as cio
from hyperspace_tpu.columnar.table import ColumnBatch
from hyperspace_tpu.models.covering import CoveringIndexConfig
from hyperspace_tpu.plan import Count, Sum, col, lit
from hyperspace_tpu.plan import tpu_exec
from hyperspace_tpu.telemetry import plan_stats
from hyperspace_tpu.telemetry.metrics import REGISTRY

N = 6000
WIDE = 2**40
MODES = ["AIR", "RAIL", "SHIP", "TRUCK"]


def _table(seed=7):
    rng = np.random.default_rng(seed)
    return {
        "k": np.sort(rng.integers(0, N // 3, N)).tolist(),
        "w": (rng.integers(0, 50, N) + WIDE).tolist(),
        "s": [MODES[i] for i in rng.integers(0, len(MODES), N)],
        "x": rng.uniform(0, 100, N).tolist(),
        "d": rng.integers(8000, 9000, N).tolist(),
        "b": (rng.random(N) < 0.5).tolist(),
    }


@pytest.fixture()
def lake(tmp_session, tmp_path):
    """(session, path): a parquet table over two files, with a covering
    index on ``k`` that holds every column; hyperspace on."""
    data = _table()
    half = N // 2
    for i, (lo, hi) in enumerate(((0, half), (half, N))):
        part = {c: v[lo:hi] for c, v in data.items()}
        cio.write_parquet(ColumnBatch.from_pydict(part),
                          str(tmp_path / "t" / f"p{i}.parquet"))
    path = str(tmp_path / "t")
    tmp_session.set_conf("hyperspace.index.numBuckets", 4)
    Hyperspace(tmp_session).create_index(
        tmp_session.read.parquet(path),
        CoveringIndexConfig("ix_lake", ["k"], ["w", "s", "x", "d", "b"]))
    tmp_session.enable_hyperspace()
    return tmp_session, path


def _on_index(pred):
    """``pred`` with a conjunct on the index's key, so FilterIndexRule
    serves it from the index."""
    return (col("k") >= 0) & pred


def _both(session, make):
    """(host answer, device answer, device routes) of one query."""
    session.set_conf(C.EXEC_TPU_ENABLED, False)
    host = make().to_pydict()
    session.set_conf(C.EXEC_TPU_ENABLED, True)
    with plan_stats.collect_scope() as stats:
        dev = make().collect().to_pydict()
    routes = {ns.route for ns in stats.nodes.values()
              if ns.executed and ns.route != "host"}
    return host, dev, routes


PREDICATES = {
    "eq": col("k") == 17,
    "range": (col("d") >= 8100) & (col("d") < 8110),
    "in": col("k").isin([3, 99, 1001, 1999]),
    "and_or_not": ((col("k") < 40) | (col("k") > 1980)) & ~(col("d") < 8500),
    "string_eq": (col("s") == "RAIL") & (col("k") < 30),
    "string_in_ne": col("s").isin(["AIR", "SHIP"]) & (col("s") != "AIR")
    & (col("k") > 1990),
    "wide_eq": (col("w") == WIDE + 7) & (col("k") < 300),
    "wide_range": (col("w") > WIDE + 47) & (col("d") < 8200),
    "bool_col": col("b") & (col("k") == 5),
    "empty": col("d") > 9000,  # no row matches; no bucket prunes away
    "absent_string": col("s") == "MAIL",
    "all_rows": col("k") >= 0,
    "over_cap": col("d") < 8400,
}


@pytest.mark.parametrize("name", sorted(PREDICATES))
def test_selection_equals_the_host_row_for_row(lake, name):
    session, path = lake
    pred = PREDICATES[name]
    over0 = REGISTRY.counter("select.cap_overflow").value
    host, dev, routes = _both(
        session, lambda: session.read.parquet(path).filter(_on_index(pred))
        .select("k", "x", "s", "w"))
    assert dev == host  # same rows, same order, float64 values unchanged
    assert routes == {"device"}
    over = REGISTRY.counter("select.cap_overflow").value - over0
    assert over == (len(host["k"]) > tpu_exec.SELECT_CAP)
    if name == "over_cap":
        assert len(host["k"]) > tpu_exec.SELECT_CAP
    if name == "all_rows":
        assert len(host["k"]) == N


def test_selection_counts_rows_scanned_and_matched(lake):
    session, path = lake
    session.set_conf(C.EXEC_TPU_ENABLED, True)
    scanned0 = REGISTRY.counter("select.rows_scanned").value
    matched0 = REGISTRY.counter("select.rows_matched").value
    out = session.read.parquet(path).filter(col("k") == 17).collect()
    scanned = REGISTRY.counter("select.rows_scanned").value - scanned0
    assert 0 < scanned < N  # the key's bucket, not the table
    assert REGISTRY.counter("select.rows_matched").value - matched0 == out.num_rows


def test_source_scans_stay_on_the_host(lake):
    """Only index data stays resident on the device; a filter over the
    source files runs on the host."""
    session, path = lake
    session.disable_hyperspace()
    host, dev, routes = _both(
        session, lambda: session.read.parquet(path).filter(col("k") == 17))
    assert dev == host and routes == set()


@pytest.mark.parametrize("pred", [col("x") < 50.0, col("d") < 8500.5,
                                  (col("d") + 1) < 8500, col("k"),
                                  col("k") & col("d"), ~col("k")])
def test_inexact_predicates_stay_on_the_host(lake, pred):
    """Floats (32 bits on the device), arithmetic (32-bit wrap) and ints
    read as truth values (bitwise on the device) could decide a row
    differently: such selections run on the host."""
    session, path = lake
    host, dev, routes = _both(
        session, lambda: session.read.parquet(path).filter(_on_index(pred)))
    assert dev == host and routes == set()


def test_nullable_predicate_column_falls_back(tmp_session, tmp_path):
    data = {"k": list(range(300)), "v": [1, None, 3, 4, None, 6] * 50}
    cio.write_parquet(ColumnBatch.from_pydict(data),
                      str(tmp_path / "n" / "p.parquet"))
    path = str(tmp_path / "n")
    Hyperspace(tmp_session).create_index(
        tmp_session.read.parquet(path), CoveringIndexConfig("ix_n", ["k"], ["v"]))
    tmp_session.enable_hyperspace()
    host, dev, routes = _both(
        tmp_session, lambda: tmp_session.read.parquet(path)
        .filter((col("k") >= 0) & (col("v") > 2)))
    assert dev == host and routes == set()


def test_index_scan_selection_is_exact_and_on_the_device(lake):
    """FilterIndexRule's rewrite: a point lookup over a covering index."""
    session, path = lake
    present = set(_table()["k"])
    for k in (0, 17, 640, N // 3 - 1, 5000):
        host, dev, routes = _both(
            session,
            lambda: session.read.parquet(path).filter(col("k") == k)
            .select("k", "d", "x", "s", "w"))
        assert dev == host
        # a key no bucket holds prunes the scan to nothing: no device work
        assert routes == ({"device"} if k in present else set())


def _builds(session, queries):
    """Kernel builds over ``queries`` (callables), from a cold cache."""
    session.set_conf(C.EXEC_TPU_ENABLED, True)
    tpu_exec._KERNEL_CACHE.clear()
    r0 = REGISTRY.counter("kernel.retrace").value
    for q in queries:
        q().collect()
    return REGISTRY.counter("kernel.retrace").value - r0


def test_64_literals_of_one_shape_build_one_kernel(lake):
    session, path = lake

    def read():
        return session.read.parquet(path)

    keys = [int(k) for k in np.random.default_rng(1).integers(0, N // 3, 64)]
    assert _builds(session, [
        (lambda k=k: read().filter(col("k") == k).select("k", "x"))
        for k in keys]) == 1
    session.disable_hyperspace()  # the aggregates over the source files
    assert _builds(session, [
        (lambda lo=lo: read().filter((col("d") >= lo) & (col("d") < lo + 50)
                                     & (col("x") < lo / 100.0))
         .agg(Sum(col("x") * (lit(1.0) - col("x"))).alias("s"),
              Count(lit(1)).alias("n")))
        for lo in range(8000, 8640, 10)]) == 1
    assert _builds(session, [
        (lambda v=v: read().filter((col("d") < v) & (col("s") != MODES[v % 4]))
         .group_by("s").agg(Sum(col("x")).alias("sx"), Count(lit(1)).alias("n")))
        for v in range(8200, 8840, 10)]) == 1


def test_int_and_float_literals_key_apart(lake):
    session, path = lake
    session.disable_hyperspace()

    def count(v):
        return lambda: (session.read.parquet(path).filter(col("d") > v)
                        .agg(Count(lit(1)).alias("n")))

    assert _builds(session, [count(8500), count(8600)]) == 1
    assert _builds(session, [count(8500), count(8500.5)]) == 2


def test_slotted_expressions_hold_the_shape_not_the_values():
    a, _, _, la = tpu_exec.slot_literals(
        (col("d") >= 8766) & (col("x") < 24) & col("k").isin([1, 2]),
        col_kinds={"d": "i", "x": "f", "k": "i"})
    b, _, _, lb = tpu_exec.slot_literals(
        (col("d") >= 9131) & (col("x") < 7) & col("k").isin([5, 6]),
        col_kinds={"d": "i", "x": "f", "k": "i"})
    assert repr(a) == repr(b) and list(la) != list(lb)
    assert "8766" not in repr(a) and "?i>f" in repr(a)  # 24 meets a float32
    # one int32 vector: 8766, the float32 bits of 24.0, then 1 and 2
    assert la.dtype == np.int32 and list(la) == [
        8766, int(np.float32(24).view(np.int32)), 1, 2]
    c, _, _, _ = tpu_exec.slot_literals(col("k").isin([1, 2, 3]),
                                        col_kinds={"k": "i"})
    assert repr(c) != repr(tpu_exec.slot_literals(
        col("k").isin([1, 2]), col_kinds={"k": "i"})[0])


def test_served_point_lookups_with_four_clients_agree_with_hyperspace_off(lake):
    from hyperspace_tpu.serve.scheduler import QueryScheduler

    session, path = lake

    def lookup(k):
        return (session.read.parquet(path).filter(col("k") == k)
                .select("k", "d", "x", "s"))

    keys = [int(k) for k in np.random.default_rng(3).integers(0, N // 3, 48)]
    session.disable_hyperspace()
    session.set_conf(C.EXEC_TPU_ENABLED, False)
    want = {k: lookup(k).to_pydict() for k in keys}
    session.enable_hyperspace()
    session.set_conf(C.EXEC_TPU_ENABLED, True)
    got, errors = {}, []
    sched = QueryScheduler(max_concurrent=4)
    try:
        def client(i):
            try:
                for k in keys[i::4]:
                    h = sched.submit_query(lookup(k), label="point",
                                           allow_approx=False)
                    got[k] = h.result(timeout=120).to_pydict()
            except Exception as e:  # reported below
                errors.append(e)

        threads = [threading.Thread(target=client, args=(i,)) for i in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    finally:
        sched.shutdown()
    assert not errors
    assert got == want


def test_a_device_failure_degrades_to_the_host_through_the_breaker(
        lake, monkeypatch):
    from hyperspace_tpu.utils import backend as B

    session, path = lake

    def lookup():
        return session.read.parquet(path).filter(col("k") == 17).select("k", "x")

    session.set_conf(C.EXEC_TPU_ENABLED, False)
    want = lookup().to_pydict()

    def boom(*a, **k):
        raise RuntimeError("device lost")

    monkeypatch.setattr(tpu_exec, "_select_once", boom)
    session.set_conf(C.EXEC_TPU_ENABLED, True)
    try:
        monkeypatch.setenv("HYPERSPACE_DEVICE_STRICT", "1")
        with pytest.raises(RuntimeError, match="device lost"):
            lookup().collect()
        monkeypatch.delenv("HYPERSPACE_DEVICE_STRICT")
        degrades0 = REGISTRY.counter("device.degrades").value
        assert lookup().to_pydict() == want
        assert REGISTRY.counter("device.degrades").value == degrades0 + 1
        assert not B.device_healthy()
        assert lookup().to_pydict() == want  # the open breaker skips the device
    finally:
        B._reset_for_testing()
