"""Each group's first passing row from the Pallas histogram pass (interpret
mode on the CPU), and the count of XLA scatters in a grouped kernel."""

import numpy as np
import pytest

import jax.numpy as jnp

from hyperspace_tpu import constants as C
from hyperspace_tpu.columnar import io as cio
from hyperspace_tpu.columnar.table import ColumnBatch
from hyperspace_tpu.ops.pallas_kernels import filter_grouped_multi_sum
from hyperspace_tpu.plan import Avg, Count, Max, Min, Sum, col, lit
from hyperspace_tpu.plan import tpu_exec

_NO_ROW = 2**31 - 1


@pytest.mark.parametrize("num_groups", [4, 6, 16])
def test_first_rows_equal_the_segment_min(num_groups):
    """The kernel's first rows equal ``_first_masked_rows`` over every
    group: a group whose first occurrence fails the predicate, a group with
    no passing row, pad rows at the tail, several grid steps."""
    rng = np.random.default_rng(num_groups)
    n, padded = 5 * 1024 + 300, 8 * 1024
    gids = np.full(padded, num_groups - 1, np.int32)  # pad rows: dump segment
    gids[:n] = rng.integers(0, num_groups, n)
    mask = np.zeros(padded, bool)
    mask[:n] = rng.random(n) < 0.5
    first_of_1 = int(np.argmax(gids == 1))
    mask[first_of_1] = False  # group 1's first occurrence fails
    mask[gids == 2] = False  # group 2 has no passing row
    assert len(np.unique(gids[:n])) == num_groups  # every group present
    x = rng.uniform(0, 10, padded).astype(np.float32)

    sums, counts, first = filter_grouped_multi_sum(
        jnp.asarray(mask), jnp.asarray(gids), (jnp.asarray(x),), num_groups
    )
    want = tpu_exec._first_masked_rows(
        jnp.asarray(mask),
        jnp.where(jnp.asarray(mask), jnp.asarray(gids), num_groups - 1),
        num_groups,
    )
    np.testing.assert_array_equal(np.asarray(first), np.asarray(want))
    assert int(first[2]) == _NO_ROW
    assert int(first[1]) > first_of_1
    np.testing.assert_array_equal(
        np.asarray(counts), np.bincount(gids[mask], minlength=num_groups)
    )
    assert len(sums) == 1


def test_first_rows_of_no_rows():
    sums, counts, first = filter_grouped_multi_sum(
        jnp.zeros(0, bool), jnp.zeros(0, jnp.int32), (), 4
    )
    assert sums == () and np.asarray(counts).tolist() == [0] * 4
    assert np.asarray(first).tolist() == [_NO_ROW] * 4


def test_forced_pallas_grouped_rows_keep_the_host_order(tmp_session, tmp_path, monkeypatch):
    """The predicate drops each group's first row, so the groups' first
    passing rows come in the reverse of their first occurrences: the
    device's rows and their order are the host tier's, from a kernel with
    no XLA scatter."""
    rng = np.random.default_rng(30)
    n = 9000
    g = rng.choice(["a", "b", "c", "d"], n)
    d = rng.integers(0, 100, n)
    g[:8] = ["a", "b", "c", "d", "d", "c", "b", "a"]
    d[:4], d[4:8] = 99, 0
    cio.write_parquet(
        ColumnBatch.from_pydict(
            {"g": g.tolist(), "d": d.tolist(), "x": rng.uniform(0, 10, n).tolist()}
        ),
        str(tmp_path / "fr" / "p.parquet"),
    )
    df = tmp_session.read.parquet(str(tmp_path / "fr"))
    q = lambda: (
        df.filter(col("d") < 60)
        .select("g", "x")
        .group_by("g")
        .agg(Sum(col("x")).alias("s"), Count(lit(1)).alias("n"))
        .to_pydict()
    )
    host = q()
    monkeypatch.setenv("HYPERSPACE_FORCE_PALLAS", "1")
    tpu_exec._KERNEL_CACHE.clear()
    tmp_session.set_conf(C.EXEC_TPU_ENABLED, True)
    try:
        dev = q()
        grouped = [
            tpu_exec._KERNEL_CACHE.get(k) for k in tpu_exec._KERNEL_CACHE
            if k[0] == "grouped"
        ]
    finally:
        tmp_session.set_conf(C.EXEC_TPU_ENABLED, False)
        tpu_exec._KERNEL_CACHE.clear()
    assert host["g"] == ["d", "c", "b", "a"]
    assert dev["g"] == host["g"] and dev["n"] == host["n"]
    assert np.allclose(dev["s"], host["s"], rtol=1e-5)
    assert [k.scatters for k in grouped] == [0]


_F, _I = col("x"), col("i")


@pytest.mark.parametrize(
    "pallas, seg_pad, aggs, scatters",
    [
        (True, 8, [("sum", _F), ("avg", _F), ("count", None)], 0),
        (False, 8, [("sum", _F), ("avg", _F), ("count", None)], 4),
        (True, 32, [("sum", _F), ("count", None)], 3),
        (False, 8, [("min", _F), ("max", _F)], 4),
        (False, 8, [("sum", _I), ("count", None)], 6),
        (True, 8, [("sum", _I), ("count", None)], 6),  # the int-sum fallback
    ],
)
def test_grouped_scatters_by_route(monkeypatch, pallas, seg_pad, aggs, scatters):
    """0 on the Pallas route; the generic route's first rows and counts, one
    per float sum, avg, min or max, and four per int sum."""
    monkeypatch.setattr(tpu_exec, "_pallas_route", lambda: pallas)
    pred, proj, agg_list, lits = tpu_exec.slot_literals(
        col("d") < 60, (), aggs, {"d": "i", "x": "f", "i": "i"}
    )
    kernel = tpu_exec._build_grouped_kernel(pred, proj, agg_list, seg_pad)
    rng = np.random.default_rng(seg_pad)
    n = 2048
    cols = {
        "d": jnp.asarray(rng.integers(0, 100, n), jnp.int32),
        "x": jnp.asarray(rng.uniform(0, 1, n), jnp.float32),
        "i": jnp.asarray(rng.integers(0, 9, n), jnp.int32),
    }
    args = (
        cols, jnp.asarray(rng.integers(0, seg_pad, n), jnp.int32),
        jnp.ones(n, bool), lits,
    )
    kernel(*args)
    assert tpu_exec._grouped_scatters(kernel, args) == scatters
    assert kernel.scatters == scatters


def test_grouped_dispatch_counts_its_scatters(tmp_session, tmp_path):
    """``kernel.grouped.scatters`` rises by the kernel's scatters at every
    grouped dispatch: a generic kernel with a float sum, a min and a count."""
    from hyperspace_tpu.telemetry.metrics import REGISTRY

    rng = np.random.default_rng(31)
    n = 3000
    cio.write_parquet(
        ColumnBatch.from_pydict(
            {"g": rng.integers(0, 40, n).tolist(), "x": rng.uniform(0, 10, n).tolist()}
        ),
        str(tmp_path / "gs" / "p.parquet"),
    )
    df = tmp_session.read.parquet(str(tmp_path / "gs"))
    q = lambda: (
        df.group_by("g")
        .agg(Sum(col("x")).alias("s"), Min(col("x")).alias("m"),
             Max(col("x")).alias("M"), Avg(col("x")).alias("a"),
             Count(lit(1)).alias("n"))
        .to_pydict()
    )
    counter = REGISTRY.counter("kernel.grouped.scatters")
    tpu_exec._KERNEL_CACHE.clear()
    tmp_session.set_conf(C.EXEC_TPU_ENABLED, True)
    try:
        before = counter.value
        q()
        q()
        after = counter.value
    finally:
        tmp_session.set_conf(C.EXEC_TPU_ENABLED, False)
        tpu_exec._KERNEL_CACHE.clear()
    assert after - before == 2 * (2 + 4)
