"""The run route of the grouped kernel: where every group is one contiguous
run of rows (a covering index's bucketed, sorted key), counts, first rows,
sums, averages, minima and maxima come from segmented scans read at the
runs' last rows, with no XLA scatter. Compared with the scatter route and
with a float64 numpy reference."""

import numpy as np
import pytest

import jax.numpy as jnp

from hyperspace_tpu import constants as C
from hyperspace_tpu.columnar import io as cio
from hyperspace_tpu.columnar.table import Column, ColumnBatch
from hyperspace_tpu.ops.runs import run_reduce, run_starts, segmented_scan
from hyperspace_tpu.plan import Avg, Count, Max, Min, Sum, col, lit
from hyperspace_tpu.plan import tpu_exec
from hyperspace_tpu.telemetry.metrics import REGISTRY

_NO_ROW = 2**31 - 1
_X = col("x")
_KINDS = {"d": "i", "x": "f"}


def _counter(name: str) -> float:
    m = REGISTRY.get(name)
    return 0.0 if m is None else m.value


def _sizes(rng, num_groups: int):
    """Group sizes from 1 row up: every third group a single row."""
    sizes = rng.integers(1, 40, num_groups)
    sizes[::3] = 1
    return sizes


def _runs(rng, num_groups: int, padded: int):
    """Group ids of ``num_groups`` runs in row order, pad rows in the dump
    segment, and each group's last row."""
    sizes = _sizes(rng, num_groups)
    n = int(sizes.sum())
    assert n <= padded
    seg_pad = 1 << max(4, int(np.ceil(np.log2(num_groups + 1))))
    gids = np.full(padded, seg_pad - 1, np.int32)
    gids[:n] = np.repeat(np.arange(num_groups), sizes)
    return n, seg_pad, gids, tpu_exec._last_rows(gids[:n], seg_pad)


def _kernel_args(rng, n, padded, gids):
    cols = {
        "d": jnp.asarray(rng.integers(0, 100, padded), jnp.int32),
        "x": jnp.asarray(rng.uniform(-5, 10, padded), jnp.float32),
    }
    mask = np.zeros(padded, bool)
    mask[:n] = True
    return cols, jnp.asarray(gids), jnp.asarray(mask)


def _reference(kinds, gids, x, keep, num_groups):
    """Per group, in float64: the count, the first kept row, and each
    aggregate over the kept rows."""
    counts = np.bincount(gids[keep], minlength=num_groups)[:num_groups]
    first = np.full(num_groups, _NO_ROW)
    rows = np.flatnonzero(keep)
    np.minimum.at(first, gids[rows], rows)
    out = []
    for kind in kinds:
        if kind == "count":
            out.append(counts)
            continue
        v = x.astype(np.float64)[keep]
        g = gids[keep]
        if kind in ("sum", "avg"):
            out.append(np.bincount(g, weights=v, minlength=num_groups)[:num_groups])
        else:
            acc = np.full(num_groups, np.inf if kind == "min" else -np.inf)
            (np.minimum if kind == "min" else np.maximum).at(acc, g, v)
            out.append(acc)
    return counts, first, out


@pytest.mark.parametrize("pred", [None, col("d") < 60], ids=["all", "pred"])
@pytest.mark.parametrize(
    "kinds",
    [("count",), ("sum", "count"), ("avg",), ("min", "max"),
     ("sum", "avg", "min", "max", "count")],
)
def test_the_run_route_equals_the_scatter_route_and_float64(monkeypatch, pred, kinds):
    """Counts and first rows exactly; floats within float32 rounding. Groups
    of one row, groups with no passing row, pad rows in the dump segment,
    and empty padding groups between the last group and the dump segment."""
    monkeypatch.setattr(tpu_exec, "_pallas_route", lambda: False)
    rng = np.random.default_rng(len(kinds) + (pred is None))
    num_groups, padded = 300, 1 << 14
    n, seg_pad, gids, last_rows = _runs(rng, num_groups, padded)
    assert seg_pad - 1 > num_groups  # empty padding groups exist
    aggs = [(k, None if k == "count" else _X) for k in kinds]
    p, proj, agg_list, lits = tpu_exec.slot_literals(pred, (), aggs, _KINDS)
    cols, gids_d, mask = _kernel_args(rng, n, padded, gids)
    runs = tpu_exec._build_grouped_kernel(p, proj, agg_list, seg_pad, True)
    scatter = tpu_exec._build_grouped_kernel(p, proj, agg_list, seg_pad)
    args = (cols, gids_d, mask, lits)
    r_counts, r_first, r_out = runs(*args, jnp.asarray(last_rows))
    s_counts, s_first, s_out = scatter(*args)
    assert tpu_exec._grouped_scatters(runs, args + (jnp.asarray(last_rows),)) == 0
    assert tpu_exec._grouped_scatters(scatter, args) > 0

    keep = np.asarray(mask).copy()
    if pred is not None:
        keep &= np.asarray(cols["d"]) < 60
    counts, first, want = _reference(
        kinds, gids, np.asarray(cols["x"]), keep, num_groups
    )
    real = slice(0, seg_pad - 1)  # every group but the dump segment
    np.testing.assert_array_equal(np.asarray(r_counts)[real], np.asarray(s_counts)[real])
    np.testing.assert_array_equal(np.asarray(r_first)[real], np.asarray(s_first)[real])
    np.testing.assert_array_equal(np.asarray(r_counts)[:num_groups], counts)
    np.testing.assert_array_equal(np.asarray(r_first)[:num_groups], first)
    assert (np.asarray(r_counts)[num_groups:real.stop] == 0).all()
    assert (np.asarray(r_first)[num_groups:real.stop] == _NO_ROW).all()
    if pred is not None:
        assert (counts == 0).any()  # a group with no passing row
    for kind, got, other, ref in zip(kinds, r_out, s_out, want):
        got, other = np.asarray(got), np.asarray(other)
        if kind == "count":
            np.testing.assert_array_equal(got[real], other[real])
            continue
        has = counts > 0
        np.testing.assert_allclose(got[:num_groups][has], ref[has], rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(got[real], other[real], rtol=1e-5, atol=1e-5)


def test_one_group_of_a_million_rows_sums_within_tree_rounding(monkeypatch):
    """2^20 rows in one run: the sum keeps float32 tree rounding, 1e-5
    relative at most, where one running sum would not."""
    monkeypatch.setattr(tpu_exec, "_pallas_route", lambda: False)
    rng = np.random.default_rng(20)
    padded = 1 << 21
    n = 1 << 20
    gids = np.full(padded, 15, np.int32)
    gids[:n] = 0
    last_rows = tpu_exec._last_rows(gids[:n], 16)
    assert last_rows.tolist() == [n - 1] + [-1] * 15
    p, proj, agg_list, lits = tpu_exec.slot_literals(
        None, (), [("sum", _X), ("avg", _X), ("count", None)], _KINDS
    )
    cols, gids_d, mask = _kernel_args(rng, n, padded, gids)
    x = np.asarray(cols["x"]).copy()
    x[:n] = rng.uniform(0, 10, n).astype(np.float32)
    cols["x"] = jnp.asarray(x)
    kernel = tpu_exec._build_grouped_kernel(p, proj, agg_list, 16, True)
    counts, first, (s, a, c) = kernel(cols, gids_d, mask, lits, jnp.asarray(last_rows))
    want = x[:n].astype(np.float64).sum()
    assert int(counts[0]) == n and int(first[0]) == 0 and int(c[0]) == n
    assert abs(float(s[0]) - want) / want <= 1e-5
    assert float(a[0]) == float(s[0])  # the host divides an average's sum


@pytest.mark.parametrize("n", [1, 100, 128, 1024, 1 << 15])
@pytest.mark.parametrize("op", ["add", "min", "max"])
def test_the_segmented_scan_equals_a_loop(n, op):
    """Every prefix of every run, against the plain loop, at sizes within
    one line, of whole lines and of several levels."""
    rng = np.random.default_rng(n)
    starts = rng.random(n) < 0.05
    starts[0] = True
    if op == "add":
        v = rng.integers(-50, 50, n).astype(np.int32)  # exact, so order-free
        fn, identity, step = jnp.add, 0, lambda a, b: a + b
    else:
        v = rng.uniform(-1, 1, n).astype(np.float32)
        fn = jnp.minimum if op == "min" else jnp.maximum
        identity = np.inf if op == "min" else -np.inf
        step = min if op == "min" else max
    got = np.asarray(segmented_scan(jnp.asarray(v), jnp.asarray(starts), fn, identity))
    want = np.empty_like(v)
    acc = None
    for i in range(n):
        acc = v[i] if starts[i] else step(acc, v[i])
        want[i] = acc
    np.testing.assert_array_equal(got, want)


def test_run_reduce_reads_each_group_at_its_last_row():
    gids = jnp.asarray([0, 0, 1, 2, 2, 2, 3, 3], jnp.int32)
    v = jnp.asarray([1, 2, 3, 4, 5, 6, 7, 8], jnp.int32)
    last = jnp.asarray([1, 2, 5, 7, -1, -1], jnp.int32)
    got = run_reduce(v, run_starts(gids), last, jnp.add, 0)
    assert np.asarray(got).tolist() == [3, 3, 15, 15, 0, 0]


def _key_batch(keys):
    return ColumnBatch({"k": Column(np.asarray(keys, np.int64), "int64")})


def test_the_grouping_records_whether_its_groups_are_runs():
    """Runs across chunks: each group's last row on the device, cached
    beside its group ids; a key split into two runs: none."""
    parts = [_key_batch([7, 7, 3]), _key_batch([3, 3, 9])]
    num_groups, seg_pad, _keys, gids_d, last_rows_d = tpu_exec._grouping(
        parts, ["k"], 1024
    )
    assert (num_groups, seg_pad) == (3, 16)
    # groups numbered in key order: 3, 7, 9
    assert np.asarray(last_rows_d).tolist() == [4, 1, 5] + [-1] * 13
    again = tpu_exec._grouping(parts, ["k"], 1024)
    assert again[3] is gids_d and again[4] is last_rows_d
    split = [_key_batch([7, 7, 3]), _key_batch([3, 7, 9])]
    assert tpu_exec._grouping(split, ["k"], 1024)[4] is None


def _grouped_query(tmp_session, tmp_path, name, keys):
    rng = np.random.default_rng(len(keys))
    n = len(keys)
    cio.write_parquet(
        ColumnBatch.from_pydict(
            {"g": list(keys), "d": rng.integers(0, 100, n).tolist(),
             "x": rng.uniform(0, 10, n).tolist()}
        ),
        str(tmp_path / name / "p.parquet"),
    )
    df = tmp_session.read.parquet(str(tmp_path / name))
    return lambda: (
        df.filter(col("d") < 70)
        .group_by("g")
        .agg(Sum(col("x")).alias("s"), Min(col("x")).alias("m"),
             Max(col("x")).alias("M"), Avg(col("x")).alias("a"),
             Count(lit(1)).alias("n"))
        .to_pydict()
    )


@pytest.mark.parametrize("layout", ["runs", "split"])
def test_a_dispatch_takes_the_run_route_only_over_runs(tmp_session, tmp_path, layout):
    """Sorted keys: one run-route dispatch and no scatter. One key in two
    runs: the scatter route, and ``kernel.grouped.runs`` does not move.
    Both answer as the host tier does, rows in the same order."""
    keys = np.repeat(np.arange(60), 50)
    if layout == "split":
        keys = np.concatenate([keys, [0]])  # group 0 again at the end
    q = _grouped_query(tmp_session, tmp_path, layout, keys.tolist())
    host = q()
    tpu_exec._KERNEL_CACHE.clear()
    tmp_session.set_conf(C.EXEC_TPU_ENABLED, True)
    try:
        runs, scatters = _counter("kernel.grouped.runs"), _counter("kernel.grouped.scatters")
        dev = q()
        runs = _counter("kernel.grouped.runs") - runs
        scatters = _counter("kernel.grouped.scatters") - scatters
    finally:
        tmp_session.set_conf(C.EXEC_TPU_ENABLED, False)
        tpu_exec._KERNEL_CACHE.clear()
    assert dev["g"] == host["g"] and dev["n"] == host["n"]
    for name in ("s", "m", "M", "a"):
        np.testing.assert_allclose(dev[name], host[name], rtol=1e-5)
    if layout == "runs":
        assert (runs, scatters) == (1, 0)
    else:
        assert runs == 0 and scatters > 0


def test_a_small_q17_takes_the_run_route_and_equals_the_reference(tmp_path):
    """Q17 at scale factor 0.01 on the streamed route: its per-part average
    over ``li_partkey`` (bucketed, sorted within each bucket) is one
    run-route dispatch with no scatter, and the answer is the reference's."""
    import os
    from unittest import mock

    from perfbench import cell as cells
    from perfbench import run, tpch_data
    from perfbench.queries import q17

    cell = cells.Cell("sf1_join")
    cfg = dict(cell.config, scale_factor=0.01, orders_per_file=5_000)
    with mock.patch.dict(os.environ, {k: str(v) for k, v in cfg["env"].items()}):
        ws = str(tmp_path / "warehouse")
        data = tpch_data.generate(ws, cfg, 2**31 + 17, keep=cell.tables())
        session = run.new_session(ws, cfg)
        tpch_data.build_indexes(session, ws, cfg)
        session.enable_hyperspace()
        tpu_exec._KERNEL_CACHE.clear()
        for p in ({"brand": 1, "container": 3}, {"brand": 2, "container": 6}):
            runs = _counter("kernel.grouped.runs")
            scatters = _counter("kernel.grouped.scatters")
            streamed = _counter("pipeline.queries")
            got = q17.dataframe(session, ws, p).to_pydict()
            assert _counter("kernel.grouped.runs") - runs == 1
            assert _counter("kernel.grouped.scatters") - scatters == 0
            assert _counter("pipeline.queries") - streamed >= 1
            want = q17.reference(data, p)
            assert want["avg_yearly"] != [None]
            wrong, err = q17.compare(got, want)
            assert wrong == 0 and err <= 1e-4
        tpu_exec._KERNEL_CACHE.clear()
