"""Co-partitioned bucketed merge join execution tests — the physical half of
JoinIndexRule (ref: BucketUnionExec / Exchange-free SMJ behavior)."""

import numpy as np
import pytest

from hyperspace_tpu import CoveringIndexConfig, Hyperspace
from hyperspace_tpu import constants as C
from hyperspace_tpu.columnar import io as cio
from hyperspace_tpu.columnar.table import ColumnBatch
from hyperspace_tpu.plan import col
from hyperspace_tpu.plan.bucket_join import try_bucketed_merge_join, _decompose_side
from hyperspace_tpu.plan.nodes import Join


def sorted_rows(d):
    keys = list(d.keys())
    return sorted(zip(*[d[k] for k in keys]), key=repr)


@pytest.fixture()
def env(tmp_session, tmp_path):
    rng = np.random.default_rng(11)
    n = 3000
    left = {
        "k": rng.integers(0, 300, n).tolist(),
        "a": rng.uniform(size=n).tolist(),
    }
    right = {
        "rk": list(range(300)),
        "b": [i * 1.0 for i in range(300)],
    }
    cio.write_parquet(ColumnBatch.from_pydict(left), str(tmp_path / "l" / "l.parquet"))
    cio.write_parquet(ColumnBatch.from_pydict(right), str(tmp_path / "r" / "r.parquet"))
    hs = Hyperspace(tmp_session)
    ldf = tmp_session.read.parquet(str(tmp_path / "l"))
    rdf = tmp_session.read.parquet(str(tmp_path / "r"))
    hs.create_index(ldf, CoveringIndexConfig("lidx", ["k"], ["a"]))
    hs.create_index(rdf, CoveringIndexConfig("ridx", ["rk"], ["b"]))
    return tmp_session, hs, tmp_path


class TestBucketedJoin:
    def test_rewritten_join_uses_bucketed_path(self, env):
        session, hs, tmp = env
        q = lambda l, r: l.select("k", "a").join(
            r.select("rk", "b"), col("k") == col("rk")
        )
        ldf = session.read.parquet(str(tmp / "l"))
        rdf = session.read.parquet(str(tmp / "r"))
        expected = q(ldf, rdf).to_pydict()
        session.enable_hyperspace()
        l2 = session.read.parquet(str(tmp / "l"))
        r2 = session.read.parquet(str(tmp / "r"))
        plan = q(l2, r2).optimized_plan()
        # the optimized join must decompose into bucketed sides
        join_node = next(n for n in plan.preorder() if isinstance(n, Join))
        assert _decompose_side(join_node.left) is not None
        assert _decompose_side(join_node.right) is not None
        out = try_bucketed_merge_join(join_node, session)
        assert out is not None
        assert sorted_rows(out.to_pydict()) == sorted_rows(expected)

    def test_collect_equals_unindexed(self, env):
        session, hs, tmp = env
        q = lambda l, r: (
            l.select("k", "a")
            .join(r.select("rk", "b"), col("k") == col("rk"))
            .filter(col("b") < 100.0)
        )
        ldf = session.read.parquet(str(tmp / "l"))
        rdf = session.read.parquet(str(tmp / "r"))
        expected = q(ldf, rdf).to_pydict()
        session.enable_hyperspace()
        got = q(
            session.read.parquet(str(tmp / "l")),
            session.read.parquet(str(tmp / "r")),
        ).to_pydict()
        assert sorted_rows(got) == sorted_rows(expected)

    def test_hybrid_append_flows_through_bucket_union(self, env):
        session, hs, tmp = env
        # append new rows to the left source after the index build
        cio.write_parquet(
            ColumnBatch.from_pydict({"k": [7, 8], "a": [111.0, 222.0]}),
            str(tmp / "l" / "l2.parquet"),
        )
        session.set_conf(C.HYBRID_SCAN_ENABLED, True)
        session.enable_hyperspace()
        q = lambda l, r: l.select("k", "a").join(
            r.select("rk", "b"), col("k") == col("rk")
        )
        l2 = session.read.parquet(str(tmp / "l"))
        r2 = session.read.parquet(str(tmp / "r"))
        got = q(l2, r2).to_pydict()
        session.disable_hyperspace()
        expected = q(
            session.read.parquet(str(tmp / "l")),
            session.read.parquet(str(tmp / "r")),
        ).to_pydict()
        assert sorted_rows(got) == sorted_rows(expected)
        assert 111.0 in got["a"]

    def test_no_matches_in_some_buckets(self, tmp_session, tmp_path):
        # keys chosen so several buckets are empty on one side
        cio.write_parquet(
            ColumnBatch.from_pydict({"k": [1, 1, 2], "a": [1.0, 2.0, 3.0]}),
            str(tmp_path / "l" / "l.parquet"),
        )
        cio.write_parquet(
            ColumnBatch.from_pydict({"rk": [2, 99], "b": [10.0, 20.0]}),
            str(tmp_path / "r" / "r.parquet"),
        )
        hs = Hyperspace(tmp_session)
        ldf = tmp_session.read.parquet(str(tmp_path / "l"))
        rdf = tmp_session.read.parquet(str(tmp_path / "r"))
        hs.create_index(ldf, CoveringIndexConfig("li", ["k"], ["a"]))
        hs.create_index(rdf, CoveringIndexConfig("ri", ["rk"], ["b"]))
        tmp_session.enable_hyperspace()
        out = (
            tmp_session.read.parquet(str(tmp_path / "l"))
            .select("k", "a")
            .join(
                tmp_session.read.parquet(str(tmp_path / "r")).select("rk", "b"),
                col("k") == col("rk"),
            )
            .to_pydict()
        )
        assert out["k"] == [2] and out["b"] == [10.0]

    def test_empty_join_result(self, tmp_session, tmp_path):
        cio.write_parquet(
            ColumnBatch.from_pydict({"k": [1], "a": [1.0]}), str(tmp_path / "l" / "l.parquet")
        )
        cio.write_parquet(
            ColumnBatch.from_pydict({"rk": [999], "b": [2.0]}), str(tmp_path / "r" / "r.parquet")
        )
        hs = Hyperspace(tmp_session)
        ldf = tmp_session.read.parquet(str(tmp_path / "l"))
        rdf = tmp_session.read.parquet(str(tmp_path / "r"))
        hs.create_index(ldf, CoveringIndexConfig("li", ["k"], ["a"]))
        hs.create_index(rdf, CoveringIndexConfig("ri", ["rk"], ["b"]))
        tmp_session.enable_hyperspace()
        out = (
            tmp_session.read.parquet(str(tmp_path / "l"))
            .select("k", "a")
            .join(
                tmp_session.read.parquet(str(tmp_path / "r")).select("rk", "b"),
                col("k") == col("rk"),
            )
            .to_pydict()
        )
        assert out == {"k": [], "a": [], "rk": [], "b": []}


class TestBucketJoinAfterRefresh:
    """Multi-file buckets (incremental refresh MERGE) must not be treated as
    sorted (regression: searchsorted over unsorted concatenation)."""

    def test_join_after_incremental_refresh(self, env):
        session, hs, tmp = env
        q = lambda l, r: l.select("k", "a").join(
            r.select("rk", "b"), col("k") == col("rk")
        )
        # append to the RIGHT side source and refresh incrementally: each
        # right bucket now spans two files
        cio.write_parquet(
            ColumnBatch.from_pydict(
                {"rk": list(range(300, 350)), "b": [float(i) for i in range(50)]}
            ),
            str(tmp / "r" / "r2.parquet"),
        )
        hs.refresh_index("ridx", "incremental")
        ldf = session.read.parquet(str(tmp / "l"))
        rdf = session.read.parquet(str(tmp / "r"))
        expected = q(ldf, rdf).to_pydict()
        session.enable_hyperspace()
        got = q(
            session.read.parquet(str(tmp / "l")),
            session.read.parquet(str(tmp / "r")),
        ).to_pydict()
        assert sorted_rows(got) == sorted_rows(expected)


class TestLineagePruneInteraction:
    """Column pruning must not leak the lineage column into the logical
    schema (regression: Union alignment crash under hybrid delete)."""

    def test_hybrid_delete_with_unused_included_column(self, tmp_session, tmp_path):
        import os as _os

        from hyperspace_tpu import CoveringIndexConfig as CIC

        session = tmp_session
        session.set_conf(C.INDEX_LINEAGE_ENABLED, True)
        src = tmp_path / "hd"
        cio.write_parquet(
            ColumnBatch.from_pydict({"k": [1, 2], "a": [1.0, 2.0], "s": ["x", "y"]}),
            str(src / "p1.parquet"),
        )
        cio.write_parquet(
            ColumnBatch.from_pydict({"k": [3], "a": [3.0], "s": ["z"]}),
            str(src / "p2.parquet"),
        )
        hs = Hyperspace(session)
        df = session.read.parquet(str(src))
        # index includes BOTH a and s; the query will not use s
        hs.create_index(df, CIC("hidx", ["k"], ["a", "s"]))
        _os.unlink(src / "p2.parquet")
        cio.write_parquet(
            ColumnBatch.from_pydict({"k": [9], "a": [9.0], "s": ["w"]}),
            str(src / "p3.parquet"),
        )
        session.enable_hyperspace()
        session.set_conf(C.HYBRID_SCAN_ENABLED, True)
        df2 = session.read.parquet(str(src))
        q = df2.filter(col("k") >= 1).select("k", "a")
        got = q.to_pydict()
        session.disable_hyperspace()
        expected = q.to_pydict()
        assert sorted_rows(got) == sorted_rows(expected)
        assert 3.0 not in got["a"] and 9.0 in got["a"]


class TestAliasedKeyNotBucketJoined:
    """A projection that rebinds the bucket column name to another column
    must NOT take the bucketed path (regression: silently wrong results)."""

    def test_aliased_key_falls_back_to_generic_join(self, tmp_session, tmp_path):
        rng = np.random.default_rng(2)
        n = 3000
        cio.write_parquet(
            ColumnBatch.from_pydict(
                {
                    "k": rng.integers(0, 300, n).tolist(),
                    "x": rng.integers(0, 300, n).tolist(),
                    "a": rng.uniform(size=n).tolist(),
                }
            ),
            str(tmp_path / "l" / "l.parquet"),
        )
        cio.write_parquet(
            ColumnBatch.from_pydict(
                {"rk": list(range(300)), "b": [float(i) for i in range(300)]}
            ),
            str(tmp_path / "r" / "r.parquet"),
        )
        hs = Hyperspace(tmp_session)
        ldf = tmp_session.read.parquet(str(tmp_path / "l"))
        rdf = tmp_session.read.parquet(str(tmp_path / "r"))
        hs.create_index(ldf, CoveringIndexConfig("li", ["k"], ["a", "x"]))
        hs.create_index(rdf, CoveringIndexConfig("ri", ["rk"], ["b"]))
        q = lambda l, r: l.select(col("x").alias("k"), "a").join(
            r.select("rk", "b"), col("k") == col("rk")
        )
        expected = q(ldf, rdf).count()
        tmp_session.enable_hyperspace()
        got = q(
            tmp_session.read.parquet(str(tmp_path / "l")),
            tmp_session.read.parquet(str(tmp_path / "r")),
        ).count()
        assert got == expected == n  # every x matches some rk


class TestCompositeKeyGrouping:
    """Grouping by a strict subset of a multi-column join key must NOT take
    the fused per-bucket aggregate: buckets hash the full key tuple, so one
    group's rows span buckets and the per-bucket partials would concatenate
    unmerged (regression: 399 rows instead of 50, wrong sums)."""

    @pytest.fixture()
    def two_key_env(self, tmp_session, tmp_path):
        rng = np.random.default_rng(5)
        n = 4000
        left = {
            "k1": rng.integers(0, 50, n).tolist(),
            "k2": rng.integers(0, 8, n).tolist(),
            "a": rng.uniform(size=n).tolist(),
        }
        # right side: the full (k1, k2) cross product so every row joins
        right = {
            "r1": [i for i in range(50) for _ in range(8)],
            "r2": [j for _ in range(50) for j in range(8)],
            "b": [1.0] * 400,
        }
        cio.write_parquet(ColumnBatch.from_pydict(left), str(tmp_path / "l" / "l.parquet"))
        cio.write_parquet(ColumnBatch.from_pydict(right), str(tmp_path / "r" / "r.parquet"))
        hs = Hyperspace(tmp_session)
        ldf = tmp_session.read.parquet(str(tmp_path / "l"))
        rdf = tmp_session.read.parquet(str(tmp_path / "r"))
        hs.create_index(ldf, CoveringIndexConfig("l2i", ["k1", "k2"], ["a"]))
        hs.create_index(rdf, CoveringIndexConfig("r2i", ["r1", "r2"], ["b"]))
        return tmp_session, tmp_path

    def _query(self, session, tmp, group_cols):
        from hyperspace_tpu.plan import Sum

        l = session.read.parquet(str(tmp / "l")).select("k1", "k2", "a")
        r = session.read.parquet(str(tmp / "r")).select("r1", "r2", "b")
        j = l.join(r, (col("k1") == col("r1")) & (col("k2") == col("r2")))
        return j.group_by(*group_cols).agg(Sum(col("a")).alias("s"))

    def test_subset_grouping_not_fused_and_correct(self, two_key_env):
        from hyperspace_tpu.plan.bucket_join import try_bucketed_join_aggregate
        from hyperspace_tpu.plan.nodes import Aggregate

        session, tmp = two_key_env
        expected = self._query(session, tmp, ["k1"]).to_pydict()
        assert len(expected["k1"]) == 50
        session.enable_hyperspace()
        q = self._query(session, tmp, ["k1"])
        plan = q.optimized_plan()
        agg = next(n for n in plan.preorder() if isinstance(n, Aggregate))
        assert try_bucketed_join_aggregate(agg, session) is None
        got = q.to_pydict()
        assert_rows_close(got, expected)

    def test_full_key_grouping_still_fused(self, two_key_env):
        from hyperspace_tpu.plan.bucket_join import try_bucketed_join_aggregate
        from hyperspace_tpu.plan.nodes import Aggregate

        session, tmp = two_key_env
        expected = self._query(session, tmp, ["k1", "k2"]).to_pydict()
        session.enable_hyperspace()
        q = self._query(session, tmp, ["k1", "k2"])
        plan = q.optimized_plan()
        agg = next(n for n in plan.preorder() if isinstance(n, Aggregate))
        fused = try_bucketed_join_aggregate(agg, session)
        assert fused is not None
        got = q.to_pydict()
        assert_rows_close(got, expected)

    def test_mixed_side_grouping_fused(self, two_key_env):
        """Grouping by one key from each side still determines every pair."""
        from hyperspace_tpu.plan.bucket_join import try_bucketed_join_aggregate
        from hyperspace_tpu.plan.nodes import Aggregate

        session, tmp = two_key_env
        expected = self._query(session, tmp, ["k1", "r2"]).to_pydict()
        session.enable_hyperspace()
        q = self._query(session, tmp, ["k1", "r2"])
        plan = q.optimized_plan()
        agg = next(n for n in plan.preorder() if isinstance(n, Aggregate))
        assert try_bucketed_join_aggregate(agg, session) is not None
        got = q.to_pydict()
        assert_rows_close(got, expected)


def assert_rows_close(got, expected, tol=1e-6):
    gr, er = sorted_rows(got), sorted_rows(expected)
    assert len(gr) == len(er)
    for g, e in zip(gr, er):
        for gv, ev in zip(g, e):
            if isinstance(gv, float):
                assert abs(gv - ev) <= tol * max(1.0, abs(ev))
            else:
                assert gv == ev


class TestDeviceJoinAggregate:
    """The fused join+aggregate lowers to the device kernels when TPU exec
    is enabled (searchsorted probe + segment reductions; the join output
    never materializes). Results must match the host path."""

    @pytest.fixture()
    def env3(self, tmp_session, tmp_path):
        from hyperspace_tpu.columnar.table import Column

        rng = np.random.default_rng(13)
        n = 6000
        n_keys = 400
        # f32 value columns: f64 Sum/Avg inputs decline to the host twin by
        # design (accumulation would diverge between tiers)
        left = ColumnBatch(
            {
                "k": Column(rng.integers(0, n_keys, n), "int64"),
                "price": Column(
                    rng.uniform(900, 10000, n).astype(np.float32), "float32"
                ),
                "disc": Column(
                    np.round(rng.uniform(0, 0.1, n), 2).astype(np.float32),
                    "float32",
                ),
            }
        )
        right = {
            "rk": list(range(n_keys)),
            "rdate": rng.integers(8000, 10000, n_keys).astype(int).tolist(),
        }
        cio.write_parquet(left, str(tmp_path / "l" / "l.parquet"))
        cio.write_parquet(ColumnBatch.from_pydict(right), str(tmp_path / "r" / "r.parquet"))
        hs = Hyperspace(tmp_session)
        hs.create_index(
            tmp_session.read.parquet(str(tmp_path / "l")),
            CoveringIndexConfig("dl", ["k"], ["price", "disc"]),
        )
        hs.create_index(
            tmp_session.read.parquet(str(tmp_path / "r")),
            CoveringIndexConfig("dr", ["rk"], ["rdate"]),
        )
        return tmp_session, tmp_path

    def _q3_shape(self, session, tmp):
        from hyperspace_tpu.plan import Avg, Count, Sum, lit

        l = session.read.parquet(str(tmp / "l")).select("k", "price", "disc")
        r = session.read.parquet(str(tmp / "r")).select("rk", "rdate").filter(
            col("rdate") < 9500
        )
        return (
            l.join(r, col("k") == col("rk"))
            .group_by("k", "rdate")
            .agg(
                Sum(col("price") * (lit(1.0) - col("disc"))).alias("revenue"),
                Count(lit(1)).alias("n"),
                Avg(col("price")).alias("ap"),
            )
        )

    def test_device_fused_matches_host(self, env3):
        from hyperspace_tpu.plan import device_join

        session, tmp = env3
        expected = self._q3_shape(session, tmp).to_pydict()
        session.enable_hyperspace()
        device_join._CACHE.clear()
        device_join._STACK_CACHE.clear()
        session.set_conf(C.EXEC_TPU_ENABLED, True)
        got = self._q3_shape(session, tmp).to_pydict()
        # the device path actually ran: the stacked all-buckets kernel (one
        # dispatch per join) or, if it declined, the per-bucket kernel
        assert len(device_join._STACK_CACHE) + len(device_join._CACHE) > 0
        assert_rows_close(got, expected)

    def test_stacked_join_is_one_dispatch(self, env3):
        """The whole fused join+aggregate — every bucket — must cost ONE
        kernel dispatch and ONE fetch (per-bucket dispatches each paid a
        host round trip)."""
        from hyperspace_tpu.plan import device_join
        from hyperspace_tpu.utils.rpc_meter import METER, RpcMeter

        session, tmp = env3
        session.enable_hyperspace()
        session.set_conf(C.EXEC_TPU_ENABLED, True)
        self._q3_shape(session, tmp).collect()  # warm compile + caches
        before = METER.snapshot()
        out = self._q3_shape(session, tmp).collect()
        delta = RpcMeter.delta(before, METER.snapshot())
        assert out.num_rows > 0
        assert len(device_join._STACK_CACHE) > 0, "stacked path must engage"
        assert delta["dispatches"] == 1, delta
        assert delta["fetches"] == 1, delta

    def test_stacked_right_side_uploads_cache(self, env3):
        """Steady-state repeats re-ship only the left (filtered) side: the
        stacked right-key/column uploads hit the device cache."""
        from hyperspace_tpu.utils.device_cache import DEVICE_CACHE
        from hyperspace_tpu.utils.rpc_meter import METER, RpcMeter

        session, tmp = env3
        session.enable_hyperspace()
        session.set_conf(C.EXEC_TPU_ENABLED, True)
        self._q3_shape(session, tmp).collect()
        h0 = DEVICE_CACHE.hits
        before = METER.snapshot()
        self._q3_shape(session, tmp).collect()
        delta = RpcMeter.delta(before, METER.snapshot())
        assert DEVICE_CACHE.hits > h0, "stacked right side must cache"
        # uploads: the left stack + per-query scalars only — strictly fewer
        # bytes than the cold query shipped
        first_bytes = delta["upload_bytes"]
        before2 = METER.snapshot()
        self._q3_shape(session, tmp).collect()
        delta2 = RpcMeter.delta(before2, METER.snapshot())
        assert delta2["upload_bytes"] <= first_bytes

    def test_stacked_dup_right_keys_left_only(self, tmp_session):
        """Duplicate right keys with left-only aggregates + key groups stay
        on the stacked device path (match-count weighting)."""
        from hyperspace_tpu.plan import Sum
        from hyperspace_tpu.plan.device_join import try_stacked_join_agg, try_host_join_agg
        from hyperspace_tpu.plan.expr import col as ecol
        from hyperspace_tpu.plan.nodes import Aggregate, InMemoryScan
        from hyperspace_tpu.columnar.table import Column

        rng = np.random.default_rng(7)
        loaded = []
        for b in range(3):
            n_l, n_r = 3000, 120
            lb = ColumnBatch(
                {
                    "k": Column(rng.integers(0, 40, n_l), "int64"),
                    "price": Column(
                        rng.uniform(0, 100, n_l).astype(np.float32), "float32"
                    ),
                }
            )
            # duplicate right keys: every key appears 3x
            rb = ColumnBatch.from_pydict(
                {"rk": sorted(list(range(40)) * 3)}
            )
            loaded.append((b, lb, rb, False, True))
        agg = Aggregate(
            [ecol("k")],
            [Sum(ecol("price")).alias("s")],
            InMemoryScan(ColumnBatch.from_pydict({"k": [], "price": []})),
        )
        tmp_session.set_conf(C.EXEC_TPU_ENABLED, True)
        try:
            out = try_stacked_join_agg(
                loaded, ["k"], ["rk"], [], tmp_session, agg
            )
        finally:
            tmp_session.set_conf(C.EXEC_TPU_ENABLED, False)
        assert out is not None
        # host twin declines dup right keys; build the expectation by
        # weighting each left row by its match count (3 per present key)
        got = out.to_pydict()
        expected_parts = []
        for _b, lb, rb, _ls, _rs in loaded:
            k = lb.column("k").data
            p = lb.column("price").data.astype(np.float64)
            sums = {}
            counts = {}
            for kk, pp in zip(k, p):
                sums[kk] = sums.get(kk, 0.0) + 3 * pp
                counts[kk] = counts.get(kk, 0) + 3
            expected_parts.append((sums, counts))
        exp_k, exp_s = [], []
        for sums, _counts in expected_parts:
            for kk in sorted(sums):
                exp_k.append(kk)
                exp_s.append(sums[kk])
        # compare as sorted multisets of (k, s) with f32 tolerance
        got_pairs = sorted(zip(got["k"], got["s"]))
        exp_pairs = sorted(zip(exp_k, exp_s))
        assert len(got_pairs) == len(exp_pairs)
        for (gk, gs), (ek, es) in zip(got_pairs, exp_pairs):
            assert gk == ek
            assert abs(gs - es) <= 1e-3 * max(1.0, abs(es))

    def test_residual_predicate_on_device_unit(self, tmp_session):
        """Residual (non-equi) conjuncts never reach the bucketed path via
        JoinIndexRule (pure equi-join only, as in the reference), but the
        device kernel supports them for direct callers: evaluate per left
        row over gathered right attributes."""
        from hyperspace_tpu.plan import Sum
        from hyperspace_tpu.plan.device_join import try_device_join_agg
        from hyperspace_tpu.plan.expr import col as ecol
        from hyperspace_tpu.plan.nodes import Aggregate, InMemoryScan

        from hyperspace_tpu.columnar.table import Column

        rng = np.random.default_rng(3)
        n = 2000
        lb = ColumnBatch(
            {
                "k": Column(rng.integers(0, 50, n), "int64"),
                # f32: f64 Sum inputs decline to the host twin by design
                "price": Column(
                    rng.uniform(0, 100, n).astype(np.float32), "float32"
                ),
            }
        )
        rb = ColumnBatch.from_pydict(
            {"rk": list(range(50)), "thr": rng.uniform(0, 100, 50).tolist()}
        )
        residual = [ecol("price") > ecol("thr")]
        agg = Aggregate(
            [ecol("k")],
            [Sum(ecol("price")).alias("s")],
            InMemoryScan(
                ColumnBatch.from_pydict({"k": [], "thr": [], "price": []})
            ),
        )
        tmp_session.set_conf(C.EXEC_TPU_ENABLED, True)
        out = try_device_join_agg(
            agg, lb, rb, ["k"], ["rk"], residual, tmp_session, r_sorted=True
        )
        assert out is not None
        got = out.to_pydict()
        # host reference
        import collections

        sums = collections.defaultdict(float)
        thr = {i: t for i, t in zip(rb.to_pydict()["rk"], rb.to_pydict()["thr"])}
        d = lb.to_pydict()
        for k, p in zip(d["k"], d["price"]):
            if p > thr[k]:
                sums[k] += p
        expected = {k: v for k, v in sums.items()}
        got_map = dict(zip(got["k"], got["s"]))
        assert set(got_map) == set(expected)
        for k in expected:
            assert got_map[k] == pytest.approx(expected[k], rel=1e-5)

    def test_f64_sum_declines_device_under_exact_conf(self, tmp_session):
        """Under hyperspace.tpu.exec.exactF64Aggregates, f64 Sum/Avg inputs
        must NOT run the device fused kernel (f32 accumulation would diverge
        from the host twin's exact f64); the host twin serves the bucket.
        With the default (relaxed) conf the device kernel accepts them and
        matches the host within f32 accumulation tolerance."""
        from hyperspace_tpu.plan import Sum
        from hyperspace_tpu.plan import device_join
        from hyperspace_tpu.plan.device_join import (
            try_device_join_agg,
            try_host_join_agg,
        )
        from hyperspace_tpu.plan.expr import col as ecol
        from hyperspace_tpu.plan.nodes import Aggregate, InMemoryScan

        rng = np.random.default_rng(5)
        n = 3000
        lb = ColumnBatch.from_pydict(
            {
                "k": rng.integers(0, 40, n).tolist(),
                "price": rng.uniform(0, 100, n).tolist(),  # float64
            }
        )
        rb = ColumnBatch.from_pydict({"rk": list(range(40))})

        def mkagg():
            return Aggregate(
                [ecol("k")],
                [Sum(ecol("price")).alias("s")],
                InMemoryScan(ColumnBatch.from_pydict({"k": [], "price": []})),
            )

        tmp_session.set_conf(C.EXEC_TPU_ENABLED, True)
        tmp_session.set_conf(C.EXEC_EXACT_F64_AGG, True)
        device_join._CACHE.clear()
        out = try_device_join_agg(
            mkagg(), lb, rb, ["k"], ["rk"], [], tmp_session, r_sorted=True
        )
        assert out is None  # declined: no kernel built, host twin takes over
        assert len(device_join._CACHE) == 0

        # relaxed default: device runs and agrees with the exact host twin
        # within f32 accumulation error
        tmp_session.set_conf(C.EXEC_EXACT_F64_AGG, False)
        dev = try_device_join_agg(
            mkagg(), lb, rb, ["k"], ["rk"], [], tmp_session, r_sorted=True
        )
        tmp_session.set_conf(C.EXEC_TPU_ENABLED, False)
        host = try_host_join_agg(
            mkagg(), lb, rb, ["k"], ["rk"], [], tmp_session, r_sorted=True
        )
        assert dev is not None and host is not None
        d, h = dev.to_pydict(), host.to_pydict()
        assert d["k"] == h["k"]
        for a, b in zip(d["s"], h["s"]):
            assert abs(a - b) <= 1e-5 * max(1.0, abs(b))

    def test_duplicate_right_keys_fall_back(self, tmp_session, tmp_path):
        """Right side with duplicate keys per bucket must use the host join
        (device gather keeps only the first match)."""
        from hyperspace_tpu.plan import Sum

        rng = np.random.default_rng(7)
        n = 3000
        cio.write_parquet(
            ColumnBatch.from_pydict(
                {
                    "k": rng.integers(0, 100, n).tolist(),
                    "a": rng.uniform(size=n).tolist(),
                }
            ),
            str(tmp_path / "l" / "l.parquet"),
        )
        # two rows per right key
        cio.write_parquet(
            ColumnBatch.from_pydict(
                {
                    "rk": [i for i in range(100) for _ in range(2)],
                    "b": [float(i) for i in range(200)],
                }
            ),
            str(tmp_path / "r" / "r.parquet"),
        )
        hs = Hyperspace(tmp_session)
        hs.create_index(
            tmp_session.read.parquet(str(tmp_path / "l")),
            CoveringIndexConfig("dupl", ["k"], ["a"]),
        )
        hs.create_index(
            tmp_session.read.parquet(str(tmp_path / "r")),
            CoveringIndexConfig("dupr", ["rk"], ["b"]),
        )

        def q(s):
            l = s.read.parquet(str(tmp_path / "l")).select("k", "a")
            r = s.read.parquet(str(tmp_path / "r")).select("rk", "b")
            return (
                l.join(r, col("k") == col("rk"))
                .group_by("k")
                .agg(Sum(col("a") * col("b")).alias("s"))
            )

        expected = q(tmp_session).to_pydict()
        tmp_session.enable_hyperspace()
        tmp_session.set_conf(C.EXEC_TPU_ENABLED, True)
        got = q(tmp_session).to_pydict()
        assert_rows_close(got, expected)


class TestDevicePlainJoin:
    """The plain (non-aggregated) co-partitioned merge join probes on
    device and gathers on host in original dtypes — output bit-identical to
    the host merge join, duplicate keys included."""

    def test_unit_matches_host_merge_join_exactly(self, tmp_session):
        from hyperspace_tpu.plan import device_join
        from hyperspace_tpu.plan.bucket_join import _merge_join_batches
        from hyperspace_tpu.plan.device_join import try_device_plain_join

        rng = np.random.default_rng(17)
        n_l, n_r = 9000, 600
        lb = ColumnBatch.from_pydict(
            {
                "k": rng.integers(0, 200, n_l).tolist(),
                "price": rng.uniform(0, 1e4, n_l).tolist(),  # f64 gathers fine
                "tag": rng.choice(["x", "y", "z"], n_l).tolist(),
            }
        )
        # duplicate right keys: three rows per key
        rb = ColumnBatch.from_pydict(
            {
                "rk": [k for k in range(200) for _ in range(3)],
                "w": rng.uniform(size=600).tolist(),
            }
        )
        tmp_session.set_conf(C.EXEC_TPU_ENABLED, True)
        device_join._PLAIN_CACHE.clear()
        dev = try_device_plain_join(
            lb, rb, ["k"], ["rk"], tmp_session, l_sorted=False, r_sorted=False
        )
        tmp_session.set_conf(C.EXEC_TPU_ENABLED, False)
        assert dev is not None and len(device_join._PLAIN_CACHE) == 1
        host = _merge_join_batches(lb, rb, ["k"], ["rk"], False, False)
        assert dev.to_pydict() == host.to_pydict()  # bit-identical, same order

    def test_e2e_join_without_aggregate_uses_device(self, tmp_session, tmp_path):
        """A Q3-shaped rewritten join whose output feeds a projection (no
        aggregate) must run the device probe per bucket in strict mode."""
        from hyperspace_tpu.plan import device_join

        rng = np.random.default_rng(23)
        n = 40000
        n_keys = 500
        cio.write_parquet(
            ColumnBatch.from_pydict(
                {
                    "k": rng.integers(0, n_keys, n).tolist(),
                    "price": rng.uniform(0, 100, n).tolist(),
                }
            ),
            str(tmp_path / "l" / "l.parquet"),
        )
        cio.write_parquet(
            ColumnBatch.from_pydict(
                {
                    "rk": list(range(n_keys)),
                    "rdate": rng.integers(8000, 10000, n_keys).astype(int).tolist(),
                }
            ),
            str(tmp_path / "r" / "r.parquet"),
        )
        tmp_session.set_conf(C.INDEX_NUM_BUCKETS, 2)  # >=4096 rows per bucket
        hs = Hyperspace(tmp_session)
        hs.create_index(
            tmp_session.read.parquet(str(tmp_path / "l")),
            CoveringIndexConfig("pjl", ["k"], ["price"]),
        )
        hs.create_index(
            tmp_session.read.parquet(str(tmp_path / "r")),
            CoveringIndexConfig("pjr", ["rk"], ["rdate"]),
        )

        def q(s):
            l = s.read.parquet(str(tmp_path / "l")).select("k", "price")
            r = s.read.parquet(str(tmp_path / "r")).select("rk", "rdate")
            return l.join(r, col("k") == col("rk")).select("k", "price", "rdate")

        expected = q(tmp_session).to_pydict()
        tmp_session.enable_hyperspace()
        device_join._PLAIN_CACHE.clear()
        tmp_session.set_conf(C.EXEC_TPU_ENABLED, True)
        got = q(tmp_session).to_pydict()
        tmp_session.set_conf(C.EXEC_TPU_ENABLED, False)
        assert len(device_join._PLAIN_CACHE) > 0  # the device probe ran
        assert sorted_rows(got) == sorted_rows(expected)


class TestDeviceJoinAggDuplicates:
    def test_duplicate_right_keys_left_only_aggs_on_device(self, tmp_session):
        """Duplicate right keys + left-only aggregates: the fused kernel
        weights each left row by its match count instead of falling back."""
        from hyperspace_tpu.plan import Avg, Count, Sum, lit
        from hyperspace_tpu.plan import device_join
        from hyperspace_tpu.plan.device_join import (
            try_device_join_agg,
            try_host_join_agg,
        )
        from hyperspace_tpu.plan.expr import col as ecol
        from hyperspace_tpu.plan.nodes import Aggregate, InMemoryScan
        from hyperspace_tpu.columnar.table import Column

        rng = np.random.default_rng(29)
        n = 6000
        lb = ColumnBatch(
            {
                "k": Column(rng.integers(0, 80, n), "int64"),
                "price": Column(
                    rng.uniform(0, 100, n).astype(np.float32), "float32"
                ),
            }
        )
        reps = rng.integers(1, 4, 80)  # 1-3 rows per right key
        rb = ColumnBatch.from_pydict(
            {"rk": [k for k in range(80) for _ in range(int(reps[k]))]}
        )
        agg = Aggregate(
            [ecol("k")],
            [
                Sum(ecol("price")).alias("s"),
                Count(lit(1)).alias("n"),
                Avg(ecol("price")).alias("m"),
            ],
            InMemoryScan(ColumnBatch.from_pydict({"k": [], "price": []})),
        )
        tmp_session.set_conf(C.EXEC_TPU_ENABLED, True)
        device_join._CACHE.clear()
        dev = try_device_join_agg(
            agg, lb, rb, ["k"], ["rk"], [], tmp_session, r_sorted=False
        )
        tmp_session.set_conf(C.EXEC_TPU_ENABLED, False)
        assert dev is not None and len(device_join._CACHE) == 1
        # host reference: per-pair expansion via the numpy merge join
        from hyperspace_tpu.plan.bucket_join import _merge_join_batches

        joined = _merge_join_batches(lb, rb, ["k"], ["rk"], False, False)
        jd = joined.to_pydict()
        import collections

        sums = collections.defaultdict(float)
        cnts = collections.defaultdict(int)
        for k, p in zip(jd["k"], jd["price"]):
            sums[k] += p
            cnts[k] += 1
        d = dev.to_pydict()
        got = {k: (s, c, m) for k, s, c, m in zip(d["k"], d["s"], d["n"], d["m"])}
        assert set(got) == set(sums)
        for k in sums:
            s, c, m = got[k]
            assert c == cnts[k]
            assert s == pytest.approx(sums[k], rel=2e-5)
            assert m == pytest.approx(sums[k] / cnts[k], rel=2e-5)


class TestFloat64JoinKeys:
    def test_f64_keys_near_f32_collapse_stay_exact(self, tmp_session, tmp_path):
        """Distinct f64 join keys that collapse in f32 (16777216.0 vs
        16777217.0) must not spuriously match: the device fused path
        declines f64 keys; the host fused path compares them exactly."""
        from hyperspace_tpu.plan import Sum

        left = {
            "k": [16777216.0, 16777217.0, 16777218.0] * 400,
            "a": [1.0] * 1200,
        }
        right = {"rk": [16777216.0, 16777218.0], "b": [10.0, 20.0]}
        cio.write_parquet(ColumnBatch.from_pydict(left), str(tmp_path / "l" / "l.parquet"))
        cio.write_parquet(ColumnBatch.from_pydict(right), str(tmp_path / "r" / "r.parquet"))
        hs = Hyperspace(tmp_session)
        hs.create_index(
            tmp_session.read.parquet(str(tmp_path / "l")),
            CoveringIndexConfig("f64l", ["k"], ["a"]),
        )
        hs.create_index(
            tmp_session.read.parquet(str(tmp_path / "r")),
            CoveringIndexConfig("f64r", ["rk"], ["b"]),
        )

        def q(s):
            l = s.read.parquet(str(tmp_path / "l")).select("k", "a")
            r = s.read.parquet(str(tmp_path / "r")).select("rk", "b")
            return (
                l.join(r, col("k") == col("rk"))
                .group_by("k")
                .agg(Sum(col("a") * col("b")).alias("s"))
            )

        expected = q(tmp_session).to_pydict()
        assert len(expected["k"]) == 2  # 16777217.0 must NOT match
        tmp_session.enable_hyperspace()
        tmp_session.set_conf(C.EXEC_TPU_ENABLED, True)
        got = q(tmp_session).to_pydict()
        assert_rows_close(got, expected)


class TestMeshMergeJoin:
    """The co-partitioned plain join probes every bucket pair across the
    8-device mesh (parallel.dist_join, shard-local under shard_map — zero
    collectives by co-partitioning); output is bit-identical to the
    per-bucket host merge join including bucket order."""

    def test_e2e_mesh_join_matches_host(self, tmp_session, tmp_path):
        from hyperspace_tpu.parallel import dist_join

        rng = np.random.default_rng(31)
        n = 40000
        n_keys = 400
        cio.write_parquet(
            ColumnBatch.from_pydict(
                {
                    "k": rng.integers(0, n_keys, n).tolist(),
                    "price": rng.uniform(0, 100, n).tolist(),
                }
            ),
            str(tmp_path / "ml" / "l.parquet"),
        )
        cio.write_parquet(
            ColumnBatch.from_pydict(
                {
                    # duplicate right keys exercise run expansion
                    "rk": [k for k in range(n_keys) for _ in range(2)],
                    "rdate": rng.integers(8000, 10000, 2 * n_keys).astype(int).tolist(),
                }
            ),
            str(tmp_path / "mr" / "r.parquet"),
        )
        tmp_session.set_conf(C.INDEX_NUM_BUCKETS, 4)
        hs = Hyperspace(tmp_session)
        hs.create_index(
            tmp_session.read.parquet(str(tmp_path / "ml")),
            CoveringIndexConfig("mjl", ["k"], ["price"]),
        )
        hs.create_index(
            tmp_session.read.parquet(str(tmp_path / "mr")),
            CoveringIndexConfig("mjr", ["rk"], ["rdate"]),
        )

        def q(s):
            l = s.read.parquet(str(tmp_path / "ml")).select("k", "price")
            r = s.read.parquet(str(tmp_path / "mr")).select("rk", "rdate")
            return l.join(r, col("k") == col("rk")).select("k", "price", "rdate")

        expected_raw = q(tmp_session).to_pydict()
        tmp_session.enable_hyperspace()
        host_tier = q(tmp_session).to_pydict()  # indexed, host tier

        dist_join._PROBE_CACHE.clear()
        tmp_session.set_conf(C.EXEC_TPU_ENABLED, True)
        tmp_session.set_conf("hyperspace.tpu.exec.meshDevices", 8)
        mesh_tier = q(tmp_session).to_pydict()
        tmp_session.set_conf(C.EXEC_TPU_ENABLED, False)
        tmp_session.set_conf("hyperspace.tpu.exec.meshDevices", 0)
        tmp_session.disable_hyperspace()

        assert len(dist_join._PROBE_CACHE) > 0, "mesh probe must have run"
        # bit-identical to the indexed host tier (same bucket order), and
        # row-set-equal to the raw join
        assert mesh_tier == host_tier
        assert sorted_rows(mesh_tier) == sorted_rows(expected_raw)


class TestBatchedDeviceJoin:
    """The single-device batched plain join (probe + run expansion on
    device, two fetches total) is bit-identical to the host merge join."""

    def test_unit_matches_host_exactly(self, tmp_session):
        from hyperspace_tpu.plan import device_join
        from hyperspace_tpu.plan.bucket_join import _merge_join_batches
        from hyperspace_tpu.plan.device_join import try_batched_plain_join
        from hyperspace_tpu.ops.join import exact_key32

        rng = np.random.default_rng(43)
        work = []
        expected = {}
        for b, (n_l, n_r) in enumerate([(9000, 900), (5000, 0), (7000, 300)]):
            lb = ColumnBatch.from_pydict(
                {
                    "k": rng.integers(0, 300, n_l).tolist(),
                    "p": rng.uniform(0, 100, n_l).tolist(),
                }
            )
            rb = ColumnBatch.from_pydict(
                {
                    "rk": sorted(rng.integers(0, 300, n_r).tolist()),
                    "w": rng.uniform(0, 1, n_r).tolist(),
                }
            )
            if n_r == 0:
                continue
            lk32 = exact_key32(lb.column("k").data)
            rk32 = exact_key32(rb.column("rk").data)
            lorder = np.argsort(lk32, kind="stable")
            work.append(
                (b, lb, rb, lk32[lorder], rk32, lorder, None,
                 lb.column("k").data, rb.column("rk").data)
            )
            expected[b] = _merge_join_batches(lb, rb, ["k"], ["rk"], False, True)
        tmp_session.set_conf(C.EXEC_TPU_ENABLED, True)
        try:
            parts = try_batched_plain_join(work, [], tmp_session)
        finally:
            tmp_session.set_conf(C.EXEC_TPU_ENABLED, False)
        assert parts is not None
        assert set(parts) == set(expected)
        for b in parts:
            assert parts[b].to_pydict() == expected[b].to_pydict()
