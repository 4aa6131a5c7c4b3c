"""Fused-XLA execution path tests: device results must match the host
executor (tolerance for float32 device accumulation)."""

import numpy as np
import pytest

from hyperspace_tpu import constants as C
from hyperspace_tpu.columnar import io as cio
from hyperspace_tpu.columnar.table import ColumnBatch
from hyperspace_tpu.plan import col, lit, Avg, Count, Max, Min, Sum


@pytest.fixture()
def df(tmp_session, tmp_path):
    rng = np.random.default_rng(5)
    n = 5000
    data = {
        "d": rng.integers(8000, 10000, n).astype(int).tolist(),
        "x": rng.uniform(0, 100, n).tolist(),
        "y": rng.uniform(0, 1, n).tolist(),
    }
    cio.write_parquet(ColumnBatch.from_pydict(data), str(tmp_path / "t" / "p.parquet"))
    return tmp_session.read.parquet(str(tmp_path / "t"))


def q(d):
    return (
        d.filter((col("d") >= 8500) & (col("d") < 9500) & (col("y") < 0.5))
        .select("d", "x", "y")
        .agg(
            Sum(col("x") * col("y")).alias("s"),
            Count(lit(1)).alias("n"),
            Min(col("x")).alias("mn"),
            Max(col("x")).alias("mx"),
            Avg(col("x")).alias("avg"),
        )
    )


class TestTpuExec:
    def test_matches_host(self, df):
        session = df.session
        host = q(df).to_pydict()
        session.set_conf(C.EXEC_TPU_ENABLED, True)
        dev = q(df).to_pydict()
        assert dev["n"] == host["n"]
        assert abs(dev["s"][0] - host["s"][0]) / abs(host["s"][0]) < 1e-4
        assert abs(dev["mn"][0] - host["mn"][0]) < 1e-4
        assert abs(dev["mx"][0] - host["mx"][0]) < 1e-4
        assert abs(dev["avg"][0] - host["avg"][0]) / abs(host["avg"][0]) < 1e-4

    def test_kernel_cache_reused(self, df):
        from hyperspace_tpu.plan import tpu_exec

        session = df.session
        session.set_conf(C.EXEC_TPU_ENABLED, True)
        tpu_exec._KERNEL_CACHE.clear()
        q(df).collect()
        assert len(tpu_exec._KERNEL_CACHE) == 1
        q(df).collect()  # same structure -> no new kernel
        assert len(tpu_exec._KERNEL_CACHE) == 1

    def test_unsupported_falls_back(self, tmp_session, tmp_path):
        # string column in batch -> host path, still correct
        cio.write_parquet(
            ColumnBatch.from_pydict({"a": [1, 2, 3], "s": ["x", "y", "x"]}),
            str(tmp_path / "t2" / "p.parquet"),
        )
        d = tmp_session.read.parquet(str(tmp_path / "t2"))
        tmp_session.set_conf(C.EXEC_TPU_ENABLED, True)
        out = d.filter(col("a") > 1).select("a", "s").agg(Count(lit(1)).alias("n")).to_pydict()
        assert out["n"] == [2]

    def test_grouped_falls_back(self, df):
        session = df.session
        session.set_conf(C.EXEC_TPU_ENABLED, True)
        out = df.group_by("d").agg(Count(lit(1)).alias("n")).collect()
        assert out.num_rows > 0

    def test_graft_entry(self):
        import __graft_entry__ as g

        fn, args = g.entry()
        matched, out = fn(*args)
        assert int(matched) > 0
        assert len(out) == 2 and float(np.asarray(out[1])) > 0

    def test_dryrun_multichip(self):
        import __graft_entry__ as g

        g.dryrun_multichip(8)


class TestTpuExecEdgeCases:
    """Regression tests for device/host semantic parity edge cases."""

    def test_zero_match_returns_null(self, df):
        session = df.session
        session.set_conf(C.EXEC_TPU_ENABLED, True)
        out = (
            df.filter(col("d") > 10**6)
            .agg(Min(col("x")).alias("mn"), Count(lit(1)).alias("n"))
            .to_pydict()
        )
        assert out == {"mn": [None], "n": [0]}

    def test_filter_above_project_falls_back_correctly(self, df):
        session = df.session
        session.set_conf(C.EXEC_TPU_ENABLED, True)
        q2 = (
            df.select((col("x") * 2).alias("z"))
            .filter(col("z") > 100)
            .agg(Sum(col("z")).alias("s"), Count(lit(1)).alias("n"))
        )
        dev = q2.to_pydict()
        session.set_conf(C.EXEC_TPU_ENABLED, False)
        host = q2.to_pydict()
        assert dev["n"] == host["n"]
        assert abs(dev["s"][0] - host["s"][0]) / abs(host["s"][0]) < 1e-9

    def test_int_min_max_exact_above_2_24(self, tmp_session, tmp_path):
        vals = [20_000_001, 20_000_005, 20_000_003]
        cio.write_parquet(
            ColumnBatch.from_pydict({"a": vals}),
            str(tmp_path / "big" / "p.parquet"),
        )
        d = tmp_session.read.parquet(str(tmp_path / "big"))
        tmp_session.set_conf(C.EXEC_TPU_ENABLED, True)
        out = d.agg(Min(col("a")).alias("mn"), Max(col("a")).alias("mx")).to_pydict()
        assert out == {"mn": [20_000_001], "mx": [20_000_005]}

    def test_int_sum_uses_host_path(self, tmp_session, tmp_path):
        # int sums can wrap in 32-bit on device -> must route to host
        n = 10_000
        cio.write_parquet(
            ColumnBatch.from_pydict({"a": [1_000_000] * n}),
            str(tmp_path / "s" / "p.parquet"),
        )
        d = tmp_session.read.parquet(str(tmp_path / "s"))
        tmp_session.set_conf(C.EXEC_TPU_ENABLED, True)
        out = d.agg(Sum(col("a")).alias("s")).to_pydict()
        assert out["s"] == [10_000_000_000]  # > 2**31: exact only on host

    def test_int64_min_sentinel_not_corrupted(self, tmp_session, tmp_path):
        cio.write_parquet(
            ColumnBatch.from_pydict({"a": [-(2**63), 5]}),
            str(tmp_path / "m" / "p.parquet"),
        )
        d = tmp_session.read.parquet(str(tmp_path / "m"))
        tmp_session.set_conf(C.EXEC_TPU_ENABLED, True)
        out = d.agg(Min(col("a")).alias("mn")).to_pydict()
        assert out["mn"] == [-(2**63)]  # guard must reject, host is exact


    def test_int_avg_uses_host_path(self, tmp_session, tmp_path):
        n = 10_000
        cio.write_parquet(
            ColumnBatch.from_pydict({"a": [1_000_000] * n}),
            str(tmp_path / "avg" / "p.parquet"),
        )
        d = tmp_session.read.parquet(str(tmp_path / "avg"))
        tmp_session.set_conf(C.EXEC_TPU_ENABLED, True)
        out = d.agg(Avg(col("a")).alias("m")).to_pydict()
        assert out["m"] == [1_000_000.0]  # int32 device accumulator would wrap


class TestPallasTierWired:
    def test_pallas_path_matches_generic(self, tmp_session, tmp_path, monkeypatch):
        """filter -> sum(a*b)+count must route to the Pallas kernel when
        forced (interpreter off-TPU) and produce the same answer."""
        from hyperspace_tpu.plan import tpu_exec

        monkeypatch.setenv("HYPERSPACE_FORCE_PALLAS", "1")
        tpu_exec._KERNEL_CACHE.clear()
        rng = np.random.default_rng(9)
        n = 3000
        cio.write_parquet(
            ColumnBatch.from_pydict(
                {
                    "d": rng.integers(0, 100, n).astype(int).tolist(),
                    "x": rng.uniform(0, 10, n).tolist(),
                    "y": rng.uniform(0, 1, n).tolist(),
                }
            ),
            str(tmp_path / "pw" / "p.parquet"),
        )
        d = tmp_session.read.parquet(str(tmp_path / "pw"))
        tmp_session.set_conf(C.EXEC_TPU_ENABLED, True)
        qq = (
            d.filter((col("d") >= 20) & (col("d") < 50))
            .agg(Sum(col("x") * col("y")).alias("s"), Count(lit(1)).alias("n"))
        )
        dev = qq.to_pydict()
        monkeypatch.delenv("HYPERSPACE_FORCE_PALLAS")
        tmp_session.set_conf(C.EXEC_TPU_ENABLED, False)
        host = qq.to_pydict()
        tpu_exec._KERNEL_CACHE.clear()
        assert dev["n"] == host["n"]
        assert abs(dev["s"][0] - host["s"][0]) / abs(host["s"][0]) < 1e-4



class TestGroupedDeviceExec:
    def test_grouped_matches_host(self, tmp_session, tmp_path):
        rng = np.random.default_rng(31)
        n = 8000
        cio.write_parquet(
            ColumnBatch.from_pydict(
                {
                    "g": rng.choice(["a", "b", "c"], n).tolist(),
                    "k": rng.integers(0, 50, n).astype(int).tolist(),
                    "x": rng.uniform(0, 10, n).tolist(),
                }
            ),
            str(tmp_path / "g" / "p.parquet"),
        )
        d = tmp_session.read.parquet(str(tmp_path / "g"))
        q = lambda: (
            d.filter(col("k") < 25)
            .select("g", "x")
            .group_by("g")
            .agg(
                Sum(col("x")).alias("s"),
                Count(lit(1)).alias("n"),
                Min(col("x")).alias("mn"),
                Avg(col("x")).alias("a"),
            )
            .sort("g")
        )
        host = q().to_pydict()
        tmp_session.set_conf(C.EXEC_TPU_ENABLED, True)
        dev = q().to_pydict()
        tmp_session.set_conf(C.EXEC_TPU_ENABLED, False)
        assert dev["g"] == host["g"]
        assert dev["n"] == host["n"]
        assert np.allclose(dev["s"], host["s"], rtol=1e-4)
        assert np.allclose(dev["mn"], host["mn"], rtol=1e-5)
        assert np.allclose(dev["a"], host["a"], rtol=1e-4)

    def test_grouped_empty_groups_dropped(self, tmp_session, tmp_path):
        cio.write_parquet(
            ColumnBatch.from_pydict({"g": [1, 2, 3], "x": [1.0, 2.0, 3.0]}),
            str(tmp_path / "ge" / "p.parquet"),
        )
        d = tmp_session.read.parquet(str(tmp_path / "ge"))
        tmp_session.set_conf(C.EXEC_TPU_ENABLED, True)
        out = (
            d.filter(col("x") > 1.5)
            .select("g", "x")
            .group_by("g")
            .agg(Sum(col("x")).alias("s"))
            .sort("g")
            .to_pydict()
        )
        tmp_session.set_conf(C.EXEC_TPU_ENABLED, False)
        assert out == {"g": [2, 3], "s": [2.0, 3.0]}

    def test_grouped_string_agg_falls_back(self, tmp_session, tmp_path):
        # Min over a string column cannot ship; host path must serve it
        cio.write_parquet(
            ColumnBatch.from_pydict({"g": [1, 1], "s": ["b", "a"]}),
            str(tmp_path / "gs" / "p.parquet"),
        )
        d = tmp_session.read.parquet(str(tmp_path / "gs"))
        tmp_session.set_conf(C.EXEC_TPU_ENABLED, True)
        out = d.group_by("g").agg(Min(col("s")).alias("mn")).to_pydict()
        tmp_session.set_conf(C.EXEC_TPU_ENABLED, False)
        assert out == {"g": [1], "mn": ["a"]}


    def test_aliased_group_key_falls_back(self, tmp_session, tmp_path):
        """A group key produced by a renaming projection must route to the
        host path, not crash the device path (regression)."""
        cio.write_parquet(
            ColumnBatch.from_pydict({"k": [1, 1, 2], "x": [1.0, 2.0, 3.0]}),
            str(tmp_path / "ag" / "p.parquet"),
        )
        d = tmp_session.read.parquet(str(tmp_path / "ag"))
        tmp_session.set_conf(C.EXEC_TPU_ENABLED, True)
        out = (
            d.select(col("k").alias("g"), col("x"))
            .group_by("g")
            .agg(Sum(col("x")).alias("s"))
            .sort("g")
            .to_pydict()
        )
        tmp_session.set_conf(C.EXEC_TPU_ENABLED, False)
        assert out == {"g": [1, 2], "s": [3.0, 3.0]}

    def test_q1_shape_uses_grouped_kernel(self, tmp_session, tmp_path):
        from hyperspace_tpu.plan import tpu_exec

        rng = np.random.default_rng(13)
        n = 4000
        cio.write_parquet(
            ColumnBatch.from_pydict(
                {
                    "f": rng.choice(["A", "B"], n).tolist(),
                    "q": rng.uniform(1, 50, n).tolist(),
                    "dt": rng.integers(0, 100, n).astype(int).tolist(),
                }
            ),
            str(tmp_path / "q1" / "p.parquet"),
        )
        d = tmp_session.read.parquet(str(tmp_path / "q1"))
        tmp_session.set_conf(C.EXEC_TPU_ENABLED, True)
        tpu_exec._KERNEL_CACHE.clear()
        out = (
            d.filter(col("dt") <= 80)
            .select("f", "q")
            .group_by("f")
            .agg(Sum(col("q")).alias("s"))
            .sort("f")
            .to_pydict()
        )
        tmp_session.set_conf(C.EXEC_TPU_ENABLED, False)
        assert any(
            isinstance(k, tuple) and k and k[0] == "grouped"
            for k in tpu_exec._KERNEL_CACHE
        ), "grouped device kernel must fire for the Q1 shape"
        assert out["f"] == ["A", "B"]



class TestMeshExecution:
    """Fragments execute over the 8-device mesh when conf requests it."""

    def _data(self, tmp_session, tmp_path, name="mesh"):
        rng = np.random.default_rng(41)
        n = 9000
        cio.write_parquet(
            ColumnBatch.from_pydict(
                {
                    "g": rng.choice(["a", "b", "c"], n).tolist(),
                    "k": rng.integers(0, 50, n).astype(int).tolist(),
                    "x": rng.uniform(0, 10, n).tolist(),
                }
            ),
            str(tmp_path / name / "p.parquet"),
        )
        return tmp_session.read.parquet(str(tmp_path / name))

    def test_global_aggregate_on_mesh(self, tmp_session, tmp_path):
        from hyperspace_tpu.plan import tpu_exec

        d = self._data(tmp_session, tmp_path)
        q = lambda: (
            d.filter(col("k") < 25)
            .select("x", "k")
            .agg(Sum(col("x")).alias("s"), Count(lit(1)).alias("n"),
                 Min(col("x")).alias("mn"), Max(col("x")).alias("mx"))
        )
        host = q().to_pydict()
        tmp_session.set_conf(C.EXEC_TPU_ENABLED, True)
        tmp_session.set_conf("hyperspace.tpu.exec.meshDevices", 8)
        tpu_exec._KERNEL_CACHE.clear()
        dev = q().to_pydict()
        tmp_session.set_conf(C.EXEC_TPU_ENABLED, False)
        tmp_session.set_conf("hyperspace.tpu.exec.meshDevices", 0)
        assert any(isinstance(k, tuple) and k and k[0] == "mesh" for k in tpu_exec._KERNEL_CACHE)
        assert dev["n"] == host["n"]
        assert abs(dev["s"][0] - host["s"][0]) / abs(host["s"][0]) < 1e-4
        assert abs(dev["mn"][0] - host["mn"][0]) < 1e-4
        assert abs(dev["mx"][0] - host["mx"][0]) < 1e-4

    def test_grouped_aggregate_on_mesh(self, tmp_session, tmp_path):
        from hyperspace_tpu.plan import tpu_exec

        d = self._data(tmp_session, tmp_path, "mesh2")
        q = lambda: (
            d.filter(col("k") < 40)
            .select("g", "x")
            .group_by("g")
            .agg(Sum(col("x")).alias("s"), Count(lit(1)).alias("n"),
                 Avg(col("x")).alias("a"))
            .sort("g")
        )
        host = q().to_pydict()
        tmp_session.set_conf(C.EXEC_TPU_ENABLED, True)
        tmp_session.set_conf("hyperspace.tpu.exec.meshDevices", 8)
        tpu_exec._KERNEL_CACHE.clear()
        dev = q().to_pydict()
        tmp_session.set_conf(C.EXEC_TPU_ENABLED, False)
        tmp_session.set_conf("hyperspace.tpu.exec.meshDevices", 0)
        assert any(isinstance(k, tuple) and k and k[0] == "mesh" for k in tpu_exec._KERNEL_CACHE)
        assert dev["g"] == host["g"] and dev["n"] == host["n"]
        assert np.allclose(dev["s"], host["s"], rtol=1e-4)
        assert np.allclose(dev["a"], host["a"], rtol=1e-4)

    def test_mesh_int_sum_and_avg_exact(self, tmp_session, tmp_path):
        """Int SUM/AVG over the mesh: per-shard 8-bit chunk sums psum'd and
        recombined on the host — exact where an f32 psum would round (the
        Q1-shaped mesh gap closed in round 3)."""
        from hyperspace_tpu.plan import tpu_exec

        rng = np.random.default_rng(7)
        n = 9000
        qty = rng.integers(16_000_000, 17_000_000, n)
        cio.write_parquet(
            ColumnBatch.from_pydict(
                {
                    "g": rng.choice(["a", "b", "c"], n).tolist(),
                    "k": rng.integers(0, 50, n).astype(int).tolist(),
                    "qty": qty.astype(int).tolist(),
                }
            ),
            str(tmp_path / "meshint" / "p.parquet"),
        )
        d = tmp_session.read.parquet(str(tmp_path / "meshint"))
        q = lambda: (
            d.filter(col("k") < 40)
            .select("g", "qty")
            .group_by("g")
            .agg(Sum(col("qty")).alias("s"), Avg(col("qty")).alias("a"),
                 Count(lit(1)).alias("n"))
            .sort("g")
        )
        host = q().to_pydict()
        tmp_session.set_conf(C.EXEC_TPU_ENABLED, True)
        tmp_session.set_conf("hyperspace.tpu.exec.meshDevices", 8)
        tpu_exec._KERNEL_CACHE.clear()
        dev = q().to_pydict()
        tmp_session.set_conf(C.EXEC_TPU_ENABLED, False)
        tmp_session.set_conf("hyperspace.tpu.exec.meshDevices", 0)
        assert any(
            isinstance(k, tuple) and k and k[0] == "mesh"
            for k in tpu_exec._KERNEL_CACHE
        )
        assert dev["g"] == host["g"] and dev["n"] == host["n"]
        assert dev["s"] == host["s"]  # exact int64 equality, not approx
        assert dev["a"] == host["a"]  # f64(exact sum)/count on both tiers

    def test_mesh_zero_match_global(self, tmp_session, tmp_path):
        d = self._data(tmp_session, tmp_path, "mesh3")
        tmp_session.set_conf(C.EXEC_TPU_ENABLED, True)
        tmp_session.set_conf("hyperspace.tpu.exec.meshDevices", 8)
        out = d.filter(col("k") > 10**6).agg(
            Min(col("x")).alias("mn"), Count(lit(1)).alias("n")
        ).to_pydict()
        tmp_session.set_conf(C.EXEC_TPU_ENABLED, False)
        tmp_session.set_conf("hyperspace.tpu.exec.meshDevices", 0)
        assert out == {"mn": [None], "n": [0]}


class TestBackendResolution:
    """The backend resolves through plain jax calls: no watchdog, no
    silent host fallback, no interpreter off the CPU."""

    def test_resolves_after_reset(self):
        from hyperspace_tpu.utils import backend as B

        B._reset_for_testing()
        assert B.platform() == "cpu"  # conftest forces the cpu platform
        assert B.device_count() == 8

    def test_backend_init_failure_raises(self, monkeypatch):
        import jax

        from hyperspace_tpu.utils import backend as B

        def broken():
            raise RuntimeError("no backend")

        monkeypatch.setattr(jax, "default_backend", broken)
        B._reset_for_testing()
        try:
            with pytest.raises(RuntimeError, match="no backend"):
                B.platform()
        finally:
            monkeypatch.undo()
            B._reset_for_testing()

    @pytest.mark.parametrize(
        "platform,interpret", [("cpu", True), ("tpu", False), ("gpu", False)]
    )
    def test_pallas_interprets_only_on_cpu(self, monkeypatch, platform, interpret):
        from hyperspace_tpu.ops import pallas_kernels
        from hyperspace_tpu.utils import backend as B

        monkeypatch.setattr(B, "platform", lambda: platform)
        assert pallas_kernels._interpret() is interpret

    def test_active_mesh_raises_when_devices_missing(self, tmp_session):
        from hyperspace_tpu.parallel.mesh import active_mesh

        tmp_session.set_conf(C.EXEC_MESH_DEVICES, 16)  # conftest gives 8
        with pytest.raises(ValueError, match="meshDevices=16"):
            active_mesh(tmp_session)
        tmp_session.set_conf(C.EXEC_MESH_DEVICES, 8)
        assert active_mesh(tmp_session).devices.size == 8


class TestStringPredicatesOnDevice:
    """String equality/membership predicates ship as dictionary codes."""

    @pytest.fixture()
    def sdf(self, tmp_session, tmp_path):
        rng = np.random.default_rng(8)
        n = 4000
        data = {
            "cat": rng.choice(["a", "b", "c", "d"], n).tolist(),
            "x": rng.uniform(0, 100, n).tolist(),
        }
        cio.write_parquet(ColumnBatch.from_pydict(data), str(tmp_path / "s" / "p.parquet"))
        return tmp_session.read.parquet(str(tmp_path / "s"))

    def _check(self, df, q):
        session = df.session
        session.set_conf(C.EXEC_TPU_ENABLED, False)
        host = q(df).to_pydict()
        session.set_conf(C.EXEC_TPU_ENABLED, True)
        from hyperspace_tpu.plan import tpu_exec

        before = len(tpu_exec._KERNEL_CACHE)
        dev = q(df).to_pydict()
        session.set_conf(C.EXEC_TPU_ENABLED, False)
        assert len(tpu_exec._KERNEL_CACHE) >= before  # device path engaged
        for k in host:
            assert len(host[k]) == len(dev[k])
            for a, b in zip(host[k], dev[k]):
                if isinstance(b, float):
                    assert a == pytest.approx(b, rel=1e-5)
                else:
                    assert a == b
        return dev

    def test_eq_string(self, sdf):
        q = lambda d: d.filter(col("cat") == "b").agg(
            Sum(col("x")).alias("s"), Count(lit(1)).alias("n")
        )
        self._check(sdf, q)

    def test_ne_and_in_string(self, sdf):
        q = lambda d: d.filter(
            (col("cat") != "a") & col("cat").isin(["b", "c", "zzz"])
        ).agg(Sum(col("x")).alias("s"), Count(lit(1)).alias("n"))
        self._check(sdf, q)

    def test_missing_value_folds_to_empty(self, sdf):
        q = lambda d: d.filter(col("cat") == "nope").agg(Count(lit(1)).alias("n"))
        out = self._check(sdf, q)
        assert out["n"] == [0]

    def test_grouped_with_string_pred(self, sdf):
        q = lambda d: (
            d.filter(col("cat") != "d")
            .group_by("cat")
            .agg(Sum(col("x")).alias("s"), Count(lit(1)).alias("n"))
        )
        self._check(sdf, q)


class TestIntSumOnDevice:
    def test_int_sum_exact(self, tmp_session, tmp_path):
        """Int SUM must be exact on device (chunked accumulation), including
        values above 2^24 where f32 would round."""
        rng = np.random.default_rng(4)
        n = 30000
        vals = rng.integers(-(2**30), 2**30, n)
        data = {"v": vals.tolist(), "g": rng.integers(0, 5, n).tolist()}
        cio.write_parquet(ColumnBatch.from_pydict(data), str(tmp_path / "t" / "p.parquet"))
        df = tmp_session.read.parquet(str(tmp_path / "t"))

        q_global = lambda d: d.filter(col("v") != 12345).agg(Sum(col("v")).alias("s"))
        q_grouped = lambda d: d.group_by("g").agg(Sum(col("v")).alias("s"))

        tmp_session.set_conf(C.EXEC_TPU_ENABLED, False)
        host_g = q_global(df).to_pydict()
        host_gr = q_grouped(df).to_pydict()
        tmp_session.set_conf(C.EXEC_TPU_ENABLED, True)
        dev_g = q_global(df).to_pydict()
        dev_gr = q_grouped(df).to_pydict()
        tmp_session.set_conf(C.EXEC_TPU_ENABLED, False)
        assert dev_g["s"] == host_g["s"]  # exact int64 equality
        assert sorted(zip(dev_gr["g"], dev_gr["s"])) == sorted(
            zip(host_gr["g"], host_gr["s"])
        )

    def test_int_avg_exact_on_device(self, tmp_session, tmp_path):
        """Int AVG accumulates via the exact chunked sums and divides on the
        host — values above 2^24 where an f32 sum would round visibly."""
        rng = np.random.default_rng(44)
        n = 30000
        vals = rng.integers(16_000_000, 17_000_000, n)
        data = {"v": vals.tolist(), "g": rng.integers(0, 5, n).tolist()}
        cio.write_parquet(ColumnBatch.from_pydict(data), str(tmp_path / "a" / "p.parquet"))
        df = tmp_session.read.parquet(str(tmp_path / "a"))
        from hyperspace_tpu.plan import tpu_exec

        q_global = lambda d: d.filter(col("v") >= 0).agg(Avg(col("v")).alias("m"))
        q_grouped = lambda d: d.group_by("g").agg(Avg(col("v")).alias("m"))
        tmp_session.set_conf(C.EXEC_TPU_ENABLED, False)
        host_g = q_global(df).to_pydict()
        host_gr = q_grouped(df).to_pydict()
        tpu_exec._KERNEL_CACHE.clear()
        tmp_session.set_conf(C.EXEC_TPU_ENABLED, True)
        dev_g = q_global(df).to_pydict()
        dev_gr = q_grouped(df).to_pydict()
        tmp_session.set_conf(C.EXEC_TPU_ENABLED, False)
        assert len(tpu_exec._KERNEL_CACHE) > 0  # the device path actually ran
        assert dev_g["m"] == host_g["m"]  # exact: f64(exact sum)/count
        assert sorted(zip(dev_gr["g"], dev_gr["m"])) == sorted(
            zip(host_gr["g"], host_gr["m"])
        )


class TestLiteralMagnitudeScreen:
    def test_big_literal_declines_without_latching_breaker(
        self, tmp_session, tmp_path
    ):
        """An int literal beyond 2^31 against a downcast int64 column is an
        unsupported shape: it must decline to the host path BEFORE tracing,
        leaving the circuit breaker untouched (strict mode would otherwise
        raise on the benign overflow)."""
        from hyperspace_tpu.utils import backend

        cio.write_parquet(
            ColumnBatch.from_pydict({"v": [1, 2, 3, 4], "x": [1.0, 2.0, 3.0, 4.0]}),
            str(tmp_path / "lit" / "p.parquet"),
        )
        df = tmp_session.read.parquet(str(tmp_path / "lit"))
        tmp_session.set_conf(C.EXEC_TPU_ENABLED, True)
        out = (
            df.filter(col("v") < 5_000_000_000)
            .agg(Sum(col("x")).alias("s"), Count(lit(1)).alias("n"))
            .to_pydict()
        )
        tmp_session.set_conf(C.EXEC_TPU_ENABLED, False)
        assert out["n"] == [4] and out["s"] == [10.0]
        assert backend.device_healthy()  # breaker must not have latched


class TestDeviceTopK:
    @pytest.mark.parametrize("asc", [True, False])
    def test_matches_host(self, tmp_session, tmp_path, asc):
        rng = np.random.default_rng(2)
        n = 20000
        data = {
            "k": rng.integers(-(2**31), 2**31 - 1, n).astype(np.int32).tolist(),
            "v": rng.uniform(size=n).tolist(),
        }
        cio.write_parquet(ColumnBatch.from_pydict(data), str(tmp_path / "t" / "p.parquet"))
        df = tmp_session.read.parquet(str(tmp_path / "t"))
        q = lambda d: d.sort("k", ascending=asc).limit(25)
        tmp_session.set_conf(C.EXEC_TPU_ENABLED, False)
        host = q(df).to_pydict()
        from hyperspace_tpu.plan import tpu_exec

        tpu_exec._TOPK_CACHE.clear()
        tmp_session.set_conf(C.EXEC_TPU_ENABLED, True)
        dev = q(df).to_pydict()
        tmp_session.set_conf(C.EXEC_TPU_ENABLED, False)
        assert len(tpu_exec._TOPK_CACHE) == 1  # the device kernel ran
        assert dev == host

    def test_float32_keys_and_ties(self, tmp_session, tmp_path):
        n = 8192
        # heavy ties: tie order must match the host's stable sort
        data = {
            "k": ([1.5, -2.5, 0.0, 3.25] * (n // 4)),
            "i": list(range(n)),
        }
        import numpy as _np

        batch = ColumnBatch.from_pydict(data)
        cio.write_parquet(batch, str(tmp_path / "t" / "p.parquet"))
        df = tmp_session.read.parquet(str(tmp_path / "t"))
        q = lambda d: d.sort("k", ascending=False).limit(12)
        tmp_session.set_conf(C.EXEC_TPU_ENABLED, False)
        host = q(df).to_pydict()
        tmp_session.set_conf(C.EXEC_TPU_ENABLED, True)
        dev = q(df).to_pydict()
        tmp_session.set_conf(C.EXEC_TPU_ENABLED, False)
        assert dev == host


class TestDeviceGeneralSort:
    """ORDER BY without LIMIT on device: multi-key, descending, full-range
    int64, and exact f64 keys — output bit-identical to the host lexsort,
    tie order included."""

    def _roundtrip(self, tmp_session, tmp_path, name, data, orders):
        cio.write_parquet(
            ColumnBatch.from_pydict(data), str(tmp_path / name / "p.parquet")
        )
        df = tmp_session.read.parquet(str(tmp_path / name))
        q = lambda d: d.sort(*[o[0] for o in orders], ascending=[o[1] for o in orders])
        tmp_session.set_conf(C.EXEC_TPU_ENABLED, False)
        host = q(df).to_pydict()
        from hyperspace_tpu.plan import tpu_exec

        tpu_exec._SORT_CACHE.clear()
        tmp_session.set_conf(C.EXEC_TPU_ENABLED, True)
        dev = q(df).to_pydict()
        tmp_session.set_conf(C.EXEC_TPU_ENABLED, False)
        assert len(tpu_exec._SORT_CACHE) == 1  # the device sort actually ran
        assert dev == host  # bit-identical rows AND order

    def test_multikey_mixed_direction(self, tmp_session, tmp_path):
        rng = np.random.default_rng(31)
        n = 8000
        self._roundtrip(
            tmp_session,
            tmp_path,
            "ms",
            {
                "a": rng.integers(0, 40, n).tolist(),  # heavy ties
                "b": rng.integers(-(2**40), 2**40, n).tolist(),  # wide int64
                "v": rng.uniform(size=n).tolist(),
            },
            [("a", True), ("b", False)],
        )

    def test_f64_keys_exact(self, tmp_session, tmp_path):
        rng = np.random.default_rng(37)
        n = 8000
        # near-tie f64 values that collapse in f32: the three-word split
        # must still order them exactly
        base = rng.uniform(0, 1, n)
        vals = np.round(base, 2) + rng.integers(0, 3, n) * 1e-12
        self._roundtrip(
            tmp_session,
            tmp_path,
            "f64",
            {"x": vals.tolist(), "i": list(range(n))},
            [("x", False)],
        )

    def test_f64_non_representable_falls_back(self, tmp_session, tmp_path):
        """Keys needing more than 76 bits decline to the host (exactness
        gate), and the result is still the host-exact ordering."""
        from hyperspace_tpu.plan import tpu_exec

        n = 5000
        rng = np.random.default_rng(41)
        # full-mantissa randomness: hi+mid+lo == x holds for most doubles
        # (52 < 72 encodable bits) but subnormal-residue cases may decline;
        # either way the RESULT must equal the host sort
        vals = rng.uniform(1e300, 1.1e300, n)
        cio.write_parquet(
            ColumnBatch.from_pydict({"x": vals.tolist()}),
            str(tmp_path / "f64b" / "p.parquet"),
        )
        df = tmp_session.read.parquet(str(tmp_path / "f64b"))
        tmp_session.set_conf(C.EXEC_TPU_ENABLED, False)
        host = df.sort("x").to_pydict()
        tmp_session.set_conf(C.EXEC_TPU_ENABLED, True)
        dev = df.sort("x").to_pydict()
        tmp_session.set_conf(C.EXEC_TPU_ENABLED, False)
        assert dev == host

    def test_string_key_falls_back(self, tmp_session, tmp_path):
        from hyperspace_tpu.plan import tpu_exec

        rng = np.random.default_rng(43)
        n = 6000
        cio.write_parquet(
            ColumnBatch.from_pydict(
                {"s": rng.choice(["aa", "bb", "cc"], n).tolist(), "i": list(range(n))}
            ),
            str(tmp_path / "str" / "p.parquet"),
        )
        df = tmp_session.read.parquet(str(tmp_path / "str"))
        tmp_session.set_conf(C.EXEC_TPU_ENABLED, False)
        host = df.sort("s").to_pydict()
        tpu_exec._SORT_CACHE.clear()
        tmp_session.set_conf(C.EXEC_TPU_ENABLED, True)
        dev = df.sort("s").to_pydict()
        tmp_session.set_conf(C.EXEC_TPU_ENABLED, False)
        assert len(tpu_exec._SORT_CACHE) == 0  # declined: host factorization
        assert dev == host


class TestWideInt64Predicates:
    """Full-range int64 columns ship as (hi, lo) word pairs when referenced
    only in literal comparisons; the two-word compare is exact."""

    def test_wide_filter_matches_host(self, tmp_session, tmp_path):
        rng = np.random.default_rng(6)
        n = 8000
        wide = rng.integers(-(2**62), 2**62, n)
        # plant exact boundary values
        wide[0], wide[1], wide[2] = 2**40 + 7, -(2**40) - 7, 2**31  # > int32
        data = {
            "w": wide.tolist(),
            "x": rng.uniform(0, 10, n).tolist(),
        }
        cio.write_parquet(ColumnBatch.from_pydict(data), str(tmp_path / "t" / "p.parquet"))
        df = tmp_session.read.parquet(str(tmp_path / "t"))
        queries = [
            lambda d: d.filter(col("w") == 2**40 + 7).agg(Count(lit(1)).alias("n")),
            lambda d: d.filter(col("w") > 0).agg(Count(lit(1)).alias("n"), Sum(col("x")).alias("s")),
            lambda d: d.filter((col("w") >= -(2**40) - 7) & (col("w") <= 2**31)).agg(
                Count(lit(1)).alias("n")
            ),
            lambda d: d.filter(col("w") != 2**31).agg(Count(lit(1)).alias("n")),
        ]
        from hyperspace_tpu.plan import tpu_exec

        for q in queries:
            tmp_session.set_conf(C.EXEC_TPU_ENABLED, False)
            host = q(df).to_pydict()
            tmp_session.set_conf(C.EXEC_TPU_ENABLED, True)
            before = len(tpu_exec._KERNEL_CACHE)
            dev = q(df).to_pydict()
            tmp_session.set_conf(C.EXEC_TPU_ENABLED, False)
            assert len(tpu_exec._KERNEL_CACHE) > before  # device path engaged
            assert dev["n"] == host["n"]
            if "s" in host:
                assert dev["s"][0] == pytest.approx(host["s"][0], rel=1e-5)

    def test_wide_in_aggregate_falls_back(self, tmp_session, tmp_path):
        """A wide column feeding an aggregate cannot ship; the host path
        answers (sum stays exact int64)."""
        data = {"w": [2**40, 2**41, -(2**40)], "g": [1, 1, 2]}
        cio.write_parquet(ColumnBatch.from_pydict(data), str(tmp_path / "t" / "p.parquet"))
        df = tmp_session.read.parquet(str(tmp_path / "t"))
        q = lambda d: d.group_by("g").agg(Sum(col("w")).alias("s"))
        tmp_session.set_conf(C.EXEC_TPU_ENABLED, True)
        out = q(df).to_pydict()
        tmp_session.set_conf(C.EXEC_TPU_ENABLED, False)
        assert sorted(zip(out["g"], out["s"])) == [(1, 2**40 + 2**41), (2, -(2**40))]


class TestWide64PropertySweep:
    def test_random_comparisons_match_numpy(self):
        """Randomized two-word compares across the int64 domain must agree
        with numpy exactly (including extremes and word boundaries)."""
        import numpy as np
        import jax.numpy as jnp

        from hyperspace_tpu.plan import expr as X
        from hyperspace_tpu.plan.tpu_exec import Wide64
        from hyperspace_tpu.ops.hashing import split64_np

        rng = np.random.default_rng(12)
        specials = np.array(
            [0, 1, -1, 2**31, -(2**31), 2**31 - 1, 2**32, -(2**32),
             2**62, -(2**62), 2**63 - 1, -(2**63)], dtype=np.int64,
        )
        vals = np.concatenate(
            [rng.integers(-(2**63), 2**63 - 1, 2000, dtype=np.int64), specials]
        )
        lo, hi = split64_np(vals)
        w = Wide64(jnp.asarray(hi), jnp.asarray(lo.view(np.uint32)))
        lits = np.concatenate(
            [rng.integers(-(2**63), 2**63 - 1, 40, dtype=np.int64), specials]
        )
        ops = {
            X.Eq: np.equal, X.Ne: np.not_equal, X.Lt: np.less,
            X.Le: np.less_equal, X.Gt: np.greater, X.Ge: np.greater_equal,
        }
        for lit in lits[:20]:
            for kind, npop in ops.items():
                got = np.asarray(w.compare(kind, int(lit)))
                np.testing.assert_array_equal(
                    got, npop(vals, lit), err_msg=f"{kind} vs {lit}"
                )


class TestDeviceCircuitBreaker:
    def test_device_failure_degrades_to_host(self, df, monkeypatch):
        """A device kernel blowing up mid-query (device lost) must fall
        back to the host executor and latch the device tier off — queries
        keep answering correctly."""
        from hyperspace_tpu.plan import tpu_exec
        from hyperspace_tpu.utils import backend as B

        session = df.session
        expected = q(df).to_pydict()
        monkeypatch.delenv("HYPERSPACE_DEVICE_STRICT", raising=False)

        def boom(*a, **k):
            raise RuntimeError("device lost")

        monkeypatch.setattr(tpu_exec, "_try_execute_tpu_inner", boom)
        try:
            session.set_conf(C.EXEC_TPU_ENABLED, True)
            got = q(df).to_pydict()
            assert not B.device_healthy()
            assert got["n"] == expected["n"]
            # subsequent queries skip the device tier entirely, still correct
            got2 = q(df).to_pydict()
            assert got2["n"] == expected["n"]
        finally:
            session.set_conf(C.EXEC_TPU_ENABLED, False)
            B._reset_for_testing()
        assert B.device_healthy()

    def test_strict_mode_reraises(self, df, monkeypatch):
        from hyperspace_tpu.plan import tpu_exec
        from hyperspace_tpu.utils import backend as B

        session = df.session
        monkeypatch.setenv("HYPERSPACE_DEVICE_STRICT", "1")

        def boom(*a, **k):
            raise RuntimeError("bug in device path")

        monkeypatch.setattr(tpu_exec, "_try_execute_tpu_inner", boom)
        session.set_conf(C.EXEC_TPU_ENABLED, True)
        try:
            with pytest.raises(RuntimeError, match="bug in device path"):
                q(df).to_pydict()
        finally:
            session.set_conf(C.EXEC_TPU_ENABLED, False)
            B._reset_for_testing()


class TestHierarchicalMesh:
    """Multi-slice (dcn x ici) topology: aggregates psum over the axis
    pair — on hardware XLA reduces within a slice over ICI and only
    per-group partials cross DCN. The 8 virtual devices arrange as 2x4."""

    def _data(self, tmp_session, tmp_path):
        rng = np.random.default_rng(43)
        n = 9000
        cio.write_parquet(
            ColumnBatch.from_pydict(
                {
                    "g": rng.choice(["a", "b", "c"], n).tolist(),
                    "k": rng.integers(0, 50, n).astype(int).tolist(),
                    "q": rng.integers(1, 1000, n).astype(int).tolist(),
                    "x": rng.uniform(0, 10, n).tolist(),
                }
            ),
            str(tmp_path / "hier" / "p.parquet"),
        )
        return tmp_session.read.parquet(str(tmp_path / "hier"))

    def _with_hier_mesh(self, session, slices=2):
        session.set_conf(C.EXEC_TPU_ENABLED, True)
        session.set_conf("hyperspace.tpu.exec.meshDevices", 8)
        session.set_conf("hyperspace.tpu.exec.meshSlices", slices)

    def _reset(self, session):
        session.set_conf(C.EXEC_TPU_ENABLED, False)
        session.set_conf("hyperspace.tpu.exec.meshDevices", 0)
        session.set_conf("hyperspace.tpu.exec.meshSlices", 1)

    def test_active_mesh_is_hierarchical(self, tmp_session):
        from hyperspace_tpu.parallel.mesh import active_mesh

        self._with_hier_mesh(tmp_session)
        try:
            mesh = active_mesh(tmp_session)
        finally:
            self._reset(tmp_session)
        assert mesh is not None
        assert tuple(mesh.axis_names) == ("dcn", "ici")
        assert mesh.shape["dcn"] == 2 and mesh.shape["ici"] == 4

    def test_grouped_int_sums_exact_on_hier_mesh(self, tmp_session, tmp_path):
        from hyperspace_tpu.plan import tpu_exec

        d = self._data(tmp_session, tmp_path)
        q = lambda: (
            d.filter(col("k") < 40)
            .select("g", "q", "x")
            .group_by("g")
            .agg(
                Sum(col("q")).alias("sq"),
                Avg(col("q")).alias("aq"),
                Sum(col("x")).alias("sx"),
                Count(lit(1)).alias("n"),
            )
            .sort("g")
        )
        host = q().to_pydict()
        self._with_hier_mesh(tmp_session)
        tpu_exec._KERNEL_CACHE.clear()
        try:
            dev = q().to_pydict()
        finally:
            self._reset(tmp_session)
        # the hierarchical kernel actually built (topology in the cache key)
        assert any(
            isinstance(k, tuple) and k and k[0] == "mesh"
            and (("dcn", 2), ("ici", 4)) in k
            for k in tpu_exec._KERNEL_CACHE
        )
        assert dev["g"] == host["g"]
        assert dev["sq"] == host["sq"]  # exact chunked int sums
        assert dev["n"] == host["n"]
        for a, b in zip(dev["aq"], host["aq"]):
            assert abs(a - b) <= 1e-12 * max(1.0, abs(b))
        for a, b in zip(dev["sx"], host["sx"]):
            assert abs(a - b) <= 1e-6 * max(1.0, abs(b))

    def test_build_partitions_per_slice(self, tmp_session, tmp_path):
        """Index builds on a hierarchical mesh split rows across the slices
        and exchange on each slice's own 1-D submesh (all_to_all never
        crosses DCN), producing one sorted run per slice per bucket — and
        queries over the multi-run layout stay correct."""
        from hyperspace_tpu import CoveringIndexConfig, Hyperspace

        d = self._data(tmp_session, tmp_path)
        hs = Hyperspace(tmp_session)
        self._with_hier_mesh(tmp_session)
        try:
            hs.create_index(d, CoveringIndexConfig("hm", ["k"], ["x"]))
            files = [f.name for f in hs.get_index("hm").index_data_files()]
            import re

            seqs = {
                m.group(1)
                for m in (re.search(r"-b\d+-(\d+s\d+)\.", f) for f in files)
                if m
            }
            # two slices -> per-slice runs in the s<slice> sub-namespace
            # (distinct from any host-fallback "-<seq>" run of the same seq)
            assert seqs == {"0s0", "0s1"}, files
            tmp_session.enable_hyperspace()
            got = (
                tmp_session.read.parquet(str(tmp_path / "hier"))
                .filter(col("k") == 7)
                .select("k", "x")
                .agg(Sum(col("x")).alias("s"), Count(lit(1)).alias("n"))
                .to_pydict()
            )
            tmp_session.disable_hyperspace()
        finally:
            self._reset(tmp_session)
        raw = (
            self._data(tmp_session, tmp_path)
            .filter(col("k") == 7)
            .select("k", "x")
            .agg(Sum(col("x")).alias("s"), Count(lit(1)).alias("n"))
            .to_pydict()
        )
        assert got["n"] == raw["n"]
        # float sums on the mesh tier carry the documented f32 tolerance
        assert abs(got["s"][0] - raw["s"][0]) <= 1e-4 * max(1.0, abs(raw["s"][0]))

    def test_slices_must_divide_devices(self, tmp_session):
        from hyperspace_tpu.exceptions import HyperspaceError

        tmp_session.set_conf("hyperspace.tpu.exec.meshDevices", 8)
        tmp_session.set_conf("hyperspace.tpu.exec.meshSlices", 3)
        with pytest.raises(HyperspaceError, match="must divide"):
            tmp_session.conf.exec_mesh_slices
        tmp_session.set_conf("hyperspace.tpu.exec.meshSlices", 1)
        tmp_session.set_conf("hyperspace.tpu.exec.meshDevices", 0)
