"""Distributed bucket-exchange tests over the 8-virtual-device CPU mesh —
the analogue of the reference's shuffle-partitioning behavior exercised via
local-mode Spark."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from hyperspace_tpu.parallel.mesh import device_mesh, num_shards
from hyperspace_tpu.parallel.exchange import bucket_exchange, exchange_with_retry
from hyperspace_tpu.ops.hashing import bucket_ids_np


@pytest.fixture(scope="module")
def mesh():
    return device_mesh()


def test_eight_devices_available():
    assert len(jax.devices()) == 8


class TestBucketExchange:
    def test_rows_land_on_destination_shard(self, mesh):
        d = num_shards(mesh)
        n_total = d * 64
        rng = np.random.default_rng(0)
        keys = rng.integers(0, 10000, n_total).astype(np.int32)
        vals = np.arange(n_total, dtype=np.float32)
        dest = bucket_ids_np([keys], d)

        cols = {"k": jnp.asarray(keys), "v": jnp.asarray(vals)}
        out, valid, overflow = bucket_exchange(
            mesh, cols, jnp.asarray(dest), capacity=64
        )
        assert int(overflow) <= 64
        out_k = np.asarray(out["k"])
        out_v = np.asarray(out["v"])
        valid = np.asarray(valid)

        per_shard = out_k.shape[0] // d
        for shard in range(d):
            sl = slice(shard * per_shard, (shard + 1) * per_shard)
            got_keys = out_k[sl][valid[sl]]
            # every received key hashes to this shard
            assert (bucket_ids_np([got_keys], d) == shard).all()

        # multiset of (k, v) pairs preserved end to end
        got = sorted(zip(out_k[valid].tolist(), out_v[valid].tolist()))
        expect = sorted(zip(keys.tolist(), vals.tolist()))
        assert got == expect

    def test_overflow_detected(self, mesh):
        d = num_shards(mesh)
        # all rows to one bucket: per-(src,dst) count = rows per device
        n_total = d * 32
        keys = np.zeros(n_total, dtype=np.int32)
        dest = np.zeros(n_total, dtype=np.int32)
        cols = {"k": jnp.asarray(keys)}
        _, _, overflow = bucket_exchange(mesh, cols, jnp.asarray(dest), capacity=8)
        assert int(overflow) == 32  # caller must retry with capacity >= 32

    def test_retry_wrapper_handles_skew(self, mesh):
        d = num_shards(mesh)
        n_total = d * 32
        keys = np.zeros(n_total, dtype=np.int32)  # max skew
        vals = np.arange(n_total, dtype=np.float32)
        dest = np.zeros(n_total, dtype=np.int32)
        cols = {"k": jnp.asarray(keys), "v": jnp.asarray(vals)}
        out, valid = exchange_with_retry(mesh, cols, jnp.asarray(dest), n_total // d)
        valid = np.asarray(valid)
        assert valid.sum() == n_total
        assert sorted(np.asarray(out["v"])[valid].tolist()) == vals.tolist()

    def test_pytree_of_many_columns(self, mesh):
        d = num_shards(mesh)
        n = d * 16
        cols = {
            "a": jnp.arange(n, dtype=jnp.int32),
            "b": jnp.arange(n, dtype=jnp.float32) * 2,
            "c": jnp.ones(n, dtype=jnp.int32),
        }
        dest = jnp.asarray(np.arange(n, dtype=np.int32) % d)
        out, valid, overflow = bucket_exchange(mesh, cols, dest, capacity=16)
        valid = np.asarray(valid)
        assert valid.sum() == n
        a = np.asarray(out["a"])[valid]
        b = np.asarray(out["b"])[valid]
        assert np.allclose(b, a * 2.0)


class TestMeshPartitionParity:
    """partition_batch_mesh must reproduce the host partition exactly — the
    bucket layout is the on-disk contract shared by build and query."""

    def _batch(self, n=5000, seed=3):
        from hyperspace_tpu.columnar.table import ColumnBatch

        rng = np.random.default_rng(seed)
        return ColumnBatch.from_pydict(
            {
                "i32": rng.integers(-(2**31), 2**31 - 1, n).astype(np.int32).tolist(),
                "i64": rng.integers(-(2**62), 2**62, n).tolist(),
                "f64": rng.uniform(-1e9, 1e9, n).tolist(),
                "s": [f"v{int(x)}" for x in rng.integers(0, 100, n)],
            }
        )

    @pytest.mark.parametrize(
        "cols", [["i32"], ["i64"], ["f64"], ["s"], ["i32", "s"], ["i64", "i32"]]
    )
    def test_matches_host_partition(self, mesh, cols):
        from hyperspace_tpu.ops.bucketize import partition_batch
        from hyperspace_tpu.parallel.exchange import partition_batch_mesh

        batch = self._batch()
        host = partition_batch(batch, cols, 8)
        dev = partition_batch_mesh(batch, cols, 8, mesh)
        assert dev is not None
        assert len(host) == len(dev)
        for (hb, hrows), (db, drows) in zip(host, dev):
            assert hb == db
            np.testing.assert_array_equal(np.sort(hrows), np.sort(drows))
            # original row order within a bucket is part of the contract
            np.testing.assert_array_equal(hrows, drows)

    def test_tiny_batch_falls_back(self, mesh):
        from hyperspace_tpu.parallel.exchange import partition_batch_mesh

        batch = self._batch(n=4)
        assert partition_batch_mesh(batch, ["i32"], 8, mesh) is None

    def test_a_partition_compiles_three_programs_and_a_repeat_none(self, mesh):
        """The hash and the exchange are one compiled program each (run op
        by op they were some hundred compiles a partition): a partition
        compiles at most three programs, and another index of the same
        table, the same shape again, compiles nothing."""
        from jax import monitoring

        from hyperspace_tpu.parallel.exchange import partition_batch_mesh

        compiles = []

        def on_duration(event, secs, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                compiles.append(secs)

        batch = self._batch(n=5003)
        monitoring.register_event_duration_secs_listener(on_duration)
        try:
            assert partition_batch_mesh(batch, ["i64"], 8, mesh) is not None
            first = len(compiles)
            assert partition_batch_mesh(batch, ["f64"], 8, mesh) is not None
        finally:
            monitoring.unregister_event_duration_listener(on_duration)
        assert first <= 3
        assert len(compiles) == first

    def test_skewed_keys_retry_capacity(self, mesh):
        """All rows share one key: per-(src,dst) counts overflow the first
        capacity guess and the retry path must still return every row."""
        from hyperspace_tpu.columnar.table import ColumnBatch
        from hyperspace_tpu.ops.bucketize import partition_batch
        from hyperspace_tpu.parallel.exchange import partition_batch_mesh

        batch = ColumnBatch.from_pydict({"k": [7] * 4096})
        host = partition_batch(batch, ["k"], 8)
        dev = partition_batch_mesh(batch, ["k"], 8, mesh)
        assert dev is not None
        assert len(dev) == len(host) == 1
        np.testing.assert_array_equal(host[0][1], dev[0][1])


class TestMeshBuildEndToEnd:
    def test_index_files_identical_host_vs_mesh(self, tmp_path):
        """A covering index built through the mesh exchange must produce
        byte-identical bucket files to the host build."""
        import pathlib

        from hyperspace_tpu import CoveringIndexConfig, Hyperspace, HyperspaceSession
        from hyperspace_tpu import constants as C
        from hyperspace_tpu.columnar import io as cio
        from hyperspace_tpu.columnar.table import ColumnBatch

        rng = np.random.default_rng(9)
        n = 20000
        data = {
            "k": rng.integers(0, 500, n).tolist(),
            "v": rng.uniform(size=n).tolist(),
            "name": [f"n{int(i)}" for i in rng.integers(0, 50, n)],
        }
        src = tmp_path / "src"
        cio.write_parquet(ColumnBatch.from_pydict(data), str(src / "p.parquet"))

        def build(ws, mesh_devices):
            session = HyperspaceSession(warehouse_dir=str(ws))
            if mesh_devices:
                session.set_conf(C.EXEC_MESH_DEVICES, mesh_devices)
            hs = Hyperspace(session)
            df = session.read.parquet(str(src))
            hs.create_index(df, CoveringIndexConfig("pidx", ["k"], ["v", "name"]))
            entry = hs.get_index("pidx")
            return {
                pathlib.Path(f).name: pathlib.Path(f).read_bytes()
                for f in entry.content.files()
            }

        host_files = build(tmp_path / "w_host", 0)
        mesh_files = build(tmp_path / "w_mesh", 8)
        assert host_files.keys() == mesh_files.keys()
        for name in host_files:
            assert host_files[name] == mesh_files[name], name
