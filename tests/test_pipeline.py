"""Pipelined executor + parallel IO + cross-query kernel cache.

Covers the PR-2 tentpole guarantees:
- parallel multi-file reads are bitwise identical to serial reads,
- the chunk iterator streams every row in file order under any thread count,
- the decoded-chunk and device caches stay coherent under concurrent readers
  (thread-pool IO makes cache thread-safety load-bearing),
- pipelined execution is bit-identical to the serial (HYPERSPACE_PIPELINE=0)
  monolithic path on the TPC-H bench queries,
- a warm kernel cache serves repeat queries with zero retraces (hit counter
  up, no compile span in the trace).
"""

import os
import threading

import numpy as np
import pytest

from hyperspace_tpu import HyperspaceSession
from hyperspace_tpu import constants as C
from hyperspace_tpu.columnar import io as cio
from hyperspace_tpu.columnar.table import ColumnBatch
from hyperspace_tpu.plan import Avg, Count, Max, Min, Sum, col, lit
from hyperspace_tpu.telemetry.metrics import REGISTRY


def _write_multifile(root, n_files=5, rows=2000, seed=0):
    rng = np.random.default_rng(seed)
    paths = []
    for i in range(n_files):
        n = rows + i * 100
        data = {
            "k": rng.integers(0, 40, n).tolist(),
            "flag": rng.choice(["A", "B", "C"], n).tolist(),
            "x": rng.uniform(0, 100, n).tolist(),
            "q": rng.integers(1, 50, n).tolist(),
            "d": rng.integers(8000, 10000, n).astype("int32").tolist(),
            "s": rng.choice(["F", "O"], n).tolist(),
        }
        p = os.path.join(root, "t", f"part-{i}.parquet")
        cio.write_parquet(ColumnBatch.from_pydict(data), p)
        paths.append(p)
    return paths


def _bits(pydict):
    return repr(
        {
            k: [x.hex() if isinstance(x, float) else x for x in v]
            for k, v in pydict.items()
        }
    )


class TestParallelIO:
    def test_parallel_read_matches_serial(self, tmp_path, monkeypatch):
        paths = _write_multifile(str(tmp_path))
        monkeypatch.setenv("HYPERSPACE_IO_THREADS", "1")
        serial = cio.read_parquet(paths)
        monkeypatch.setenv("HYPERSPACE_IO_THREADS", "4")
        parallel = cio.read_parquet(paths)
        assert _bits(serial.to_pydict()) == _bits(parallel.to_pydict())

    def test_chunk_iterator_covers_in_order(self, tmp_path, monkeypatch):
        paths = _write_multifile(str(tmp_path))
        monkeypatch.setenv("HYPERSPACE_STREAM_CHUNK_MB", "0.01")
        monkeypatch.setenv("HYPERSPACE_IO_THREADS", "4")
        whole = cio.read_parquet(paths, ["k", "x"])
        chunks = list(cio.iter_chunks(paths, ["k", "x"]))
        assert [c.index for c in chunks] == list(range(len(chunks)))
        assert len(chunks) >= 2  # small target: several groups
        cat = ColumnBatch.concat([c.batch for c in chunks])
        assert _bits(whole.to_pydict()) == _bits(cat.to_pydict())
        # serial (overlap off) yields the identical stream
        serial = list(cio.iter_chunks(paths, ["k", "x"], overlap=False))
        assert len(serial) == len(chunks)
        cat2 = ColumnBatch.concat([c.batch for c in serial])
        assert _bits(cat.to_pydict()) == _bits(cat2.to_pydict())

    def test_chunk_groups_respect_order_and_target(self, tmp_path):
        paths = _write_multifile(str(tmp_path))
        groups = cio.plan_chunk_groups(paths, target_bytes=1)  # one per file
        assert [p for g in groups for p in g] == paths
        assert all(len(g) == 1 for g in groups)
        one = cio.plan_chunk_groups(paths, target_bytes=1 << 40)
        assert one == [paths]

    def test_chunk_read_error_wraps_io_failures(self, tmp_path):
        with pytest.raises(cio.ChunkReadError):
            list(cio.iter_chunks([str(tmp_path / "missing.parquet")]))

    def test_chunk_cache_concurrent_readers(self, tmp_path, monkeypatch):
        """Decoded-chunk cache under thread-pool readers: every thread must
        see the same decoded bytes, and the cache's byte accounting must
        stay consistent under racing set/evict."""
        paths = _write_multifile(str(tmp_path), n_files=3)
        monkeypatch.setenv("HYPERSPACE_IO_THREADS", "4")
        expected = _bits(cio.read_parquet(paths, cache=True).to_pydict())
        errors = []

        def reader():
            try:
                for _ in range(5):
                    got = cio.read_parquet(paths, cache=True)
                    assert _bits(got.to_pydict()) == expected
            except Exception as e:  # noqa: BLE001
                errors.append(e)

        threads = [threading.Thread(target=reader) for _ in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errors
        assert cio._INDEX_CHUNK_CACHE._bytes >= 0

    def test_bytes_lru_eviction_accounting(self):
        lru = cio._BytesBoundedLRU(100, metric_name="_test_lru")
        lru.set("a", 1, 60)
        lru.set("b", 2, 60)  # evicts a
        assert lru.get("a") is None
        assert lru.get("b") == 2
        assert lru._bytes == 60
        assert REGISTRY.counter("cache._test_lru.evicted_bytes").value >= 60
        assert REGISTRY.gauge("cache._test_lru.bytes").value == 60


class TestDeviceCacheConcurrency:
    def test_concurrent_get_or_put_single_value(self, monkeypatch):
        from hyperspace_tpu.utils.device_cache import DeviceArrayCache

        monkeypatch.setenv("HYPERSPACE_TEST_DC_MB", "64")
        cache = DeviceArrayCache("HYPERSPACE_TEST_DC_MB", "64")
        src = np.arange(1000)
        builds = []

        def build():
            builds.append(1)
            return np.asarray(src, dtype=np.float32)

        results, errors = [], []

        def worker():
            try:
                for _ in range(20):
                    results.append(cache.get_or_put(src, ("pad", 1024), build))
            except Exception as e:  # noqa: BLE001
                errors.append(e)

        threads = [threading.Thread(target=worker) for _ in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errors
        # every returned value is THE cached object after the first build(s)
        assert len({id(r) for r in results[-100:]}) == 1
        assert cache.hits > 0

    def test_eviction_records_bytes_and_gauge(self, monkeypatch):
        from hyperspace_tpu.utils.device_cache import DeviceArrayCache

        monkeypatch.setenv("HYPERSPACE_TEST_DC2_MB", "0.01")  # ~10 KB budget
        cache = DeviceArrayCache("HYPERSPACE_TEST_DC2_MB", "0.01")
        srcs = [np.arange(1000) for _ in range(4)]  # 8 KB each
        for s in srcs:
            cache.get_or_put(s, ("x",), lambda s=s: s.astype(np.float32))
        assert cache.evictions > 0
        assert cache.evicted_bytes > 0
        assert cache.occupancy_bytes <= 0.01 * 2**20
        assert (
            REGISTRY.gauge("cache.host_derived.bytes").value
            == cache.occupancy_bytes
        )


@pytest.fixture()
def pipe_session(tmp_path, monkeypatch):
    """Session over a 5-file table with chunking forced small so streaming
    engages; EXEC on."""
    monkeypatch.setenv("HYPERSPACE_STREAM_CHUNK_MB", "0.02")
    _write_multifile(str(tmp_path))
    session = HyperspaceSession(warehouse_dir=str(tmp_path))
    session.set_conf(C.EXEC_TPU_ENABLED, True)
    return session, str(tmp_path / "t")


_QUERIES = {
    # float sums
    "global_float": lambda t: t.filter(col("d") < 9000).agg(
        Sum(col("x") * col("x")).alias("s"), Count(lit(1)).alias("n")
    ),
    # exact aggregates only: count, int sum and avg, min, max
    "global_exact": lambda t: t.filter(col("d") < 9500).agg(
        Sum(col("q")).alias("sq"), Min(col("x")).alias("mn"),
        Max(col("q")).alias("mx"), Avg(col("q")).alias("aq"),
        Count(lit(1)).alias("n"),
    ),
    # grouped, with string keys (keys stay host-side)
    "grouped_float": lambda t: t.filter(col("d") < 9500)
    .group_by("flag")
    .agg(Sum(col("x")).alias("sx"), Avg(col("x")).alias("ax"),
         Count(lit(1)).alias("n")),
    # grouped on an int64 key
    "grouped_float_int_key": lambda t: t.filter(col("d") < 9500)
    .group_by("k")
    .agg(Sum(col("x")).alias("sx"), Count(lit(1)).alias("n")),
    # grouped on two string keys (Q1's shape)
    "grouped_float_two_keys": lambda t: t.filter(col("d") < 9500)
    .group_by("flag", "s")
    .agg(Sum(col("x")).alias("sx"), Count(lit(1)).alias("n")),
    # grouped exact aggregates (int sums, min, int avg)
    "grouped_exact": lambda t: t.filter(col("d") < 9500)
    .group_by("k")
    .agg(Sum(col("q")).alias("sq"), Min(col("q")).alias("mn"),
         Avg(col("q")).alias("aq"), Count(lit(1)).alias("n")),
    # a string predicate: the stream declines (chunk dictionaries differ)
    # and the monolithic device read re-encodes it once
    "string_pred": lambda t: t.filter(col("flag") == "A").agg(
        Count(lit(1)).alias("n"), Sum(col("q")).alias("sq")
    ),
}


class TestPipelinedBitIdentity:
    @pytest.mark.parametrize("qname", sorted(_QUERIES))
    def test_pipelined_matches_serial(self, pipe_session, monkeypatch, qname):
        session, table = pipe_session
        q = _QUERIES[qname]
        monkeypatch.setenv("HYPERSPACE_PIPELINE", "1")
        declines = qname == "string_pred"
        counter = "pipeline.declined" if declines else "pipeline.chunks"
        before = REGISTRY.counter(counter).value
        on = q(session.read.parquet(table)).to_pydict()
        assert REGISTRY.counter(counter).value > before  # declined / streamed
        monkeypatch.setenv("HYPERSPACE_PIPELINE", "0")
        off = q(session.read.parquet(table)).to_pydict()
        monkeypatch.setenv("HYPERSPACE_PIPELINE", "serial")
        serial = q(session.read.parquet(table)).to_pydict()
        assert _bits(on) == _bits(off)  # pipelined == monolithic, bit for bit
        assert _bits(on) == _bits(serial)  # overlap never changes results
        if declines:  # exact count and int sum: the host tier's answer too
            assert on == _host_answer(session, table, q)

    def test_pipelined_matches_host_exact_aggs(self, pipe_session, monkeypatch):
        """Exact aggregates (counts, int sums) must agree with the HOST tier
        too, not just across device paths."""
        session, table = pipe_session
        monkeypatch.setenv("HYPERSPACE_PIPELINE", "1")
        dev = _QUERIES["global_exact"](session.read.parquet(table)).to_pydict()
        session.set_conf(C.EXEC_TPU_ENABLED, False)
        host = _QUERIES["global_exact"](session.read.parquet(table)).to_pydict()
        session.set_conf(C.EXEC_TPU_ENABLED, True)
        assert dev["sq"] == host["sq"]
        assert dev["n"] == host["n"]
        assert dev["mx"] == host["mx"]

    def test_pipeline_off_streams_nothing(self, pipe_session, monkeypatch):
        session, table = pipe_session
        monkeypatch.setenv("HYPERSPACE_PIPELINE", "0")
        before = REGISTRY.counter("pipeline.chunks").value
        _QUERIES["global_exact"](session.read.parquet(table)).collect()
        assert REGISTRY.counter("pipeline.chunks").value == before

    def test_nullable_chunk_aborts_to_monolithic(self, tmp_path, monkeypatch):
        """A chunk with NULLs can't ship; the stream must abort cleanly and
        the query still answers (host tier) with correct results."""
        monkeypatch.setenv("HYPERSPACE_STREAM_CHUNK_MB", "0.01")
        root = str(tmp_path / "nt")
        rng = np.random.default_rng(1)
        for i in range(3):
            q = rng.integers(1, 50, 1000).astype(np.float64)
            data = {"q": q.tolist(), "d": rng.integers(0, 10, 1000).tolist()}
            b = ColumnBatch.from_pydict(data)
            if i == 1:  # poison the middle chunk with NULLs
                c = b.column("q")
                validity = np.ones(1000, dtype=bool)
                validity[::7] = False
                from hyperspace_tpu.columnar.table import Column

                b = b.with_column("q", Column(c.data, c.dtype, validity))
            cio.write_parquet(b, os.path.join(root, f"p{i}.parquet"))
        session = HyperspaceSession(warehouse_dir=str(tmp_path))
        session.set_conf(C.EXEC_TPU_ENABLED, True)
        monkeypatch.setenv("HYPERSPACE_PIPELINE", "1")
        before = REGISTRY.counter("pipeline.aborted").value
        got = (
            session.read.parquet(root)
            .filter(col("d") < 5)
            .agg(Sum(col("q")).alias("s"), Count(lit(1)).alias("n"))
            .to_pydict()
        )
        session.set_conf(C.EXEC_TPU_ENABLED, False)
        host = (
            session.read.parquet(root)
            .filter(col("d") < 5)
            .agg(Sum(col("q")).alias("s"), Count(lit(1)).alias("n"))
            .to_pydict()
        )
        assert got == host
        assert REGISTRY.counter("pipeline.aborted").value > before


@pytest.fixture()
def indexed_pipe(pipe_session, monkeypatch):
    """pipe_session's table behind a covering index: its scans read index
    chunks through the decoded-chunk cache, whose buffers stay the same
    objects from query to query, as a served index's do."""
    from hyperspace_tpu import CoveringIndexConfig, Hyperspace

    session, table = pipe_session
    hs = Hyperspace(session)
    hs.create_index(
        session.read.parquet(table),
        CoveringIndexConfig("pidx", ["d"], ["k", "flag", "s", "x", "q"]),
    )
    session.enable_hyperspace()
    monkeypatch.setenv("HYPERSPACE_PIPELINE", "1")
    return session, table, hs


class _Work:
    """What one query cost the grouping: the grouping counters' deltas, the
    host uploads, and the calls of the O(rows) steps a build runs."""

    def __init__(self, monkeypatch):
        from hyperspace_tpu.plan import executor

        self.calls = []
        factorize, concat = executor.factorize_group_keys, ColumnBatch.concat

        def counted_factorize(key_cols):
            self.calls.append("factorize_group_keys")
            return factorize(key_cols)

        def counted_concat(batches):
            self.calls.append("ColumnBatch.concat")
            return concat(batches)

        monkeypatch.setattr(executor, "factorize_group_keys", counted_factorize)
        monkeypatch.setattr(ColumnBatch, "concat", staticmethod(counted_concat))

    def run(self, session, table, q):
        from hyperspace_tpu.utils.rpc_meter import METER

        names = ("pipeline.grouping.builds", "pipeline.grouping.hits")
        before = [REGISTRY.counter(n).value for n in names]
        uploads, self.calls[:] = METER.uploads, []
        out = q(session.read.parquet(table)).to_pydict()
        self.builds, self.hits = (
            REGISTRY.counter(n).value - b for n, b in zip(names, before)
        )
        self.uploads = METER.uploads - uploads
        return out


def _host_answer(session, table, q):
    session.set_conf(C.EXEC_TPU_ENABLED, False)
    try:
        return q(session.read.parquet(table)).to_pydict()
    finally:
        session.set_conf(C.EXEC_TPU_ENABLED, True)


def _uncached_answer(session, table, q, monkeypatch):
    with monkeypatch.context() as m:
        m.setenv("HYPERSPACE_DEVICE_CACHE_MB", "0")
        m.setenv("HYPERSPACE_HOST_DERIVED_CACHE_MB", "0")
        return q(session.read.parquet(table)).to_pydict()


def _assert_exact(session, table, q, got, monkeypatch):
    """Bit for bit the answer, row order included, of the route with both
    caches off; the host tier's groups and counts, and its float sums to
    float32 (the host tier numbers its groups in key order, the device
    tier in first-match order, and the query has no ORDER BY)."""
    assert _bits(got) == _bits(_uncached_answer(session, table, q, monkeypatch))
    host = _host_answer(session, table, q)
    assert list(got) == list(host)
    keys = [k for k in got if k in ("k", "flag", "s")]

    def by_key(ans):
        rows = sorted(zip(*[ans[k] for k in keys], range(len(ans[keys[0]]))))
        order = [r[-1] for r in rows]
        return {name: [ans[name][i] for i in order] for name in ans}

    got, host = by_key(got), by_key(host)
    for name in got:
        if name in ("sx", "ax"):
            np.testing.assert_allclose(got[name], host[name], rtol=1e-5)
        else:
            assert got[name] == host[name]


def _grouped_x(d_min, k_max):
    """A grouped float sum whose literals prune no index file: the index
    sorts each bucket by d, every bucket spans d's range, k is not indexed."""
    return lambda t: (
        t.filter((col("d") >= d_min) & (col("k") < k_max))
        .group_by("k")
        .agg(Sum(col("x")).alias("sx"), Count(lit(1)).alias("n"))
    )


class TestGroupingCache:
    """The grouped aggregate's grouping (group ids, group count, first keys)
    is a cached derivation of its key chunk buffers."""

    @pytest.mark.parametrize("pipeline", ["1", "0"])
    @pytest.mark.parametrize(
        "qname",
        [
            "grouped_exact", "grouped_float", "grouped_float_int_key",
            "grouped_float_two_keys",
        ],
    )
    def test_a_warm_repeat_reuses_the_grouping(
        self, indexed_pipe, monkeypatch, qname, pipeline
    ):
        session, table, _hs = indexed_pipe
        monkeypatch.setenv("HYPERSPACE_PIPELINE", pipeline)
        q = _QUERIES[qname]
        work = _Work(monkeypatch)
        first = work.run(session, table, q)
        assert (work.builds, work.hits) == (1, 0)
        assert "factorize_group_keys" in work.calls
        second = work.run(session, table, q)
        assert (work.builds, work.hits) == (0, 1)
        assert work.calls == []  # no key concat, no factorize
        assert work.uploads == 0  # the gids, as the columns, stay resident
        assert _bits(second) == _bits(first)
        _assert_exact(session, table, q, second, monkeypatch)

    @pytest.mark.parametrize("literals", [(9000, 40), (8000, 5)])
    def test_a_new_literal_reuses_the_grouping(
        self, indexed_pipe, monkeypatch, literals
    ):
        session, table, _hs = indexed_pipe
        work = _Work(monkeypatch)
        warm = work.run(session, table, _grouped_x(8000, 40))
        assert work.builds == 1 and len(warm["k"]) == 40
        q = _grouped_x(*literals)
        got = work.run(session, table, q)
        assert (work.builds, work.hits) == (0, 1)
        assert work.calls == []
        _assert_exact(session, table, q, got, monkeypatch)
        if literals[1] == 5:  # keys 5-39 match no row: their groups drop out
            assert sorted(got["k"]) == list(range(5))
        else:  # the first match of each group, and so the row order, moved
            assert sorted(got["k"]) == sorted(warm["k"])
            assert got["k"] != warm["k"]

    @pytest.mark.parametrize("change", ["chunk_cache_cleared", "append"])
    def test_new_key_buffers_build_again(
        self, indexed_pipe, monkeypatch, change
    ):
        session, table, hs = indexed_pipe
        q = _QUERIES["grouped_float_two_keys"]
        work = _Work(monkeypatch)
        work.run(session, table, q)
        if change == "append":
            extra = os.path.join(str(os.path.dirname(table)), "extra")
            _write_multifile(extra, n_files=1, rows=700, seed=9)
            os.replace(
                os.path.join(extra, "t", "part-0.parquet"),
                os.path.join(table, "part-9.parquet"),
            )
            hs.append("pidx", session.read.parquet(table))
        else:
            cio._INDEX_CHUNK_CACHE.clear()
        got = work.run(session, table, q)
        assert (work.builds, work.hits) == (1, 0)
        _assert_exact(session, table, q, got, monkeypatch)

    @pytest.mark.parametrize(
        "budget",
        ["HYPERSPACE_HOST_DERIVED_CACHE_MB", "HYPERSPACE_DEVICE_CACHE_MB"],
    )
    def test_a_budget_of_0_caches_no_grouping(
        self, indexed_pipe, monkeypatch, budget
    ):
        from hyperspace_tpu.utils import device_cache

        session, table, _hs = indexed_pipe
        q = _QUERIES["grouped_float_two_keys"]
        cache = (
            device_cache.HOST_DERIVED_CACHE
            if budget == "HYPERSPACE_HOST_DERIVED_CACHE_MB"
            else device_cache.DEVICE_CACHE
        )
        work = _Work(monkeypatch)
        monkeypatch.setenv(budget, "0")

        def cached_keys():
            return {k for lru in cache._chips.values() for k in lru}

        keys = cached_keys()
        answers = []
        for _ in range(2):
            answers.append(work.run(session, table, q))
            assert (work.builds, work.hits) == (1, 0)
        assert not {
            k[1][0] for k in cached_keys() - keys
        } & {"grouping", "gids", "last_rows"}
        monkeypatch.delenv(budget)
        assert _bits(answers[0]) == _bits(answers[1])
        assert _bits(answers[1]) == _bits(work.run(session, table, q))
        _assert_exact(session, table, q, answers[1], monkeypatch)

    def test_a_nullable_key_caches_under_its_validity_buffer(self, monkeypatch):
        """A key's NULL mask joins the cache key: the same data under
        another mask is another grouping, NULL a group of its own."""
        from hyperspace_tpu.columnar.table import Column
        from hyperspace_tpu.plan import tpu_exec
        from hyperspace_tpu.plan.executor import factorize_group_keys

        data = [np.array([3, 1, 3, 2]), np.array([1, 2, 2, 3])]
        masks = [
            np.array([True, False, True, True]),
            np.array([True, True, False, True]),
        ]
        work = _Work(monkeypatch)

        def grouping(valid):
            parts = [
                ColumnBatch({"k": Column(d, "int64", v)})
                for d, v in zip(data, valid)
            ]
            before = REGISTRY.counter("pipeline.grouping.builds").value
            got = tpu_exec._grouping(parts, ["k"], 16)
            built = REGISTRY.counter("pipeline.grouping.builds").value - before
            want_ids, want_n, want_first = factorize_group_keys(
                [ColumnBatch.concat(parts).column("k")]
            )
            num_groups, seg_pad, first_keys, gids_d, last_rows_d = got
            assert last_rows_d is None  # group 0 (key 3) is two runs
            assert num_groups == want_n
            assert seg_pad == 16
            want_keys = ColumnBatch.concat(parts).column("k").take(want_first)
            assert list(first_keys[0].decode()) == list(want_keys.decode())
            gids = np.asarray(gids_d)
            assert list(gids[:8]) == list(want_ids)
            assert list(gids[8:]) == [15] * 8
            return built, gids_d

        built, gids = grouping(masks)
        assert built == 1 and "factorize_group_keys" in work.calls
        work.calls.clear()
        again, gids_again = grouping(masks)
        assert again == 0 and gids_again is gids
        assert "factorize_group_keys" not in work.calls
        other, _ = grouping([masks[0], ~masks[1]])
        assert other == 1


class TestKernelCacheCrossQuery:
    def test_warm_repeat_has_zero_retraces(self, pipe_session, monkeypatch):
        from hyperspace_tpu.telemetry import trace

        session, table = pipe_session
        monkeypatch.setenv("HYPERSPACE_PIPELINE", "1")
        q = _QUERIES["global_float"]
        q(session.read.parquet(table)).collect()  # cold: compiles
        retraces_warm = REGISTRY.counter("kernel.retrace").value
        hits_before = REGISTRY.counter("cache.kernel.hits").value
        sink = _ListSink()
        trace.enable(sink)
        try:
            got = q(session.read.parquet(table)).to_pydict()
        finally:
            trace.disable()
        assert REGISTRY.counter("kernel.retrace").value == retraces_warm
        assert REGISTRY.counter("cache.kernel.hits").value > hits_before
        names = [s["name"] for s in sink.spans]
        assert not [n for n in names if n.startswith("compile:")]
        assert [n for n in names if n.startswith("pipeline:")]
        assert got["n"][0] is not None

    def test_fingerprints_shared_between_paths(self, pipe_session, monkeypatch):
        """A kernel compiled by the monolithic path must serve the pipelined
        path (and vice versa): identical fingerprints by construction."""
        from hyperspace_tpu.plan import tpu_exec

        session, table = pipe_session
        q = _QUERIES["global_float"]
        tpu_exec._KERNEL_CACHE.clear()
        monkeypatch.setenv("HYPERSPACE_PIPELINE", "0")
        q(session.read.parquet(table)).collect()
        n_mono = len(tpu_exec._KERNEL_CACHE)
        assert n_mono > 0
        monkeypatch.setenv("HYPERSPACE_PIPELINE", "1")
        retraces = REGISTRY.counter("kernel.retrace").value
        q(session.read.parquet(table)).collect()
        assert len(tpu_exec._KERNEL_CACHE) == n_mono  # no new kernels
        assert REGISTRY.counter("kernel.retrace").value == retraces


class _ListSink:
    """In-memory TraceSink: collects completed span names."""

    def __init__(self):
        self.spans = []

    def write_span(self, span):
        self.spans.append({"name": span.name})

    def close(self):
        pass
