"""Pipelined, skew-aware bucketed join execution tests.

The streamed + banded join path (plan/bucket_join._iter_bucket_pairs feeding
device_join's band-stacked probe / stacked fused aggregate) must be
bit-identical to the ``HYPERSPACE_PIPELINE=0`` barrier + global-pad path on
every fixture — uniform keys, a heavily skewed hot key, empty buckets, and
split oversized buckets — and a warm repeat join must be served entirely
from the kernel cache (zero ``compile:*`` spans)."""

import numpy as np
import pytest

from hyperspace_tpu import CoveringIndexConfig, Hyperspace
from hyperspace_tpu import constants as C
from hyperspace_tpu.columnar import io as cio
from hyperspace_tpu.columnar.table import ColumnBatch
from hyperspace_tpu.plan import Count, Max, Min, Sum, col, lit
from hyperspace_tpu.telemetry.metrics import REGISTRY


def hex_rows(d: dict) -> str:
    """Bit-exact repr: floats rendered via .hex() so f32/f64 accumulation
    differences can never hide behind printing."""
    return repr(
        {
            k: [x.hex() if isinstance(x, float) else x for x in v]
            for k, v in d.items()
        }
    )


def _write_sides(tmp_path, left, right):
    cio.write_parquet(
        ColumnBatch.from_pydict(left), str(tmp_path / "l" / "l.parquet")
    )
    cio.write_parquet(
        ColumnBatch.from_pydict(right), str(tmp_path / "r" / "r.parquet")
    )


def _index_sides(session, tmp_path, buckets=4):
    session.set_conf(C.INDEX_NUM_BUCKETS, buckets)
    hs = Hyperspace(session)
    hs.create_index(
        session.read.parquet(str(tmp_path / "l")),
        CoveringIndexConfig("jl", ["k"], ["p"]),
    )
    hs.create_index(
        session.read.parquet(str(tmp_path / "r")),
        CoveringIndexConfig("jr", ["rk"], ["w"]),
    )
    return hs


@pytest.fixture()
def skew_env(tmp_session, tmp_path):
    """Heavily skewed left side: 40% of rows carry ONE hot key, so one
    bucket dwarfs the rest — the banding/splitting target shape — plus
    right keys missing from a range so some buckets come up empty."""
    rng = np.random.default_rng(101)
    n = 24_000
    k = rng.integers(0, 400, n)
    k[: int(n * 0.4)] = 7  # hot key -> one monster bucket
    left = {"k": k.tolist(), "p": rng.uniform(0, 100, n).tolist()}
    # only low keys on the right: high-key buckets join to nothing
    right = {"rk": list(range(0, 200)), "w": rng.uniform(size=200).tolist()}
    _write_sides(tmp_path, left, right)
    _index_sides(tmp_session, tmp_path)
    return tmp_session, tmp_path


def _plain_q(session, tmp_path):
    l = session.read.parquet(str(tmp_path / "l")).select("k", "p")
    r = session.read.parquet(str(tmp_path / "r")).select("rk", "w")
    return l.join(r, col("k") == col("rk")).select("k", "p", "w")


def _agg_q(session, tmp_path):
    l = session.read.parquet(str(tmp_path / "l")).select("k", "p")
    r = session.read.parquet(str(tmp_path / "r")).select("rk", "w")
    return (
        l.join(r, col("k") == col("rk"))
        .group_by("k")
        .agg(Sum(col("p")).alias("s"))
    )


def _foldable_agg_q(session, tmp_path):
    # count/min/max only: the split-eligible aggregate set
    l = session.read.parquet(str(tmp_path / "l")).select("k", "p")
    r = session.read.parquet(str(tmp_path / "r")).select("rk", "w")
    return (
        l.join(r, col("k") == col("rk"))
        .group_by("k")
        .agg(
            Count(lit(1)).alias("n"),
            Min(col("p")).alias("lo"),
            Max(col("p")).alias("hi"),
        )
    )


def _run_modes(session, tmp_path, q, monkeypatch, **env):
    """The query under HYPERSPACE_PIPELINE=0 (barrier + global pad) and =1
    (streamed + banded), both on the device tier, as pydicts."""
    session.enable_hyperspace()
    session.set_conf(C.EXEC_TPU_ENABLED, True)
    try:
        for key, val in env.items():
            monkeypatch.setenv(key, val)
        monkeypatch.setenv("HYPERSPACE_PIPELINE", "0")
        serial = q(session, tmp_path).to_pydict()
        monkeypatch.setenv("HYPERSPACE_PIPELINE", "1")
        pipelined = q(session, tmp_path).to_pydict()
    finally:
        session.set_conf(C.EXEC_TPU_ENABLED, False)
        session.disable_hyperspace()
    return serial, pipelined


class TestStreamedBandedBitIdentity:
    def test_plain_join_skewed(self, skew_env, monkeypatch):
        session, tmp_path = skew_env
        pairs0 = REGISTRY.counter("pipeline.join.pairs").value
        bands0 = REGISTRY.counter("pipeline.join.bands").value
        merge0 = REGISTRY.counter("join.probe.merge").value
        serial, pipelined = _run_modes(session, tmp_path, _plain_q, monkeypatch)
        assert hex_rows(pipelined) == hex_rows(serial)
        assert REGISTRY.counter("pipeline.join.pairs").value > pairs0
        assert REGISTRY.counter("pipeline.join.bands").value > bands0
        # comparable pads: every band probed in the merge form
        assert REGISTRY.counter("join.probe.merge").value > merge0

    def test_plain_join_split_buckets(self, skew_env, monkeypatch):
        session, tmp_path = skew_env
        splits0 = REGISTRY.counter("pipeline.join.splits").value
        serial, pipelined = _run_modes(
            session, tmp_path, _plain_q, monkeypatch,
            HYPERSPACE_JOIN_SPLIT_ROWS="1024",
        )
        assert hex_rows(pipelined) == hex_rows(serial)
        assert REGISTRY.counter("pipeline.join.splits").value > splits0

    def test_fused_agg_join_skewed(self, skew_env, monkeypatch):
        session, tmp_path = skew_env
        serial, pipelined = _run_modes(session, tmp_path, _agg_q, monkeypatch)
        assert hex_rows(pipelined) == hex_rows(serial)

    def test_fused_agg_split_folds_exactly(self, skew_env, monkeypatch):
        session, tmp_path = skew_env
        splits0 = REGISTRY.counter("pipeline.join.splits").value
        serial, pipelined = _run_modes(
            session, tmp_path, _foldable_agg_q, monkeypatch,
            HYPERSPACE_JOIN_SPLIT_ROWS="1024",
        )
        assert hex_rows(pipelined) == hex_rows(serial)
        assert REGISTRY.counter("pipeline.join.splits").value > splits0

    def test_sum_agg_never_splits(self, skew_env, monkeypatch):
        """f32 sums are not decomposition-invariant: the split gate must
        keep sum-bearing buckets whole even under a tiny split threshold."""
        session, tmp_path = skew_env
        splits0 = REGISTRY.counter("pipeline.join.splits").value
        serial, pipelined = _run_modes(
            session, tmp_path, _agg_q, monkeypatch,
            HYPERSPACE_JOIN_SPLIT_ROWS="1024",
        )
        assert hex_rows(pipelined) == hex_rows(serial)
        assert REGISTRY.counter("pipeline.join.splits").value == splits0

    def test_empty_buckets_and_disjoint_keys(self, tmp_session, tmp_path, monkeypatch):
        rng = np.random.default_rng(5)
        n = 12_000
        left = {
            "k": rng.integers(0, 64, n).tolist(),
            "p": rng.uniform(0, 10, n).tolist(),
        }
        # two sparse right keys -> most buckets empty on the right
        right = {"rk": [3, 11], "w": [1.5, 2.5]}
        _write_sides(tmp_path, left, right)
        _index_sides(tmp_session, tmp_path)
        serial, pipelined = _run_modes(
            tmp_session, tmp_path, _plain_q, monkeypatch
        )
        assert hex_rows(pipelined) == hex_rows(serial)
        assert set(pipelined["k"]) == {3, 11}

    def test_disjoint_keys_empty_result(self, tmp_session, tmp_path, monkeypatch):
        rng = np.random.default_rng(6)
        n = 9_000
        left = {
            "k": rng.integers(0, 50, n).tolist(),
            "p": rng.uniform(size=n).tolist(),
        }
        right = {"rk": [1000, 2000], "w": [1.0, 2.0]}
        _write_sides(tmp_path, left, right)
        _index_sides(tmp_session, tmp_path)
        serial, pipelined = _run_modes(
            tmp_session, tmp_path, _plain_q, monkeypatch
        )
        assert hex_rows(pipelined) == hex_rows(serial)
        assert pipelined["k"] == []


class TestWarmJoinKernelCache:
    def test_warm_repeat_zero_compile_spans(self, skew_env, monkeypatch):
        """A repeated join (plain AND fused-aggregate) must serve every
        join kernel from the KernelCache: no kernel.retrace growth and no
        compile:* span in the warm trace."""
        from hyperspace_tpu.telemetry import trace

        session, tmp_path = skew_env
        monkeypatch.setenv("HYPERSPACE_PIPELINE", "1")
        session.enable_hyperspace()
        session.set_conf(C.EXEC_TPU_ENABLED, True)
        try:
            _plain_q(session, tmp_path).collect()  # cold: compiles
            _agg_q(session, tmp_path).collect()
            retraces = REGISTRY.counter("kernel.retrace").value
            hits0 = REGISTRY.counter("cache.kernel_join.hits").value
            sink = _ListSink()
            trace.enable(sink)
            try:
                _plain_q(session, tmp_path).collect()
                _agg_q(session, tmp_path).collect()
            finally:
                trace.disable()
        finally:
            session.set_conf(C.EXEC_TPU_ENABLED, False)
            session.disable_hyperspace()
        assert REGISTRY.counter("kernel.retrace").value == retraces
        assert REGISTRY.counter("cache.kernel_join.hits").value > hits0
        names = [s["name"] for s in sink.spans]
        assert not [n for n in names if n.startswith("compile:")]
        assert [n for n in names if n.startswith("join:")]

    def test_per_bucket_probe_kernel_warm(self, skew_env, monkeypatch):
        """With the batched path off, the per-bucket device probe
        (join_probe kind) runs and caches across repeats."""
        from hyperspace_tpu.plan import bucket_join, device_join

        session, tmp_path = skew_env
        monkeypatch.setenv("HYPERSPACE_PIPELINE", "1")
        monkeypatch.setattr(
            device_join, "try_batched_plain_join",
            lambda *a, **k: None,
        )
        monkeypatch.setattr(
            bucket_join, "_try_device_join_paths",
            lambda *a, **k: (None, None, None),
        )
        session.enable_hyperspace()
        session.set_conf(C.EXEC_TPU_ENABLED, True)
        try:
            _plain_q(session, tmp_path).collect()
            retraces = REGISTRY.counter("kernel.retrace").value
            _plain_q(session, tmp_path).collect()
        finally:
            session.set_conf(C.EXEC_TPU_ENABLED, False)
            session.disable_hyperspace()
        assert REGISTRY.counter("kernel.retrace").value == retraces
        assert ("join", "probe", (), "int32", (), (), (), (), ()) in (
            device_join.JOIN_CACHE
        )

    def test_per_bucket_agg_kernel_warm(self, skew_env, monkeypatch):
        """With the eager stacked path gated off, the per-bucket fused
        join+aggregate kernel (join_agg kind) runs and caches."""
        from hyperspace_tpu.plan import bucket_join

        session, tmp_path = skew_env
        monkeypatch.setenv("HYPERSPACE_PIPELINE", "1")
        monkeypatch.setattr(
            bucket_join, "_fused_device_possible", lambda *a, **k: False
        )
        session.enable_hyperspace()
        session.set_conf(C.EXEC_TPU_ENABLED, True)
        try:
            _agg_q(session, tmp_path).collect()
            retraces = REGISTRY.counter("kernel.retrace").value
            _agg_q(session, tmp_path).collect()
        finally:
            session.set_conf(C.EXEC_TPU_ENABLED, False)
            session.disable_hyperspace()
        assert REGISTRY.counter("kernel.retrace").value == retraces


class TestJoinUsageEvents:
    def test_bucketed_exec_emits_usage_event(self, skew_env, monkeypatch):
        """Every bucketed-join execution path emits a uniform
        HyperspaceIndexUsageEvent naming both side indexes (the device
        paths used to emit nothing)."""
        import importlib

        from hyperspace_tpu.telemetry.logger import clear_event_logger_cache

        session, tmp_path = skew_env
        clear_event_logger_cache(session)
        session.set_conf(
            C.EVENT_LOGGER_CLASS, "tests.test_join_pipeline.CapturingLogger"
        )
        canonical = importlib.import_module(
            "tests.test_join_pipeline"
        ).CapturingLogger
        canonical.events.clear()
        monkeypatch.setenv("HYPERSPACE_PIPELINE", "1")
        session.enable_hyperspace()
        session.set_conf(C.EXEC_TPU_ENABLED, True)
        try:
            _plain_q(session, tmp_path).collect()
        finally:
            session.set_conf(C.EXEC_TPU_ENABLED, False)
            session.disable_hyperspace()
            clear_event_logger_cache(session)
            session.unset_conf(C.EVENT_LOGGER_CLASS)
        usage = [
            e for e in canonical.events
            if type(e).__name__ == "HyperspaceIndexUsageEvent"
            and e.rule == "BucketedJoinExec"
        ]
        assert usage, "bucketed join execution must emit a usage event"
        assert usage[0].index_names == ["jl", "jr"]


class CapturingLogger:
    events: list = []

    def log_event(self, event):
        CapturingLogger.events.append(event)


class TestWorkerHelper:
    def test_io_worker_count_honors_env(self, monkeypatch):
        from hyperspace_tpu.utils.workers import io_thread_cap, io_worker_count

        monkeypatch.setenv("HYPERSPACE_IO_THREADS", "3")
        assert io_thread_cap() == 3
        assert io_worker_count(10) == 3
        assert io_worker_count(2) == 2
        assert io_worker_count(10, cap=1) == 1
        assert io_worker_count(0) == 1  # pools need a positive width
        monkeypatch.setenv("HYPERSPACE_IO_THREADS", "not-a-number")
        assert io_thread_cap() == 1

    def test_io_reader_delegates(self, monkeypatch):
        monkeypatch.setenv("HYPERSPACE_IO_THREADS", "5")
        assert cio.io_threads() == 5


def _hlo_ops(kernel, *shapes) -> str:
    """CPU-compiled HLO text of a kernel at int32 argument shapes."""
    import jax
    import jax.numpy as jnp

    args = [jax.ShapeDtypeStruct(s, jnp.int32) for s in shapes]
    return kernel.lower(*args).compile().as_text()


class TestProbeForm:
    """The band kernels probe by sort-merge at comparable pads and keep
    the binary search only for a small left side into a large right one:
    a silent return to per-row search loops at Q3's pads fails here."""

    @pytest.mark.parametrize("kernel", ["probe", "expand"])
    def test_q3_pads_merge_without_loops(self, kernel):
        from hyperspace_tpu.plan import device_join as DJ

        b, pad_l, pad_r = 8, 1 << 18, 1 << 17
        if kernel == "probe":
            text = _hlo_ops(DJ._build_stacked_probe_kernel(pad_l, pad_r),
                            (b, pad_l), (b, pad_r), (b,), (b,))
        else:
            text = _hlo_ops(DJ._build_stacked_expand_kernel(pad_l),
                            (b, pad_l), (b, pad_l), (b,))
        assert " while(" not in text
        assert " sort(" in text

    def test_small_left_large_right_keeps_search(self):
        from hyperspace_tpu.ops.join import merge_probe
        from hyperspace_tpu.plan import device_join as DJ

        b, pad_l, pad_r = 8, 1 << 10, 1 << 20
        assert not merge_probe(pad_l, pad_r)
        text = _hlo_ops(DJ._build_stacked_probe_kernel(pad_l, pad_r),
                        (b, pad_l), (b, pad_r), (b,), (b,))
        assert " while(" in text
        assert " sort(" not in text


class TestSerialModeStreams:
    def test_serial_mode_bit_identical(self, skew_env, monkeypatch):
        """HYPERSPACE_PIPELINE=serial keeps the staged executor without IO
        overlap — still banded, still bit-identical."""
        session, tmp_path = skew_env
        session.enable_hyperspace()
        session.set_conf(C.EXEC_TPU_ENABLED, True)
        try:
            monkeypatch.setenv("HYPERSPACE_PIPELINE", "0")
            serial = _plain_q(session, tmp_path).to_pydict()
            monkeypatch.setenv("HYPERSPACE_PIPELINE", "serial")
            staged = _plain_q(session, tmp_path).to_pydict()
        finally:
            session.set_conf(C.EXEC_TPU_ENABLED, False)
            session.disable_hyperspace()
        assert hex_rows(staged) == hex_rows(serial)


class _ListSink:
    """In-memory TraceSink collecting completed span names."""

    def __init__(self):
        self.spans = []

    def write_span(self, span):
        self.spans.append({"name": span.name})

    def close(self):
        pass
