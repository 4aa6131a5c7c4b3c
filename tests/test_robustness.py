"""Failure-hardening tests: deterministic fault injection, retry/backoff,
the device breaker state machine, and crash-safe index maintenance.

The contract under test (docs/robustness.md): under ANY injected failure
the engine returns either the exact answer (a full device answer or a full
host recompute — bitwise, never a torn mix) or a typed HyperspaceError;
and any crash mid-action leaves a warehouse that ``recover()`` returns to
a stable, orphan-free state from which the action re-runs to a result
bit-identical to a never-crashed build.
"""

import errno
import os
import time

import numpy as np
import pytest

from hyperspace_tpu import Hyperspace, HyperspaceSession
from hyperspace_tpu import constants as C
from hyperspace_tpu.columnar import io as cio
from hyperspace_tpu.columnar.table import ColumnBatch
from hyperspace_tpu.exceptions import ConcurrentWriteError, HyperspaceError
from hyperspace_tpu.meta.data_manager import IndexDataManager
from hyperspace_tpu.meta.entry import LogEntry
from hyperspace_tpu.meta.log_manager import IndexLogManager, STABLE_STATES
from hyperspace_tpu.models.covering import CoveringIndexConfig
from hyperspace_tpu.plan import col, lit, Count, Max, Min, Sum
from hyperspace_tpu.telemetry.metrics import REGISTRY
from hyperspace_tpu.utils import backend, faults, retry


def _val(name: str) -> int:
    m = REGISTRY.get(name)
    return 0 if m is None else int(m.value)


def _bits(d: dict) -> str:
    return repr(
        {
            k: [x.hex() if isinstance(x, float) else x for x in v]
            for k, v in d.items()
        }
    )


@pytest.fixture(autouse=True)
def _pristine_failure_state():
    """Faults disarmed, breaker closed, real clock — before AND after every
    test in this module (they mutate process-global state)."""
    faults.disarm()
    backend._set_clock_for_testing(time.monotonic)
    backend._reset_for_testing()
    yield
    faults.disarm()
    backend._set_clock_for_testing(time.monotonic)
    backend._reset_for_testing()


# ---------------------------------------------------------------------------
# fault-spec parsing
# ---------------------------------------------------------------------------

class TestFaultSpec:
    def test_nth_rule(self):
        (r,) = faults.parse_spec("io.read_file:ioerror:n=3")
        assert r.point == "io.read_file" and r.kind == "ioerror" and r.nth == 3

    def test_probabilistic_rule_with_seed(self):
        (r,) = faults.parse_spec("device.dispatch:oom:p=0.25,seed=9")
        assert r.p == 0.25 and r.seed == 9 and r.nth is None

    def test_always_and_multi_rule(self):
        rules = faults.parse_spec(
            "log.write:crash_before:always; data.publish:crash_after:n=1"
        )
        assert [r.kind for r in rules] == ["crash_before", "crash_after"]
        assert rules[0].always and rules[1].nth == 1

    def test_wildcard_point(self):
        (r,) = faults.parse_spec("device.*:ioerror:n=1")
        assert r.matches("device.upload") and r.matches("device.fetch")
        assert not r.matches("io.read_file")

    @pytest.mark.parametrize(
        "bad",
        [
            "nope.unknown:ioerror:n=1",         # unknown point
            "io.read_file:frob:n=1",            # unknown kind
            "io.read_file:ioerror",             # missing trigger
            "io.read_file:ioerror:n=1,p=0.5",   # both triggers
            "io.read_file:ioerror:n=0",         # n < 1
            "io.read_file:ioerror:p=1.5",       # p out of range
            "io.read_file:ioerror:k=2",         # unknown trigger key
            "io.read_file:ioerror:n=x",         # non-numeric
        ],
    )
    def test_malformed_specs_fail_loudly(self, bad):
        with pytest.raises(faults.FaultSpecError):
            faults.parse_spec(bad)

    def test_probabilistic_is_deterministic_per_seed(self):
        def fires(seed):
            faults.arm(f"io.read_file:ioerror:p=0.5,seed={seed}")
            out = []
            for _ in range(20):
                try:
                    faults.fire("io.read_file")
                    out.append(False)
                except faults.InjectedIOError:
                    out.append(True)
            faults.disarm()
            return out

        assert fires(7) == fires(7)
        assert fires(7) != fires(8)

    def test_nth_fires_exactly_once(self):
        faults.arm("io.read_file:ioerror:n=2")
        faults.fire("io.read_file")  # hit 1: no fire
        with pytest.raises(faults.InjectedIOError):
            faults.fire("io.read_file")  # hit 2: fires
        faults.fire("io.read_file")  # hit 3: spent
        (snap,) = faults.snapshot()
        assert snap["hits"] == 3 and snap["fired"] == 1

    def test_typed_error_hierarchy(self):
        assert issubclass(faults.InjectedIOError, IOError)
        assert issubclass(faults.InjectedIOError, HyperspaceError)
        assert issubclass(faults.InjectedOOMError, MemoryError)
        assert issubclass(faults.InjectedOOMError, HyperspaceError)
        # crash must be un-swallowable by `except Exception`
        assert issubclass(faults.InjectedCrash, BaseException)
        assert not issubclass(faults.InjectedCrash, Exception)

    def test_crash_before_vs_after(self):
        faults.arm("log.write:crash_before:n=1")
        with pytest.raises(faults.InjectedCrash):
            faults.fire("log.write")
        faults.arm("log.write:crash_after:n=1")
        faults.fire("log.write")  # before phase: crash_after stays quiet
        with pytest.raises(faults.InjectedCrash):
            faults.fire_after("log.write")

    def test_unset_is_zero_overhead(self):
        """Disarmed hooks touch no counters (the clean path stays clean)."""
        faults.disarm()
        before_total = _val("faults.injected")
        before_point = _val("faults.injected.io.read_file")
        for _ in range(1000):
            faults.fire("io.read_file")
            faults.fire_after("io.read_file")
        assert _val("faults.injected") == before_total
        assert _val("faults.injected.io.read_file") == before_point

    def test_injection_is_counted_and_attributed(self):
        faults.arm("io.footer:ioerror:n=1")
        before = _val("faults.injected.io.footer")
        with pytest.raises(faults.InjectedIOError):
            faults.fire("io.footer")
        assert _val("faults.injected.io.footer") == before + 1


# ---------------------------------------------------------------------------
# retry / backoff
# ---------------------------------------------------------------------------

class FakeClock:
    def __init__(self):
        self.sleeps = []

    def __call__(self, s: float) -> None:
        self.sleeps.append(s)


class TestRetry:
    def test_absorbs_transient_then_succeeds(self):
        clock = FakeClock()
        calls = {"n": 0}

        def flaky():
            calls["n"] += 1
            if calls["n"] < 3:
                raise OSError("transient hiccup")
            return "ok"

        before = _val("io.retry.attempts")
        assert retry.retry_call(flaky, "unit", attempts=3, clock=clock) == "ok"
        assert calls["n"] == 3
        assert _val("io.retry.attempts") == before + 2
        # deterministic backoff schedule: exact, reproducible delays
        assert clock.sleeps == [
            retry.backoff_delay("unit", 1),
            retry.backoff_delay("unit", 2),
        ]

    def test_permanent_error_fails_immediately(self):
        clock = FakeClock()
        calls = {"n": 0}

        def missing():
            calls["n"] += 1
            raise FileNotFoundError("gone")

        with pytest.raises(FileNotFoundError):
            retry.retry_call(missing, "unit", attempts=5, clock=clock)
        assert calls["n"] == 1 and clock.sleeps == []

    def test_exhaustion_raises_original_and_counts(self):
        clock = FakeClock()
        before = _val("io.retry.gave_up")

        def always():
            raise OSError("still down")

        with pytest.raises(OSError, match="still down"):
            retry.retry_call(always, "unit", attempts=3, clock=clock)
        assert len(clock.sleeps) == 2
        assert _val("io.retry.gave_up") == before + 1

    def test_backoff_shape(self):
        d1, d2, d3 = (retry.backoff_delay("w", k) for k in (1, 2, 3))
        assert 0 < d1 <= retry.BASE_DELAY_S
        assert d1 < d3  # grows
        for k in range(1, 30):
            assert retry.backoff_delay("w", k) <= retry.MAX_DELAY_S
        # jitter is per-site deterministic, decorrelated across sites
        assert retry.backoff_delay("w", 1) == retry.backoff_delay("w", 1)
        assert retry.backoff_delay("w", 1) != retry.backoff_delay("z", 1)

    def test_classifier(self):
        assert retry.is_transient(OSError("io"))
        assert retry.is_transient(TimeoutError())
        assert retry.is_transient(faults.InjectedIOError("x"))
        assert not retry.is_transient(FileNotFoundError())
        assert not retry.is_transient(PermissionError())
        assert not retry.is_transient(ValueError("parse"))
        assert not retry.is_transient(faults.InjectedOOMError("x"))

    def test_footer_fault_absorbed_by_retry(self, tmp_path):
        cio.write_parquet(
            ColumnBatch.from_pydict({"x": [1.0, 2.0, 3.0]}),
            str(tmp_path / "t" / "p.parquet"),
        )
        path = str(tmp_path / "t" / "p.parquet")
        clean = cio.read_rowgroup_stats(path, ["x"])
        cio._ROWGROUP_STATS_CACHE.clear()
        before = _val("io.retry.attempts")
        faults.arm("io.footer:ioerror:n=1")
        got = cio.read_rowgroup_stats(path, ["x"])
        faults.disarm()
        assert got == clean
        assert _val("io.retry.attempts") == before + 1

    def test_read_file_fault_absorbed_bit_identical(self, tmp_session, tmp_path):
        rng = np.random.default_rng(3)
        data = {"a": rng.integers(0, 9, 500).tolist(), "b": rng.random(500).tolist()}
        cio.write_parquet(ColumnBatch.from_pydict(data), str(tmp_path / "t" / "p.parquet"))
        df = tmp_session.read.parquet(str(tmp_path / "t"))
        clean = _bits(df.filter(col("a") > 4).select("b").to_pydict())
        cio._SOURCE_COL_CACHE.clear()
        cio._INDEX_CHUNK_CACHE.clear()
        faults.arm("io.read_file:ioerror:n=1")
        got = _bits(df.filter(col("a") > 4).select("b").to_pydict())
        snap = faults.snapshot()
        faults.disarm()
        assert sum(r["fired"] for r in snap) == 1  # it actually injected
        assert got == clean


# ---------------------------------------------------------------------------
# device breaker state machine
# ---------------------------------------------------------------------------

class TestBreaker:
    @pytest.fixture(autouse=True)
    def _not_strict(self, monkeypatch):
        monkeypatch.setenv("HYPERSPACE_DEVICE_STRICT", "0")
        monkeypatch.setenv("HYPERSPACE_BREAKER_COOLDOWN", "10")

    def _clock(self):
        t = {"now": 1000.0}
        backend._set_clock_for_testing(lambda: t["now"])
        return t

    def test_transient_opens_then_probe_recovers(self):
        t = self._clock()
        assert backend.breaker_state() == backend.CLOSED
        backend.record_device_failure(OSError("device lost"))
        assert backend.breaker_state() == backend.OPEN
        assert not backend.device_healthy()  # cooldown running
        t["now"] += 10.5  # past cooldown: exactly one probe admitted
        assert backend.device_healthy()
        assert backend.breaker_state() == backend.HALF_OPEN
        assert not backend.device_healthy()  # second caller stays on host
        backend.record_device_success()
        assert backend.breaker_state() == backend.CLOSED
        assert backend.device_healthy()

    def test_failed_probe_reopens_with_doubled_cooldown(self):
        t = self._clock()
        backend.record_device_failure(TimeoutError("t0"))
        t["now"] += 10.5
        assert backend.device_healthy()  # the probe
        backend.record_device_failure(TimeoutError("t1"))  # probe failed
        assert backend.breaker_state() == backend.OPEN
        t["now"] += 10.5  # base cooldown no longer enough (doubled)
        assert not backend.device_healthy()
        t["now"] += 10.0  # 2x base now elapsed
        assert backend.device_healthy()
        assert backend.breaker_state() == backend.HALF_OPEN

    def test_cooldown_factor_is_capped(self):
        t = self._clock()
        backend.record_device_failure(OSError("x"))
        for _ in range(8):  # reopen far past the 16x cap
            t["now"] += 10 * 16 + 1
            assert backend.device_healthy()
            backend.record_device_failure(OSError("x"))
        t["now"] += 10 * 16 + 1  # capped cooldown always suffices
        assert backend.device_healthy()

    def test_permanent_error_latches(self):
        t = self._clock()
        backend.record_device_failure(ValueError("bad lowering"))
        assert backend.breaker_state() == backend.LATCHED
        t["now"] += 1e9  # no cooldown ever reopens a latch
        assert not backend.device_healthy()
        backend.record_device_success()  # success signal can't unlatch
        assert backend.breaker_state() == backend.LATCHED

    def test_success_when_closed_is_noop(self):
        backend.record_device_success()
        assert backend.breaker_state() == backend.CLOSED

    def test_classifier_policy(self):
        classify = backend.classify_device_failure
        assert classify(OSError("io")) == "transient"
        assert classify(TimeoutError()) == "transient"
        assert classify(MemoryError("RESOURCE_EXHAUSTED")) == "transient"
        assert classify(faults.InjectedIOError("x")) == "transient"
        assert classify(ValueError("shape mismatch")) == "permanent"
        assert classify(TypeError("tracer")) == "permanent"
        assert classify(NotImplementedError()) == "permanent"
        assert classify(RuntimeError("compilation failure")) == "permanent"
        # unknown runtime errors default to transient (latching forever on
        # an unclassified error is the costlier mistake)
        assert classify(RuntimeError("???")) == "transient"

    def test_strict_mode_reraises(self, monkeypatch):
        monkeypatch.setenv("HYPERSPACE_DEVICE_STRICT", "1")
        with pytest.raises(OSError):
            backend.record_device_failure(OSError("surface me"))

    def test_snapshot_surface(self):
        snap = backend.breaker_snapshot()
        assert snap["state"] == backend.CLOSED
        backend.record_device_failure(OSError("x"))
        snap = backend.breaker_snapshot()
        assert snap["state"] == backend.OPEN
        assert snap["last_failure_kind"] == "transient"


# ---------------------------------------------------------------------------
# mid-stream device failure: clean host-recompute degradation
# ---------------------------------------------------------------------------

def _agg_query(d):
    return (
        d.filter((col("d") >= 2) & (col("y") < 0.7))
        .select("d", "x", "y")
        .agg(
            Sum(col("x") * col("y")).alias("s"),
            Count(lit(1)).alias("n"),
            Min(col("x")).alias("mn"),
            Max(col("x")).alias("mx"),
        )
    )


class TestDeviceDegradation:
    @pytest.fixture()
    def multi_file_df(self, tmp_session, tmp_path):
        # several files so the pipelined chunk streamer engages
        rng = np.random.default_rng(17)
        for part in range(4):
            data = {
                "d": rng.integers(0, 10, 2000).astype(int).tolist(),
                "x": rng.uniform(0, 100, 2000).tolist(),
                "y": rng.uniform(0, 1, 2000).tolist(),
            }
            cio.write_parquet(
                ColumnBatch.from_pydict(data),
                str(tmp_path / "t" / f"p{part}.parquet"),
            )
        return tmp_session.read.parquet(str(tmp_path / "t"))

    @pytest.mark.parametrize("point", ["device.dispatch", "device.upload", "device.fetch"])
    def test_mid_stream_failure_degrades_bit_identical(
        self, multi_file_df, monkeypatch, point
    ):
        """A device failure mid-query yields EXACTLY the host executor's
        bits — a full recompute, never a partial device fold."""
        monkeypatch.setenv("HYPERSPACE_DEVICE_STRICT", "0")
        monkeypatch.setenv("HYPERSPACE_STREAM_CHUNK_MB", "0.05")
        session = multi_file_df.session
        host = _bits(_agg_query(multi_file_df).to_pydict())  # device tier off

        session.set_conf(C.EXEC_TPU_ENABLED, True)
        faults.arm(f"{point}:ioerror:n=1")
        degraded = _bits(_agg_query(multi_file_df).to_pydict())
        snap = faults.snapshot()
        faults.disarm()
        assert sum(r["fired"] for r in snap) == 1
        assert degraded == host
        # the transient failure opened (not latched) the breaker
        assert backend.breaker_state() == backend.OPEN

    def test_clean_device_run_unaffected_by_hardening(self, multi_file_df):
        """With faults unset the device path still runs (no behavior change
        from planting the injection points)."""
        session = multi_file_df.session
        session.set_conf(C.EXEC_TPU_ENABLED, True)
        before = _val("faults.injected")
        out = _agg_query(multi_file_df).to_pydict()
        assert out["n"][0] > 0
        assert _val("faults.injected") == before
        assert backend.breaker_state() == backend.CLOSED


# ---------------------------------------------------------------------------
# log CAS portability + temp-file hygiene (satellite)
# ---------------------------------------------------------------------------

def _entry(state, log_id=0):
    e = LogEntry(state=state, id=log_id)
    e.stamp()
    return e


class TestLogCasPortability:
    def _no_tmp(self, m):
        return not [n for n in os.listdir(m.log_dir) if n.startswith(".tmp-")]

    def test_linkless_fs_falls_back_to_o_excl(self, tmp_path, monkeypatch):
        m = IndexLogManager(str(tmp_path / "idx"))

        def no_links(src, dst, **kw):
            raise OSError(errno.EPERM, "hard links not supported")

        monkeypatch.setattr(os, "link", no_links)
        assert m.write_log(0, _entry("CREATING"))
        got = m.get_log(0)
        assert got is not None and got.state == "CREATING"
        assert self._no_tmp(m)
        # lose-if-present semantics survive the fallback
        assert not m.write_log(0, _entry("CREATING"))

    def test_exclusive_create_loses_when_target_exists(self, tmp_path):
        m = IndexLogManager(str(tmp_path / "idx"))
        assert m.write_log(0, _entry("CREATING"))
        tmp = str(tmp_path / "idx" / "_hyperspace_log" / "spool")
        with open(tmp, "w") as f:
            f.write("{}")
        assert not m._exclusive_create(tmp, m._entry_path(0))

    def test_unexpected_link_errno_propagates(self, tmp_path, monkeypatch):
        m = IndexLogManager(str(tmp_path / "idx"))

        def enospc(src, dst, **kw):
            raise OSError(errno.ENOSPC, "disk full")

        monkeypatch.setattr(os, "link", enospc)
        with pytest.raises(OSError, match="disk full"):
            m.write_log(0, _entry("CREATING"))
        assert self._no_tmp(m)  # spool cleaned even on the raise path

    def test_tmp_cleaned_when_fsync_fails(self, tmp_path, monkeypatch):
        m = IndexLogManager(str(tmp_path / "idx"))
        os.makedirs(m.log_dir, exist_ok=True)

        def bad_fsync(fd):
            raise OSError(errno.EIO, "fsync failed")

        monkeypatch.setattr(os, "fsync", bad_fsync)
        with pytest.raises(OSError):
            m.write_log(0, _entry("CREATING"))
        monkeypatch.undo()
        assert self._no_tmp(m)
        assert m.get_latest_id() is None  # nothing half-committed

    def test_tmp_cleaned_on_loss(self, tmp_path):
        m = IndexLogManager(str(tmp_path / "idx"))
        assert m.write_log(0, _entry("CREATING"))
        assert not m.write_log(0, _entry("CREATING"))
        assert self._no_tmp(m)

    def test_stale_temp_age_gate(self, tmp_path):
        m = IndexLogManager(str(tmp_path / "idx"))
        os.makedirs(m.log_dir, exist_ok=True)
        p = os.path.join(m.log_dir, ".tmp-stranded")
        with open(p, "w") as f:
            f.write("x")
        assert m.stale_temp_files(min_age_s=60.0) == []  # fresh: maybe live
        assert m.stale_temp_files(min_age_s=0.0) == [p]
        old = time.time() - 3600
        os.utime(p, (old, old))
        assert m.stale_temp_files(min_age_s=60.0) == [p]
        assert m.clear_temp_files(min_age_s=60.0) == 1


# ---------------------------------------------------------------------------
# action conflict retry (satellite)
# ---------------------------------------------------------------------------

def _make_source(src: str, parts: int, rows: int = 600, start: int = 0) -> None:
    """Write parts [start, parts): existing files must not be rewritten —
    a fresh mtime makes an identical file look deleted+appended."""
    os.makedirs(src, exist_ok=True)
    for part in range(start, parts):
        rng = np.random.default_rng(100 + part)
        data = {
            "k": rng.integers(0, 20, rows).astype(int).tolist(),
            "v": rng.random(rows).tolist(),
            "w": rng.integers(0, 1000, rows).astype(int).tolist(),
        }
        cio.write_parquet(
            ColumnBatch.from_pydict(data), os.path.join(src, f"p{part}.parquet")
        )


class TestConflictRetry:
    def _indexed_session(self, root):
        s = HyperspaceSession(warehouse_dir=root)
        s.set_conf(C.INDEX_NUM_BUCKETS, 4)
        h = Hyperspace(s)
        src = os.path.join(root, "src")
        _make_source(src, 2)
        h.create_index(s.read.parquet(src), CoveringIndexConfig("cidx", ["k"], ["v"]))
        return s, h

    def test_conflict_is_retried_and_succeeds(self, tmp_path, monkeypatch):
        from hyperspace_tpu.actions.lifecycle import DeleteAction
        from hyperspace_tpu.index_manager import index_manager_for

        s, h = self._indexed_session(str(tmp_path))
        lm = IndexLogManager(index_manager_for(s).resolver.get_index_path("cidx"))
        orig = lm.write_log
        losses = {"n": 0}

        def contended(log_id, entry):
            if losses["n"] == 0:
                losses["n"] += 1
                return False  # simulate a concurrent winner at this id
            return orig(log_id, entry)

        monkeypatch.setattr(lm, "write_log", contended)
        before = _val("action.retry.attempts")
        DeleteAction(lm).run()
        assert losses["n"] == 1
        assert _val("action.retry.attempts") == before + 1
        assert lm.get_latest_log().state == "DELETED"

    def test_surviving_conflict_raises_with_attempt_count(self, tmp_path, monkeypatch):
        from hyperspace_tpu.actions.lifecycle import DeleteAction
        from hyperspace_tpu.index_manager import index_manager_for

        monkeypatch.setenv("HYPERSPACE_ACTION_RETRIES", "3")
        s, h = self._indexed_session(str(tmp_path))
        lm = IndexLogManager(index_manager_for(s).resolver.get_index_path("cidx"))
        monkeypatch.setattr(lm, "write_log", lambda log_id, entry: False)
        before = _val("action.retry.gave_up")
        with pytest.raises(ConcurrentWriteError, match="survived 3 attempts"):
            DeleteAction(lm).run()
        assert _val("action.retry.gave_up") == before + 1

    def test_retries_knob_of_one_disables(self, tmp_path, monkeypatch):
        from hyperspace_tpu.actions.lifecycle import DeleteAction
        from hyperspace_tpu.index_manager import index_manager_for

        monkeypatch.setenv("HYPERSPACE_ACTION_RETRIES", "1")
        s, h = self._indexed_session(str(tmp_path))
        lm = IndexLogManager(index_manager_for(s).resolver.get_index_path("cidx"))
        monkeypatch.setattr(lm, "write_log", lambda log_id, entry: False)
        with pytest.raises(ConcurrentWriteError):
            DeleteAction(lm).run()


# ---------------------------------------------------------------------------
# crash-at-every-point recovery matrix (the tentpole's durability proof)
# ---------------------------------------------------------------------------

_LOG_CRASHES = [
    "log.write:crash_before:n=1",
    "log.write:crash_after:n=1",
    "log.write:crash_before:n=2",
    "log.write:crash_after:n=2",
]
_PUBLISH_CRASHES = [
    "data.publish:crash_before:n=1",
    "data.publish:crash_after:n=1",
]
_MATRIX = [
    (action, spec)
    for action in ("create", "refresh", "optimize", "delete")
    for spec in (_LOG_CRASHES + ([] if action == "delete" else _PUBLISH_CRASHES))
]


def _fresh(root):
    s = HyperspaceSession(warehouse_dir=root)
    s.set_conf(C.INDEX_NUM_BUCKETS, 4)
    return s, Hyperspace(s)


def _run_action(h, s, root, action, phase):
    src = os.path.join(root, "src")
    if phase == "setup":
        _make_source(src, 2)
        if action != "create":
            h.create_index(
                s.read.parquet(src), CoveringIndexConfig("cidx", ["k"], ["v", "w"])
            )
        if action == "optimize":
            _make_source(src, 3, start=2)  # adds p2: incremental refresh
            h.refresh_index("cidx", C.REFRESH_MODE_INCREMENTAL)
            # ...and every bucket now holds 2 small files to compact
        return
    if action == "create":
        h.create_index(
            s.read.parquet(src), CoveringIndexConfig("cidx", ["k"], ["v", "w"])
        )
    elif action == "refresh":
        _make_source(src, 3, start=2)
        h.refresh_index("cidx", C.REFRESH_MODE_FULL)
    elif action == "optimize":
        h.optimize_index("cidx")
    elif action == "delete":
        h.delete_index("cidx")


def _query_bits(s, root):
    df = s.read.parquet(os.path.join(root, "src"))
    return _bits(df.filter(df["k"] == 7).select("v", "w").collect().to_pydict())


def _assert_no_debris(root):
    sys_dir = os.path.join(root, C.INDEXES_DIR)
    if not os.path.isdir(sys_dir):
        return
    from hyperspace_tpu.index_manager import IndexCollectionManager

    for name in os.listdir(sys_dir):
        ip = os.path.join(sys_dir, name)
        if not os.path.isdir(ip):
            continue
        lm, dm = IndexLogManager(ip), IndexDataManager(ip)
        latest = lm.get_latest_log()
        assert latest is None or latest.state in STABLE_STATES, (
            f"{name}: unstable tail {latest.state}"
        )
        assert dm.staged_versions() == [], f"{name}: staging left behind"
        assert lm.stale_temp_files() == [], f"{name}: .tmp spool left behind"
        refs = IndexCollectionManager._referenced_versions(lm)
        if latest is not None and latest.state == "DOESNOTEXIST":
            refs = set()
        orphans = [v for v in dm.get_all_versions() if v not in refs]
        assert orphans == [], f"{name}: orphan data versions {orphans}"
        if latest is not None and latest.state in STABLE_STATES:
            assert lm.stable_pointer_id() == latest.id


class TestCrashRecoveryMatrix:
    @pytest.mark.parametrize("action,spec", _MATRIX, ids=[f"{a}-{s}" for a, s in _MATRIX])
    def test_crash_recover_rerun_bit_identical(self, action, spec, tmp_path):
        # never-crashed twin: the reference end state
        twin = str(tmp_path / "twin")
        ts, th = _fresh(twin)
        _run_action(th, ts, twin, action, "setup")
        _run_action(th, ts, twin, action, "act")
        ts.enable_hyperspace()
        want = _query_bits(ts, twin)

        # crashed cell: same build, process dies at the injection point
        cell = str(tmp_path / "cell")
        s, h = _fresh(cell)
        _run_action(h, s, cell, action, "setup")
        faults.arm(spec)
        with pytest.raises(faults.InjectedCrash):
            _run_action(h, s, cell, action, "act")
        faults.disarm()

        # the "restarted process": recover, converge, compare
        s2, h2 = _fresh(cell)
        report = h2.recover(force=True)
        _assert_no_debris(cell)
        try:
            _run_action(h2, s2, cell, action, "act")
        except HyperspaceError:
            # the crash landed AFTER the final commit: action already done
            pass  # hslint: HS402 — convergence retry; the asserts below are the gate
        _assert_no_debris(cell)
        s2.enable_hyperspace()
        assert _query_bits(s2, cell) == want

        # recovery is idempotent: a second forced pass finds nothing
        report2 = h2.recover(force=True)
        assert not report2["repaired"], report2

    def test_recovery_skips_live_transaction(self, tmp_path):
        from hyperspace_tpu.actions import base as action_base
        from hyperspace_tpu.index_manager import index_manager_for

        root = str(tmp_path)
        s, h = _fresh(root)
        src = os.path.join(root, "src")
        _make_source(src, 2)
        h.create_index(s.read.parquet(src), CoveringIndexConfig("cidx", ["k"], ["v"]))
        ip = index_manager_for(s).resolver.get_index_path("cidx")
        # simulate a live in-process transaction holding the index
        action_base._tx_enter(ip)
        try:
            rep = h.recover(force=True)
            assert rep["per_index"]["cidx"]["skipped"] == "live-transaction"
        finally:
            action_base._tx_exit(ip)

    def test_fresh_transient_entry_is_age_gated(self, tmp_path):
        root = str(tmp_path)
        s, h = _fresh(root)
        src = os.path.join(root, "src")
        _make_source(src, 2)
        h.create_index(s.read.parquet(src), CoveringIndexConfig("cidx", ["k"], ["v"]))
        from hyperspace_tpu.index_manager import index_manager_for

        lm = IndexLogManager(index_manager_for(s).resolver.get_index_path("cidx"))
        nxt = lm.get_latest_id() + 1
        assert lm.write_log(nxt, _entry("REFRESHING", nxt))  # freshly stamped
        rep = h.recover()  # not forced: the entry might be another process's
        assert rep["per_index"]["cidx"]["skipped"].startswith("fresh-transient")
        assert lm.get_latest_log().state == "REFRESHING"
        # a stale one (older than HYPERSPACE_STALE_TX_S) IS rolled back
        e = lm.get_log(nxt)
        e.timestamp = int((time.time() - 7200) * 1000)
        os.unlink(lm._entry_path(nxt))
        assert lm.write_log(nxt, e)
        rep = h.recover()
        assert rep["per_index"]["cidx"]["rolled_back"] == "REFRESHING"
        assert lm.get_latest_log().state == "ACTIVE"

    def test_pointer_fix_forward(self, tmp_path):
        root = str(tmp_path)
        s, h = _fresh(root)
        src = os.path.join(root, "src")
        _make_source(src, 2)
        h.create_index(s.read.parquet(src), CoveringIndexConfig("cidx", ["k"], ["v"]))
        from hyperspace_tpu.index_manager import index_manager_for

        lm = IndexLogManager(index_manager_for(s).resolver.get_index_path("cidx"))
        lm.delete_latest_stable_log()  # crash window: final entry, no pointer
        rep = h.recover()
        assert rep["per_index"]["cidx"]["pointer_fixed"]
        assert lm.stable_pointer_id() == lm.get_latest_id()

    def test_auto_recovery_on_manager_construction(self, tmp_path):
        """A NEW session over a crashed warehouse heals it transparently
        (stale transient entry rolled back, staging swept)."""
        root = str(tmp_path)
        s, h = _fresh(root)
        src = os.path.join(root, "src")
        _make_source(src, 2)
        h.create_index(s.read.parquet(src), CoveringIndexConfig("cidx", ["k"], ["v"]))
        from hyperspace_tpu.index_manager import index_manager_for

        ip = index_manager_for(s).resolver.get_index_path("cidx")
        lm, dm = IndexLogManager(ip), IndexDataManager(ip)
        # hand-plant stale crash debris: old transient entry + staging dir
        nxt = lm.get_latest_id() + 1
        e = _entry("REFRESHING", nxt)
        e.timestamp = int((time.time() - 7200) * 1000)
        assert lm.write_log(nxt, e)
        os.makedirs(dm.staging_path(9))
        with open(os.path.join(dm.staging_path(9), "half.parquet"), "w") as f:
            f.write("partial")

        s2, h2 = _fresh(root)  # construction runs the age-gated pass
        assert lm.get_latest_log().state == "ACTIVE"
        assert dm.staged_versions() == []
