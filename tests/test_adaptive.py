"""Mid-query adaptive re-optimization (``HYPERSPACE_ADAPTIVE``).

Pins the PR-18 tentpole guarantees:

- default-off is bit-identical off: every hook is one mode read returning
  the static answer, and no ``adaptive.*`` counter ever moves,
- conjunct reordering produces the exact static filter mask (including
  Kleene NULL semantics) in any observed order, and records the switch,
- join re-planning is fed by a NON-destructive ``observe_actual`` (the
  estimate map survives), flips banded→split from decoded actuals after
  the warmup window, and stays bit-identical end-to-end under planted
  footer-stats mis-estimates,
- an index scan that underdelivers its prune prediction aborts at a chunk
  boundary, is vetoed, and the replanned query completes bit-identical to
  the raw scan — driven both by an honest prediction with a sub-1 abort
  factor and by a planted sketch-NDV tamper under the default factor,
- ``HYPERSPACE_ADAPTIVE=verify`` re-runs the final plan statically and
  raises on any planted divergence (and stays silent on honest runs).
"""

import json
import glob
import os

import numpy as np
import pytest

from hyperspace_tpu import CoveringIndexConfig, Hyperspace
from hyperspace_tpu import constants as C
from hyperspace_tpu.columnar import io as cio
from hyperspace_tpu.columnar.table import Column, ColumnBatch
from hyperspace_tpu.exceptions import HyperspaceError
from hyperspace_tpu.models import covering
from hyperspace_tpu.models.dataskipping import sketch_store
from hyperspace_tpu.plan import Count, Max, Min, col, lit
from hyperspace_tpu.plan import adaptive, join_memory
from hyperspace_tpu.serve import budget as serve_budget
from hyperspace_tpu.telemetry import plan_stats
from hyperspace_tpu.telemetry.metrics import REGISTRY


def _bits(d: dict) -> str:
    return repr(
        {
            k: [x.hex() if isinstance(x, float) else x for x in v]
            for k, v in d.items()
        }
    )


def _counter(name: str) -> float:
    return REGISTRY.counter(name).value


@pytest.fixture(autouse=True)
def _fresh_device_ledger():
    yield
    serve_budget.reset_device_budget()


# ---------------------------------------------------------------------------
# mode plumbing + the default-off pin
# ---------------------------------------------------------------------------


class TestModeAndOff:
    def test_mode_parsing(self, monkeypatch):
        monkeypatch.delenv("HYPERSPACE_ADAPTIVE", raising=False)
        assert adaptive.mode() == "0"
        assert not adaptive.active()
        for raw, want in (
            ("0", "0"), ("off", "0"), ("", "0"), ("no", "0"),
            ("1", "1"), ("true", "1"), ("ON", "1"),
            ("verify", "verify"), (" Verify ", "verify"),
        ):
            monkeypatch.setenv("HYPERSPACE_ADAPTIVE", raw)
            assert adaptive.mode() == want, raw

    def test_force_mode_overrides_knob(self, monkeypatch):
        monkeypatch.setenv("HYPERSPACE_ADAPTIVE", "1")
        with adaptive.force_mode("0"):
            assert adaptive.mode() == "0"
            with adaptive.force_mode("verify"):
                assert adaptive.mode() == "verify"
            assert adaptive.mode() == "0"
        assert adaptive.mode() == "1"

    def test_knob_defaults(self, monkeypatch):
        monkeypatch.delenv("HYPERSPACE_ADAPTIVE_ABORT_FACTOR", raising=False)
        monkeypatch.delenv("HYPERSPACE_ADAPTIVE_WARMUP_CHUNKS", raising=False)
        assert adaptive.abort_factor() == 4.0
        assert adaptive.warmup_chunks() == 2
        monkeypatch.setenv("HYPERSPACE_ADAPTIVE_WARMUP_CHUNKS", "0")
        assert adaptive.warmup_chunks() == 1  # floored: never zero warmup

    def test_off_hooks_return_static_answers(self, monkeypatch):
        monkeypatch.delenv("HYPERSPACE_ADAPTIVE", raising=False)
        rng = np.random.default_rng(0)
        batch = ColumnBatch.from_pydict(
            {"a": rng.integers(0, 10, 4000).tolist()}
        )
        cond = (col("a") > 1) & (col("a") < 8)
        assert adaptive.conjunct_mask(cond, batch) is None
        chunks = iter(())
        assert adaptive.monitor_scan_chunks(
            chunks, _FakeScan(), ({}, [])
        ) is chunks
        assert adaptive.vetoed_indexes() == frozenset()

    def test_off_query_is_bit_identical_and_counter_silent(
        self, tmp_session, tmp_path, monkeypatch
    ):
        """The acceptance pin: unset vs explicit 0 — same bits, and the
        whole adaptive counter family stays untouched."""
        rng = np.random.default_rng(3)
        n = 6000
        cio.write_parquet(
            ColumnBatch.from_pydict(
                {
                    "a": rng.integers(0, 50, n).tolist(),
                    "b": rng.integers(0, 50, n).tolist(),
                    "c": rng.integers(0, 50, n).tolist(),
                }
            ),
            str(tmp_path / "t" / "p.parquet"),
        )
        # col-vs-col conjuncts never push to arrow: the host Filter sees
        # the full batch, so the off-path pin exercises the real site
        q = lambda: (
            tmp_session.read.parquet(str(tmp_path / "t"))
            .filter(
                (col("a") != col("c"))
                & (col("a") > col("b"))
                & (col("b") >= col("c"))
            )
            .select("a", "b", "c")
        )
        monkeypatch.setattr(adaptive, "_REORDER_CHUNK_ROWS", 1024)
        before = {
            k: v
            for k, v in REGISTRY.snapshot().items()
            if k.startswith("adaptive.")
        }
        monkeypatch.delenv("HYPERSPACE_ADAPTIVE", raising=False)
        unset = q().to_pydict()
        monkeypatch.setenv("HYPERSPACE_ADAPTIVE", "0")
        explicit = q().to_pydict()
        assert _bits(unset) == _bits(explicit)
        after = {
            k: v
            for k, v in REGISTRY.snapshot().items()
            if k.startswith("adaptive.")
        }
        assert after == before


class _FakeScan:
    prune_spec = None
    index_info = None
    plan_id = -1


# ---------------------------------------------------------------------------
# site 2: observed-selectivity conjunct reordering
# ---------------------------------------------------------------------------


def _nullable_int(values):
    data = np.array([0 if v is None else v for v in values], dtype=np.int64)
    validity = np.array([v is not None for v in values], dtype=bool)
    return Column(data, "int64", validity)


@pytest.fixture()
def reorder_env(monkeypatch):
    """Small chunks + 1-chunk warmup so a few thousand rows adapt."""
    monkeypatch.setattr(adaptive, "_REORDER_CHUNK_ROWS", 1024)
    monkeypatch.setenv("HYPERSPACE_ADAPTIVE_WARMUP_CHUNKS", "1")
    monkeypatch.setenv("HYPERSPACE_ADAPTIVE", "1")


class TestConjunctReorder:
    def _batch(self, n=6000, seed=11):
        rng = np.random.default_rng(seed)
        return ColumnBatch.from_pydict(
            {
                "a": rng.integers(0, 100, n).tolist(),
                "b": rng.integers(0, 100, n).tolist(),
                "c": rng.uniform(0, 1, n).tolist(),
            }
        )

    def test_mask_identical_to_static_and_switch_recorded(self, reorder_env):
        batch = self._batch()
        # written worst-first: keep 90%, 50%, 5% — the reorder must flip
        cond = (col("a") >= 10) & (col("b") < 50) & (col("c") < 0.05)
        static = np.asarray(cond.eval(batch).data, dtype=bool)
        before = _counter("adaptive.reorder")
        got = adaptive.conjunct_mask(cond, batch)
        assert got is not None
        assert np.array_equal(got, static)
        assert _counter("adaptive.reorder") == before + 1

    def test_null_kleene_mask_identical(self, reorder_env):
        rng = np.random.default_rng(5)
        n = 6000
        vals_a = [
            None if rng.uniform() < 0.2 else int(rng.integers(0, 40))
            for _ in range(n)
        ]
        vals_b = [
            None if rng.uniform() < 0.3 else int(rng.integers(0, 40))
            for _ in range(n)
        ]
        batch = ColumnBatch(
            {
                "a": _nullable_int(vals_a),
                "b": _nullable_int(vals_b),
                "c": Column(
                    rng.integers(0, 40, n).astype(np.int64), "int64", None
                ),
            }
        )
        cond = (col("a") > 5) & (col("b") < 30) & (col("c") != 7)
        static = np.asarray(cond.eval(batch).data, dtype=bool)
        got = adaptive.conjunct_mask(cond, batch)
        assert got is not None
        assert np.array_equal(got, static)

    def test_static_cases_return_none(self, reorder_env):
        batch = self._batch(n=6000)
        # single conjunct: nothing to reorder
        assert adaptive.conjunct_mask(col("a") > 3, batch) is None
        # OR at the top: not a conjunction
        assert adaptive.conjunct_mask(
            (col("a") > 3) | (col("b") > 3), batch
        ) is None
        # all-warmup batch: too small to learn anything worth applying
        small = self._batch(n=1500)
        assert adaptive.conjunct_mask(
            (col("a") > 3) & (col("b") > 3), small
        ) is None

    def test_e2e_filter_query_bit_identical(
        self, tmp_session, tmp_path, reorder_env, monkeypatch
    ):
        rng = np.random.default_rng(9)
        n = 9000
        cio.write_parquet(
            ColumnBatch.from_pydict(
                {
                    "a": rng.integers(0, 100, n).tolist(),
                    "b": rng.integers(0, 100, n).tolist(),
                    "c": rng.integers(0, 100, n).tolist(),
                }
            ),
            str(tmp_path / "t" / "p.parquet"),
        )
        # col-vs-col: no arrow pushdown, the Filter node sees all 9000 rows
        q = lambda: (
            tmp_session.read.parquet(str(tmp_path / "t"))
            .filter(
                (col("a") != col("c"))
                & (col("a") > col("b"))
                & (col("b") >= col("c"))
            )
            .select("a", "b", "c")
        )
        before = _counter("adaptive.reorder")
        on = q().to_pydict()
        assert _counter("adaptive.reorder") > before  # the site engaged
        monkeypatch.setenv("HYPERSPACE_ADAPTIVE", "0")
        off = q().to_pydict()
        assert _bits(on) == _bits(off)

    def test_switch_renders_in_explain_analyze_summary(
        self, tmp_session, tmp_path, reorder_env
    ):
        rng = np.random.default_rng(13)
        n = 9000
        cio.write_parquet(
            ColumnBatch.from_pydict(
                {
                    "a": rng.integers(0, 100, n).tolist(),
                    "b": rng.integers(0, 100, n).tolist(),
                    "c": rng.integers(0, 100, n).tolist(),
                }
            ),
            str(tmp_path / "t" / "p.parquet"),
        )
        df = (
            tmp_session.read.parquet(str(tmp_path / "t"))
            .filter(
                (col("a") != col("c"))
                & (col("a") > col("b"))
                & (col("b") >= col("c"))
            )
            .select("a", "b")
        )
        with plan_stats.collect_scope() as colr:
            df.to_pydict()
        assert colr.switches, "no switch event recorded"
        sw = colr.switches[0]
        assert sw["site"] == "reorder"
        rendered = plan_stats.summary_string(colr)
        assert "[adapted:" in rendered and "@chunk" in rendered

    def test_verify_mode_clean(
        self, tmp_session, tmp_path, reorder_env, monkeypatch
    ):
        monkeypatch.setenv("HYPERSPACE_ADAPTIVE", "verify")
        rng = np.random.default_rng(17)
        n = 9000
        cio.write_parquet(
            ColumnBatch.from_pydict(
                {
                    "a": rng.integers(0, 100, n).tolist(),
                    "b": rng.integers(0, 100, n).tolist(),
                }
            ),
            str(tmp_path / "t" / "p.parquet"),
        )
        before = _counter("adaptive.verified")
        out = (
            tmp_session.read.parquet(str(tmp_path / "t"))
            .filter((col("a") > col("b")) & (col("a") != 3))
            .select("a", "b")
            .to_pydict()
        )
        assert out["a"]  # non-empty: verify compared real rows
        assert _counter("adaptive.verified") == before + 1

    def test_verify_catches_planted_divergence(
        self, tmp_session, tmp_path, reorder_env, monkeypatch
    ):
        """Corrupt the adaptive mask path only — the verify baseline runs
        under force_mode("0") and never calls it, so the comparison must
        blow up (the HYPERSPACE_PRUNE=verify discipline)."""
        real = adaptive._conjunct_data_mask

        def corrupted(conj, batch):
            m = real(conj, batch)
            if m.size:
                m = m.copy()
                m[0] = not m[0]
            return m

        monkeypatch.setattr(adaptive, "_conjunct_data_mask", corrupted)
        monkeypatch.setenv("HYPERSPACE_ADAPTIVE", "verify")
        rng = np.random.default_rng(19)
        n = 9000
        cio.write_parquet(
            ColumnBatch.from_pydict(
                {
                    "a": rng.integers(0, 100, n).tolist(),
                    "b": rng.integers(0, 100, n).tolist(),
                }
            ),
            str(tmp_path / "t" / "p.parquet"),
        )
        with pytest.raises(HyperspaceError, match="verify mismatch"):
            (
                tmp_session.read.parquet(str(tmp_path / "t"))
                .filter((col("a") > col("b")) & (col("a") != col("b")))
                .select("a", "b")
                .collect()
            )


# ---------------------------------------------------------------------------
# site 1: per-bucket-pair join re-planning
# ---------------------------------------------------------------------------


def _mem_plan(grant=1 << 20, estimates=None, strategies=None):
    estimates = estimates or {}
    strategies = strategies or {b: "banded" for b in estimates}
    split_by = {
        b: (0 if s == "broadcast" else 4096) for b, s in strategies.items()
    }
    return join_memory.JoinMemoryPlan(
        strategies, split_by, grant, 4096, None,
        estimates=estimates, index_name="jx",
    )


class TestJoinReplan:
    def test_observe_actual_is_non_destructive(self):
        plan = _mem_plan(estimates={0: (1000, 16000.0), 1: (2000, 32000.0)})
        plan.observe_actual(0, 5000, 80000)
        assert plan.estimates == {0: (1000, 16000.0), 1: (2000, 32000.0)}
        assert plan.observed[0] == (5000, 80000)
        plan.observe_actual(0, 9, 9)  # one observation per bucket, ever
        assert plan.observed[0] == (5000, 80000)
        # unknown bucket: ignored, never invents an estimate
        plan.observe_actual(7, 1, 1)
        assert 7 not in plan.observed

    def test_split_rows_static_when_off(self, monkeypatch):
        monkeypatch.delenv("HYPERSPACE_ADAPTIVE", raising=False)
        plan = _mem_plan(estimates={0: (1000, 16000.0), 1: (2000, 32000.0)})
        plan.observe_actual(0, 500_000, 8_000_000)
        assert plan.split_rows(1) == 4096  # planned threshold untouched

    def test_flip_banded_to_split_from_correction(self, monkeypatch):
        """Warmup pair observes 50x the estimated bytes; the NEXT pair's
        threshold re-derives from the geometric-mean correction and the
        flip is recorded exactly once."""
        monkeypatch.setenv("HYPERSPACE_ADAPTIVE", "1")
        monkeypatch.setenv("HYPERSPACE_ADAPTIVE_WARMUP_CHUNKS", "1")
        plan = _mem_plan(
            grant=1 << 20,
            estimates={0: (1000, 16000.0), 1: (2000, 32000.0)},
        )
        plan.observe_actual(0, 50_000, 800_000)
        before = _counter("adaptive.replan")
        got = plan.split_rows(1)
        assert got == join_memory.derive_split_rows(1 << 20, 16.0)
        assert 0 < got < 100_000  # corrected act_rows ≈ 100k: split engages
        assert _counter("adaptive.replan") == before + 1
        assert plan.split_rows(1) == got  # idempotent: one event per bucket
        assert _counter("adaptive.replan") == before + 1

    def test_observed_bucket_uses_its_own_actuals(self, monkeypatch):
        monkeypatch.setenv("HYPERSPACE_ADAPTIVE", "1")
        monkeypatch.setenv("HYPERSPACE_ADAPTIVE_WARMUP_CHUNKS", "1")
        plan = _mem_plan(
            grant=1 << 20,
            estimates={0: (1000, 16000.0), 1: (100, 1600.0)},
        )
        plan.observe_actual(0, 1000, 16000)   # honest pair: no correction
        plan.observe_actual(1, 60_000, 960_000)  # this pair blew up 600x
        got = plan.split_rows(1)
        assert got == join_memory.derive_split_rows(1 << 20, 16.0)
        assert got < 60_000  # its own decoded truth drove the re-derive

    def test_broadcast_and_unsplittable_never_flip(self, monkeypatch):
        monkeypatch.setenv("HYPERSPACE_ADAPTIVE", "1")
        monkeypatch.setenv("HYPERSPACE_ADAPTIVE_WARMUP_CHUNKS", "1")
        plan = _mem_plan(
            estimates={0: (10, 160.0), 1: (1000, 16000.0)},
            strategies={0: "broadcast", 1: "banded"},
        )
        plan.observe_actual(0, 90_000, 1_440_000)
        assert plan.split_rows(0) == 0  # broadcast pairs never split
        before = _counter("adaptive.replan")
        plan.split_rows(1, splittable=False)  # agg state can't fold: no event
        assert _counter("adaptive.replan") == before

    def test_e2e_join_bit_identical_under_planted_misestimate(
        self, tmp_session, tmp_path, monkeypatch
    ):
        """Footer byte stats tampered 64x low: the static plan under-sizes
        its waves; adaptive corrects mid-join and flips to split — results
        stay bit-identical and the ledger never parks MORE than static."""
        rng = np.random.default_rng(7)
        n = 30_000
        cio.write_parquet(
            ColumnBatch.from_pydict(
                {
                    "k": rng.integers(0, 600, n).tolist(),
                    "p": rng.uniform(0, 100, n).tolist(),
                }
            ),
            str(tmp_path / "l" / "l.parquet"),
        )
        cio.write_parquet(
            ColumnBatch.from_pydict(
                {
                    "rk": list(range(0, 500)),
                    "w": rng.uniform(size=500).tolist(),
                }
            ),
            str(tmp_path / "r" / "r.parquet"),
        )
        tmp_session.set_conf(C.INDEX_NUM_BUCKETS, 4)
        hs = Hyperspace(tmp_session)
        hs.create_index(
            tmp_session.read.parquet(str(tmp_path / "l")),
            CoveringIndexConfig("jl", ["k"], ["p"]),
        )
        hs.create_index(
            tmp_session.read.parquet(str(tmp_path / "r")),
            CoveringIndexConfig("jr", ["rk"], ["w"]),
        )
        tmp_session.enable_hyperspace()
        tmp_session.set_conf(C.EXEC_TPU_ENABLED, True)

        real = join_memory._bucket_estimates

        def tampered(side, b):
            rows, nbytes = real(side, b)
            return rows, nbytes / 64.0

        monkeypatch.setattr(join_memory, "_bucket_estimates", tampered)
        monkeypatch.setenv("HYPERSPACE_JOIN_BROADCAST_ROWS", "10")
        monkeypatch.setenv("HYPERSPACE_DEVICE_BUDGET_MB", "0.25")
        monkeypatch.setenv("HYPERSPACE_PARK_WAIT_MS", "1")
        monkeypatch.setenv("HYPERSPACE_ADAPTIVE_WARMUP_CHUNKS", "1")
        serve_budget.reset_device_budget()

        def q():
            l = tmp_session.read.parquet(str(tmp_path / "l")).select("k", "p")
            r = tmp_session.read.parquet(str(tmp_path / "r")).select(
                "rk", "w"
            )
            return (
                l.join(r, col("k") == col("rk"))
                .group_by("k")
                .agg(
                    Count(lit(1)).alias("n"),
                    Min(col("p")).alias("lo"),
                    Max(col("p")).alias("hi"),
                )
            )

        monkeypatch.setenv("HYPERSPACE_ADAPTIVE", "0")
        parks0 = _counter("join.spill.parks")
        off = q().to_pydict()
        parks_static = _counter("join.spill.parks") - parks0

        monkeypatch.setenv("HYPERSPACE_ADAPTIVE", "1")
        replans0 = _counter("adaptive.replan")
        parks0 = _counter("join.spill.parks")
        try:
            on = q().to_pydict()
        finally:
            tmp_session.set_conf(C.EXEC_TPU_ENABLED, False)
        parks_adaptive = _counter("join.spill.parks") - parks0

        assert _bits(on) == _bits(off)
        assert _counter("adaptive.replan") > replans0  # the flip happened
        assert parks_adaptive <= parks_static


# ---------------------------------------------------------------------------
# site 3: scan abort-and-replan
# ---------------------------------------------------------------------------

N = 12_000
N_FILES = 4
RGS = 512


def _events(i, n_per, base):
    rng = np.random.default_rng(100 + i)
    return {
        "ev_k": list(range(base, base + n_per)),
        "ev_id": [10_000_000 + base + j for j in range(n_per)],
        # an int category: a string predicate would decline the stream,
        # and the abort monitor watches streamed scans only
        "ev_cat": [(base + j) % 3 for j in range(n_per)],
        "ev_v": rng.uniform(0, 1, n_per).tolist(),
    }


@pytest.fixture()
def scan_env(tmp_session, tmp_path, monkeypatch):
    """Covering index with sketch sidecars, several row groups per bucket,
    streaming execution in small chunks — the abort monitor's habitat."""
    monkeypatch.setenv("HYPERSPACE_SKETCHES", "1")
    monkeypatch.setattr(covering, "INDEX_ROW_GROUP_SIZE", RGS)
    src = str(tmp_path / "events")
    per = N // N_FILES
    for i in range(N_FILES):
        cio.write_parquet(
            ColumnBatch.from_pydict(_events(i, per, i * per)),
            os.path.join(src, f"part-{i:02d}.parquet"),
        )
    tmp_session.set_conf(C.INDEX_NUM_BUCKETS, 2)
    hs = Hyperspace(tmp_session)
    hs.create_index(
        tmp_session.read.parquet(src),
        CoveringIndexConfig("ev_idx", ["ev_k"], ["ev_id", "ev_cat", "ev_v"]),
    )
    tmp_session.enable_hyperspace()
    tmp_session.set_conf(C.EXEC_TPU_ENABLED, True)
    monkeypatch.setenv("HYPERSPACE_STREAM_CHUNK_MB", "0.02")
    monkeypatch.setenv("HYPERSPACE_ADAPTIVE_WARMUP_CHUNKS", "1")
    yield tmp_session, hs, src
    tmp_session.set_conf(C.EXEC_TPU_ENABLED, False)
    tmp_session.disable_hyperspace()


def _sidecars(session, name="ev_idx"):
    root = os.path.join(session.warehouse_dir, "indexes", name)
    return sorted(
        glob.glob(os.path.join(root, "**", "_sketch.*.json"), recursive=True)
    )


def _agg_q(session, src):
    return (
        session.read.parquet(src)
        .filter(col("ev_cat") == 1)
        .group_by("ev_cat")
        .agg(
            Count(lit(1)).alias("n"),
            Min(col("ev_v")).alias("lo"),
            Max(col("ev_v")).alias("hi"),
        )
    )


def _raw_bits(session, src):
    session.disable_hyperspace()
    try:
        return _bits(_agg_q(session, src).to_pydict())
    finally:
        session.enable_hyperspace()


class TestScanAbortReplan:
    def test_monitor_pass_through_outside_replan_scope(self, monkeypatch):
        monkeypatch.setenv("HYPERSPACE_ADAPTIVE", "1")
        chunks = iter(())
        # active, but no execute_collect scope installed: disarmed
        assert adaptive.monitor_scan_chunks(
            chunks, _FakeScan(), ({}, [])
        ) is chunks

    def test_abort_replans_to_raw_bit_identical(self, scan_env, monkeypatch):
        """Honest prediction + sub-1 abort factor: any pruned streamed scan
        'underdelivers', aborts after the warmup chunk, the index is
        vetoed, and the replanned (raw) run matches the raw scan bit for
        bit."""
        session, hs, src = scan_env
        raw = _raw_bits(session, src)
        monkeypatch.setenv("HYPERSPACE_ADAPTIVE", "1")
        monkeypatch.setenv("HYPERSPACE_ADAPTIVE_ABORT_FACTOR", "0.1")
        aborts0 = _counter("adaptive.abort")
        replans0 = _counter("adaptive.scan_replans")
        got = _bits(_agg_q(session, src).to_pydict())
        assert _counter("adaptive.abort") == aborts0 + 1
        assert _counter("adaptive.scan_replans") == replans0 + 1
        assert got == raw
        # outside the replan scope again: the veto does not leak
        assert adaptive.vetoed_indexes() == frozenset()

    def test_tampered_ndv_triggers_abort_at_default_factor(
        self, scan_env, monkeypatch
    ):
        """Planted mis-estimate: sidecar NDV for ev_cat tampered 1e9 so the
        sketch stage promises to keep almost nothing, while the honest
        blooms keep every group — a >4x underdelivery at the DEFAULT
        abort factor."""
        session, hs, src = scan_env
        raw = _raw_bits(session, src)
        sides = _sidecars(session)
        assert sides, "fixture must have sketch sidecars"
        for side in sides:
            rawd = json.load(open(side))
            if "ev_cat" in rawd.get("ndv", {}):
                rawd["ndv"]["ev_cat"] = 10**9
                json.dump(rawd, open(side, "w"))
        sketch_store._SIDECAR_CACHE.clear()
        monkeypatch.setenv("HYPERSPACE_ADAPTIVE", "1")
        monkeypatch.delenv("HYPERSPACE_ADAPTIVE_ABORT_FACTOR", raising=False)
        aborts0 = _counter("adaptive.abort")
        got = _bits(_agg_q(session, src).to_pydict())
        assert _counter("adaptive.abort") == aborts0 + 1
        assert got == raw

    def test_abort_disarmed_when_off(self, scan_env, monkeypatch):
        session, hs, src = scan_env
        monkeypatch.setenv("HYPERSPACE_ADAPTIVE", "0")
        monkeypatch.setenv("HYPERSPACE_ADAPTIVE_ABORT_FACTOR", "0.1")
        aborts0 = _counter("adaptive.abort")
        _agg_q(session, src).to_pydict()
        assert _counter("adaptive.abort") == aborts0

    def test_hs_top_renders_adaptive_column(self):
        import importlib.util

        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        spec = importlib.util.spec_from_file_location(
            "hs_top", os.path.join(repo, "tools", "hs_top.py")
        )
        hs_top = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(hs_top)
        snap = {
            "ts": 0,
            "queries": {
                "recent": [
                    {
                        "query_id": 1,
                        "label": "adapted-q",
                        "counters": {
                            "adaptive.replan": 2,
                            "adaptive.abort": 1,
                            "adaptive.verified": 9,  # not a site: excluded
                        },
                    },
                    {"query_id": 2, "label": "static-q", "counters": {}},
                ]
            },
        }
        out = hs_top.render(snap)
        assert "adapt" in out
        row1 = next(l for l in out.splitlines() if "adapted-q" in l)
        row2 = next(l for l in out.splitlines() if "static-q" in l)
        assert " 3 " in row1
        assert " - " in row2

    def test_verify_mode_clean_across_abort(self, scan_env, monkeypatch):
        """verify adapts (abort + replan) AND re-runs the final plan
        statically — clean, because the switches change scheduling, never
        values."""
        session, hs, src = scan_env
        monkeypatch.setenv("HYPERSPACE_ADAPTIVE", "verify")
        monkeypatch.setenv("HYPERSPACE_ADAPTIVE_ABORT_FACTOR", "0.1")
        verified0 = _counter("adaptive.verified")
        aborts0 = _counter("adaptive.abort")
        out = _agg_q(session, src).to_pydict()
        assert out["n"] == [N // 3]
        assert _counter("adaptive.abort") == aborts0 + 1
        assert _counter("adaptive.verified") == verified0 + 1
