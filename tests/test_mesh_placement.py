"""A deployment on several chips (``hyperspace.tpu.exec.meshDevices``,
``parallel/placement.py``, the scheduler's home chips).

Five layers pin the contract:

* the deployment's chips come from the one conf, and more chips than are
  visible raise;
* the skew-aware placer (``bandPlacement=spread``) as a pure function —
  largest-first bin packing with fair-share splitting of hot buckets,
  deterministic fallback round-robin for stats-starved buckets, and strict
  determinism on fixed inputs;
* end-to-end bit-identity — with the conftest's 8 forced host devices, a
  query served on a home chip must produce float.hex-identical results to
  one chip on the skewed bucketed-join fixtures and the TPC-H join queries
  (the home chip moves work, never changes answers), and every band of its
  joins runs on that chip — or, with ``bandPlacement=spread``, on the
  chips the placer chose;
* per-device memory ledgers — each chip holds its own
  ``BudgetAccountant``, a saturated chip parks/spills without stalling its
  neighbors, and every ledger conserves exactly (sum of releases == sum of
  admissions; zero held at quiescence);
* the scheduler's home chip: the least-occupied chip, none without the
  conf.
"""

import types

import numpy as np
import pytest

from hyperspace_tpu import CoveringIndexConfig, Hyperspace
from hyperspace_tpu import constants as C
from hyperspace_tpu.columnar import io as cio
from hyperspace_tpu.columnar.table import ColumnBatch
from hyperspace_tpu.parallel import placement
from hyperspace_tpu.plan import Count, Max, Min, Sum, col
from hyperspace_tpu.serve import budget as serve_budget
from hyperspace_tpu.telemetry.metrics import REGISTRY

MB = 2**20


def hex_rows(d: dict) -> str:
    """Bit-exact repr: floats rendered via .hex() so f32/f64 accumulation
    differences can never hide behind printing."""
    return repr(
        {
            k: [x.hex() if isinstance(x, float) else x for x in v]
            for k, v in d.items()
        }
    )


# ---------------------------------------------------------------------------
# the deployment's chips
# ---------------------------------------------------------------------------


DEV8 = [f"dev{i}" for i in range(8)]


class TestPlacerBinPacking:
    """The chips a session deploys on, and the placer's packing of join
    bands over them (planted skewed stats; devices are opaque)."""

    def test_uniform_stats_spread_over_all_devices(self):
        est = {b: 10 * MB for b in range(8)}
        p = placement.plan_bucket_placement(est, devices=DEV8)
        ordinals = {p.ordinal_for(b) for b in est}
        assert ordinals == set(range(8))
        assert REGISTRY.gauge("mesh.placement.bytes_imbalance_ratio").value == 1.0

    def test_hot_bucket_splits_across_devices(self):
        """One bucket carrying 30% of the bytes exceeds the per-chip fair
        share, so the placer splits it into ranges its chunks rotate
        through — without the split one chip would hold 30% of the work
        (imbalance ~2.4x on 8 chips)."""
        est = {0: 30 * MB}
        est.update({b: 10 * MB for b in range(1, 8)})
        p = placement.plan_bucket_placement(est, devices=DEV8)
        hot_ordinals = {p.ordinal_for(0, chunk=c) for c in range(8)}
        assert len(hot_ordinals) >= 2, "hot bucket must span devices"
        assert REGISTRY.gauge("mesh.placement.devices_used").value >= 4
        assert REGISTRY.gauge("mesh.placement.bytes_imbalance_ratio").value < 2.0

    def test_placement_deterministic(self):
        rng = np.random.default_rng(7)
        est = {b: int(rng.integers(1, 50)) * MB for b in range(16)}
        a = placement.plan_bucket_placement(dict(est), devices=DEV8)
        b = placement.plan_bucket_placement(dict(est), devices=DEV8)
        for bucket in range(16):
            for chunk in range(4):
                assert a.ordinal_for(bucket, chunk) == b.ordinal_for(
                    bucket, chunk
                )

    def test_unseen_bucket_round_robins_and_counts_fallback(self):
        p = placement.plan_bucket_placement({0: MB, 1: MB}, devices=DEV8)
        before = REGISTRY.counter("mesh.placement.fallbacks").value
        got = [p.ordinal_for(99, chunk=c) for c in range(3)]
        assert got == [(99 + c) % 8 for c in range(3)]
        assert REGISTRY.counter("mesh.placement.fallbacks").value == before + 3

    def test_offset_rotates_packing(self):
        """The query's home chip breaks load ties, so two concurrent
        queries with different homes pack onto different chips instead
        of both starting at ordinal 0."""
        est = {0: MB}
        p0 = placement.plan_bucket_placement(dict(est), devices=DEV8, offset=0)
        p3 = placement.plan_bucket_placement(dict(est), devices=DEV8, offset=3)
        assert p0.ordinal_for(0) == 0
        assert p3.ordinal_for(0) == 3
        assert p3.slot_for(0) == (3, tuple(DEV8))

    def test_single_device_mesh_is_none(self):
        assert placement.plan_bucket_placement({0: MB}, devices=["d0"]) is None

    def test_mesh_off_means_no_devices(self, tmp_session):
        assert placement.mesh_devices(tmp_session) == []
        tmp_session.set_conf(C.EXEC_MESH_DEVICES, 1)
        assert placement.mesh_devices(tmp_session) == []
        assert placement.mesh_devices(None) == []

    def test_mesh_on_sees_forced_host_devices(self, tmp_session):
        import jax

        tmp_session.set_conf(C.EXEC_MESH_DEVICES, 8)
        assert placement.mesh_devices(tmp_session) == jax.devices()[:8]
        tmp_session.set_conf(C.EXEC_MESH_DEVICES, 2)
        assert placement.mesh_devices(tmp_session) == jax.devices()[:2]
        tmp_session.set_conf(C.EXEC_MESH_DEVICES, 16)  # conftest gives 8
        with pytest.raises(ValueError, match="meshDevices=16"):
            placement.mesh_devices(tmp_session)


# ---------------------------------------------------------------------------
# end-to-end bit-identity: mesh on vs off on the forced 8-device CPU mesh
# ---------------------------------------------------------------------------


def _write_sides(tmp_path, left, right):
    cio.write_parquet(
        ColumnBatch.from_pydict(left), str(tmp_path / "l" / "l.parquet")
    )
    cio.write_parquet(
        ColumnBatch.from_pydict(right), str(tmp_path / "r" / "r.parquet")
    )


@pytest.fixture()
def skew_env(tmp_session, tmp_path):
    """Heavily skewed left side (40% of rows on ONE hot key) over 8
    buckets: the shape where naive per-bucket placement pins one device
    and the fair-share split must spread the hot bucket."""
    rng = np.random.default_rng(101)
    n = 24_000
    k = rng.integers(0, 400, n)
    k[: int(n * 0.4)] = 7
    left = {"k": k.tolist(), "p": rng.uniform(0, 100, n).tolist()}
    right = {"rk": list(range(0, 200)), "w": rng.uniform(size=200).tolist()}
    _write_sides(tmp_path, left, right)
    tmp_session.set_conf(C.INDEX_NUM_BUCKETS, 8)
    hs = Hyperspace(tmp_session)
    hs.create_index(
        tmp_session.read.parquet(str(tmp_path / "l")),
        CoveringIndexConfig("jl", ["k"], ["p"]),
    )
    hs.create_index(
        tmp_session.read.parquet(str(tmp_path / "r")),
        CoveringIndexConfig("jr", ["rk"], ["w"]),
    )
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
        .agg(Sum(col("p")).alias("s"), Count(col("p")).alias("c"),
             Min(col("w")).alias("mn"), Max(col("w")).alias("mx"))
    )


def _served_on(session, df, chip: int):
    """``df`` collected twice through a scheduler on ``session``'s
    deployment, homed on ``chip``: the second answer, and each chip's
    dispatches in the second run (the first, which compiled, also ran on
    every other chip to leave its executables there)."""
    from hyperspace_tpu.serve.context import current_query
    from hyperspace_tpu.serve.scheduler import QueryScheduler

    def fn():
        current_query().device_home = chip
        return df.collect()

    sched = QueryScheduler(max_concurrent=1, queue_depth=4)
    try:
        first = sched.submit(fn, label="homed").result(timeout=300)
        before = [REGISTRY.counter(f"device.dispatch.d{k}").value
                  for k in range(8)]
        out = sched.submit(fn, label="homed").result(timeout=300)
        after = [REGISTRY.counter(f"device.dispatch.d{k}").value
                 for k in range(8)]
    finally:
        sched.shutdown(wait=True)
    assert hex_rows(first.to_pydict()) == hex_rows(out.to_pydict())
    return out, [a - b for a, b in zip(after, before)]


def _homed_vs_one_chip(session, tmp_path, q, chip: int = 3):
    session.enable_hyperspace()
    session.set_conf(C.EXEC_TPU_ENABLED, True)
    try:
        off = hex_rows(q(session, tmp_path).to_pydict())
        session.set_conf(C.EXEC_MESH_DEVICES, 8)
        out, dispatched = _served_on(session, q(session, tmp_path), chip)
    finally:
        session.set_conf(C.EXEC_MESH_DEVICES, 0)
        session.set_conf(C.EXEC_TPU_ENABLED, False)
        session.disable_hyperspace()
    return off, hex_rows(out.to_pydict()), dispatched


class TestMeshBitIdentity:
    def test_plain_join_bit_identical(self, skew_env):
        session, tmp_path = skew_env
        bands0 = REGISTRY.counter("pipeline.join.bands").value
        off, on, dispatched = _homed_vs_one_chip(session, tmp_path, _plain_q)
        assert on == off
        # every band wave of the homed query ran on its home chip
        assert REGISTRY.counter("pipeline.join.bands").value > bands0
        assert dispatched[3] > 0
        assert sum(dispatched) == dispatched[3]

    def test_fused_agg_bit_identical_and_balanced(self, skew_env):
        """The same aggregate join homed on each of the 8 chips in turn:
        the same bits everywhere, and each chip dispatches as much."""
        session, tmp_path = skew_env
        per_chip = []
        for chip in range(8):
            off, on, dispatched = _homed_vs_one_chip(
                session, tmp_path, _agg_q, chip)
            assert on == off
            assert sum(dispatched) == dispatched[chip] > 0
            per_chip.append(dispatched[chip])
        assert len(set(per_chip)) == 1

    def test_spread_bands_bit_identical_and_balanced(self, skew_env):
        """``bandPlacement=spread``: the placer spreads the homed query's
        bands over the chips (the skew fixture is the acceptance shape:
        >= 4 of 8 chips, the hot bucket does not pin the balance past
        2x), the bits stay those of one chip, and each wave's kernel runs
        on the chip it was placed on."""
        session, tmp_path = skew_env
        session.set_conf(C.EXEC_BAND_PLACEMENT, "spread")
        try:
            usage0 = REGISTRY.counter("rules.usage.MeshBucketedExec").value
            off, on, dispatched = _homed_vs_one_chip(
                session, tmp_path, _agg_q)
        finally:
            session.set_conf(C.EXEC_BAND_PLACEMENT, "home")
        assert on == off
        assert REGISTRY.gauge("mesh.placement.devices_used").value >= 4
        assert REGISTRY.gauge("mesh.placement.bytes_imbalance_ratio").value < 2.0
        assert sum(1 for n in dispatched if n) >= 4
        assert REGISTRY.counter("rules.usage.MeshBucketedExec").value > usage0

    def test_band_placement_conf_is_checked(self, tmp_session):
        assert tmp_session.conf.exec_band_placement == "home"
        tmp_session.set_conf(C.EXEC_BAND_PLACEMENT, "everywhere")
        with pytest.raises(Exception, match="bandPlacement"):
            tmp_session.conf.exec_band_placement

    def test_mesh_emits_usage_event(self, skew_env):
        """A direct collect on the deployment takes the mesh join route
        (``MeshBucketedExec``); a query served on a home chip never does."""
        session, tmp_path = skew_env
        session.enable_hyperspace()
        session.set_conf(C.EXEC_TPU_ENABLED, True)
        session.set_conf(C.EXEC_MESH_DEVICES, 8)
        try:
            before = REGISTRY.counter("rules.usage.MeshBucketedExec").value
            direct = hex_rows(_plain_q(session, tmp_path).to_pydict())
            mid = REGISTRY.counter("rules.usage.MeshBucketedExec").value
            out, _ = _served_on(session, _plain_q(session, tmp_path), 5)
            after = REGISTRY.counter("rules.usage.MeshBucketedExec").value
        finally:
            session.set_conf(C.EXEC_MESH_DEVICES, 0)
            session.set_conf(C.EXEC_TPU_ENABLED, False)
            session.disable_hyperspace()
        assert mid > before
        assert after == mid
        assert hex_rows(out.to_pydict()) == direct


@pytest.fixture(scope="module")
def tpch_env(tmp_path_factory):
    from hyperspace_tpu.benchmark import generate_tpch, tpch_indexes
    from hyperspace_tpu.session import HyperspaceSession

    root = str(tmp_path_factory.mktemp("tpch_mesh"))
    session = HyperspaceSession(warehouse_dir=root)
    generate_tpch(root, rows_lineitem=30_000, seed=1)
    hs = Hyperspace(session)
    tpch_indexes(session, hs, root)
    return session, root


class TestMeshTPCH:
    @pytest.mark.parametrize("name", ["q3", "q10", "q17"])
    def test_tpch_bit_identical(self, tpch_env, name):
        from hyperspace_tpu.benchmark import TPCH_QUERIES

        session, root = tpch_env
        q = TPCH_QUERIES[name]
        session.enable_hyperspace()
        session.set_conf(C.EXEC_TPU_ENABLED, True)
        try:
            off = hex_rows(q(session, root).to_pydict())
            session.set_conf(C.EXEC_MESH_DEVICES, 8)
            out, dispatched = _served_on(session, q(session, root), 6)
        finally:
            session.set_conf(C.EXEC_MESH_DEVICES, 0)
            session.set_conf(C.EXEC_TPU_ENABLED, False)
            session.disable_hyperspace()
        assert hex_rows(out.to_pydict()) == off, (
            f"{name} diverges on a home chip")
        assert sum(dispatched) == dispatched[6] > 0


# ---------------------------------------------------------------------------
# per-device memory ledgers
# ---------------------------------------------------------------------------


@pytest.fixture()
def small_device_budget(monkeypatch):
    monkeypatch.setenv("HYPERSPACE_DEVICE_BUDGET_MB", "1")
    serve_budget.reset_device_budget()
    yield 1 * MB
    monkeypatch.delenv("HYPERSPACE_DEVICE_BUDGET_MB", raising=False)
    serve_budget.reset_device_budget()


class TestPerDeviceLedgers:
    def test_registry_names_and_isolation(self, small_device_budget):
        a0 = serve_budget.device_budget()
        a3 = serve_budget.device_budget(3)
        assert a0 is serve_budget.device_budget(0)
        assert a3 is serve_budget.device_budget(3)
        assert a0 is not a3
        # ordinal 0 keeps the historical metric name; mesh ordinals suffix
        st = a3.state()
        assert serve_budget.device_budgets() == {0: a0, 3: a3}
        assert st["held_bytes"] == 0

    def test_ledger_conservation_across_devices(self, small_device_budget):
        from hyperspace_tpu.plan.join_memory import DeviceLedger

        ledger = DeviceLedger("t-conserve")
        try:
            ledger.admit(300_000, lambda: False, device=1)
            ledger.admit(400_000, lambda: False, device=2)
            ledger.admit(200_000, lambda: False, device=1)
            assert serve_budget.device_budget(1).held_bytes() == 500_000
            assert serve_budget.device_budget(2).held_bytes() == 400_000
            ledger.release(300_000, device=1)
            ledger.release(400_000, device=2)
            ledger.release(200_000, device=1)
            for acct in serve_budget.device_budgets().values():
                assert acct.held_bytes() == 0
                assert acct.check_consistency()
        finally:
            ledger.close()

    def test_saturated_device_parks_neighbors_proceed(
        self, small_device_budget
    ):
        """Filling device 1's ledger must not stall device 2: the park loop
        is per-accountant. The second admit on device 1 spills this join's
        own in-flight wave (the spill_one callback) and then proceeds."""
        from hyperspace_tpu.plan.join_memory import DeviceLedger

        budget = small_device_budget
        ledger = DeviceLedger("t-park")
        spilled = []

        def spill_one():
            if spilled:
                return False
            spilled.append(True)
            ledger.release(budget - 1024, device=1)
            return True

        try:
            ledger.admit(budget - 1024, spill_one, device=1)  # fills d1
            parks0 = REGISTRY.counter("join.spill.parks").value
            # a full neighbor never blocks d2: no park recorded
            ledger.admit(budget // 2, lambda: False, device=2)
            assert REGISTRY.counter("join.spill.parks").value == parks0
            # d1 over budget -> parks once, spills our wave, resumes
            ledger.admit(budget // 2, spill_one, device=1)
            assert REGISTRY.counter("join.spill.parks").value == parks0 + 1
            assert spilled
            assert (
                serve_budget.device_budget(1).held_bytes() == budget // 2
            )
            ledger.release(budget // 2, device=1)
            ledger.release(budget // 2, device=2)
            for acct in serve_budget.device_budgets().values():
                assert acct.held_bytes() == 0
        finally:
            ledger.close()

    def test_reset_clears_mesh_ordinals(self, small_device_budget):
        serve_budget.device_budget(5)
        assert 5 in serve_budget.device_budgets()
        serve_budget.reset_device_budget()
        assert set(serve_budget.device_budgets()) == {0}


# ---------------------------------------------------------------------------
# QoS home-device assignment
# ---------------------------------------------------------------------------


class TestSchedulerHomeDevice:
    def _scheduler(self):
        from hyperspace_tpu.serve.scheduler import QueryScheduler

        return QueryScheduler(max_concurrent=2, queue_depth=8)

    def _fake_active(self, homes, tenant="default"):
        return {
            i: types.SimpleNamespace(
                ctx=types.SimpleNamespace(device_home=h, tenant=tenant)
            )
            for i, h in enumerate(homes)
        }

    def _df(self, session, tmp_path):
        cio.write_parquet(
            ColumnBatch.from_pydict({"a": [1, 2, 3]}),
            str(tmp_path / "t" / "t.parquet"),
        )
        return session.read.parquet(str(tmp_path / "t")).select("a")

    def test_home_none_with_mesh_off(self, tmp_session, tmp_path):
        from hyperspace_tpu import serve

        df = self._df(tmp_session, tmp_path)
        homes = [REGISTRY.counter(f"serve.home.d{k}").value for k in range(8)]
        sched = serve.QueryScheduler(max_concurrent=1, queue_depth=4)
        try:
            h = sched.submit_query(df, label="no-home")
            h.result(timeout=60)
            assert h.ctx.device_home is None
        finally:
            sched.shutdown(wait=True)
        assert [
            REGISTRY.counter(f"serve.home.d{k}").value for k in range(8)
        ] == homes

    def test_home_is_least_occupied_ordinal(self):
        sched = self._scheduler()
        try:
            n = 8
            sched._active = self._fake_active([0, 0, 1, 3])
            home = sched._home_device_locked(n)
            assert home == 2  # first zero-occupancy ordinal
            sched._active = self._fake_active(list(range(n)))
            assert sched._home_device_locked(n) == 0  # all equal: lowest wins
            sched._active = self._fake_active([0, 1, 2, 3])
            assert sched._home_device_locked(4) == 0  # a deployment of 4
        finally:
            sched._active = {}
            sched.shutdown(wait=True)

    def test_submitted_query_gets_home(self, tmp_session, tmp_path):
        from hyperspace_tpu import serve

        tmp_session.set_conf(C.EXEC_MESH_DEVICES, 8)
        df = self._df(tmp_session, tmp_path)
        homes = [REGISTRY.counter(f"serve.home.d{k}").value for k in range(8)]
        sched = serve.QueryScheduler(max_concurrent=1, queue_depth=4)
        try:
            h = sched.submit_query(df, label="home-probe")
            h.result(timeout=60)
            assert h.ctx.device_home is not None
            assert 0 <= h.ctx.device_home < 8
        finally:
            sched.shutdown(wait=True)
        k = h.ctx.device_home
        assert REGISTRY.counter(f"serve.home.d{k}").value == homes[k] + 1
