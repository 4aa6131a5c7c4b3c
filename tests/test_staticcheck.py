"""Static-analysis subsystem tests: plan invariant verifier, kernel/jaxpr
auditor + retrace watchdog, hslint repo cleanliness, env-registry docs sync.

The verifier must accept every plan the engine actually produces (the
plan-stability query set whose renderings live in tests/approved_plans/,
plus all TPC-H bench queries) and reject hand-mutated plans with the right
violation code AND node path — a verifier that cries wolf is disabled
within a week, one that misses a planted bug is decoration.
"""

import os
import subprocess
import sys
from dataclasses import replace

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from hyperspace_tpu import CoveringIndexConfig, Hyperspace
from hyperspace_tpu.benchmark import TPCH_QUERIES, generate_tpch, tpch_indexes
from hyperspace_tpu.columnar import io as cio
from hyperspace_tpu.columnar.table import ColumnBatch
from hyperspace_tpu.meta.entry import FileInfo
from hyperspace_tpu.plan import col
from hyperspace_tpu.plan.kernel_cache import KernelCache
from hyperspace_tpu.plan.nodes import (
    BucketSpec,
    FileScan,
    Join,
    Project,
)
from hyperspace_tpu.plan.expr import Col
from hyperspace_tpu.staticcheck import kernel_audit
from hyperspace_tpu.staticcheck.plan_verifier import (
    DUPLICATE_FILE,
    EMPTY_FILE_SCAN,
    FILE_NOT_IN_INDEX,
    JOIN_BUCKET_MISMATCH,
    PRUNE_SPEC_LAYOUT_MISMATCH,
    UNRESOLVED_COLUMN_REF,
    PlanInvariantError,
    maybe_verify_plan,
    verify_plan,
)
from hyperspace_tpu.telemetry.metrics import REGISTRY
from hyperspace_tpu.utils import env as env_registry

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HSLINT = os.path.join(REPO_ROOT, "tools", "hslint.py")


def _counter(name: str) -> int:
    m = REGISTRY.get(name)
    return 0 if m is None else m.value


# ---------------------------------------------------------------------------
# fixtures
# ---------------------------------------------------------------------------

@pytest.fixture()
def ci_env(tmp_session, tmp_path):
    """The plan-stability fixture: two tables, two covering indexes — the
    query set whose approved renderings live in tests/approved_plans/."""
    n = 100
    left = {
        "k": [i % 10 for i in range(n)],
        "a": [float(i) for i in range(n)],
        "b": [i * 2 for i in range(n)],
    }
    right = {"rk": list(range(10)), "c": [float(i) for i in range(10)]}
    cio.write_parquet(ColumnBatch.from_pydict(left), str(tmp_path / "L" / "l.parquet"))
    cio.write_parquet(ColumnBatch.from_pydict(right), str(tmp_path / "R" / "r.parquet"))
    hs = Hyperspace(tmp_session)
    ldf = tmp_session.read.parquet(str(tmp_path / "L"))
    rdf = tmp_session.read.parquet(str(tmp_path / "R"))
    hs.create_index(ldf, CoveringIndexConfig("ci_k", ["k"], ["a"]))
    hs.create_index(rdf, CoveringIndexConfig("ci_rk", ["rk"], ["c"]))
    tmp_session.enable_hyperspace()
    return tmp_session, tmp_path


@pytest.fixture(scope="module")
def tpch_env(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("tpch_sc"))
    from hyperspace_tpu.session import HyperspaceSession

    session = HyperspaceSession(warehouse_dir=root)
    generate_tpch(root, rows_lineitem=30_000, seed=3)
    hs = Hyperspace(session)
    tpch_indexes(session, hs, root)
    return session, root


def _approved_plan_queries(session, tmp):
    """The exact query shapes of the approved_plans golden set."""
    from hyperspace_tpu.plan import Count, Sum

    ldf = session.read.parquet(str(tmp / "L"))
    rdf = session.read.parquet(str(tmp / "R"))
    return {
        "filter_index_scan": ldf.filter(col("k") == 3).select("k", "a"),
        "filter_no_index": ldf.filter(col("b") == 4).select("k", "b"),
        "join_index_scan": ldf.join(rdf, col("k") == col("rk")).select("k", "a", "c"),
        "filter_agg": (
            ldf.filter(col("k") == 3).agg(Sum(col("a")), Count(col("a")))
        ),
    }


def _indexed_scan(plan) -> FileScan:
    for n in plan.preorder():
        if isinstance(n, FileScan) and n.index_info is not None:
            return n
    raise AssertionError("no index scan in plan")


# ---------------------------------------------------------------------------
# plan verifier
# ---------------------------------------------------------------------------

class TestPlanVerifierAccepts:
    def test_approved_plan_query_set(self, ci_env):
        session, tmp = ci_env
        for name, q in _approved_plan_queries(session, tmp).items():
            violations = verify_plan(q.optimized_plan(), session)
            assert violations == [], f"{name}: {violations}"

    def test_all_tpch_bench_plans(self, tpch_env):
        session, root = tpch_env
        session.enable_hyperspace()
        try:
            for name, q in TPCH_QUERIES.items():
                plan = q(session, root).optimized_plan()
                violations = verify_plan(plan, session)
                assert violations == [], f"{name}: {violations}"
        finally:
            session.disable_hyperspace()

    def test_tpch_raw_plans_too(self, tpch_env):
        session, root = tpch_env
        session.disable_hyperspace()
        for name, q in TPCH_QUERIES.items():
            violations = verify_plan(q(session, root).optimized_plan(), session)
            assert violations == [], f"{name}: {violations}"

    def test_verified_result_identical(self, ci_env, monkeypatch):
        session, tmp = ci_env
        q = lambda: session.read.parquet(str(tmp / "L")).filter(  # noqa: E731
            col("k") == 3
        ).select("k", "a")
        plain = q().to_pydict()
        runs0 = _counter("staticcheck.plan.runs")
        monkeypatch.setenv("HYPERSPACE_VERIFY_PLAN", "1")
        verified = q().to_pydict()
        assert verified == plain
        assert _counter("staticcheck.plan.runs") > runs0

    def test_hook_noop_when_disabled(self, ci_env, monkeypatch):
        session, tmp = ci_env
        monkeypatch.delenv("HYPERSPACE_VERIFY_PLAN", raising=False)
        plan = _approved_plan_queries(session, tmp)["filter_index_scan"].optimized_plan()
        runs0 = _counter("staticcheck.plan.runs")
        maybe_verify_plan(plan, session)
        assert _counter("staticcheck.plan.runs") == runs0


class TestPlanVerifierRejects:
    def test_dangling_column(self, ci_env):
        session, tmp = ci_env
        plan = _approved_plan_queries(session, tmp)["filter_index_scan"].optimized_plan()
        bad = Project([Col("does_not_exist")], plan)
        with pytest.raises(PlanInvariantError) as ei:
            verify_plan(bad, session)
        err = ei.value
        assert err.code == UNRESOLVED_COLUMN_REF
        assert err.path.startswith("Project")

    def test_stale_prune_spec_num_buckets(self, ci_env):
        session, tmp = ci_env
        plan = _approved_plan_queries(session, tmp)["filter_index_scan"].optimized_plan()
        scan = _indexed_scan(plan)
        assert scan.prune_spec is not None
        scan.prune_spec = replace(
            scan.prune_spec, num_buckets=scan.prune_spec.num_buckets + 3
        )
        with pytest.raises(PlanInvariantError) as ei:
            verify_plan(plan, session)
        assert ei.value.code == PRUNE_SPEC_LAYOUT_MISMATCH
        assert "FileScan" in ei.value.path

    def test_file_not_in_index(self, ci_env, tmp_path):
        session, tmp = ci_env
        plan = _approved_plan_queries(session, tmp)["filter_index_scan"].optimized_plan()
        scan = _indexed_scan(plan)
        stray = FileInfo.from_path(str(tmp / "L" / "l.parquet"))
        scan.files = list(scan.files) + [stray]
        with pytest.raises(PlanInvariantError) as ei:
            verify_plan(plan, session)
        codes = {v.code for v in ei.value.violations}
        assert FILE_NOT_IN_INDEX in codes

    def test_duplicate_file(self, ci_env):
        session, tmp = ci_env
        plan = _approved_plan_queries(session, tmp)["filter_no_index"].optimized_plan()
        for n in plan.preorder():
            if isinstance(n, FileScan):
                n.files = list(n.files) + [n.files[0]]
                break
        with pytest.raises(PlanInvariantError) as ei:
            verify_plan(plan, session)
        assert DUPLICATE_FILE in {v.code for v in ei.value.violations}

    def test_empty_unpruned_scan(self, ci_env):
        session, tmp = ci_env
        plan = _approved_plan_queries(session, tmp)["filter_no_index"].optimized_plan()
        for n in plan.preorder():
            if isinstance(n, FileScan):
                n.files = []
                break
        with pytest.raises(PlanInvariantError) as ei:
            verify_plan(plan, session)
        assert EMPTY_FILE_SCAN in {v.code for v in ei.value.violations}

    def test_join_bucket_mismatch(self, ci_env):
        session, tmp = ci_env
        q = _approved_plan_queries(session, tmp)["join_index_scan"]
        plan = q.optimized_plan()

        joins = [n for n in plan.preorder() if isinstance(n, Join)]
        assert joins, "join plan must contain a Join node"
        scans = [
            n for n in plan.preorder()
            if isinstance(n, FileScan) and n.bucket_spec is not None
        ]
        if len(scans) < 2:
            pytest.skip("join rewrite did not bucket both sides")
        spec = scans[0].bucket_spec
        scans[0].bucket_spec = BucketSpec(
            spec.num_buckets * 2, spec.bucket_columns, spec.sort_columns
        )
        # keep the layout contract consistent with the (mutated) hint so
        # ONLY the cross-side invariant fires
        if scans[0].prune_spec is not None:
            scans[0].prune_spec = replace(
                scans[0].prune_spec, num_buckets=spec.num_buckets * 2
            )
        violations = verify_plan(plan, session=None, raise_on_violation=False)
        assert JOIN_BUCKET_MISMATCH in {v.code for v in violations}


# ---------------------------------------------------------------------------
# kernel audit
# ---------------------------------------------------------------------------

class TestKernelAudit:
    def test_flags_host_callback_kernel(self, monkeypatch):
        monkeypatch.setenv("HYPERSPACE_KERNEL_AUDIT", "1")
        cache = KernelCache("audit_test", 8)

        def build():
            def cb(x):
                return np.asarray(x) * 2

            def kernel(x):
                return jax.pure_callback(
                    cb, jax.ShapeDtypeStruct(x.shape, x.dtype), x
                )

            return jax.jit(kernel)  # hslint: HS201 — synthetic hazard fixture

        before = _counter("staticcheck.kernel.hazard.HOST_CALLBACK")
        k = cache.get_or_build(("hostcb", (("x", "int32"),)), build, "hostcb")
        out = k(jnp.arange(4))
        assert list(np.asarray(out)) == [0, 2, 4, 6]  # behavior unchanged
        assert _counter("staticcheck.kernel.hazard.HOST_CALLBACK") == before + 1

    def test_flags_nondeterministic_primitive(self):
        jaxpr = jax.make_jaxpr(
            lambda: jax.lax.rng_uniform(jnp.float32(0), jnp.float32(1), (4,))
        )()
        hazards = kernel_audit.audit_jaxpr("rng_kind", jaxpr)
        assert any(h.code == kernel_audit.NONDETERMINISTIC for h in hazards)

    def test_flags_implicit_f64_promotion(self):
        try:
            from jax.experimental import enable_x64
        except ImportError:
            pytest.skip("jax.experimental.enable_x64 unavailable")
        with enable_x64():
            jaxpr = jax.make_jaxpr(lambda x: x + 0.5)(np.arange(3, dtype=np.int64))
        hazards = kernel_audit.audit_jaxpr("promo_kind", jaxpr)
        assert any(h.code == kernel_audit.IMPLICIT_F64 for h in hazards)

    def test_clean_kernel_has_no_hazards(self):
        jaxpr = jax.make_jaxpr(lambda x: jnp.where(x > 1, x, 0).sum())(
            np.arange(8, dtype=np.int32)
        )
        assert kernel_audit.audit_jaxpr("clean_kind", jaxpr) == []

    def test_audit_disabled_is_transparent(self, monkeypatch):
        monkeypatch.delenv("HYPERSPACE_KERNEL_AUDIT", raising=False)
        sentinel = object()
        out = kernel_audit.observe_compile(
            "cache", "kind_x", ("kind_x", (("a", "i32"),)), sentinel
        )
        assert out is sentinel

    def test_retrace_watchdog_fires_on_fingerprint_churn(self, monkeypatch):
        monkeypatch.setenv("HYPERSPACE_RETRACE_WARN", "5")
        kernel_audit.reset_watchdog()
        try:
            sig = (("x", "int32"),)
            msg = None
            for i in range(8):
                msg = kernel_audit.WATCHDOG.record(
                    "wd_cache", "wd_kind", ("wd_kind", f"pred_{i}", sig)
                ) or msg
            assert msg is not None, "watchdog must fire past the threshold"
            assert "wd_kind" in msg and "pos 1" in msg  # the varying position
        finally:
            kernel_audit.reset_watchdog()

    def test_watchdog_quiet_across_distinct_signatures(self, monkeypatch):
        monkeypatch.setenv("HYPERSPACE_RETRACE_WARN", "5")
        kernel_audit.reset_watchdog()
        try:
            for i in range(16):
                msg = kernel_audit.WATCHDOG.record(
                    "wd_cache2", "wd_kind2",
                    ("wd_kind2", "pred", (("x", f"dtype_{i}"),)),
                )
                assert msg is None  # each signature group has ONE key
        finally:
            kernel_audit.reset_watchdog()


# ---------------------------------------------------------------------------
# hslint + env registry
# ---------------------------------------------------------------------------

class TestHslint:
    def test_package_is_clean_modulo_baseline(self):
        proc = subprocess.run(
            [sys.executable, HSLINT],
            capture_output=True, text=True, timeout=120,
        )
        assert proc.returncode == 0, proc.stdout + proc.stderr
        assert "0 new violation(s)" in proc.stdout

    def test_catches_planted_violations(self, tmp_path):
        bad = tmp_path / "bad_module.py"
        bad.write_text(
            "import os, time, threading, jax\n"
            "from hyperspace_tpu.telemetry import trace\n"
            "MODE = os.environ.get('HYPERSPACE_WHATEVER', '1')\n"
            "kernel = jax.jit(lambda x: x)\n"
            "def f():\n"
            "    with trace.span('exec:thing'):\n"
            "        return time.time()\n"
            "class C:\n"
            "    def __init__(self):\n"
            "        self._lock = threading.Lock()\n"
            "        self._d = dict()\n"
            "    def put(self, k, v):\n"
            "        self._d[k] = v\n"
        )
        proc = subprocess.run(
            [sys.executable, HSLINT, str(bad), "--no-baseline"],
            capture_output=True, text=True, timeout=120,
        )
        assert proc.returncode == 1
        for code in ("HS201", "HS301", "HS302", "HS303"):
            assert code in proc.stdout, f"{code} missing:\n{proc.stdout}"

    def test_suppression_comment_silences(self, tmp_path):
        ok = tmp_path / "ok_module.py"
        ok.write_text(
            "import jax\n"
            "kernel = jax.jit(lambda x: x)  # hslint: HS201 — fixture\n"
        )
        proc = subprocess.run(
            [sys.executable, HSLINT, str(ok), "--no-baseline"],
            capture_output=True, text=True, timeout=120,
        )
        assert proc.returncode == 0, proc.stdout


class TestEnvRegistry:
    def test_docs_table_in_sync(self):
        assert env_registry.update_docs(
            os.path.join(REPO_ROOT, "docs", "performance.md"), check_only=True
        ), "docs/performance.md env table is stale — run " \
           "python -m hyperspace_tpu.utils.env --update-docs"

    def test_every_scattered_knob_is_registered(self):
        names = {k.name for k in env_registry.all_knobs()}
        for expected in (
            "HYPERSPACE_PIPELINE", "HYPERSPACE_PRUNE", "HYPERSPACE_IO_THREADS",
            "HYPERSPACE_JOIN_SPLIT_ROWS", "HYPERSPACE_TRACE",
            "HYPERSPACE_DEVICE_STRICT", "HYPERSPACE_VERIFY_PLAN",
            "HYPERSPACE_KERNEL_AUDIT", "HYPERSPACE_RETRACE_WARN",
        ):
            assert expected in names

    def test_typed_reads(self, monkeypatch):
        monkeypatch.delenv("HYPERSPACE_ADAPTIVE_WARMUP_CHUNKS", raising=False)
        assert env_registry.env_int("HYPERSPACE_ADAPTIVE_WARMUP_CHUNKS") == 2
        monkeypatch.setenv("HYPERSPACE_ADAPTIVE_WARMUP_CHUNKS", "5")
        assert env_registry.env_int("HYPERSPACE_ADAPTIVE_WARMUP_CHUNKS") == 5
        monkeypatch.delenv("HYPERSPACE_VERIFY_PLAN", raising=False)
        assert env_registry.env_bool("HYPERSPACE_VERIFY_PLAN") is False
        monkeypatch.setenv("HYPERSPACE_VERIFY_PLAN", "1")
        assert env_registry.env_bool("HYPERSPACE_VERIFY_PLAN") is True
        # unregistered names need an explicit default
        with pytest.raises(KeyError):
            env_registry.env_int("HYPERSPACE_NOT_A_KNOB")
        assert env_registry.env_int("HYPERSPACE_NOT_A_KNOB", 7) == 7
