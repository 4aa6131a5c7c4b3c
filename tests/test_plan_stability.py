"""Golden-file plan-stability tests.

Reference parity: goldstandard/PlanStabilitySuite.scala:83-289 — render a
normalized plan string for fixed queries and string-compare against approved
files; regenerate with GENERATE_GOLDEN_FILES=1.

Normalization strips run-dependent details (absolute paths, file counts per
se stay — the fixture is deterministic — and log versions are stable).
"""

import os
import re

import pytest

from hyperspace_tpu import CoveringIndexConfig, DataSkippingIndexConfig, Hyperspace, MinMaxSketch
from hyperspace_tpu.columnar import io as cio
from hyperspace_tpu.columnar.table import ColumnBatch
from hyperspace_tpu.plan import col, lit, Count, Sum

GOLDEN_DIR = os.path.join(os.path.dirname(__file__), "approved_plans")
GENERATE = os.environ.get("GENERATE_GOLDEN_FILES") == "1"


def normalize(plan_str: str, tmp: str) -> str:
    s = plan_str.replace(tmp, "<ROOT>")
    s = re.sub(r"/tmp/[^/ ]+", "<TMP>", s)
    return s + "\n"


def check(name: str, plan_str: str, tmp: str) -> None:
    os.makedirs(GOLDEN_DIR, exist_ok=True)
    path = os.path.join(GOLDEN_DIR, f"{name}.txt")
    rendered = normalize(plan_str, tmp)
    if GENERATE:
        with open(path, "w") as f:
            f.write(rendered)
        return
    assert os.path.exists(path), (
        f"No approved plan for {name!r}; generate it deliberately with "
        f"GENERATE_GOLDEN_FILES=1 after reviewing the plan:\n{rendered}"
    )
    with open(path) as f:
        approved = f.read()
    assert rendered == approved, (
        f"Plan for {name!r} changed; regenerate with GENERATE_GOLDEN_FILES=1 "
        f"if intended.\n--- approved ---\n{approved}\n--- actual ---\n{rendered}"
    )


@pytest.fixture()
def env(tmp_session, tmp_path):
    # deterministic fixture (fixed sizes, no randomness)
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


class TestPlanStability:
    def test_q_filter(self, env):
        session, tmp = env
        df = session.read.parquet(str(tmp / "L"))
        q = df.filter(col("k") == 3).select("k", "a")
        check("filter_index_scan", q.optimized_plan().pretty(), str(tmp))

    def test_q_join(self, env):
        session, tmp = env
        l = session.read.parquet(str(tmp / "L"))
        r = session.read.parquet(str(tmp / "R"))
        q = l.select("k", "a").join(r.select("rk", "c"), col("k") == col("rk"))
        check("join_index_scan", q.optimized_plan().pretty(), str(tmp))

    def test_q_agg(self, env):
        session, tmp = env
        df = session.read.parquet(str(tmp / "L"))
        q = (
            df.filter(col("k") == 3)
            .select("k", "a")
            .agg(Sum(col("a")).alias("s"), Count(lit(1)).alias("n"))
        )
        check("filter_agg", q.optimized_plan().pretty(), str(tmp))

    def test_q_no_index(self, env):
        session, tmp = env
        df = session.read.parquet(str(tmp / "L"))
        # needs column b: no index covers it -> plan unchanged
        q = df.filter(col("k") == 3).select("k", "b")
        check("filter_no_index", q.optimized_plan().pretty(), str(tmp))


@pytest.fixture(scope="module")
def tpch_golden_env(tmp_path_factory):
    """Deterministic tiny TPC-H with the full BASELINE index set (covering,
    z-order, data-skipping) — the golden corpus analogue of the reference's
    TPC-DS approved plans (goldstandard/PlanStabilitySuite.scala:83-289,
    src/test/resources/tpcds/)."""
    from hyperspace_tpu.benchmark import generate_tpch, tpch_indexes
    from hyperspace_tpu.session import HyperspaceSession

    root = str(tmp_path_factory.mktemp("tpch_golden"))
    session = HyperspaceSession(warehouse_dir=root)
    generate_tpch(root, rows_lineitem=2000, seed=7)
    hs = Hyperspace(session)
    tpch_indexes(session, hs, root)
    hs.create_index(
        session.read.parquet(os.path.join(root, "lineitem")),
        DataSkippingIndexConfig("li_ds_minmax", [MinMaxSketch("l_shipdate")]),
    )
    session.enable_hyperspace()
    return session, hs, root


class TestTPCHPlanStability:
    """Approved optimized plans for the TPC-H query set, one per index kind
    in play: Q6 (z-order covering), Q3 (join indexes + fused aggregate
    shape), Q17 (join index + per-part aggregate), Q1 (no covering index
    applies; DS sketch candidacy shows in whyNot)."""

    @pytest.mark.parametrize("name", ["q1", "q3", "q6", "q10", "q17", "q18"])
    def test_query_plan(self, tpch_golden_env, name):
        from hyperspace_tpu.benchmark import TPCH_QUERIES

        session, hs, root = tpch_golden_env
        q = TPCH_QUERIES[name](session, root)
        check(f"tpch_{name}", q.optimized_plan().pretty(), root)

    def test_q6_explain(self, tpch_golden_env):
        from hyperspace_tpu.benchmark import TPCH_QUERIES
        from hyperspace_tpu import constants as C

        session, hs, root = tpch_golden_env
        session.set_conf(C.DISPLAY_MODE, "plaintext")
        q = TPCH_QUERIES["q6"](session, root)
        check("tpch_q6_explain", hs.explain(q, verbose=True), root)

    def test_q3_why_not(self, tpch_golden_env):
        from hyperspace_tpu.benchmark import TPCH_QUERIES

        session, hs, root = tpch_golden_env
        q = TPCH_QUERIES["q3"](session, root)
        check("tpch_q3_whynot", hs.why_not(q, extended=True), root)

    def test_q10_explain(self, tpch_golden_env):
        """Verbose explain over the join+topk shape: both rewritten sides
        highlight, and the applicable-index table lists the join rule."""
        from hyperspace_tpu.benchmark import TPCH_QUERIES
        from hyperspace_tpu import constants as C

        session, hs, root = tpch_golden_env
        session.set_conf(C.DISPLAY_MODE, "plaintext")
        q = TPCH_QUERIES["q10"](session, root)
        check("tpch_q10_explain", hs.explain(q, verbose=True), root)

    def test_q18_why_not(self, tpch_golden_env):
        """Non-extended whyNot over the HAVING-over-aggregate join: the
        COL_SCHEMA_MISMATCH noise rows stay hidden with a count."""
        from hyperspace_tpu.benchmark import TPCH_QUERIES

        session, hs, root = tpch_golden_env
        q = TPCH_QUERIES["q18"](session, root)
        check("tpch_q18_whynot", hs.why_not(q), root)


class TestKernelJaxprStability:
    """Golden over the REWRITTEN COMPUTE IR, not just the logical plan
    (SURVEY §4's implication (b): golden-file tests over the jaxpr/HLO of
    the lowered kernels): the flagship Q6 fused kernel's jaxpr must not
    drift unnoticed — fusion regressions show up as structural changes
    here before they show up as latency."""

    def test_q6_fused_kernel_jaxpr(self, tmp_path):
        import jax
        import numpy as np

        from __graft_entry__ import entry

        kernel, args = entry()
        jaxpr = jax.make_jaxpr(kernel)(*args)
        rendered = str(jaxpr)
        # normalize: drop memory-space/layout annotations that vary by
        # backend; keep the op structure
        rendered = re.sub(r"memory_kind=[a-z_]+", "memory_kind=<mk>", rendered)
        check("q6_fused_kernel_jaxpr", rendered, str(tmp_path))


@pytest.fixture(scope="module")
def tpcds_golden_env(tmp_path_factory):
    """The reference's goldstandard corpus is TPC-DS with exactly q1 enabled
    (goldstandard/TPCDSBase.scala:41); mirror it: q1-relevant tables, the
    q1 index set, approved plans for the q1 core shapes."""
    from hyperspace_tpu.benchmark.tpcds import generate_tpcds, tpcds_indexes
    from hyperspace_tpu.session import HyperspaceSession

    root = str(tmp_path_factory.mktemp("tpcds_golden"))
    session = HyperspaceSession(warehouse_dir=root)
    generate_tpcds(root, rows_store_returns=5_000, seed=3)
    hs = Hyperspace(session)
    tpcds_indexes(session, hs, root)
    session.enable_hyperspace()
    return session, hs, root


class TestTPCDSPlanStability:
    def test_q1_ctr_plan(self, tpcds_golden_env):
        from hyperspace_tpu.benchmark.tpcds import q1_customer_total_return

        session, hs, root = tpcds_golden_env
        q = q1_customer_total_return(session, root)
        check("tpcds_q1_ctr", q.optimized_plan().pretty(), root)

    def test_q1_store_avg_plan(self, tpcds_golden_env):
        from hyperspace_tpu.benchmark.tpcds import q1_store_avg

        session, hs, root = tpcds_golden_env
        q = q1_store_avg(session, root)
        check("tpcds_q1_store_avg", q.optimized_plan().pretty(), root)

    def test_q1_results_match_raw(self, tpcds_golden_env):
        from hyperspace_tpu.benchmark.tpcds import q1_customer_total_return

        session, hs, root = tpcds_golden_env
        session.disable_hyperspace()
        expected = q1_customer_total_return(session, root).to_pydict()
        session.enable_hyperspace()
        got = q1_customer_total_return(session, root).to_pydict()
        key = lambda d: sorted(
            zip(d["sr_customer_sk"], d["sr_store_sk"], [round(v, 6) for v in d["ctr_total_return"]])
        )
        assert key(got) == key(expected)

    def test_bloom_point_lookup_skips(self, tpcds_golden_env):
        """The config-5 bloom index prunes store_returns point-lookup files
        BEFORE any IO: the rewritten scan lists fewer files than the raw
        scan (customer keys are file-local, so most blooms reject)."""
        from hyperspace_tpu.plan.nodes import FileScan

        session, hs, root = tpcds_golden_env
        q = (
            session.read.parquet(root + "/store_returns")
            .filter(col("sr_customer_sk") == 17)
            .select("sr_customer_sk", "sr_return_amt")
        )
        plan = q.optimized_plan()
        scans = [n for n in plan.preorder() if isinstance(n, FileScan)]
        assert len(scans) == 1
        assert len(scans[0].files) < 8  # bloom rejected most of the 8 files
        session.disable_hyperspace()
        expected = q.to_pydict()
        session.enable_hyperspace()
        assert q.to_pydict() == expected
