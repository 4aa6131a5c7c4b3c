"""Per-query phase accounting on the query thread.

Served queries (a Q6-like fused device aggregate and a bucketed
co-partitioned join) through ``QueryScheduler`` on the CPU:

- on the worker thread phases are exclusive, so the phases charged there
  plus ``host`` equal the query's run time, and ``host`` >= 0;
- ``rpc_meter.device_get`` charges the device wait (``device_wait``) and
  the copy (``fetch``) as two phases, and its meter counts stay one fetch;
- a new literal's XLA compile is charged to its query as ``compile``, and
  a repeat charges none;
- the kernel-call phase is ``enqueue``; ``dispatch`` and the
  ``kernel.*dispatch_ms`` histograms are gone.
"""

import os
import threading
import time

import jax
import jax.numpy as jnp
import pytest

from hyperspace_tpu import Hyperspace, HyperspaceSession, serve
from hyperspace_tpu import constants as C
from hyperspace_tpu.benchmark import generate_tpch
from hyperspace_tpu.models.covering import CoveringIndexConfig
from hyperspace_tpu.models.zorder import ZOrderCoveringIndexConfig
from hyperspace_tpu.plan import Sum, col, lit
from hyperspace_tpu.serve.context import QueryContext
from hyperspace_tpu.telemetry import attribution
from hyperspace_tpu.telemetry.attribution import LEDGER, PHASES, QueryStatsLedger
from hyperspace_tpu.telemetry.metrics import REGISTRY
from hyperspace_tpu.utils.rpc_meter import METER, device_get

ON_THREAD = ("plan", "io_wait", "upload", "enqueue", "device_wait", "fetch",
             "fold", "park", "compile")


@pytest.fixture(scope="module")
def warehouse(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("phase_wh"))
    generate_tpch(root, rows_lineitem=20_000, seed=5)
    session = HyperspaceSession(warehouse_dir=root)
    session.set_conf(C.INDEX_NUM_BUCKETS, 4)
    session.set_conf(C.EXEC_TPU_ENABLED, True)
    hs = Hyperspace(session)
    li = session.read.parquet(os.path.join(root, "lineitem"))
    od = session.read.parquet(os.path.join(root, "orders"))
    hs.create_index(li, ZOrderCoveringIndexConfig(
        "ph_shipdate_z", ["l_shipdate"],
        ["l_extendedprice", "l_discount", "l_quantity"]))
    hs.create_index(li, CoveringIndexConfig(
        "ph_orderkey", ["l_orderkey"], ["l_extendedprice", "l_discount"]))
    hs.create_index(od, CoveringIndexConfig(
        "ph_o_orderkey", ["o_orderkey"], ["o_orderdate"]))
    session.enable_hyperspace()
    return session, root


def _q6(session, root, quantity):
    li = session.read.parquet(os.path.join(root, "lineitem"))
    return (
        li.filter((col("l_shipdate") >= 8766) & (col("l_shipdate") < 9131)
                  & (col("l_quantity") < quantity))
        .select("l_shipdate", "l_extendedprice", "l_discount", "l_quantity")
        .agg(Sum(col("l_extendedprice") * col("l_discount")).alias("rev"))
    )


def _join(session, root):
    li = session.read.parquet(os.path.join(root, "lineitem"))
    od = session.read.parquet(os.path.join(root, "orders"))
    return (
        li.select("l_orderkey", "l_extendedprice", "l_discount")
        .join(od.select("o_orderkey", "o_orderdate"),
              col("l_orderkey") == col("o_orderkey"))
        .filter(col("o_orderdate") < 9500)
        .group_by("o_orderdate")
        .agg(Sum(col("l_extendedprice") * (lit(1.0) - col("l_discount")))
             .alias("revenue"))
    )


def _serve(dfs, monkeypatch):
    """Serve each DataFrame once, in order; their finished QueryStats."""
    monkeypatch.setenv("HYPERSPACE_IO_THREADS", "4")
    serve.reset_global_budget()
    seq0 = LEDGER.last_seq()
    sched = serve.QueryScheduler(max_concurrent=1, queue_depth=64)
    try:
        for i, df in enumerate(dfs):
            sched.submit_query(df, label=f"ph{i}").result(120)
    finally:
        sched.shutdown()
    return [s for s in list(LEDGER._recent) if s.seq > seq0]


def test_phases_on_the_query_thread_sum_to_its_run_time(warehouse,
                                                        monkeypatch):
    session, root = warehouse
    dfs = [_q6(session, root, 24), _join(session, root)] * 3
    stats = _serve(dfs, monkeypatch)
    assert len(stats) == len(dfs)
    for s in stats:
        own = s.on_thread_s()
        ph = s.phases_s()
        assert set(own) <= set(PHASES) - {"host"}
        assert ph["host"] >= 0.0
        # the phases charged on the worker plus host are its run time
        total = sum(own.values()) + ph["host"]
        assert total == pytest.approx(s.duration_s,
                                      abs=1e-3 + 0.01 * s.duration_s)
        # phases that only ever run on the query thread are all its own
        for p in ON_THREAD:
            assert own.get(p, 0.0) == pytest.approx(ph.get(p, 0.0),
                                                    abs=1e-9)
        assert ph["plan"] > 0 and ph["enqueue"] > 0
        assert ph["device_wait"] > 0 and ph["fetch"] > 0
    joins = [s for s in stats if s.label in ("ph1", "ph3", "ph5")]
    # the bucketed join decodes its pairs on the IO pool (resource time)
    assert all(s.phases_s().get("io", 0) > 0 for s in joins)
    rec = joins[-1].record()
    assert list(rec["phases_ms"]) == list(PHASES)
    assert rec["phases_ms"]["host"] >= 0


def test_device_get_charges_device_wait_and_fetch_separately():
    led = QueryStatsLedger(window=4)
    x = jnp.ones((512, 512), jnp.float32)
    f = jax.jit(lambda a: (a @ a @ a @ a).sum(axis=0))
    f(x).block_until_ready()  # compiled outside the query
    before = METER.snapshot()
    stats = led.begin(QueryContext(label="dg"))
    with attribution.scope(stats):
        with attribution.phase("fold"):
            out = device_get(f(x))
            time.sleep(0.002)  # host work inside the fold
    led.finish(stats, "done")
    ph, own = stats.phases_s(), stats.on_thread_s()
    assert ph["device_wait"] > 0 and ph["fetch"] > 0
    # the fold pauses for the two nested phases it contains
    assert own["fold"] == pytest.approx(ph["fold"])
    assert ph["fold"] >= 0.002
    assert ph["fold"] + ph["device_wait"] + ph["fetch"] <= (
        stats.duration_s + 1e-3)
    delta = METER.delta_since(before)
    assert delta["fetches"] == 1
    assert delta["fetch_bytes"] == out.nbytes


def test_phases_charged_off_the_query_thread_are_resource_time():
    led = QueryStatsLedger(window=4)
    stats = led.begin(QueryContext(label="pool"))
    try:
        with attribution.scope(stats):

            def task():
                with attribution.phase("io"):
                    time.sleep(0.01)

            t = threading.Thread(target=attribution.bound(task))
            with attribution.phase("io_wait"):
                t.start()
                t.join(10)
            assert not t.is_alive()
    finally:
        led.finish(stats, "done")
    ph, own = stats.phases_s(), stats.on_thread_s()
    assert ph["io"] >= 0.01 and "io" not in own
    assert own["io_wait"] >= 0.01 and ph["host"] >= 0


def test_a_new_literal_charges_its_compile_and_a_repeat_none(warehouse,
                                                             monkeypatch):
    session, root = warehouse
    # 19.5: a float literal where the module's other queries have an int, a
    # shape no other query compiles; literals are kernel arguments, so the
    # repeat with another value of that shape compiles nothing
    first, again = _serve([_q6(session, root, 19.5), _q6(session, root, 21.5)],
                          monkeypatch)
    assert first.phases_s().get("compile", 0.0) > 0
    assert again.phases_s().get("compile", 0.0) == 0
    assert again.record()["phases_ms"]["compile"] == 0


def test_enqueue_replaces_dispatch(warehouse, monkeypatch):
    session, root = warehouse
    (s,) = _serve([_q6(session, root, 24)], monkeypatch)
    rec = s.record()
    assert "dispatch" not in PHASES and "enqueue" in PHASES
    assert rec["phases_ms"]["enqueue"] > 0 and "dispatch" not in rec[
        "phases_ms"]
    names = {name for name, _kind, _v in REGISTRY.export()}
    assert "serve.query.phase.enqueue_ms" in names
    assert not any(n.startswith("kernel.") and n.endswith("dispatch_ms")
                   for n in names)
    assert "serve.query.phase.dispatch_ms" not in names
