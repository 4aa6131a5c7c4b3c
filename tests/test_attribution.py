"""Per-query attribution, live metrics export, and the serving health plane.

Covers the PR-9 tentpole guarantees:

- the metrics registry's attributed write path: counter/histogram deltas
  charged to the installed QueryStats in addition to the global value,
  propagated onto IO-pool tasks via ``attribution.bound``;
- conservation: for served queries, per-query ledger sums equal the global
  counter deltas over the serving window (no increment escapes, none is
  double-charged);
- ``MetricsRegistry`` snapshot/export consistency under a concurrent
  write hammer (no torn histogram bucket/count pairs);
- exporter lifecycle: disabled by default (no thread, no socket),
  ephemeral-port bind/release, Prometheus text parses and is internally
  consistent under concurrent scrapes, /healthz flips on an open breaker;
- the query log: rolling window, slow-query JSONL, zero-charge records
  for queries cancelled while queued, phase percentiles for bench;
- tools/trace_report.py --query extracts one serving query's span tree.
"""

import json
import os
import socket
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from hyperspace_tpu import HyperspaceSession, serve
from hyperspace_tpu import constants as C
from hyperspace_tpu.columnar import io as cio
from hyperspace_tpu.columnar.table import ColumnBatch
from hyperspace_tpu.plan import Count, Sum, col, lit
from hyperspace_tpu.serve.context import QueryContext
from hyperspace_tpu.telemetry import attribution, exporter
from hyperspace_tpu.telemetry.attribution import (
    LEDGER,
    QueryStats,
    QueryStatsLedger,
    phase_percentiles,
)
from hyperspace_tpu.telemetry.metrics import (
    REGISTRY,
    Histogram,
    MetricsRegistry,
)
from hyperspace_tpu.utils import backend, faults

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _pristine_observability_state():
    yield
    exporter.stop_exporter()
    exporter.stop_snapshot_sink()
    faults.disarm()
    backend._reset_for_testing()
    serve.reset_global_budget()


def _stats(qid=1, label="t", **kw) -> QueryStats:
    return QueryStats(qid, label=label, **kw)


# ---------------------------------------------------------------------------
# attributed write path
# ---------------------------------------------------------------------------

class TestAttributedWrites:
    def test_counter_inc_charges_scope_and_global(self):
        s = _stats()
        c = REGISTRY.counter("test.attr.counter")
        before = c.value
        with attribution.scope(s):
            c.inc(3)
            c.inc()
        c.inc(10)  # outside the scope: global only
        assert c.value == before + 14
        assert s.counters() == {"test.attr.counter": 4}

    def test_histogram_observe_charges_count_and_sum(self):
        s = _stats()
        h = REGISTRY.histogram("test.attr.hist")
        with attribution.scope(s):
            h.observe(2.0)
            h.observe(3.0)
        h.observe(100.0)
        rec = s.record()
        assert rec["histograms"]["test.attr.hist"] == {"count": 2, "sum": 5.0}

    def test_no_scope_no_charge(self):
        assert attribution.current_stats() is None
        REGISTRY.counter("test.attr.untracked").inc()
        # nothing to assert beyond "no crash": the contextvar read is the
        # entire disabled-path cost

    def test_nested_scope_restores_outer(self):
        outer, inner = _stats(1), _stats(2)
        c = REGISTRY.counter("test.attr.nested")
        with attribution.scope(outer):
            with attribution.scope(inner):
                assert attribution.current_stats() is inner
                c.inc()
            assert attribution.current_stats() is outer
            c.inc()
        assert attribution.current_stats() is None
        assert inner.counters() == {"test.attr.nested": 1}
        assert outer.counters() == {"test.attr.nested": 1}

    def test_bound_propagates_target_to_pool_thread(self):
        from hyperspace_tpu.utils.workers import io_pool

        s = _stats()
        c = REGISTRY.counter("test.attr.pool")

        def task(n):
            c.inc(n)
            return attribution.current_stats()

        with attribution.scope(s):
            with io_pool(2, "hs-test-attr") as pool:
                seen = list(pool.map(attribution.bound(task), [1, 2, 3]))
        assert all(x is s for x in seen)
        assert s.counters()["test.attr.pool"] == 6

    def test_bound_is_identity_without_target(self):
        def fn():
            pass

        assert attribution.bound(fn) is fn

    def test_phase_context_and_charge_phase(self):
        s = _stats()
        with attribution.scope(s):
            with attribution.phase("io"):
                pass
            attribution.charge_phase("enqueue", 0.25)
        attribution.charge_phase("fetch", 9.0)  # no scope: dropped
        phases = s.phases_s()
        assert phases["io"] >= 0.0
        assert phases["enqueue"] == pytest.approx(0.25)
        assert "fetch" not in phases
        assert set(phases) <= set(attribution.PHASES)


# ---------------------------------------------------------------------------
# query records + ledger lifecycle
# ---------------------------------------------------------------------------

class TestQueryLedger:
    def test_record_fields_and_cache_ratio(self):
        s = _stats(7, label="q7")
        s.charge_counter("io.bytes_decoded", 1024)
        s.charge_counter("io.rows_decoded", 10)
        s.charge_counter("cache.index_chunk.hits", 3)
        s.charge_counter("cache.kernel.misses", 1)
        s.charge_phase("io", 0.01)
        rec = s.record()
        assert rec["query_id"] == 7 and rec["label"] == "q7"
        assert rec["outcome"] == "running"
        assert rec["bytes_read"] == 1024 and rec["rows_decoded"] == 10
        assert rec["cache_hits"] == 3 and rec["cache_misses"] == 1
        assert rec["cache_hit_ratio"] == pytest.approx(0.75)
        assert rec["phases_ms"]["io"] == pytest.approx(10.0)

    def test_cache_ratio_none_without_lookups(self):
        assert _stats().record()["cache_hit_ratio"] is None

    def test_begin_finish_moves_to_recent_and_emits_rollups(self):
        led = QueryStatsLedger(window=8)
        ctx = QueryContext(label="unit")
        records = REGISTRY.counter("serve.query.records").value
        done = REGISTRY.counter("serve.query.outcome.done").value
        s = led.begin(ctx, queue_wait_s=0.5)
        assert led.active_records()[0]["query_id"] == ctx.query_id
        rec = led.finish(s, "done")
        assert rec["outcome"] == "done"
        assert rec["queue_wait_ms"] == pytest.approx(500.0)
        assert not led.active_records()
        assert led.recent_records()[0]["query_id"] == ctx.query_id
        assert REGISTRY.counter("serve.query.records").value == records + 1
        assert REGISTRY.counter("serve.query.outcome.done").value == done + 1

    def test_rollup_not_charged_back_to_query(self):
        """finish() runs after the scope exits: the serve.query.* rollups
        must not appear in the query's own counters."""
        led = QueryStatsLedger(window=8)
        s = led.begin(QueryContext(label="meta"))
        led.finish(s, "done")
        assert not any(k.startswith("serve.query.") for k in s.counters())

    def test_record_unrun_zero_charge_cancelled(self):
        led = QueryStatsLedger(window=8)
        rec = led.record_unrun(QueryContext(label="never-ran"))
        assert rec["outcome"] == "cancelled"
        assert rec["bytes_read"] == 0 and rec["counters"] == {}

    def test_window_eviction(self):
        led = QueryStatsLedger(window=2)
        for i in range(5):
            led.finish(led.begin(QueryContext(label=f"q{i}")), "done")
        recent = led.recent_records()
        assert len(recent) == 2
        assert [r["label"] for r in recent] == ["q3", "q4"]
        assert led.snapshot()["totals"]["recorded"] == 5

    def test_aggregate_counters_sums_entries(self):
        led = QueryStatsLedger(window=8)
        a = led.begin(QueryContext())
        b = led.begin(QueryContext())
        a.charge_counter("io.chunks", 2)
        b.charge_counter("io.chunks", 3)
        b.charge_counter("cache.kernel.hits", 1)
        led.finish(a, "done")
        agg = led.aggregate_counters()  # one active + one recent
        assert agg == {"io.chunks": 5, "cache.kernel.hits": 1}

    def test_health_window_rates(self):
        led = QueryStatsLedger(window=16)
        for outcome in ("done", "done", "failed", "cancelled"):
            led.finish(led.begin(QueryContext()), outcome)
        s = led.begin(QueryContext())
        s.charge_counter("device.degrades", 1)
        led.finish(s, "done")
        w = led.health_window()
        assert w["window_records"] == 5
        assert w["failed"] == 1 and w["cancelled"] == 1 and w["degraded"] == 1
        assert w["error_rate"] == pytest.approx(0.2)
        assert w["degrade_rate"] == pytest.approx(0.2)

    def test_slow_query_log_threshold(self, tmp_path, monkeypatch):
        path = str(tmp_path / "slow.jsonl")
        monkeypatch.setenv("HYPERSPACE_SLOW_QUERY_FILE", path)
        monkeypatch.setenv("HYPERSPACE_SLOW_QUERY_MS", "50")
        led = QueryStatsLedger(window=8)
        fast = led.begin(QueryContext(label="fast"))
        led.finish(fast, "done")  # ~0 ms: below threshold
        slow = led.begin(QueryContext(label="slow"))
        slow.started_s -= 1.0  # pretend it ran for a second
        led.finish(slow, "done")
        lines = [
            json.loads(ln)
            for ln in open(path, encoding="utf-8").read().splitlines()
        ]
        assert [r["label"] for r in lines] == ["slow"]
        assert lines[0]["total_ms"] >= 50
        assert led.snapshot()["totals"]["slow"] == 1

    def test_phase_percentiles(self):
        recs = [
            {"total_ms": 10.0, "queue_wait_ms": 1.0,
             "phases_ms": {"io": 4.0, "enqueue": 2.0}},
            {"total_ms": 20.0, "queue_wait_ms": 3.0,
             "phases_ms": {"io": 8.0}},
        ]
        out = phase_percentiles(recs)
        assert out["total"] == {"count": 2, "mean_ms": 15.0, "p99_ms": 20.0}
        assert out["io"]["mean_ms"] == pytest.approx(6.0)
        assert out["enqueue"]["count"] == 1
        assert out["queue"]["count"] == 2
        assert phase_percentiles([]) == {}


# ---------------------------------------------------------------------------
# registry snapshot consistency (concurrent hammer)
# ---------------------------------------------------------------------------

class TestSnapshotConsistency:
    def test_histogram_full_is_one_consistent_cut(self):
        h = Histogram("hammer.hist")
        stop = threading.Event()

        def writer():
            i = 0
            while not stop.is_set():
                h.observe(float(i % 1000))
                i += 1

        threads = [threading.Thread(target=writer) for _ in range(4)]
        for t in threads:
            t.start()
        try:
            for _ in range(300):
                full = h.full()
                # the torn pair snapshot() could historically produce:
                # bucket counts from one instant, count/sum from another
                assert sum(full["buckets"]) == full["count"]
                assert len(full["buckets"]) == len(full["bounds"]) + 1
        finally:
            stop.set()
            for t in threads:
                t.join()

    def test_registry_export_consistent_mid_update(self):
        reg = MetricsRegistry()
        stop = threading.Event()

        def writer(seed):
            i = seed
            while not stop.is_set():
                reg.counter("hammer.c%d" % (i % 3)).inc()
                reg.histogram("hammer.h%d" % (i % 2)).observe(i % 500)
                reg.gauge("hammer.g").set(i)
                i += 1

        threads = [threading.Thread(target=writer, args=(s,)) for s in range(4)]
        for t in threads:
            t.start()
        try:
            for _ in range(200):
                for name, kind, value in reg.export():
                    if kind == "histogram":
                        assert sum(value["buckets"]) == value["count"], name
                snap = reg.snapshot()  # single pass, no torn summaries
                for name, v in snap.items():
                    if isinstance(v, dict) and "count" in v:
                        assert v["count"] >= 0
        finally:
            stop.set()
            for t in threads:
                t.join()


# ---------------------------------------------------------------------------
# exporter lifecycle + health plane
# ---------------------------------------------------------------------------

def _get(url: str):
    """(status, body) following http.server semantics; 4xx/5xx bodies
    still read."""
    try:
        with urllib.request.urlopen(url, timeout=10) as r:
            return r.status, r.read().decode("utf-8")
    except urllib.error.HTTPError as e:
        return e.code, e.read().decode("utf-8")


def _prom_violations(text: str) -> list:
    """Histogram consistency of a /metrics body: cumulative buckets and
    +Inf == _count for every histogram family."""
    buckets, counts = {}, {}
    out = []
    for ln in text.splitlines():
        if not ln or ln.startswith("#"):
            continue
        series, raw = ln.rsplit(" ", 1)
        float(raw)  # every sample line must end in a number
        if '{le="' in series:
            name = series.split("{", 1)[0]
            buckets.setdefault(name, []).append(
                (series.split('le="', 1)[1].split('"', 1)[0], float(raw))
            )
        elif series.endswith("_count"):
            counts[series[: -len("_count")]] = float(raw)
    for name, bs in buckets.items():
        cum = [v for _le, v in bs]
        if any(b < a for a, b in zip(cum, cum[1:])):
            out.append(f"{name}: not cumulative")
        base = name[: -len("_bucket")]
        if not bs or bs[-1][0] != "+Inf" or counts.get(base) != bs[-1][1]:
            out.append(f"{name}: +Inf != _count")
    return out


class TestExporter:
    def test_disabled_by_default_no_thread_no_socket(self, monkeypatch):
        monkeypatch.delenv("HYPERSPACE_METRICS_PORT", raising=False)
        monkeypatch.delenv("HYPERSPACE_SNAPSHOT_FILE", raising=False)
        exporter.maybe_start_from_env()
        assert exporter.get_exporter() is None
        assert not [
            t for t in threading.enumerate()
            if t.name.startswith("hs-metrics")
        ]
        assert exporter.start_exporter() is None  # knob unset: stays off

    def test_bind_serve_stop_release(self):
        exp = exporter.start_exporter(port=0)
        assert exp is not None and exp.port > 0
        assert REGISTRY.gauge("exporter.up").value == 1
        code, body = _get(exp.url + "/metrics")
        assert code == 200
        assert "hyperspace_" in body
        assert _prom_violations(body) == []
        port = exp.port
        exporter.stop_exporter()
        assert REGISTRY.gauge("exporter.up").value == 0
        # the port is actually released: we can bind it again
        s = socket.socket()
        try:
            s.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
            s.bind(("127.0.0.1", port))
        finally:
            s.close()
        exporter.stop_exporter()  # idempotent

    def test_start_is_singleton(self):
        a = exporter.start_exporter(port=0)
        b = exporter.start_exporter(port=0)
        assert a is b

    def test_snapshot_endpoint_shape(self):
        exp = exporter.start_exporter(port=0)
        code, body = _get(exp.url + "/snapshot")
        assert code == 200
        snap = json.loads(body)
        assert set(snap) >= {"ts", "metrics", "serving", "breaker", "queries"}
        assert set(snap["queries"]) >= {"window", "totals", "active", "recent"}
        code, _404 = _get(exp.url + "/nope")
        assert code == 404
        assert REGISTRY.counter("exporter.scrapes").value > 0

    def test_healthz_ok_then_flips_on_open_breaker(self, monkeypatch):
        monkeypatch.setenv("HYPERSPACE_DEVICE_STRICT", "0")
        backend._reset_for_testing()
        exp = exporter.start_exporter(port=0)
        code, body = _get(exp.url + "/healthz")
        assert code == 200 and json.loads(body)["status"] == "ok"
        # a transient device failure (the PR 7 injected flavor) opens the
        # breaker: the health plane must flip to degraded/503
        backend.record_device_failure(
            faults.InjectedIOError("injected: device lost")
        )
        assert backend.breaker_state() == "open"
        code, body = _get(exp.url + "/healthz")
        payload = json.loads(body)
        assert code == 503
        assert payload["status"] == "degraded"
        assert payload["breaker"] == "open"

    def test_concurrent_scrapes_stay_consistent(self):
        exp = exporter.start_exporter(port=0)
        stop = threading.Event()

        def writer():
            h = REGISTRY.histogram("scrape.hammer_ms")
            i = 0
            while not stop.is_set():
                h.observe(i % 750)
                REGISTRY.counter("scrape.hammer").inc()
                i += 1

        t = threading.Thread(target=writer)
        t.start()
        try:
            for _ in range(25):
                code, body = _get(exp.url + "/metrics")
                assert code == 200
                assert _prom_violations(body) == []
        finally:
            stop.set()
            t.join()

    def test_snapshot_sink_writes_and_final_flush(self, tmp_path):
        path = str(tmp_path / "snaps.jsonl")
        sink = exporter.start_snapshot_sink(path, interval_s=0.05)
        assert sink is not None
        time.sleep(0.2)
        exporter.stop_snapshot_sink()  # also writes one final snapshot
        lines = [
            json.loads(ln)
            for ln in open(path, encoding="utf-8").read().splitlines()
        ]
        assert len(lines) >= 2
        assert all(
            set(s) >= {"ts", "metrics", "serving", "breaker", "queries"}
            for s in lines
        )

    def test_sink_disabled_without_knob(self, monkeypatch):
        monkeypatch.delenv("HYPERSPACE_SNAPSHOT_FILE", raising=False)
        assert exporter.start_snapshot_sink() is None


# ---------------------------------------------------------------------------
# served-query integration: conservation + query log + scheduler wiring
# ---------------------------------------------------------------------------

def _write_multifile(root, n_files=6, rows=2500, seed=3):
    rng = np.random.default_rng(seed)
    for i in range(n_files):
        n = rows + i * 97
        data = {
            "k": rng.integers(0, 40, n).tolist(),
            "x": rng.uniform(0, 100, n).tolist(),
            "q": rng.integers(1, 50, n).tolist(),
        }
        cio.write_parquet(
            ColumnBatch.from_pydict(data),
            os.path.join(root, "t", f"part-{i}.parquet"),
        )


CONSERVED = ("io.", "cache.", "rpc.", "pipeline.", "serve.budget.")


def _conserved_globals() -> dict:
    return {
        name: value
        for name, kind, value in REGISTRY.export()
        if kind == "counter" and name.startswith(CONSERVED)
    }


class TestServedAttribution:
    def _session_query(self, tmp_path, monkeypatch):
        _write_multifile(str(tmp_path))
        monkeypatch.setenv("HYPERSPACE_IO_THREADS", "4")
        monkeypatch.setenv("HYPERSPACE_STREAM_CHUNK_MB", "0.01")
        session = HyperspaceSession(warehouse_dir=str(tmp_path))
        session.set_conf(C.EXEC_TPU_ENABLED, True)

        def q():
            return (
                session.read.parquet(os.path.join(str(tmp_path), "t"))
                .filter(col("q") > 10)
                .agg(Sum(col("x")).alias("sx"), Count(lit(1)).alias("n"))
            )

        return session, q

    def test_conservation_per_query_sums_equal_global_deltas(
        self, tmp_path, monkeypatch
    ):
        """THE invariant: every conserved-counter increment during serving
        is charged to exactly one query, so ledger sums == global deltas."""
        session, q = self._session_query(tmp_path, monkeypatch)
        serve.reset_global_budget()
        q().collect()  # warm caches outside the serving window
        g0 = _conserved_globals()
        l0 = {
            k: v for k, v in LEDGER.aggregate_counters().items()
            if k.startswith(CONSERVED)
        }
        sched = serve.QueryScheduler(max_concurrent=4, queue_depth=64)
        try:
            hs = [
                sched.submit(q().collect, label=f"c{i}") for i in range(8)
            ]
            for h in hs:
                h.result(60)
        finally:
            sched.shutdown()

        def mismatches():
            g1 = _conserved_globals()
            deltas = {k: g1.get(k, 0) - g0.get(k, 0) for k in set(g0) | set(g1)}
            lsum = {
                k: v - l0.get(k, 0)
                for k, v in LEDGER.aggregate_counters().items()
                if k.startswith(CONSERVED)
            }
            return {
                k: (deltas.get(k, 0), lsum.get(k, 0))
                for k in set(deltas) | set(lsum)
                if deltas.get(k, 0) != lsum.get(k, 0)
            }

        m = mismatches()
        deadline = time.time() + 10
        while m and time.time() < deadline:
            time.sleep(0.1)  # straggler bound tasks may still be landing
            m = mismatches()
        assert m == {}
        # and the machinery demonstrably engaged
        recent = LEDGER.recent_records()
        mine = [r for r in recent if r["label"].startswith("c")]
        assert len(mine) >= 8
        assert any(r["bytes_read"] > 0 for r in mine)
        assert any(r["phases_ms"].get("io", 0) > 0 for r in mine)

    def test_served_query_record_has_phases_and_outcome(
        self, tmp_path, monkeypatch
    ):
        session, q = self._session_query(tmp_path, monkeypatch)
        serve.reset_global_budget()
        sched = serve.QueryScheduler(max_concurrent=1, queue_depth=8)
        try:
            h = sched.submit(q().collect, label="prof-me")
            h.result(60)
        finally:
            sched.shutdown()
        rec = next(
            r for r in reversed(LEDGER.recent_records())
            if r["label"] == "prof-me"
        )
        assert rec["outcome"] == "done"
        assert rec["total_ms"] > 0
        assert rec["phases_ms"].get("plan", 0) > 0
        assert rec["bytes_read"] > 0 and rec["rows_decoded"] > 0

    def test_queued_cancel_lands_in_query_log(self):
        gate = threading.Event()
        sched = serve.QueryScheduler(max_concurrent=1, queue_depth=8)
        try:
            blocker = sched.submit(lambda: gate.wait(30), label="blocker")
            victim = sched.submit(lambda: None, label="queued-victim")
            victim.cancel()
            with pytest.raises(serve.QueryCancelledError):
                victim.result(10)
            gate.set()
            blocker.result(30)
            sched.drain(timeout=30)
        finally:
            gate.set()
            sched.shutdown()
        rec = next(
            r for r in reversed(LEDGER.recent_records())
            if r["label"] == "queued-victim"
        )
        assert rec["outcome"] == "cancelled"
        assert rec["counters"] == {}  # never ran: zero charges

    def test_query_log_string_renders(self, tmp_path, monkeypatch):
        from hyperspace_tpu.analysis.explain import query_log_string

        session, q = self._session_query(tmp_path, monkeypatch)
        serve.reset_global_budget()
        sched = serve.QueryScheduler(max_concurrent=1, queue_depth=8)
        try:
            sched.submit(q().collect, label="render-me").result(60)
        finally:
            sched.shutdown()
        out = query_log_string()
        assert "Query log (per-query attribution):" in out
        assert "render-me" in out


# ---------------------------------------------------------------------------
# tools: trace_report --query and hs_top rendering
# ---------------------------------------------------------------------------

def _span_line(span_id, parent_id, name, ms, attrs):
    return json.dumps({
        "span_id": span_id, "parent_id": parent_id, "name": name,
        "start_s": 0.0, "duration_ms": ms, "attrs": attrs, "rpc": {},
    })


class TestTools:
    def test_trace_report_query_filter(self, tmp_path):
        trace_path = str(tmp_path / "mixed.jsonl")
        lines = [
            # children precede parents, as JsonlTraceSink writes them
            _span_line(2, 1, "exec:Aggregate", 5.0, {}),
            _span_line(1, None, "serve:query", 9.0,
                       {"query_id": 11, "label": "mine"}),
            _span_line(4, 3, "exec:Filter", 2.0, {}),
            _span_line(3, None, "serve:query", 4.0,
                       {"query_id": 12, "label": "other"}),
            _span_line(5, None, "serve:admit", 0.1,
                       {"query_id": 11, "label": "mine"}),
        ]
        with open(trace_path, "w", encoding="utf-8") as f:
            f.write("\n".join(lines) + "\n")
        out = subprocess.run(
            [sys.executable, os.path.join(REPO, "tools", "trace_report.py"),
             trace_path, "--query", "11"],
            capture_output=True, text=True, cwd=REPO, check=True,
        ).stdout
        assert "serve:query" in out and "exec:Aggregate" in out
        assert "serve:admit" in out
        assert "exec:Filter" not in out  # the other query's subtree
        missing = subprocess.run(
            [sys.executable, os.path.join(REPO, "tools", "trace_report.py"),
             trace_path, "--query", "99"],
            capture_output=True, text=True, cwd=REPO, check=True,
        ).stdout
        assert "no serve:query spans with query_id=99" in missing

    def test_hs_top_renders_snapshot(self):
        import importlib.util

        spec = importlib.util.spec_from_file_location(
            "hs_top", os.path.join(REPO, "tools", "hs_top.py")
        )
        hs_top = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(hs_top)
        led = QueryStatsLedger(window=8)
        s = led.begin(QueryContext(label="topq"))
        s.charge_counter("io.bytes_decoded", 5_000_000)
        s.charge_phase("io", 0.12)
        led.finish(s, "done")
        snap = exporter.snapshot_dict()
        snap["queries"] = led.snapshot()
        out = hs_top.render(snap)
        assert "hs_top @" in out and "topq" in out
        assert "RECENT" in out
        # rates need two snapshots; a second one unlocks them
        snap2 = dict(snap, ts=snap["ts"] + 2.0)
        assert "qps" in hs_top.render(snap2, prev=snap)
