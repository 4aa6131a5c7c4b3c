"""The profiler bridge: attribution phases and spans as
``jax.profiler.TraceAnnotation``s while a profiler session records, and
nothing built while none does."""

import glob
import os

import jax
import pytest
from jax.profiler import ProfileData

from hyperspace_tpu import HyperspaceSession, serve
from hyperspace_tpu import constants as C
from hyperspace_tpu.columnar import io as cio
from hyperspace_tpu.columnar.table import ColumnBatch
from hyperspace_tpu.plan import Count, Sum, col, lit
from hyperspace_tpu.telemetry import trace


@pytest.fixture(scope="module")
def query(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("bridge"))
    cio.write_parquet(
        ColumnBatch.from_pydict({"x": [float(i) for i in range(4000)],
                                 "q": [i % 50 for i in range(4000)]}),
        os.path.join(root, "t", "part-0.parquet"))
    session = HyperspaceSession(warehouse_dir=root)
    session.set_conf(C.EXEC_TPU_ENABLED, True)

    def q():
        return (session.read.parquet(os.path.join(root, "t"))
                .filter(col("q") > 10)
                .agg(Sum(col("x")).alias("sx"), Count(lit(1)).alias("n")))

    q().collect()  # compile outside the traced query
    return q


def _serve_one(q):
    serve.reset_global_budget()
    sched = serve.QueryScheduler(max_concurrent=1, queue_depth=8)
    try:
        h = sched.submit_query(q(), label="bridge")
        h.result(60)
    finally:
        sched.shutdown()
    return h.query_id


def _events(log_dir):
    (path,) = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                        recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("hs:"):
                        out.append((e.name, e.start_ns, e.end_ns,
                                    dict(e.stats).get("query_id")))
    return out


def test_a_served_query_leaves_its_phases_on_the_profiler_trace(query,
                                                                tmp_path):
    with jax.profiler.trace(str(tmp_path)):
        qid = _serve_one(query)
    events = _events(str(tmp_path))
    (q0, q1) = next((s, e) for n, s, e, i in events
                    if n == "hs:serve:query" and i == qid)
    for name in ("hs:phase:plan", "hs:phase:device_wait", "hs:phase:fetch"):
        mine = [(s, e) for n, s, e, i in events if n == name and i == qid]
        assert mine, name
        assert all(q0 <= s and e <= q1 for s, e in mine), name
    # a span and a phase of one extent emit one annotation
    assert not any(n in ("hs:plan", "hs:fetch") for n, *_ in events)
    assert any(n == "hs:exec:Aggregate" for n, *_ in events)


def test_no_annotation_is_built_without_a_profiler_session(query,
                                                          monkeypatch,
                                                          tmp_path):
    built = []

    class Counting(jax.profiler.TraceAnnotation):
        def __init__(self, *a, **k):
            built.append(a[0])
            super().__init__(*a, **k)

    monkeypatch.setattr(trace, "_Annotation", Counting)
    assert not trace.recording()
    _serve_one(query)
    assert built == []
    with jax.profiler.trace(str(tmp_path)):
        _serve_one(query)
    assert "hs:phase:plan" in built and "hs:serve:query" in built
