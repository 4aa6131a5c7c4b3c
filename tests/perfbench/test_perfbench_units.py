"""The benchmark's own pieces, without running the program: traffic,
references and comparisons, the trace reduction, the peak table, the
layout by file name, and the control that the limits must catch."""

import json
import os
import re
import shutil

import numpy as np
import pytest

from perfbench import cell as cells
from perfbench import check, devtrace, tpch_data, traffic
from perfbench.answers import compare_rows, days

ROOT = cells.ROOT
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
WORKLOADS = [w["name"] for w in BENCH["workloads"]]
TRAFFICS = sorted({w["traffic"] for w in BENCH["workloads"]})


def _cell(name):
    return cells.Cell(name)


def check_pools(c, seed):
    """The run's parameter sets: fixed by the seed, distinct, as many as
    the traffic file asks, and each within its query's ranges."""
    dom = tpch_data.domain(c.config)
    a = traffic.pools(c.traffic, c.queries, dom, seed)
    assert a == traffic.pools(c.traffic, c.queries, dom, seed)
    assert a != traffic.pools(c.traffic, c.queries, dom, seed + 1)
    for q, pool in a.items():
        assert len(pool) == c.traffic["distinct"][q]
        assert len({json.dumps(p, sort_keys=True) for p in pool}) == len(pool)
        ranges = c.traffic["ranges"].get(q, {})
        assert all(c.queries[q].in_range(p, ranges, dom) for p in pool), q


@pytest.mark.parametrize("seed", [0, 2**31 + 17, 3 * 2**32 + 5])
@pytest.mark.parametrize("workload", WORKLOADS)
def test_pools_are_drawn_from_the_seed_distinct_and_within_the_spec(
        workload, seed):
    check_pools(_cell(workload), seed)


def test_a_query_judges_its_own_ranges():
    c = _cell("sf1_scan_agg")
    dom = tpch_data.domain(c.config)
    q6, q1 = c.queries["q6"], c.queries["q1"]
    ranges = c.traffic["ranges"]
    assert q6.in_range({"year": 1997, "cents": 2, "quantity": 25},
                       ranges["q6"], dom)
    assert not q6.in_range({"year": 1998, "cents": 2, "quantity": 25},
                           ranges["q6"], dom)
    assert not q1.in_range({"delta": 121}, ranges["q1"], dom)
    assert q1.in_range({"delta": 121}, {"delta": [60, 121]}, dom)
    q3, q17 = (_cell("sf1_join").queries[q] for q in ("q3", "q17"))
    assert not q3.in_range({"day": 10, "segment": 5}, {"day": [1, 31]}, dom)
    assert not q17.in_range({"brand": 0, "container": 40}, {}, dom)


def check_sequences(c, seed):
    """Each client's sequence: fixed by the seed and the client, and made
    of whole blocks that keep the weights and every drawn set."""
    pool = traffic.pools(c.traffic, c.queries, tpch_data.domain(c.config),
                         seed)
    block = traffic.block(c.traffic, pool)
    counts = {q: sum(1 for x, _ in block if x == q) for q in c.queries}
    for q, w in c.traffic["mix"].items():  # the weights, in every block
        assert counts[q] * c.traffic["mix"][min(c.queries)] == \
            counts[min(c.queries)] * w
        assert {json.dumps(p) for x, p in block if x == q} == {
            json.dumps(p) for p in pool[q]}

    def take(s, client, n):
        seq = traffic.client_sequence(c.traffic, pool, s, client)
        return [next(seq) for _ in range(n)]

    def key(items):
        return sorted((q, json.dumps(p, sort_keys=True)) for q, p in items)

    first = take(seed, 0, 3 * len(block))
    assert first == take(seed, 0, 3 * len(block))
    assert first != take(seed + 1, 0, 3 * len(block))
    assert first != take(seed, 1, 3 * len(block))
    for i in range(0, len(first), len(block)):
        assert key(first[i:i + len(block)]) == key(block)


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 17, 3 * 2**32 + 5, -9])
@pytest.mark.parametrize("workload", WORKLOADS)
def test_client_sequences_are_seeded_and_give_every_seed_the_same_work(
        workload, seed):
    check_sequences(_cell(workload), seed)


def test_compare_rows_treats_nulls_and_nan_as_wrong():
    want = {"k": [1, 2], "s": [1.0, None]}
    assert compare_rows({"k": [1, 2], "s": [1.0, None]}, want, ("s",)) == (
        0, 0.0)
    assert compare_rows({"k": [1, 2], "s": [1.0, 3.0]}, want, ("s",))[0] == 1
    assert compare_rows({"k": [1, 2], "s": [float("nan"), None]}, want,
                        ("s",))[0] == 1
    assert compare_rows({"k": [1, 3], "s": [1.0, None]}, want, ("s",))[0] == 1
    assert compare_rows({"k": [1], "s": [1.0]}, want, ("s",))[0] == 2
    wrong, err = compare_rows({"k": [1, 2], "s": [1.001, None]}, want, ("s",))
    assert wrong == 0 and err == pytest.approx(1e-3)


TINY = dict(json.load(open(os.path.join(ROOT, "perfbench", "configs",
                                        "tpch_sf1.json"))),
            scale_factor=0.004, orders_per_file=2000)
EVERY = {t: sorted({c for q in ("q1", "q3", "q6", "q17")
                    for c in cells._module(ROOT, "queries", q).TABLES.get(
                        t, ())}) for t in ("lineitem", "orders", "part",
                                           "customer")}


def _tiny_data(seed=3):
    return tpch_data.generate(None, TINY, seed, keep=EVERY)


SPEC_COLUMNS = {
    "region": 3, "nation": 4, "supplier": 7, "customer": 8, "part": 9,
    "partsupp": 5, "orders": 9, "lineitem": 16}


def test_the_generator_writes_every_table_and_column_of_the_spec(tmp_path):
    import pyarrow.parquet as pq

    tpch_data.generate(str(tmp_path), TINY, 5, keep={})
    n = tpch_data.sizes(TINY)
    rows = {}
    for t, width in SPEC_COLUMNS.items():
        table = pq.read_table(str(tmp_path / t))
        assert table.num_columns == width, t
        rows[t] = table.num_rows
        if t == "part":
            part = table.to_pydict()
        if t == "customer":
            cust = table.to_pydict()
    assert rows["region"] == 5 and rows["nation"] == 25
    assert rows["part"] == n["part"] and rows["partsupp"] == 4 * n["part"]
    assert rows["orders"] == n["orders"]
    assert n["orders"] <= rows["lineitem"] <= 7 * n["orders"]
    assert part["p_brand"][0].startswith("Brand#") and len(
        part["p_name"][0].split()) == 5
    assert cust["c_name"][4] == "Customer#000000005"
    assert len(cust["c_phone"][0]) == 15


def test_the_generator_keeps_dbgens_rules():
    d = tpch_data.generate(None, TINY, 9, keep={
        "lineitem": ["l_orderkey", "l_shipdate", "l_receiptdate",
                     "l_commitdate", "l_returnflag", "l_linestatus",
                     "l_extendedprice", "l_quantity", "l_partkey"],
        "orders": ["o_orderkey", "o_orderdate", "o_custkey"],
        "part": ["p_brand", "p_container"]})
    li, od = d["lineitem"], d["orders"]
    assert np.all((od["o_orderkey"] - 1) % 32 < 8)
    row = tpch_data.order_index(li["l_orderkey"])
    assert np.array_equal(od["o_orderkey"][row], li["l_orderkey"])
    per = np.bincount(row)
    assert per.min() >= 1 and per.max() <= 7
    lag = li["l_shipdate"] - od["o_orderdate"][row]
    assert lag.min() >= 1 and lag.max() <= 121
    assert np.all(li["l_receiptdate"] > li["l_shipdate"])
    late = li["l_receiptdate"] > tpch_data.CURRENTDATE
    assert np.all((li["l_returnflag"] == 1) == late)
    assert np.all(li["l_linestatus"] == (
        li["l_shipdate"] > tpch_data.CURRENTDATE))
    assert np.all(od["o_custkey"] % 3 != 0)
    assert np.allclose(li["l_extendedprice"], li["l_quantity"]
                       * tpch_data.retail_cents(li["l_partkey"]) / 100)
    assert d["part"]["p_brand"].max() < 25
    assert d["part"]["p_container"].max() < 40


def test_roofline_counts_the_bytes_the_query_must_read():
    data = _tiny_data()
    q6 = cells._module(ROOT, "queries", "q6")
    p = {"year": 1995, "cents": 6, "quantity": 24}
    ship = data["lineitem"]["l_shipdate"]
    rows = int(np.sum((ship >= days(1995, 1, 1)) & (ship < days(1996, 1, 1))))
    assert q6.must_read_bytes(data, p) == rows * 4 * 4
    q3 = cells._module(ROOT, "queries", "q3")
    d = days(1995, 3, 10)
    seg = int(np.sum(data["customer"]["c_mktsegment"] == 2))
    od = int(np.sum(data["orders"]["o_orderdate"] < d))
    lines = int(np.sum(ship > d))
    assert q3.must_read_bytes(data, {"segment": 2, "day": 10}) == (
        seg * 2 + od * 4 + lines * 4) * 4
    read = cells._module(ROOT, "metrics", "roofline_share").read

    class M:
        trace = {"busy_s": 0.5, "window_s": 2.0}
        must_read_bytes = 819e9 * 0.005
        peaks = {"hbm_bytes_per_s": 819e9}

    assert read(M) == pytest.approx(1.0)
    M.trace = {"busy_s": 0.0, "window_s": 2.0}
    assert read(M) is None
    M.trace = None
    assert read(M) is None


def test_q3_top_k_is_judged_by_what_it_says():
    data = _tiny_data()
    q3 = cells._module(ROOT, "queries", "q3")
    want = q3.reference(data, {"segment": 1, "day": 20})
    assert len(want["revenue"]) == 10
    got = check.public(want)
    assert q3.compare(got, want) == (0, 0.0)
    swapped = {k: list(v) for k, v in got.items()}
    bad = dict(swapped, l_orderkey=[swapped["l_orderkey"][1]]
               + swapped["l_orderkey"][1:])
    assert q3.compare(bad, want)[0] >= 1
    low = dict(swapped, revenue=[r * (1 + 1e-3) for r in swapped["revenue"]])
    assert q3.compare(low, want)[1] == pytest.approx(1e-3, rel=1e-6)


def _xspace(ops, spans, async_ops=()):
    """A serialized XSpace: device ops (and async device ops) and host
    spans as (name, start_ns, duration_ns)."""
    from jax.profiler import ProfileData

    def plane(pid, name, lines):
        names = sorted({n for _, events in lines for n, _, _ in events})
        ids = {n: i + 1 for i, n in enumerate(names)}
        body = " ".join(
            f'lines {{ id: {k + 1} name: "{line}" timestamp_ns: 0 '
            + " ".join(f"events {{ metadata_id: {ids[n]} offset_ps: "
                       f"{s * 1000} duration_ps: {d * 1000} }}"
                       for n, s, d in events) + " }"
            for k, (line, events) in enumerate(lines) if events)
        meta = " ".join(
            f'event_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }}'
            for n, i in ids.items())
        return f'planes {{ id: {pid} name: "{name}" {body} {meta} }}'

    text = (plane(1, "/device:TPU:0", [("XLA Ops", ops),
                                       ("Async XLA Ops", async_ops)])
            + plane(2, "/host:CPU", [("python", spans)]))
    return ProfileData.from_serialized_xspace(
        ProfileData.text_proto_to_serialized_xspace(text))


def test_trace_reduction_unions_ops_and_names_gaps_by_host_span():
    prof = _xspace(
        ops=[("%fusion.1 = f32[] fusion(x)", 100, 200),
             ("%fusion.1 = f32[] fusion(x)", 150, 100),  # overlaps: once
             ("copy.2", 600, 100),
             ("early", 0, 50)],  # before the window: left out
        spans=[("bench:window", 50, 1000), ("bench:wait", 60, 500),
               ("bench:compare", 700, 350)])
    r = devtrace.reduce(prof)
    assert r["window_s"] == pytest.approx(1000e-9)
    assert r["busy_s"] == pytest.approx(300e-9)
    assert r["devices"] == 1
    assert r["device_ops"][0] == ["fusion.1", pytest.approx(300e-9)]
    assert r["idle_gaps"] == [["bench:compare", pytest.approx(350e-9)],
                              ["bench:wait", pytest.approx(300e-9)],
                              ["bench:wait", pytest.approx(50e-9)]]
    assert sum(g[1] for g in r["idle_gaps"]) == pytest.approx(700e-9)


def test_idle_gaps_are_named_after_the_programs_innermost_annotation():
    """Inside the program's ``hs:`` annotations a gap takes the one that
    started last (its metadata dropped); outside them the benchmark's
    ``bench:`` span."""
    prof = _xspace(
        ops=[("fusion.1", 100, 100), ("fusion.2", 400, 100),
             ("fusion.3", 700, 100)],
        spans=[("bench:window", 0, 1000), ("bench:wait", 0, 1000),
               ("hs:exec:Aggregate#query_id=7#", 180, 240),
               ("hs:pipeline:concat#query_id=7#", 220, 160),
               ("hs:phase:io", 500, 190)])
    r = devtrace.reduce(prof)
    assert r["idle_gaps"] == [["hs:pipeline:concat", pytest.approx(200e-9)],
                              ["hs:phase:io", pytest.approx(200e-9)],
                              ["bench:wait", pytest.approx(200e-9)],
                              ["bench:wait", pytest.approx(100e-9)]]


def test_trace_reduction_counts_async_device_ops_as_busy():
    prof = _xspace(ops=[("fusion.1", 100, 200)],
                   spans=[("bench:window", 50, 1000)],
                   async_ops=[("copy-start.3", 250, 300)])
    r = devtrace.reduce(prof)
    assert r["busy_s"] == pytest.approx(450e-9)
    assert ["copy-start.3", pytest.approx(300e-9)] in r["device_ops"]


def test_trace_reduction_reads_nothing_without_window_or_ops():
    assert devtrace.reduce(_xspace([("a", 0, 10)], [("bench:x", 0, 5)])) \
        is None
    assert devtrace.reduce(_xspace([("a", 0, 10)],
                                   [("bench:window", 100, 50)])) is None


def test_union_merges_touching_and_nested_intervals():
    assert devtrace.union([(5, 6), (0, 2), (1, 3), (3, 4), (10, 20),
                           (11, 12)]) == [[0, 4], [5, 6], [10, 20]]


def test_peak_table_knows_v5e_and_refuses_unknown_kinds():
    p = cells.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert p["int8_ops_per_s"] == 393e12
    assert p["hbm_bytes_per_s"] == 819e9
    assert p["hbm_bytes"] == 16e9
    with pytest.raises(KeyError):
        cells.peaks("cpu")


def test_benchmark_json_keeps_to_the_contract():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    for path in BENCH["paths"]:
        assert os.path.isdir(os.path.join(ROOT, path))
    assert BENCH["command"][1] == "perfbench/run.py"
    names = set()
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["file"].startswith("perfbench/")
        assert all(NAME.match(k) for k in c["reduced"])
        assert json.load(open(os.path.join(ROOT, c["file"])))["name"] == \
            c["name"]
        names.add(c["name"])
    seen = set()
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and w["config"] in names
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        assert (w["config"], w["traffic"]) not in seen
        seen.add((w["config"], w["traffic"]))
    assert any(m["name"] == "setup_s" for m in BENCH["end_to_end"])
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")


@pytest.mark.parametrize("workload", WORKLOADS)
def test_every_cell_finds_its_files_and_reports_enough(workload):
    c = _cell(workload)
    assert c.limits["float_rel_err"] > 0
    for q in c.queries.values():
        for fn in ("draw", "dataframe", "reference", "compare",
                   "must_read_bytes"):
            assert callable(getattr(q, fn))
    e2e = {m["name"] for m in c.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert c.per_layer
    for m in c.per_layer:
        assert m["moves"] in e2e
        assert callable(c.reader(m["name"]))


# A query shape none of the cells has, as a later PR adds it: an
# order-detail lookup on l_orderkey
POINT_QUERY = '''"""The lines of one order by l_orderkey."""

import os

import numpy as np

from perfbench.answers import BYTES_PER_VALUE, compare_rows
from perfbench.tpch_data import order_index

TABLES = {"lineitem": ("l_orderkey", "l_quantity", "l_extendedprice")}
FLOATS = ("l_extendedprice",)


def _key(row):
    return (row // 8) * 32 + row % 8 + 1


def draw(rng, ranges, domain):
    return {"orderkey": int(_key(int(rng.integers(0, domain["orders"]))))}


def in_range(p, ranges, domain):
    row = int(order_index(p["orderkey"]))
    return 0 <= row < domain["orders"] and _key(row) == p["orderkey"]


def dataframe(session, root, p):
    from hyperspace_tpu.plan import col

    return (session.read.parquet(os.path.join(root, "lineitem"))
            .filter(col("l_orderkey") == p["orderkey"])
            .select(*TABLES["lineitem"]))


def reference(data, p):
    li = data["lineitem"]
    m = li["l_orderkey"] == p["orderkey"]
    return {c: li[c][m].tolist() for c in TABLES["lineitem"]}


def compare(got, want):
    return compare_rows(got, want, FLOATS)


def must_read_bytes(data, p):
    lines = np.count_nonzero(data["lineitem"]["l_orderkey"] == p["orderkey"])
    return int(lines) * len(TABLES["lineitem"]) * BYTES_PER_VALUE
'''


def test_adding_files_adds_a_cell_with_no_edit(tmp_path):
    """A new configuration, traffic mix, query and metric are new files and
    new entries in BENCHMARK.json; the harness finds them by name."""
    root = tmp_path / "checkout"
    shutil.copytree(os.path.join(ROOT, "perfbench"), root / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = json.loads(json.dumps(BENCH))
    pb = root / "perfbench"
    cfg = json.load(open(pb / "configs" / "tpch_sf1.json"))
    cfg["name"], cfg["scale_factor"] = "tpch_tiny", 0.01
    (pb / "configs" / "tpch_tiny.json").write_text(json.dumps(cfg))
    bench["configs"].append({"name": "tpch_tiny", "source": "x",
                             "file": "perfbench/configs/tpch_tiny.json",
                             "reduced": [], "why": "x"})
    (pb / "queries" / "q6_copy.py").write_text(
        (pb / "queries" / "q6.py").read_text())
    (pb / "queries" / "point.py").write_text(POINT_QUERY)
    (pb / "traffic" / "copy.json").write_text(json.dumps({
        "clients": 2, "mix": {"q6_copy": 1, "q1": 2, "point": 3},
        "distinct": {"q6_copy": 2, "q1": 2, "point": 6},
        "ranges": json.load(open(pb / "traffic" / "scan_agg.json"))[
            "ranges"] | {"q6_copy": {"year": [1993, 1997], "cents": [2, 9],
                                     "quantity": [24, 25]}}}))
    (pb / "cells" / "tiny_copy.json").write_text(
        json.dumps({"limits": {"float_rel_err": 1e-4}}))
    (pb / "metrics" / "twice_plan.py").write_text(
        "def read(run):\n    return 2 * run.n\n")
    bench["workloads"].append({"name": "tiny_copy", "config": "tpch_tiny",
                               "traffic": "copy", "chips": 1, "why": "x"})
    bench["end_to_end"].append({"name": "qps.copy", "unit": "queries/s",
                                "better": "higher", "bound": 0.05,
                                "source": "host_clock",
                                "workloads": ["tiny_copy"]})
    bench["per_layer"].append({"name": "twice_plan.copy", "unit": "ms",
                               "better": "lower",
                               "source": "program_counter", "layer": "x",
                               "moves": "qps.copy", "workloads": ["tiny_copy"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    c = cells.Cell("tiny_copy", root=str(root))
    assert sorted(c.queries) == ["point", "q1", "q6_copy"]
    assert c.config["scale_factor"] == 0.01
    assert [m["name"] for m in c.per_layer] == ["twice_plan.copy"]
    assert [m["name"] for m in c.end_to_end] == ["setup_s", "qps.copy"]

    class M:
        n = 21

    assert c.reader("twice_plan.copy")(M) == 42
    assert c.tables()["lineitem"]
    pool = traffic.pools(c.traffic, c.queries, tpch_data.domain(c.config), 4)
    assert len(pool["q6_copy"]) == 2 and len(pool["point"]) == 6
    # the checks every cell of BENCHMARK.json gets hold for the new query
    # shape with no edit to the harness or to them
    for seed in (0, 2**31 + 17):
        check_pools(c, seed)
        check_sequences(c, seed)
    check_control(c)


def check_control(c):
    """The reference in the program's place, its float inputs rounded to
    bfloat16, must read above the cell's limit; the reference against
    itself reads nothing."""
    cfg = dict(c.config, scale_factor=0.02, orders_per_file=15_000)
    c.config = cfg
    data = tpch_data.generate(None, cfg, 11, keep=c.tables())
    pool = traffic.pools(c.traffic, c.queries, tpch_data.domain(c.config),
                         11)
    seq = traffic.client_sequence(c.traffic, pool, 11, 0)
    requests = [next(seq) for _ in range(60)]
    wrong, err = check.control_answers(c, data, requests)
    assert wrong > 0 or err > c.limits["float_rel_err"]
    ref = check.References(c, data)
    for q, p in requests:
        assert c.queries[q].compare(check.public(ref(q, p)), ref(q, p)) == (
            0, 0.0)


@pytest.mark.parametrize("workload", WORKLOADS)
def test_the_bf16_control_fails_the_cell_limit(workload):
    check_control(_cell(workload))


def test_a_counter_reads_its_window_delta_and_0_when_never_registered():
    from perfbench.run import Measured

    before = {"histogram": {"h": 2.0}, "counter": {"cache.kernel.hits": 5}}
    after = {"histogram": {"h": 9.5},
             "counter": {"cache.kernel.hits": 12, "kernel.retrace": 3}}
    m = Measured(4, before, after, {}, None, 0, "TPU v5 lite")
    assert m.counter("cache.kernel.hits") == 7.0
    assert m.counter("kernel.retrace") == 3.0  # registered in the window
    assert m.counter("never.registered") == 0.0
    assert m.counter("h") == 0.0  # a histogram is no counter
    assert m.hist_sum("h") == 7.5
