"""A deployment on four chips (``hyperspace.tpu.exec.meshDevices`` = 4) on
four of the conftest's eight host devices: the ``sf1_mesh`` cell's data,
indexes and join mix at a tiny scale, served to four clients through the
``QueryScheduler``. Each query runs on a home chip; answers stay those of
the reference and, bit for bit, of one chip; every chip gets queries,
keeps its own device-cache entries under its own budget, and holds what
the warm-up compiled."""

import threading

import jax
import numpy as np
import pytest

from hyperspace_tpu import constants as C
from hyperspace_tpu.plan import kernel_cache
from hyperspace_tpu.serve.context import current_query
from hyperspace_tpu.serve.scheduler import QueryScheduler
from hyperspace_tpu.telemetry.metrics import REGISTRY
from hyperspace_tpu.utils import backend
from hyperspace_tpu.utils.device_cache import DEVICE_CACHE, DeviceArrayCache

from perfbench import cell as cells
from perfbench import check, run, tpch_data, traffic

SEED = 2**31 + 31
CHIPS = 4


def _bits(batch) -> str:
    return repr({
        k: [x.hex() if isinstance(x, float) else x for x in v]
        for k, v in batch.to_pydict().items()
    })


def _counter(name: str) -> float:
    m = REGISTRY.get(name)
    return 0.0 if m is None else m.value


def _compile_ms() -> float:
    for name, kind, v in REGISTRY.export():
        if name == "serve.query.phase.compile_ms" and kind == "histogram":
            return v["sum"]
    return 0.0


@pytest.fixture(scope="module")
def deployment(tmp_path_factory):
    """The sf1_mesh cell at scale factor 0.01: data, the index set built
    over the four chips, the drawn parameter sets, a four-client scheduler
    and the warm-up's one run of each set."""
    cell = cells.Cell("sf1_mesh")
    cfg = dict(cell.config, scale_factor=0.01, orders_per_file=5_000)
    assert cfg["session"][C.EXEC_MESH_DEVICES] == CHIPS
    ws = str(tmp_path_factory.mktemp("sf1_mesh") / "warehouse")
    data = tpch_data.generate(ws, cfg, SEED, keep=cell.tables())
    session = run.new_session(ws, cfg)
    tpch_data.build_indexes(session, ws, cfg)
    session.enable_hyperspace()
    pool = traffic.pools(cell.traffic, cell.queries, tpch_data.domain(cfg),
                         SEED)
    sched = QueryScheduler(max_concurrent=CHIPS)
    readied = _counter("kernel.ready.compiles")
    warmed = run.warm_up(sched, cell, session, ws, pool)
    # what the warm-up compiled on chip 0 compiles for chips 1-3 aside
    assert kernel_cache.ready_idle(timeout=300)
    readied = _counter("kernel.ready.compiles") - readied
    one_chip = run.new_session(ws, cfg)
    one_chip.unset_conf(C.EXEC_MESH_DEVICES)
    one_chip.enable_hyperspace()
    yield {"cell": cell, "data": data, "session": session, "ws": ws,
           "pool": pool, "sched": sched, "warmed": warmed,
           "readied": readied, "one_chip": one_chip}
    sched.shutdown()


def _on_chip(d, query, params, chip):
    """One query through the scheduler, homed on ``chip``."""
    df = d["cell"].queries[query].dataframe(d["session"], d["ws"], params)

    def fn():
        current_query().device_home = chip
        return df.collect()

    return d["sched"].submit(fn, label=query, allow_approx=False).result(
        timeout=300)


def test_four_clients_answer_as_the_reference_and_one_chip(deployment):
    d = deployment
    cell, ref = d["cell"], check.References(d["cell"], d["data"])
    answers = [[] for _ in range(CHIPS)]

    def client(i):
        seq = traffic.client_sequence(cell.traffic, d["pool"], SEED, i)
        for _ in range(6):
            query, params = next(seq)
            df = cell.queries[query].dataframe(d["session"], d["ws"], params)
            h = d["sched"].submit_query(df, label=query, allow_approx=False)
            answers[i].append((query, params, h.result(timeout=300),
                               h.ctx.device_home))

    threads = [threading.Thread(target=client, args=(i,))
               for i in range(CHIPS)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=600)
    assert not any(t.is_alive() for t in threads)
    got = [a for lst in answers for a in lst]
    assert len(got) == 6 * CHIPS
    one_chip = {}
    for query, params, out, home in got:
        assert home in range(CHIPS)
        wrong, err = cell.queries[query].compare(out.to_pydict(),
                                                 ref(query, params))
        assert wrong == 0 and err <= cell.limits["float_rel_err"]
        key = (query, repr(sorted(params.items())))
        if key not in one_chip:
            one_chip[key] = _bits(cell.queries[query].dataframe(
                d["one_chip"], d["ws"], params).collect())
        assert _bits(out) == one_chip[key], (query, params, home)


def test_every_chip_gets_queries(deployment):
    """Four queries in flight at once take the four chips: the scheduler
    homes each on the chip with the fewest active queries."""
    d = deployment
    before = [_counter(f"serve.home.d{k}") for k in range(CHIPS)]
    gate = threading.Barrier(CHIPS)
    query, params = "q3_homed", d["pool"]["q3_homed"][0]
    handles = []
    for _ in range(CHIPS):
        df = d["cell"].queries[query].dataframe(d["session"], d["ws"], params)

        def fn(df=df):
            gate.wait(timeout=60)
            return df.collect()

        handles.append(d["sched"].submit(fn, label=query))
    outs = [h.result(timeout=300) for h in handles]
    assert sorted(h.ctx.device_home for h in handles) == list(range(CHIPS))
    after = [_counter(f"serve.home.d{k}") for k in range(CHIPS)]
    assert [a - b for a, b in zip(after, before)] == [1] * CHIPS
    assert len({_bits(o) for o in outs}) == 1


def test_each_chip_holds_its_own_device_cache_entries(deployment):
    """Every chip has entries, and each entry lives on the chip it is
    filed under: the uploads of a homed query land on its home chip."""
    d = deployment
    for chip in range(CHIPS):
        _on_chip(d, "q17_homed", d["pool"]["q17_homed"][0], chip)
    by_id = {dev.id: dev for dev in jax.devices()[:CHIPS]}
    assert set(by_id) <= set(DEVICE_CACHE.chip_bytes())
    for chip, lru in DEVICE_CACHE._chips.items():
        if chip is None:
            continue
        for _refs, value, _nbytes in lru.values():
            for leaf in jax.tree_util.tree_leaves(value):
                if isinstance(leaf, jax.Array):
                    assert leaf.devices() == {by_id[chip]}
    assert DEVICE_CACHE.check_consistency()


def test_each_chip_evicts_under_its_own_budget(monkeypatch):
    """A budget per chip: filling chip 0 past it evicts chip 0's oldest
    entries and none of chip 1's."""
    monkeypatch.setenv("HYPERSPACE_DEVICE_CACHE_MB", str(3 * 4096 / 2**20))
    cache = DeviceArrayCache(per_chip=True)
    devices = tuple(jax.devices()[:CHIPS])
    srcs = [np.full(1024, i, np.float32) for i in range(6)]  # 4 KiB each

    def put(chip, src):
        with backend.on_home((chip, devices)):
            return cache.get_or_put(src, "t", lambda: jax.numpy.asarray(src),
                                    meter=False)

    for src in srcs[:2]:
        assert put(1, src).devices() == {devices[1]}
    for src in srcs[2:]:
        assert put(0, src).devices() == {devices[0]}
    chips = cache.chip_bytes()
    assert chips == {devices[0].id: 3 * 4096, devices[1].id: 2 * 4096}
    assert cache.evictions == 1
    with backend.on_home((1, devices)):
        hits = cache.hits
        cache.get_or_put(srcs[0], "t", lambda: pytest.fail("evicted"),
                         meter=False)
        assert cache.hits == hits + 1
    assert cache.check_consistency()


def test_a_second_round_compiles_nothing_on_any_chip(deployment):
    """The warm-up ran each drawn set once, all homed on chip 0; each
    kernel it compiled there was compiled for the other chips aside. So a
    second round of every set on every chip compiles nothing, and every
    kernel call lands on the chip the query is homed on."""
    d = deployment
    compiled = _compile_ms()
    retraces = _counter("kernel.retrace")
    for chip in range(CHIPS):
        before = [_counter(f"device.dispatch.d{k}") for k in range(CHIPS)]
        for query in sorted(d["pool"]):
            for params in d["pool"][query]:
                _on_chip(d, query, params, chip)
        after = [_counter(f"device.dispatch.d{k}") for k in range(CHIPS)]
        dispatched = [a - b for a, b in zip(after, before)]
        assert sum(dispatched) == dispatched[chip] > 0, (chip, dispatched)
    assert _compile_ms() == compiled
    assert _counter("kernel.retrace") == retraces


def test_the_warm_up_homes_on_chip_0_and_readies_the_others(deployment):
    """The sequential warm-up homes every query on chip 0 (ties go to the
    lowest ordinal); every kernel it called there was compiled for the
    three other chips without a query waiting, and none failed."""
    assert {a.query for a in deployment["warmed"]} == {"q3_homed", "q17_homed"}
    assert deployment["readied"] > 0
    assert deployment["readied"] % (CHIPS - 1) == 0
    assert _counter("kernel.ready.errors") == 0


def test_without_the_conf_no_query_gets_a_home(deployment):
    d = deployment
    query, params = "q17_homed", d["pool"]["q17_homed"][0]
    df = d["cell"].queries[query].dataframe(d["one_chip"], d["ws"], params)
    homes = [_counter(f"serve.home.d{k}") for k in range(CHIPS)]
    h = d["sched"].submit_query(df, label=query, allow_approx=False)
    h.result(timeout=300)
    assert h.ctx.device_home is None
    assert [_counter(f"serve.home.d{k}") for k in range(CHIPS)] == homes
