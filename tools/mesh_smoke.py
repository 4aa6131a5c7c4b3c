#!/usr/bin/env python
"""Deployment smoke: the TPC-H join bench queries (q3, q10, q17) over
covering join indexes, served on a FORCED 8-virtual-device CPU host
deployed on all eight (``hyperspace.tpu.exec.meshDevices=8``): eight
clients at once through the ``QueryScheduler``, each query on its own home
chip, against the same queries collected on one chip — including the
hot-key skew variant where 30% of lineitem rows carry ONE order key — and
asserts the results are bit-identical. Every chip must take a query and
keep its own device-cache entries. Then the same queries, served one at a
time with ``hyperspace.tpu.exec.bandPlacement=spread``, must stay
bit-identical while the skew-aware placer spreads their join bands: >= 4
of the 8 chips used on the skew fixture and a predicted-bytes imbalance
ratio under 2.0 (the fair-share split gate: a naive per-bucket packing of
the hot bucket lands near 3x). Every per-chip memory ledger must drain to
zero; the whole smoke runs with HYPERSPACE_LOCK_AUDIT=1 — any lock-order
violation fails it. Prints one JSON line; exit 0 iff every gate holds.

    timeout 600 env JAX_PLATFORMS=cpu python tools/mesh_smoke.py

Env: SMOKE_ROWS (lineitem rows, default 120000); HYPERSPACE_JOIN_SPLIT_ROWS
is forced small so the hot bucket's probe chunks rotate through their
placed chip ranges.
"""

import json
import os
import sys
import threading

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    # the virtual mesh must exist before jax initializes its backends
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8"
        ).strip()
    os.environ.setdefault("HYPERSPACE_DEVICE_STRICT", "1")
    os.environ.setdefault("HYPERSPACE_JOIN_SPLIT_ROWS", "8192")
    os.environ.setdefault("HYPERSPACE_LOCK_AUDIT", "1")
    import jax

    jax.config.update("jax_platforms", os.environ.get("JAX_PLATFORMS", "cpu"))
    import tempfile

    from hyperspace_tpu import CoveringIndexConfig, Hyperspace, HyperspaceSession
    from hyperspace_tpu import constants as C
    from hyperspace_tpu.benchmark import TPCH_QUERIES, generate_tpch
    from hyperspace_tpu.serve import budget as serve_budget
    from hyperspace_tpu.serve.scheduler import QueryScheduler
    from hyperspace_tpu.telemetry.metrics import REGISTRY
    from hyperspace_tpu.utils.backend import device_count
    from hyperspace_tpu.utils.device_cache import DEVICE_CACHE

    rows = int(os.environ.get("SMOKE_ROWS", 120_000))
    ws = tempfile.mkdtemp(prefix="hs_mesh_smoke_")
    generate_tpch(ws, rows_lineitem=rows, seed=11)
    # skew lineitem: rewrite 30% of order keys to ONE hot order so a single
    # bucket dwarfs the rest (the placement fair-share-split target shape)
    _skew_lineitem(ws, hot_frac=0.3)

    session = HyperspaceSession(warehouse_dir=ws)
    session.set_conf(C.INDEX_NUM_BUCKETS, 8)
    hs = Hyperspace(session)
    li = session.read.parquet(os.path.join(ws, "lineitem"))
    od = session.read.parquet(os.path.join(ws, "orders"))
    pt = session.read.parquet(os.path.join(ws, "part"))
    hs.create_index(
        li,
        CoveringIndexConfig(
            "li_orderkey",
            ["l_orderkey"],
            ["l_extendedprice", "l_discount", "l_returnflag", "l_quantity"],
        ),
    )
    hs.create_index(
        li,
        CoveringIndexConfig(
            "li_partkey", ["l_partkey"], ["l_quantity", "l_extendedprice"]
        ),
    )
    hs.create_index(
        od,
        CoveringIndexConfig(
            "od_orderkey", ["o_orderkey"], ["o_orderdate", "o_custkey"]
        ),
    )
    hs.create_index(
        pt, CoveringIndexConfig("pt_partkey", ["p_partkey"], ["p_brand"])
    )

    join_queries = ("q3", "q10", "q17")
    devices_visible = device_count()

    def bits(d):
        return repr(
            {
                k: [x.hex() if isinstance(x, float) else x for x in v]
                for k, v in d.items()
            }
        )

    def collect_all(sess) -> dict:
        return {name: TPCH_QUERIES[name](sess, ws).to_pydict()
                for name in join_queries}

    session.enable_hyperspace()
    session.set_conf(C.EXEC_TPU_ENABLED, True)
    off = collect_all(session)  # one chip: no query has a home

    deployed = HyperspaceSession(warehouse_dir=ws)
    deployed.set_conf(C.INDEX_NUM_BUCKETS, 8)
    deployed.set_conf(C.EXEC_MESH_DEVICES, devices_visible)
    deployed.set_conf(C.EXEC_TPU_ENABLED, True)
    deployed.enable_hyperspace()
    homes0 = [REGISTRY.counter(f"serve.home.d{k}").value
              for k in range(devices_visible)]
    gate = threading.Barrier(devices_visible)
    sched = QueryScheduler(max_concurrent=devices_visible)
    on: dict = {}
    homes: list = []
    try:
        handles = []
        for i in range(devices_visible):
            name = join_queries[i % len(join_queries)]
            df = TPCH_QUERIES[name](deployed, ws)

            def fn(df=df):
                gate.wait(timeout=300)  # every client's query in flight
                return df.collect()

            handles.append((name, sched.submit(fn, label=name)))
        mismatches = []
        for name, h in handles:
            got = h.result(timeout=600).to_pydict()
            homes.append(h.ctx.device_home)
            on.setdefault(name, got)
            if bits(got) != bits(off[name]):
                mismatches.append(f"{name}@d{h.ctx.device_home}")
        homed = [REGISTRY.counter(f"serve.home.d{k}").value - homes0[k]
                 for k in range(devices_visible)]
        # the skew-aware placer: each query's bands over all the chips
        deployed.set_conf(C.EXEC_BAND_PLACEMENT, "spread")
        buckets0 = REGISTRY.counter("mesh.placement.buckets").value
        usage0 = REGISTRY.counter("rules.usage.MeshBucketedExec").value
        for name in join_queries:
            got = sched.submit_query(
                TPCH_QUERIES[name](deployed, ws), label=name
            ).result(timeout=600).to_pydict()
            if bits(got) != bits(off[name]):
                mismatches.append(f"{name}@spread")
        placed_buckets = (
            REGISTRY.counter("mesh.placement.buckets").value - buckets0)
        usage_events = (
            REGISTRY.counter("rules.usage.MeshBucketedExec").value - usage0)
        devices_used = int(REGISTRY.gauge("mesh.placement.devices_used").value)
        imbalance = REGISTRY.gauge("mesh.placement.bytes_imbalance_ratio").value
    finally:
        sched.shutdown()
    chips_cached = sorted(DEVICE_CACHE.chip_bytes(), key=str)
    ledgers = {
        f"d{o}": acct.held_bytes()
        for o, acct in serve_budget.device_budgets().items()
    }
    ledgers_drained = all(v == 0 for v in ledgers.values()) and all(
        acct.check_consistency()
        for acct in serve_budget.device_budgets().values()
    )
    lock_violations = int(
        REGISTRY.counter("staticcheck.lock.violations").value
    )
    result = {
        "rows": rows,
        "queries": len(handles),
        "devices_visible": devices_visible,
        "bit_identical": not mismatches,
        "mismatches": mismatches,
        "homes": sorted(homes),
        "homed_per_chip": homed,
        "chips_cached": len(chips_cached),
        "cache_consistent": DEVICE_CACHE.check_consistency(),
        "placed_buckets": placed_buckets,
        "devices_used": devices_used,
        "bytes_imbalance_ratio": round(imbalance, 4),
        "usage_events": usage_events,
        "ledgers_held": ledgers,
        "ledgers_drained": ledgers_drained,
        "lock_violations": lock_violations,
    }
    print(json.dumps(result))
    ok = (
        not mismatches
        and devices_visible >= 8
        and sorted(homes) == list(range(devices_visible))
        and all(n == 1 for n in homed)
        and len(chips_cached) >= devices_visible
        and result["cache_consistent"]
        and placed_buckets > 0
        and devices_used >= 4
        and imbalance < 2.0
        and usage_events > 0
        and ledgers_drained
        and lock_violations == 0
    )
    return 0 if ok else 1


def _skew_lineitem(ws: str, hot_frac: float) -> None:
    import glob

    import numpy as np

    from hyperspace_tpu.columnar import io as cio
    from hyperspace_tpu.columnar.table import Column

    files = sorted(glob.glob(os.path.join(ws, "lineitem", "*.parquet")))
    batch = cio.read_parquet(files)
    k = np.asarray(batch.column("l_orderkey").data).copy()
    n_hot = int(len(k) * hot_frac)
    k[:n_hot] = k[0]
    batch = batch.with_column("l_orderkey", Column(k, "int64"))
    for f in files:
        os.remove(f)
    cio.write_parquet(batch, os.path.join(ws, "lineitem", "part-0000.parquet"))


if __name__ == "__main__":
    sys.exit(main())
