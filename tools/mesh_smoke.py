#!/usr/bin/env python
"""Mesh scale-out smoke: run the TPC-H join bench queries (q3, q10, q17)
over covering join indexes on a FORCED 8-virtual-device CPU mesh with
mesh-sharded execution ON (HYPERSPACE_MESH=1, skew-aware bucket→device
placement) and OFF (=0, everything on device 0) on the same generated
dataset — including the hot-key skew variant where 30% of lineitem rows
carry ONE order key — and assert the results are bit-identical. Placement
must actually engage: >= 4 of the 8 devices used on the skew fixture and a
predicted-bytes imbalance ratio under 2.0 (the fair-share split gate: a
naive per-bucket packing of the hot bucket lands near 3x). Every per-device
memory ledger must drain to zero and the whole smoke runs with
HYPERSPACE_LOCK_AUDIT=1 — any lock-order violation fails it. Prints one
JSON line; exit 0 iff every gate holds.

    timeout 600 env JAX_PLATFORMS=cpu python tools/mesh_smoke.py

Env: SMOKE_ROWS (lineitem rows, default 120000); HYPERSPACE_JOIN_SPLIT_ROWS
is forced small so the hot bucket's probe chunks rotate through their
placed device ranges.
"""

import json
import os
import sys

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
    from hyperspace_tpu.telemetry.metrics import REGISTRY
    from hyperspace_tpu.utils.backend import device_count

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

    def run(mesh: str) -> dict:
        os.environ["HYPERSPACE_MESH"] = mesh
        session.enable_hyperspace()
        session.set_conf(C.EXEC_TPU_ENABLED, True)
        out = {}
        try:
            for name in join_queries:
                out[name] = TPCH_QUERIES[name](session, ws).to_pydict()
        finally:
            session.set_conf(C.EXEC_TPU_ENABLED, False)
            session.disable_hyperspace()
        return out

    off = run("0")
    buckets0 = REGISTRY.counter("mesh.placement.buckets").value
    fallbacks0 = REGISTRY.counter("mesh.placement.fallbacks").value
    usage0 = REGISTRY.counter("rules.usage.MeshBucketedExec").value
    on = run("1")
    os.environ.pop("HYPERSPACE_MESH", None)
    placed_buckets = REGISTRY.counter("mesh.placement.buckets").value - buckets0
    fallbacks = REGISTRY.counter("mesh.placement.fallbacks").value - fallbacks0
    usage_events = (
        REGISTRY.counter("rules.usage.MeshBucketedExec").value - usage0
    )
    devices_used = int(REGISTRY.gauge("mesh.placement.devices_used").value)
    imbalance = REGISTRY.gauge("mesh.placement.bytes_imbalance_ratio").value
    ledgers = {
        f"d{o}": acct.held_bytes()
        for o, acct in serve_budget.device_budgets().items()
    }
    ledgers_drained = all(v == 0 for v in ledgers.values()) and all(
        acct.check_consistency()
        for acct in serve_budget.device_budgets().values()
    )

    def bits(d):
        return repr(
            {
                k: [x.hex() if isinstance(x, float) else x for x in v]
                for k, v in d.items()
            }
        )

    mismatches = [name for name in on if bits(on[name]) != bits(off[name])]
    lock_violations = int(
        REGISTRY.counter("staticcheck.lock.violations").value
    )
    result = {
        "rows": rows,
        "queries": len(on),
        "devices_visible": devices_visible,
        "bit_identical": not mismatches,
        "mismatches": mismatches,
        "placed_buckets": placed_buckets,
        "placement_fallbacks": fallbacks,
        "devices_used": devices_used,
        "bytes_imbalance_ratio": round(imbalance, 4),
        "usage_events": usage_events,
        "ledgers_held": ledgers,
        "ledgers_drained": ledgers_drained,
        "lock_violations": lock_violations,
        "mesh_counters": {
            k: v
            for k, v in REGISTRY.snapshot().items()
            if k.startswith(("mesh.", "serve.device_budget"))
            and not isinstance(v, dict)
        },
    }
    print(json.dumps(result))
    ok = (
        not mismatches
        and devices_visible >= 8
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
