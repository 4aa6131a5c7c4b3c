#!/usr/bin/env python
"""Mid-query adaptive re-optimization smoke (HYPERSPACE_ADAPTIVE).

Plants mis-estimates at all three adaptation sites and asserts that every
switch fires AND that adaptive execution stays bit-identical to static:

- **join replan**: footer byte stats tampered 64x low under a small device
  grant — the static plan's banded waves overrun the ledger and park; the
  adaptive run observes decoded actuals on the first bucket pair, flips
  banded→split, and must finish with STRICTLY fewer parks+spills and the
  exact static bits (count/min/max aggregates fold exactly),
- **conjunct reorder**: a worst-order col-vs-col conjunction (no arrow
  pushdown) over enough rows to leave the warmup window — the reordered
  mask must reproduce the static filter bit for bit, and the switch must
  render in EXPLAIN ANALYZE as ``[adapted: ...]``,
- **scan abort-and-replan**: sketch-NDV sidecars tampered 1e9 high so the
  sketch stage promises to keep almost nothing while honest blooms keep
  every row group — the streamed index scan aborts after its warmup
  chunks, the index is vetoed, and the replanned query must match the
  raw (hyperspace-disabled) scan bit for bit.

The whole smoke runs with the lock-order audit forced on
(``HYPERSPACE_LOCK_AUDIT=1``) — any violation across the replan loop
fails it. Prints one JSON line; exit 0 iff every section passes.

    timeout 300 env JAX_PLATFORMS=cpu python tools/adapt_smoke.py

Env: SMOKE_ROWS (events rows, default 60000).
"""

import glob
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _bits(d: dict) -> str:
    return repr(
        {
            k: [x.hex() if isinstance(x, float) else x for x in v]
            for k, v in d.items()
        }
    )


def main() -> int:
    os.environ.setdefault("HYPERSPACE_DEVICE_STRICT", "1")
    os.environ["HYPERSPACE_LOCK_AUDIT"] = "1"
    os.environ.pop("HYPERSPACE_ADAPTIVE", None)
    import jax

    jax.config.update("jax_platforms", os.environ.get("JAX_PLATFORMS", "cpu"))
    import tempfile

    import numpy as np

    from hyperspace_tpu import (
        CoveringIndexConfig,
        Hyperspace,
        HyperspaceSession,
    )
    from hyperspace_tpu import constants as C
    from hyperspace_tpu.columnar import io as cio
    from hyperspace_tpu.columnar.table import ColumnBatch
    from hyperspace_tpu.models import covering
    from hyperspace_tpu.models.dataskipping import sketch_store
    from hyperspace_tpu.plan import Count, Max, Min, col, lit
    from hyperspace_tpu.plan import join_memory
    from hyperspace_tpu.serve import budget as serve_budget
    from hyperspace_tpu.telemetry.metrics import REGISTRY

    n_ev = int(os.environ.get("SMOKE_ROWS", 60_000))
    ws = tempfile.mkdtemp(prefix="hs_adapt_smoke_")
    rng = np.random.default_rng(7)

    def cnt(name: str) -> float:
        return REGISTRY.counter(name).value

    session = HyperspaceSession(warehouse_dir=ws)
    hs = Hyperspace(session)
    out = {"rows": n_ev, "sections": {}}
    failures = []

    # -- section 1: join replan under tampered footer byte stats ----------
    # Fixed geometry (independent of SMOKE_ROWS): 4 buckets of ~37k rows
    # each pad to a 65536-row band wave, so the static banded plan
    # reserves ~2x the decoded bytes and parks under a 2 MB grant, while
    # the adaptive flip to grant-derived split slabs fits exactly. The
    # /64 byte tamper keeps the planned classification banded (row_bytes
    # clamps at 1.0 -> threshold grant/32 rows > any bucket).
    n_join = 150_000
    cio.write_parquet(
        ColumnBatch.from_pydict(
            {
                "k": rng.integers(0, 600, n_join).tolist(),
                "p": rng.uniform(0, 100, n_join).tolist(),
            }
        ),
        os.path.join(ws, "jl", "l.parquet"),
    )
    cio.write_parquet(
        ColumnBatch.from_pydict(
            {
                "rk": list(range(500)),
                "w": rng.uniform(size=500).tolist(),
            }
        ),
        os.path.join(ws, "jr", "r.parquet"),
    )
    session.set_conf(C.INDEX_NUM_BUCKETS, 4)
    hs.create_index(
        session.read.parquet(os.path.join(ws, "jl")),
        CoveringIndexConfig("jl_idx", ["k"], ["p"]),
    )
    hs.create_index(
        session.read.parquet(os.path.join(ws, "jr")),
        CoveringIndexConfig("jr_idx", ["rk"], ["w"]),
    )
    session.enable_hyperspace()
    session.set_conf(C.EXEC_TPU_ENABLED, True)

    real_estimates = join_memory._bucket_estimates
    join_memory._bucket_estimates = lambda side, b: (
        lambda r, nb: (r, nb / 64.0)
    )(*real_estimates(side, b))
    os.environ["HYPERSPACE_JOIN_BROADCAST_ROWS"] = "10"
    os.environ["HYPERSPACE_DEVICE_BUDGET_MB"] = "2.0"
    os.environ["HYPERSPACE_PARK_WAIT_MS"] = "1"
    os.environ["HYPERSPACE_ADAPTIVE_WARMUP_CHUNKS"] = "1"
    serve_budget.reset_device_budget()

    def join_q():
        l = session.read.parquet(os.path.join(ws, "jl")).select("k", "p")
        r = session.read.parquet(os.path.join(ws, "jr")).select("rk", "w")
        return (
            l.join(r, col("k") == col("rk"))
            .group_by("k")
            .agg(
                Count(lit(1)).alias("n"),
                Min(col("p")).alias("lo"),
                Max(col("p")).alias("hi"),
            )
            .to_pydict()
        )

    os.environ["HYPERSPACE_ADAPTIVE"] = "0"
    parks0, spills0 = cnt("join.spill.parks"), cnt("join.spill.spills")
    static = join_q()
    static_parks = cnt("join.spill.parks") - parks0
    static_spills = cnt("join.spill.spills") - spills0

    os.environ["HYPERSPACE_ADAPTIVE"] = "1"
    parks0, spills0 = cnt("join.spill.parks"), cnt("join.spill.spills")
    flips0 = cnt("adaptive.replan")
    adaptive = join_q()
    adapt_parks = cnt("join.spill.parks") - parks0
    adapt_spills = cnt("join.spill.spills") - spills0
    flips = cnt("adaptive.replan") - flips0

    join_match = _bits(adaptive) == _bits(static)
    join_fewer = (adapt_parks + adapt_spills) < (static_parks + static_spills)
    out["sections"]["join_replan"] = {
        "flips": flips,
        "static_parks": static_parks,
        "static_spills": static_spills,
        "adaptive_parks": adapt_parks,
        "adaptive_spills": adapt_spills,
        "results_match_static": join_match,
        "fewer_parks_and_spills": join_fewer,
    }
    if not (join_match and flips >= 1 and join_fewer):
        failures.append("join_replan")
    join_memory._bucket_estimates = real_estimates
    session.set_conf(C.EXEC_TPU_ENABLED, False)
    os.environ.pop("HYPERSPACE_DEVICE_BUDGET_MB", None)
    serve_budget.reset_device_budget()

    # -- section 2: conjunct reorder + EXPLAIN ANALYZE rendering ----------
    # needs more rows than the warmup window (_REORDER_CHUNK_ROWS x
    # (warmup + 1) = 128k at defaults) or every chunk is warmup and the
    # reorder never arms
    n_flt = max(150_000, n_ev)
    cio.write_parquet(
        ColumnBatch.from_pydict(
            {
                "a": rng.integers(0, 100, n_flt).tolist(),
                "b": rng.integers(0, 100, n_flt).tolist(),
                "c": rng.integers(0, 100, n_flt).tolist(),
            }
        ),
        os.path.join(ws, "flt", "p.parquet"),
    )

    def filter_df():
        # written worst-first; col-vs-col never pushes to arrow, so the
        # host Filter node sees every row
        return (
            session.read.parquet(os.path.join(ws, "flt"))
            .filter(
                (col("a") != col("c"))
                & (col("a") > col("b"))
                & (col("b") >= col("c"))
            )
            .select("a", "b", "c")
        )

    os.environ["HYPERSPACE_ADAPTIVE"] = "1"
    reorders0 = cnt("adaptive.reorder")
    adaptive = filter_df().to_pydict()
    reorders = cnt("adaptive.reorder") - reorders0
    report = hs.explain_analyze(filter_df())
    os.environ["HYPERSPACE_ADAPTIVE"] = "0"
    static = filter_df().to_pydict()
    reorder_match = _bits(adaptive) == _bits(static)
    rendered = "[adapted:" in report
    out["sections"]["conjunct_reorder"] = {
        "reorders": reorders,
        "results_match_static": reorder_match,
        "explain_renders_switch": rendered,
        "rows_kept": len(adaptive["a"]),
    }
    if not (reorder_match and reorders >= 1 and rendered):
        failures.append("conjunct_reorder")

    # -- section 3: scan abort-and-replan under tampered sketch NDV -------
    os.environ["HYPERSPACE_SKETCHES"] = "1"
    rgs_orig = covering.INDEX_ROW_GROUP_SIZE
    covering.INDEX_ROW_GROUP_SIZE = 1024
    n_files = 4
    per = n_ev // n_files
    try:
        for i in range(n_files):
            base = i * per
            cio.write_parquet(
                ColumnBatch.from_pydict(
                    {
                        "ev_k": list(range(base, base + per)),
                        # an int category: a string predicate would
                        # decline the stream the abort monitor watches
                        "ev_cat": [(base + j) % 3 for j in range(per)],
                        "ev_v": rng.uniform(0, 1, per).tolist(),
                    }
                ),
                os.path.join(ws, "events", f"part-{i:02d}.parquet"),
            )
        session.set_conf(C.INDEX_NUM_BUCKETS, 2)
        hs.create_index(
            session.read.parquet(os.path.join(ws, "events")),
            CoveringIndexConfig("ev_idx", ["ev_k"], ["ev_cat", "ev_v"]),
        )
    finally:
        covering.INDEX_ROW_GROUP_SIZE = rgs_orig
    # plant the mis-estimate: NDV 1e9 says "almost no group holds ev_cat 1"
    sides = sorted(
        glob.glob(
            os.path.join(ws, "indexes", "ev_idx", "**", "_sketch.*.json"),
            recursive=True,
        )
    )
    for side in sides:
        raw = json.load(open(side))
        if "ev_cat" in raw.get("ndv", {}):
            raw["ndv"]["ev_cat"] = 10**9
            json.dump(raw, open(side, "w"))
    sketch_store._SIDECAR_CACHE.clear()

    session.set_conf(C.EXEC_TPU_ENABLED, True)
    os.environ["HYPERSPACE_STREAM_CHUNK_MB"] = "0.02"

    def scan_q():
        return (
            session.read.parquet(os.path.join(ws, "events"))
            .filter(col("ev_cat") == 1)
            .group_by("ev_cat")
            .agg(
                Count(lit(1)).alias("n"),
                Min(col("ev_v")).alias("lo"),
                Max(col("ev_v")).alias("hi"),
            )
            .to_pydict()
        )

    session.disable_hyperspace()
    raw = scan_q()
    session.enable_hyperspace()
    os.environ["HYPERSPACE_ADAPTIVE"] = "1"
    aborts0 = cnt("adaptive.abort")
    replans0 = cnt("adaptive.scan_replans")
    adaptive = scan_q()
    aborts = cnt("adaptive.abort") - aborts0
    replans = cnt("adaptive.scan_replans") - replans0
    abort_match = _bits(adaptive) == _bits(raw)
    out["sections"]["scan_abort"] = {
        "aborts": aborts,
        "scan_replans": replans,
        "tampered_sidecars": len(sides),
        "results_match_raw": abort_match,
    }
    if not (abort_match and aborts >= 1 and replans >= 1 and sides):
        failures.append("scan_abort")
    os.environ.pop("HYPERSPACE_ADAPTIVE", None)

    lock_violations = int(cnt("staticcheck.lock.violations"))
    out["lock_violations"] = lock_violations
    out["failures"] = failures
    ok = not failures and lock_violations == 0
    out["ok"] = ok
    print(json.dumps(out))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
