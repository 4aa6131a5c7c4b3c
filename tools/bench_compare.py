#!/usr/bin/env python
"""Diff two bench.py JSON artifacts section by section.

    python tools/bench_compare.py BENCH_before.json BENCH_after.json
    python tools/bench_compare.py A.json B.json --threshold 5

Walks the per-query sections plus the hybrid-refresh / bloom-skipping /
build / staticcheck / robustness blocks, prints one row per (section,
metric) with the old value, new
value, and signed percent delta (negative = B is faster/smaller). Metrics
present in only one artifact print with a `-` on the missing side.
``--threshold N`` hides rows whose |delta| is under N percent (timings
only; counters always print when changed).
"""

from __future__ import annotations

import argparse
import json
import sys

# per-query timing metrics worth diffing (ms unless noted)
_QUERY_METRICS = (
    "raw_ms",
    "indexed_hostexec_ms",
    "indexed_device_ms",
    "indexed_ms",
    "external_pandas_ms",
    "speedup_self",
    "speedup_vs_external",
)

_SECTION_METRICS = {
    "point_lookup": ("raw_ms", "indexed_ms", "speedup"),
    "hybrid_refresh": (
        "q3_hybrid_ms",
        "refresh_incremental_s",
        "q3_after_refresh_ms",
    ),
    "bloom_skipping": ("index_build_s", "raw_ms", "indexed_ms", "speedup"),
    "build": ("build_s",),
    # memory-adaptive spilling join: over-budget grant vs unconstrained
    "spill_join": (
        "unconstrained_ms",
        "constrained_ms",
        "spill_overhead_pct",
        "parks",
        "spills",
        "concurrent_parks",
    ),
    # mixed read/write serving: freshness lag + query latency under ingest
    "ingest_rw": (
        "wall_s",
        "ingest_rows_per_s",
        "freshness_p50_ms",
        "freshness_max_ms",
        "baseline_p50_ms",
        "baseline_p99_ms",
        "under_ingest_p50_ms",
        "under_ingest_p99_ms",
        "rows_ingested",
        "queries_under_ingest",
    ),
    # mesh-sharded scale-out: band waves across the device mesh vs the
    # single-device reference (bit-identical by construction; timings and
    # placement balance are the diffable signal)
    "mesh_scale": (
        "devices_visible",
        "mesh_off_ms",
        "mesh_on_ms",
        "placed_buckets",
        "placement_fallbacks",
        "devices_used",
        "bytes_imbalance_ratio",
    ),
    # approximate query tier: exact leg vs sampled legs on the dedicated
    # join fixture, plus the acceptance bar (best sampled speedup >= 5x)
    "approx_tier": (
        "index_build_s",
        "exact_ms",
        "best_sampled_speedup",
    ),
    # workload-intelligence plane: all zero with HYPERSPACE_WORKLOAD_DIR
    # unset (the default bench run) — drift here means the disabled plane
    # did work
    "workload": (
        "journal_records",
        "journal_rotations",
        "journal_errors",
        "index_applied",
        "benefit_bytes",
        "bytes_skipped",
        "maintenance_actions",
        "maintenance_s",
        "indexes_tracked",
        "drift_series",
        "drift_regressions",
    ),
}

_TOP_LEVEL = ("value", "vs_baseline", "index_build_gbps", "host_wall_s", "wall_s")


def _load(path: str) -> dict:
    with open(path, encoding="utf-8") as f:
        text = f.read().strip()
    # bench prints ONE JSON line, but tolerate logs around it: last line wins
    obj = None
    for line in reversed(text.splitlines()):
        line = line.strip()
        if line.startswith("{"):
            try:
                obj = json.loads(line)
                break
            except json.JSONDecodeError:
                continue
    if obj is None:
        try:
            obj = json.loads(text)
        except json.JSONDecodeError:
            raise ValueError(f"no JSON object found in {path}") from None
    if "queries" in obj:
        return obj
    # driver wrapper: {"cmd":..., "rc":..., "tail": <stdout tail>, "parsed": <bench json|null>}
    if isinstance(obj.get("parsed"), dict):
        return obj["parsed"]
    raise ValueError(
        f"{path} holds no bench result (wrapper with parsed=null — the run's "
        "stdout was truncated or the bench failed)"
    )


def _fmt(v) -> str:
    if v is None:
        return "-"
    if isinstance(v, float):
        return f"{v:.3f}" if abs(v) < 100 else f"{v:.1f}"
    return str(v)


def _delta_pct(a, b):
    if not isinstance(a, (int, float)) or not isinstance(b, (int, float)):
        return None
    if a == 0:
        return None if b == 0 else float("inf")
    return (b - a) / abs(a) * 100


def compare(a: dict, b: dict) -> list[tuple[str, str, object, object]]:
    """[(section, metric, a_value, b_value)] over every diffable metric."""
    rows: list[tuple[str, str, object, object]] = []
    for m in _TOP_LEVEL:
        rows.append(("total", m, a.get(m), b.get(m)))
    qa, qb = a.get("queries", {}), b.get("queries", {})
    for name in sorted(set(qa) | set(qb)):
        ea, eb = qa.get(name, {}), qb.get(name, {})
        for m in _QUERY_METRICS:
            if m in ea or m in eb:
                rows.append((name, m, ea.get(m), eb.get(m)))
        # per-query join-pipeline counters (pairs/bands/splits/pad savings)
        ja, jb = ea.get("join_pipeline") or {}, eb.get("join_pipeline") or {}
        for m in sorted(set(ja) | set(jb)):
            rows.append((name, f"join_pipeline.{m}", ja.get(m), jb.get(m)))
        # per-query index-pruning counters (files/rowgroups kept vs total)
        pa_, pb = ea.get("pruning") or {}, eb.get("pruning") or {}
        for m in sorted(set(pa_) | set(pb)):
            rows.append((name, f"pruning.{m}", pa_.get(m), pb.get(m)))
    for section, metrics in _SECTION_METRICS.items():
        sa, sb = a.get(section, {}) or {}, b.get(section, {}) or {}
        for m in metrics:
            if m in sa or m in sb:
                rows.append((section, m, sa.get(m), sb.get(m)))
        # nested pruning counter deltas (point_lookup section)
        pa_, pb = sa.get("pruning") or {}, sb.get("pruning") or {}
        for m in sorted(set(pa_) | set(pb)):
            rows.append((section, f"pruning.{m}", pa_.get(m), pb.get(m)))
        # nested ingest counter deltas (ingest_rw section: appends,
        # compaction runs, vacuumed/deferred versions, snapshot pins)
        ia, ib = sa.get("counters") or {}, sb.get("counters") or {}
        for m in sorted(set(ia) | set(ib)):
            rows.append((section, f"counters.{m}", ia.get(m), ib.get(m)))
    # sketch-prune section: per-query raw / minmax-only / sketches-on legs
    # plus their nested pruning counter deltas (bytes_skipped included)
    ska, skb = a.get("sketch_prune") or {}, b.get("sketch_prune") or {}
    for m in ("index_build_s",):
        if m in ska or m in skb:
            rows.append(("sketch_prune", m, ska.get(m), skb.get(m)))
    for sub in sorted(
        k for k in (set(ska) | set(skb))
        if isinstance(ska.get(k) or skb.get(k), dict)
    ):
        ea, eb = ska.get(sub) or {}, skb.get(sub) or {}
        for m in (
            "raw_ms", "minmax_only_ms", "sketch_ms",
            "speedup_vs_raw", "speedup_vs_minmax",
        ):
            if m in ea or m in eb:
                rows.append(("sketch_prune", f"{sub}.{m}", ea.get(m), eb.get(m)))
        pa_, pb = ea.get("pruning") or {}, eb.get("pruning") or {}
        for m in sorted(set(pa_) | set(pb)):
            rows.append(
                ("sketch_prune", f"{sub}.pruning.{m}", pa_.get(m), pb.get(m))
            )

    # adaptive re-optimization section: static vs adaptive legs on TPC-H
    # (overhead + switch counts) and the planted-misestimate join fixture
    # (flips / parks / spills are the signal)
    ada, adb = a.get("adaptive") or {}, b.get("adaptive") or {}
    for leg in ("tpch", "planted"):
        fa, fb = ada.get(leg) or {}, adb.get(leg) or {}
        for m in (
            "static_ms", "adaptive_ms", "adaptive_overhead_pct", "switches",
            "flips", "static_parks", "static_spills", "adaptive_parks",
            "adaptive_spills", "adaptive_speedup",
        ):
            if m in fa or m in fb:
                rows.append(("adaptive", f"{leg}.{m}", fa.get(m), fb.get(m)))

    # sustained-QPS serving section: closed-loop per client count + open loop
    qa_, qb_ = a.get("sustained_qps") or {}, b.get("sustained_qps") or {}
    def _phase_rows(prefix: str, ea: dict, eb: dict) -> None:
        """Per-phase mean/p99 (the attribution-ledger breakdown) under
        ``<prefix>.phase.<name>.<stat>``."""
        pa_, pb = ea.get("phases") or {}, eb.get("phases") or {}
        for ph in sorted(set(pa_) | set(pb)):
            fa, fb = pa_.get(ph) or {}, pb.get(ph) or {}
            for m in ("mean_ms", "p99_ms"):
                if m in fa or m in fb:
                    rows.append(("sustained_qps", f"{prefix}.phase.{ph}.{m}",
                                 fa.get(m), fb.get(m)))

    for tier in sorted(set(qa_.get("closed") or {}) | set(qb_.get("closed") or {})):
        ta = (qa_.get("closed") or {}).get(tier) or {}
        tb = (qb_.get("closed") or {}).get(tier) or {}
        for m in ("qps", "p50_ms", "p99_ms", "wall_s"):
            if m in ta or m in tb:
                rows.append(("sustained_qps", f"closed.{tier}.{m}",
                             ta.get(m), tb.get(m)))
        _phase_rows(f"closed.{tier}", ta, tb)
    oa, ob = qa_.get("open") or {}, qb_.get("open") or {}
    for m in ("offered_qps", "achieved_qps", "p50_ms", "p99_ms", "rejected"):
        if m in oa or m in ob:
            rows.append(("sustained_qps", f"open.{m}", oa.get(m), ob.get(m)))
    _phase_rows("open", oa, ob)
    if "qps_scaling_c4_vs_c1" in qa_ or "qps_scaling_c4_vs_c1" in qb_:
        rows.append(("sustained_qps", "qps_scaling_c4_vs_c1",
                     qa_.get("qps_scaling_c4_vs_c1"),
                     qb_.get("qps_scaling_c4_vs_c1")))
    # multi-tenant QoS section: hog-vs-light queue-wait percentiles with
    # weighted-fair scheduling off vs on, and the isolation ratio
    ma, mb = a.get("multi_tenant") or {}, b.get("multi_tenant") or {}
    for m in (
        "light_p50_off_ms", "light_p50_on_ms", "light_p99_off_ms",
        "light_p99_on_ms", "light_p99_isolation_x",
    ):
        if m in ma or m in mb:
            rows.append(("multi_tenant", m, ma.get(m), mb.get(m)))
    for leg in ("off", "on"):
        for party in ("hog", "light"):
            fa = ((ma.get(leg) or {}).get(party)) or {}
            fb = ((mb.get(leg) or {}).get(party)) or {}
            for m in ("p50_ms", "p99_ms"):
                if m in fa or m in fb:
                    rows.append(("multi_tenant", f"{leg}.{party}.{m}",
                                 fa.get(m), fb.get(m)))
    # result-cache serving section: cold vs warm repeat latency, hit ratio,
    # fold engagement, and the freshness lag under ingest with caching on
    ca, cb = a.get("cached_qps") or {}, b.get("cached_qps") or {}
    for m in (
        "cold_p50_ms", "warm_p50_ms", "repeat_speedup_p50", "hit_ratio",
        "folds", "freshness_p50_ms", "freshness_max_ms",
    ):
        if m in ca or m in cb:
            rows.append(("cached_qps", m, ca.get(m), cb.get(m)))
    for tier in ("cold", "warm"):
        ta, tb = ca.get(tier) or {}, cb.get(tier) or {}
        for m in ("qps", "p50_ms", "p99_ms", "wall_s"):
            if m in ta or m in tb:
                rows.append(("cached_qps", f"{tier}.{m}",
                             ta.get(m), tb.get(m)))
    # approximate-tier section: per-fraction sampled legs (latency, speedup
    # vs exact, realized error vs CI width) and the deadline-degrade leg
    apa, apb = a.get("approx_tier") or {}, b.get("approx_tier") or {}
    for sub in sorted(
        set(apa.get("sampled") or {}) | set(apb.get("sampled") or {})
    ):
        fa = (apa.get("sampled") or {}).get(sub) or {}
        fb = (apb.get("sampled") or {}).get(sub) or {}
        for m in ("sampled_ms", "speedup_vs_exact", "rel_err_max", "ci_rel_max"):
            if m in fa or m in fb:
                rows.append(("approx_tier", f"{sub}.{m}", fa.get(m), fb.get(m)))
    dga, dgb = apa.get("degrade") or {}, apb.get("degrade") or {}
    for m in (
        "deadline_s", "degraded_ms", "degraded_fraction", "speedup_vs_exact",
    ):
        if m in dga or m in dgb:
            rows.append(("approx_tier", f"degrade.{m}", dga.get(m), dgb.get(m)))
    for section in (
        "kernel_cache", "pipeline", "pruning", "device_cache", "staticcheck",
        "robustness", "serving", "ingest", "approx", "estimator",
    ):
        sa, sb = a.get(section, {}) or {}, b.get(section, {}) or {}
        for m in sorted(set(sa) | set(sb)):
            va, vb = sa.get(m), sb.get(m)
            if isinstance(va, dict) or isinstance(vb, dict):
                continue  # histogram summaries: not a scalar diff
            rows.append((section, m, va, vb))
    # nested lock-order audit block (staticcheck.concurrency)
    ca = (a.get("staticcheck") or {}).get("concurrency") or {}
    cb = (b.get("staticcheck") or {}).get("concurrency") or {}
    for m in sorted(set(ca) | set(cb)):
        rows.append(("staticcheck", f"concurrency.{m}", ca.get(m), cb.get(m)))
    # nested robustness blocks: breaker state machine + recovery-pass counts
    for sub in ("breaker", "recovery"):
        ra = (a.get("robustness") or {}).get(sub) or {}
        rb = (b.get("robustness") or {}).get(sub) or {}
        for m in sorted(set(ra) | set(rb)):
            rows.append(("robustness", f"{sub}.{m}", ra.get(m), rb.get(m)))
    return rows


def render(rows, threshold: float = 0.0) -> str:
    out = []
    header = f"{'section':<16} {'metric':<26} {'A':>12} {'B':>12} {'Δ%':>9}"
    out.append(header)
    out.append("-" * len(header))
    for section, metric, va, vb in rows:
        d = _delta_pct(va, vb)
        is_timing = metric.endswith(("_ms", "_s", "_gbps")) or metric in (
            "value", "vs_baseline", "speedup", "speedup_self",
            "speedup_vs_external",
        )
        if threshold and is_timing and d is not None and abs(d) < threshold:
            continue
        if threshold and not is_timing and va == vb:
            continue
        ds = "-" if d is None else ("inf" if d == float("inf") else f"{d:+.1f}")
        out.append(
            f"{section:<16} {metric:<26} {_fmt(va):>12} {_fmt(vb):>12} {ds:>9}"
        )
    return "\n".join(out)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("a", help="baseline BENCH_*.json")
    p.add_argument("b", help="candidate BENCH_*.json")
    p.add_argument(
        "--threshold", type=float, default=0.0,
        help="hide timing rows with |delta| below this percent",
    )
    args = p.parse_args(argv)
    a, b = _load(args.a), _load(args.b)
    # device-topology guard: timings from different mesh sizes are not
    # comparable (an 8-device mesh run vs a single-device run diffs
    # placement, not the engine). Older artifacts without the fact pass.
    da = (a.get("host") or {}).get("devices_visible")
    db = (b.get("host") or {}).get("devices_visible")
    if da is not None and db is not None and da != db:
        print(
            f"refusing to compare: device counts differ "
            f"({args.a}: {da} visible devices, {args.b}: {db}); "
            "re-run one side under the other's topology "
            "(XLA_FLAGS=--xla_force_host_platform_device_count=N)",
            file=sys.stderr,
        )
        return 2
    rows = compare(a, b)
    print(render(rows, args.threshold))
    return 0


if __name__ == "__main__":
    sys.exit(main())
