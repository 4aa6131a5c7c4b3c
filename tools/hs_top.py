#!/usr/bin/env python
"""hs_top — top(1) for a hyperspace serving process.

Renders the serving telemetry plane as a terminal table: health + breaker
state, scheduler occupancy, global-budget occupancy, the device-memory
ledger (occupancy, parked/spilled/resumed join waves), the per-tenant QoS
table (weights, virtual clocks, delivered share, quota rejections),
serving rates, the active queries, and the tail of the per-query log
(tenant, phase breakdown, bytes, cache hit ratio per query). Three
sources, same payload shape (the exporter's ``/snapshot``):

    python tools/hs_top.py --url http://127.0.0.1:9090           # one shot
    python tools/hs_top.py --url http://127.0.0.1:9090 --watch 2 # live
    python tools/hs_top.py --file snapshots.jsonl                # JSONL sink
    python tools/hs_top.py --file snapshots.jsonl --watch 2      # follow

``--url`` scrapes a live exporter (telemetry/exporter.py, enabled with
``HYPERSPACE_METRICS_PORT``); ``--file`` reads the LAST line of a periodic
snapshot-sink JSONL (``HYPERSPACE_SNAPSHOT_FILE``), so a headless run can
be watched from another terminal. In ``--watch`` mode rates (QPS, bytes/s)
are derived from successive snapshots' counter deltas.

See docs/observability.md ("Query log") for the column definitions.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
import urllib.request

_PHASE_SHORT = (
    ("plan", "plan"), ("io", "io"), ("io_wait", "iow"), ("upload", "up"),
    ("enqueue", "enq"), ("device_wait", "dwait"), ("fetch", "fetch"),
    ("fold", "fold"), ("park", "park"), ("compile", "comp"),
    ("host", "host"),
)


def _fetch_url(url: str) -> dict:
    with urllib.request.urlopen(url.rstrip("/") + "/snapshot", timeout=10) as r:
        return json.loads(r.read().decode("utf-8"))


def _fetch_file(path: str) -> dict:
    last = None
    with open(path, encoding="utf-8") as f:
        for line in f:
            line = line.strip()
            if line:
                last = line
    if last is None:
        raise ValueError(f"no snapshots in {path} yet")
    return json.loads(last)


def _mb(n) -> str:
    return f"{(n or 0) / 1e6:.2f}"


def _phase_cell(rec: dict) -> str:
    phases = rec.get("phases_ms") or {}
    parts = [
        f"{short}={phases[name]:.0f}"
        for name, short in _PHASE_SHORT
        if phases.get(name, 0) >= 0.05
    ]
    return " ".join(parts) if parts else "-"


def _adapt_cell(rec: dict) -> str:
    """Mid-query adaptation events charged to this query: the sum of its
    ``adaptive.{replan,reorder,abort}`` site counters ("-" when none)."""
    counters = rec.get("counters") or {}
    n = sum(
        int(v)
        for k, v in counters.items()
        if k in ("adaptive.replan", "adaptive.reorder", "adaptive.abort")
    )
    return str(n) if n else "-"


def _approx_cell(rec: dict) -> str:
    """Approximate-tier column: ``~f`` when the query was served sampled
    at fraction f, ``d`` prefix when the QoS door degraded it (``d!``
    alone = degraded but the plan was ineligible, served exact). "-" for
    plain exact queries."""
    ap = rec.get("approx") or {}
    if not ap:
        return "-"
    deg = "d" if ap.get("degraded") else ""
    if ap.get("engaged"):
        return f"{deg}~{ap.get('fraction', 0):g}"
    return f"{deg}!" if deg else "-"


def _rates(prev: dict | None, cur: dict) -> str:
    """QPS / MB/s derived from two successive snapshots' counters."""
    if prev is None:
        return "rates: (need two snapshots)"
    dt = (cur.get("ts") or 0) - (prev.get("ts") or 0)
    if dt <= 0:
        return "rates: (no time delta)"
    pm, cm = prev.get("metrics") or {}, cur.get("metrics") or {}

    def d(name):
        return (cm.get(name) or 0) - (pm.get(name) or 0)

    return (
        f"rates: {d('serve.query.records') / dt:.2f} qps, "
        f"{d('io.bytes_decoded') / dt / 1e6:.2f} MB/s decoded, "
        f"{d('serve.budget.stalls') / dt:.2f} stalls/s, "
        f"{d('exporter.scrapes') / dt:.2f} scrapes/s over {dt:.1f}s"
    )


def render(snap: dict, prev: dict | None = None, recent: int = 15) -> str:
    serving = snap.get("serving") or {}
    queries = snap.get("queries") or {}
    breaker = snap.get("breaker") or {}
    budget = serving.get("budget") or {}
    totals = serving.get("totals") or {}
    qtotals = queries.get("totals") or {}
    lines = []
    ts = snap.get("ts")
    when = time.strftime("%H:%M:%S", time.localtime(ts)) if ts else "?"
    lines.append(
        f"hs_top @ {when} | breaker={breaker.get('state', '?')} | "
        f"scheduler {len(serving.get('active') or [])} active / "
        f"{len(serving.get('queued') or [])} queued "
        f"(max={serving.get('max_concurrent')}, "
        f"depth={serving.get('queue_depth_limit')})"
    )
    held, limit = budget.get("held_bytes", 0), budget.get("limit_bytes", 0)
    pct = 100.0 * held / limit if limit else 0.0
    lines.append(
        f"budget {_mb(held)}/{_mb(limit)} MB held ({pct:.1f}%), "
        f"{len(budget.get('streams') or [])} stream(s) | "
        f"admitted={totals.get('admitted', 0)} done={totals.get('done', 0)} "
        f"failed={totals.get('failed', 0)} "
        f"cancelled={totals.get('cancelled', 0)} "
        f"rejected={totals.get('rejected', 0)} | "
        f"log recorded={qtotals.get('recorded', 0)} "
        f"slow={qtotals.get('slow', 0)}"
    )
    dev = serving.get("device_budget") or {}
    if dev:
        dheld, dlimit = dev.get("held_bytes", 0), dev.get("limit_bytes", 0)
        if dlimit:
            dpct = 100.0 * dheld / dlimit
            lines.append(
                f"device {_mb(dheld)}/{_mb(dlimit)} MB held ({dpct:.1f}%), "
                f"{len(dev.get('streams') or [])} stream(s) | "
                f"parks={dev.get('parks', 0)} spills={dev.get('spills', 0)} "
                f"resumes={dev.get('resumes', 0)}"
            )
        else:
            lines.append("device ledger disabled (HYPERSPACE_DEVICE_BUDGET_MB=0)")
    rc = snap.get("result_cache") or {}
    if rc and rc.get("mode", "0") != "0":
        looked = (rc.get("hits", 0) or 0) + (rc.get("misses", 0) or 0)
        ratio = 100.0 * rc.get("hits", 0) / looked if looked else 0.0
        lines.append(
            f"result-cache mode={rc.get('mode')} "
            f"{rc.get('entries', 0)} entries "
            f"({rc.get('foldable_entries', 0)} foldable) "
            f"{_mb(rc.get('bytes'))}/{_mb(rc.get('max_bytes'))} MB | "
            f"hits={rc.get('hits', 0)} misses={rc.get('misses', 0)} "
            f"({ratio:.1f}%) folds={rc.get('folds', 0)} "
            f"refreshes={rc.get('refreshes', 0)} "
            f"evictions={rc.get('evictions', 0)}"
        )
    tenants = snap.get("tenants") or {}
    tsched = tenants.get("scheduler") or {}
    trolls = tenants.get("rollups") or {}
    tnames = sorted(set(tsched) | set(trolls))
    # the single zero-config default tenant with nothing notable is noise;
    # any configured weight/quota, rejection, or second tenant prints
    if tnames and not (
        tnames == ["default"]
        and (tsched.get("default") or {}).get("weight", 1.0) == 1.0
        and not any(
            (tsched.get("default") or {}).get(f"rejected_{k}", 0)
            for k in ("rate", "quota", "deadline")
        )
    ):
        lines.append(
            f"TENANTS ({len(tnames)}): "
            f"{'tenant':<12} {'w':>5} {'share':>6} {'vclock':>9} "
            f"{'q/a':>5} {'done':>5} {'rej':>4} {'MB':>8}"
        )
        for name in tnames:
            s = tsched.get(name) or {}
            r = trolls.get(name) or {}
            rej = (
                s.get("rejected_rate", 0) + s.get("rejected_quota", 0)
                + s.get("rejected_deadline", 0)
            )
            lines.append(
                f"  tenant: {name[:12]:<12} {s.get('weight', 1.0):>5.2f} "
                f"{s.get('delivered_share', 0.0):>6.2f} "
                f"{s.get('vclock', 0.0):>9.3f} "
                f"{s.get('queued', 0)}/{s.get('active', 0):>3} "
                f"{s.get('done', 0):>5} {rej:>4} "
                f"{_mb(r.get('bytes_read')):>8}"
            )
    est = snap.get("estimator") or {}
    if est.get("observations"):
        qcells = [
            f"{name} n={h.get('count', 0)} mean={h.get('mean', 0):.2f} "
            f"max={h.get('max', 0):.2f}"
            for name, h in sorted((est.get("qerror") or {}).items())
            if h.get("count")
        ]
        lines.append(
            "estimator q-errors: " + (" | ".join(qcells) or "(none)")
            + f" | corrections={est.get('correction_keys', 0)}"
        )
    wl = snap.get("workload") or {}
    if wl.get("enabled"):
        jst = wl.get("journal") or {}
        lines.append(
            f"workload journal: {jst.get('writes', 0)} writes, "
            f"{jst.get('files', 0)} file(s), "
            f"{jst.get('rotations', 0)} rotation(s), "
            f"{_mb(jst.get('current_bytes'))} MB current"
        )
        idx = wl.get("indexes") or []
        if idx:
            lines.append(
                f"INDEXES ({len(idx)}): "
                f"{'index':<20} {'queries':>7} {'benefit_MB':>10} "
                f"{'skip_MB':>8} {'maint_s':>8} {'net_s':>9}"
            )
            for r in idx:
                lines.append(
                    f"  index: {str(r.get('name', '?'))[:20]:<20} "
                    f"{r.get('queries', 0):>7} "
                    f"{_mb(r.get('benefit_bytes')):>10} "
                    f"{_mb(r.get('bytes_skipped')):>8} "
                    f"{r.get('maintenance_s', 0.0):>8.3f} "
                    f"{r.get('net_utility_s', 0.0):>9.3f}"
                )
            cold = wl.get("cold_indexes") or []
            if cold:
                lines.append(f"  cold candidates: {', '.join(cold)}")
        drift = wl.get("drift") or {}
        regs = drift.get("regressions") or []
        lines.append(
            f"DRIFT: {drift.get('series', 0)} series, "
            f"{len(regs)} regression(s)"
            + (f" [factor={drift.get('factor')}]" if regs else "")
        )
        for r in regs:
            lines.append(
                f"  drift: {r.get('kind')}:{r.get('key')} "
                f"baseline={r.get('baseline')} current={r.get('current')} "
                f"ratio={r.get('ratio')}x"
            )
    ap = snap.get("approx") or {}
    if ap.get("degrades") or ap.get("sampled_queries") or ap.get("ineligible"):
        mean_ci = ap.get("mean_ci_rel")
        lines.append(
            f"APPROX: degrades={ap.get('degrades', 0)} "
            f"sampled={ap.get('sampled_queries', 0)} "
            f"ineligible={ap.get('ineligible', 0)} "
            f"verify_checked={ap.get('verify_checked', 0)}"
            + (f" mean_ci=±{100 * mean_ci:.2f}%" if mean_ci is not None else "")
        )
    lines.append(_rates(prev, snap))
    hdr = (
        f"{'qid':>5} {'label':<20} {'tenant':<10} {'pri':>3} {'outcome':<9} "
        f"{'total_ms':>9} {'queue_ms':>8} {'MB':>7} {'hit%':>5} "
        f"{'stall':>5} {'adapt':>5} {'apx':>6}  phases_ms"
    )
    active = queries.get("active") or []
    lines.append("")
    lines.append(f"ACTIVE ({len(active)})")
    lines.append(hdr)
    rows = active + (queries.get("recent") or [])[-recent:]
    for i, r in enumerate(rows):
        if i == len(active):
            lines.append("")
            lines.append(f"RECENT (last {min(recent, len(rows) - i)})")
            lines.append(hdr)
        ratio = r.get("cache_hit_ratio")
        lines.append(
            f"{r.get('query_id', '?'):>5} {str(r.get('label', ''))[:20]:<20} "
            f"{str(r.get('tenant', '-'))[:10]:<10} "
            f"{r.get('priority', 0):>3} {str(r.get('outcome', '?'))[:9]:<9} "
            f"{r.get('total_ms', 0):>9.1f} {r.get('queue_wait_ms', 0):>8.1f} "
            f"{_mb(r.get('bytes_read')):>7} "
            f"{100 * ratio if ratio is not None else 0:>5.1f} "
            f"{r.get('budget_stalls', 0):>5} {_adapt_cell(r):>5} "
            f"{_approx_cell(r):>6}  "
            f"{_phase_cell(r)}"
        )
    if len(rows) == len(active):
        lines.append("(no finished queries in the log window)")
    return "\n".join(lines)


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__)
    src = p.add_mutually_exclusive_group(required=True)
    src.add_argument("--url", help="exporter base URL (scrapes /snapshot)")
    src.add_argument("--file", help="snapshot-sink JSONL (reads last line)")
    p.add_argument("--watch", type=float, metavar="SECONDS",
                   help="refresh every SECONDS (default: render once)")
    p.add_argument("--recent", type=int, default=15,
                   help="recent-query rows to show (default 15)")
    args = p.parse_args()

    def fetch() -> dict:
        return _fetch_url(args.url) if args.url else _fetch_file(args.file)

    if not args.watch:
        print(render(fetch(), recent=args.recent))
        return 0
    prev = None
    try:
        while True:
            try:
                snap = fetch()
            except Exception as e:  # noqa: BLE001 - keep polling a flaky target
                sys.stdout.write(f"\x1b[2J\x1b[H(snapshot failed: {e!r})\n")
                sys.stdout.flush()
                time.sleep(args.watch)
                continue
            out = render(snap, prev, recent=args.recent)
            sys.stdout.write("\x1b[2J\x1b[H" + out + "\n")
            sys.stdout.flush()
            prev = snap
            time.sleep(args.watch)
    except KeyboardInterrupt:
        return 0


if __name__ == "__main__":
    sys.exit(main())
