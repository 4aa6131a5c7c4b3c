#!/usr/bin/env python
"""Benchmark: TPC-H Q1/Q3/Q6/Q10/Q17/Q18 end-to-end, indexed vs raw scans.

Runs the BASELINE.md workloads from hyperspace_tpu.benchmark on generated
TPC-H-shaped data; both sides execute on the same engine, so the measured
difference is what the indexes buy: layout, pruning, shuffle-free joins.

Prints ONE JSON line; the primary metric tracks the BASELINE.json north star
("Q3 p50 latency with JoinIndexRule"): the end-to-end indexed-join speedup.
vs_baseline divides the speedup of the indexed path over an EXTERNAL engine
(pandas, the stand-in for BASELINE.md's unavailable 32-core Spark-CPU) by
the 4x target; `q3_speedup_self` stays the same-engine comparison.

One process holds the device: JAX initializes once, in this process, on
its default backend, and the artifact records the platform, device kind
and device count it ran on. A device-phase failure fails the run.

Every timing section reports p50/min/max over BENCH_REPEATS runs (r3 item
6), and every device-tier query records its RPC/transfer deltas (r3 item 1:
dispatches, fetches, bytes up/down) so losses are attributable.

Env knobs: BENCH_ROWS (lineitem rows, default 4_000_000), BENCH_REPEATS
(default 3), BENCH_MAX_BUILD_MB (force hyperspace.tpu.build
.maxBytesInMemory, so scale runs exercise streaming file-group builds),
BENCH_LIFECYCLE_AUDIT=0 (opt out of the resource-lifecycle audit that is
otherwise on for the whole run; staticcheck.lifecycle_leaks in the
artifact).

`--profile` traces every query into a JSONL span artifact
(BENCH_PROFILE_FILE, default BENCH_profile.jsonl) with one `bench:<section>`
span per section; inspect with tools/trace_report.py. See
docs/observability.md.
"""

import json
import os
import sys
import time

# resource-lifecycle audit on for the whole bench by default
# (BENCH_LIFECYCLE_AUDIT=0 opts out): leaks flushed out by the bench's own
# workload land in the artifact's staticcheck block as lifecycle_leaks
if os.environ.get("BENCH_LIFECYCLE_AUDIT", "1") == "1":
    os.environ.setdefault("HYPERSPACE_LIFECYCLE_AUDIT", "1")


def _host_facts() -> dict:
    """Environment facts for the artifact (self-describing benchmarks)."""
    import platform

    facts: dict = {
        "nproc": os.cpu_count(),
        "platform": platform.platform(),
        "python": platform.python_version(),
    }
    try:
        with open("/proc/meminfo") as f:
            for line in f:
                if line.startswith("MemTotal:"):
                    facts["mem_total_gb"] = round(
                        int(line.split()[1]) / 1024 / 1024, 1
                    )
                    break
    except OSError:
        pass
    for mod in ("numpy", "pandas", "pyarrow", "jax"):
        try:
            facts[mod] = __import__(mod).__version__
        except Exception:
            facts[mod] = None
    facts["env"] = {
        k: os.environ.get(k)
        for k in ("JAX_PLATFORMS", "XLA_FLAGS")
        if os.environ.get(k) is not None
    }
    # mesh facts: artifacts from different device topologies are not
    # comparable (tools/bench_compare.py refuses mismatched counts)
    try:
        from hyperspace_tpu.utils.backend import device_count

        facts["devices_visible"] = device_count()
    except Exception:
        facts["devices_visible"] = None
    try:
        from hyperspace_tpu import native

        facts["native"] = native.build_facts()
    except Exception:
        facts["native"] = None
    return facts


def _stats(times: list[float]) -> dict:
    times = sorted(times)
    return {
        "p50_ms": round(times[len(times) // 2] * 1000, 1),
        "min_ms": round(times[0] * 1000, 1),
        "max_ms": round(times[-1] * 1000, 1),
        "n": len(times),
    }


def _timed(fn, repeats: int):
    """Warm once (compilation, page cache, device cache), then measure
    `repeats` runs. Returns (p50 seconds, stats dict)."""
    fn()
    times = []
    for _ in range(repeats):
        t0 = time.time()
        fn()
        times.append(time.time() - t0)
    return sorted(times)[len(times) // 2], _stats(times)


def _rpc_delta(fn):
    """One run of fn with the RPC meter delta captured around it."""
    from hyperspace_tpu.utils.rpc_meter import METER

    with METER.measure() as m:
        fn()
    return m.delta


def _bench_span(name: str):
    """A `bench:<section>` span when --profile is on (no-op otherwise), so
    the JSONL artifact groups query spans by bench section."""
    from hyperspace_tpu.telemetry import trace

    return trace.span(f"bench:{name}")


def _measure_point_lookup(session, ws: str, repeats: int) -> dict:
    """Index-pruning showcase: a point lookup on the li_orderkey covering
    index bucket-prunes to 1/num_buckets of the index files and row-group
    -skips within the kept bucket (sorted runs + footer stats). The raw
    side scans every lineitem file. Counter deltas land in the artifact so
    tools/bench_compare.py can diff the pruning win."""
    from hyperspace_tpu.plan import Count, Sum, col, lit

    key = 12345
    q = lambda: (
        session.read.parquet(os.path.join(ws, "lineitem"))
        .filter(col("l_orderkey") == key)
        .agg(Sum(col("l_extendedprice")).alias("s"), Count(lit(1)).alias("n"))
        .collect()
    )
    session.disable_hyperspace()
    t_raw, raw_stats = _timed(q, repeats)
    session.enable_hyperspace()
    _, prune_delta = _prefix_counter_delta(q, "pruning.")
    t_idx, idx_stats = _timed(q, repeats)
    session.disable_hyperspace()
    return {
        "raw_ms": round(t_raw * 1000, 1),
        "raw_stats": raw_stats,
        "indexed_ms": round(t_idx * 1000, 1),
        "indexed_stats": idx_stats,
        "speedup": round(t_raw / t_idx, 3) if t_idx > 0 else 0.0,
        "pruning": prune_delta,
    }


def _measure_sketch_prune(session, ws: str, rows: int, repeats: int) -> dict:
    """Per-row-group sketch pruning showcase: Eq/IN on NON-sort columns of
    a covering index. Three legs per query: raw (no index), minmax-only
    (HYPERSPACE_SKETCHES=0 — the pre-sketch engine: a predicate that never
    touches the leading indexed column cannot use the index at all), and
    sketches-on (bloom/value-list/z-region sidecars skip row groups).
    Every leg's result feeds results_match; pruning counter deltas
    (bytes_skipped included) land in the artifact per query for
    tools/bench_compare.py."""
    import numpy as np

    from hyperspace_tpu import CoveringIndexConfig, Hyperspace
    from hyperspace_tpu.columnar import io as cio
    from hyperspace_tpu.columnar.table import ColumnBatch
    from hyperspace_tpu.plan import col

    n = max(400_000, min(rows, 4_000_000))
    n_files = 16
    per = n // n_files
    root = os.path.join(ws, "events_sk")
    rng = np.random.default_rng(23)
    cat_div = max(1, n // 64)
    for i in range(n_files):
        k = np.arange(per, dtype=np.int64) + i * per
        data = {
            "ev_k": k.tolist(),
            # high-NDV monotone id and low-NDV time-bucket dimension, both
            # clustered with the sort key (the ingest-ordered shape the
            # sketch store exists for)
            "ev_id": (k + 10_000_000).tolist(),
            "ev_cat": (k // cat_div).tolist(),
            "ev_v": rng.uniform(0, 100, per).tolist(),
        }
        cio.write_parquet(
            ColumnBatch.from_pydict(data),
            os.path.join(root, f"part-{i:02d}.parquet"),
        )
    prev = os.environ.get("HYPERSPACE_SKETCHES")
    os.environ["HYPERSPACE_SKETCHES"] = "1"
    out: dict = {"rows": n, "files": n_files}
    match = True
    try:
        hs = Hyperspace(session)
        t0 = time.time()
        hs.create_index(
            session.read.parquet(root),
            CoveringIndexConfig("ev_sk_idx", ["ev_k"], ["ev_id", "ev_cat", "ev_v"]),
        )
        out["index_build_s"] = round(time.time() - t0, 2)
        key = int(10_000_000 + n * 5 // 8 + 17)
        cats = [3, int((n - 1) // cat_div) - 1]
        # sorted on the unique key: the raw scan and the bucketed index
        # scan emit rows in different physical orders — the sort makes the
        # three-leg comparison order-exact without changing what is scanned
        queries = {
            "eq": lambda: (
                session.read.parquet(root)
                .filter(col("ev_id") == key)
                .select("ev_k", "ev_id", "ev_cat")
                .sort("ev_k")
                .to_pydict()
            ),
            "in": lambda: (
                session.read.parquet(root)
                .filter(col("ev_cat").isin(cats))
                .select("ev_k", "ev_cat")
                .sort("ev_k")
                .to_pydict()
            ),
        }

        def bits(d):
            return {
                k: [x.hex() if isinstance(x, float) else x for x in v]
                for k, v in d.items()
            }

        for name, q in queries.items():
            session.disable_hyperspace()
            ref = q()
            t_raw, raw_stats = _timed(q, repeats)
            session.enable_hyperspace()
            os.environ["HYPERSPACE_SKETCHES"] = "0"
            got_mm = q()
            t_mm, mm_stats = _timed(q, repeats)
            os.environ["HYPERSPACE_SKETCHES"] = "1"
            got_sk, prune_delta = _prefix_counter_delta(q, "pruning.")
            t_sk, sk_stats = _timed(q, repeats)
            session.disable_hyperspace()
            match = match and bits(got_mm) == bits(ref) == bits(got_sk)
            out[name] = {
                "raw_ms": round(t_raw * 1000, 1),
                "raw_stats": raw_stats,
                "minmax_only_ms": round(t_mm * 1000, 1),
                "minmax_only_stats": mm_stats,
                "sketch_ms": round(t_sk * 1000, 1),
                "sketch_stats": sk_stats,
                "speedup_vs_raw": round(t_raw / t_sk, 3) if t_sk > 0 else 0.0,
                "speedup_vs_minmax": round(t_mm / t_sk, 3) if t_sk > 0 else 0.0,
                "pruning": prune_delta,
            }
    finally:
        if prev is None:
            os.environ.pop("HYPERSPACE_SKETCHES", None)
        else:
            os.environ["HYPERSPACE_SKETCHES"] = prev
        session.disable_hyperspace()
    out["results_match"] = match
    return out


def _measure_approx_tier(session, ws: str, rows: int, repeats: int) -> dict:
    """Approximate query tier showcase: sampled execution with error bounds
    and deadline-driven degradation, on a dedicated join fixture (high-NDV
    join key, skew-free — the shape the universe-sampling tier accepts).

    Three leg families land in the artifact:

    - **exact leg**: the covering-index join with the tier idle (twins on
      disk, nothing requested) — checked bit-identical to a
      HYPERSPACE_APPROX=0 run (the tier is invisible until asked for) and
      value-equal to the raw scan; both feed ``results_match``;
    - **sampled legs**, one per configured fraction: latency, speedup vs
      the exact leg, relative error vs the exact answer, CI half-width
      relative to the answer, and whether every 95% CI covered exact
      (coverage feeds ``results_match`` — honest bounds are correctness);
    - **degrade leg**: the serve scheduler learns the exact-tier wall over
      three runs, then a submit with a 5x-tighter deadline and
      allow_approx (the default) must come back from the sampled tier;
      fraction, wall, and speedup vs exact are recorded.

    The fixture's indexes are built with HYPERSPACE_APPROX=1 so the create
    path writes sample twins (the TPC-H indexes above are built with the
    tier off and have none); the env var is restored on exit, so no other
    section sees the tier. ``speedup_ok`` records the >=5x acceptance bar
    at the smallest-latency sampled leg.
    """
    import numpy as np

    from hyperspace_tpu import CoveringIndexConfig, Hyperspace, serve
    from hyperspace_tpu.columnar import io as cio
    from hyperspace_tpu.columnar.table import ColumnBatch
    from hyperspace_tpu.models import sample_store
    from hyperspace_tpu.plan import Count, Sum, col, lit, sampling
    from hyperspace_tpu.serve import qos
    from hyperspace_tpu.telemetry import plan_stats

    n = int(
        os.environ.get("BENCH_APPROX_ROWS", max(400_000, min(rows, 2_000_000)))
    )
    n_files = 8
    per = n // n_files
    n_dim = max(1024, n // 8)
    fact_root = os.path.join(ws, "apx_fact")
    dim_root = os.path.join(ws, "apx_dim")
    rng = np.random.default_rng(29)
    for i in range(n_files):
        cio.write_parquet(
            ColumnBatch.from_pydict(
                {
                    "fk": rng.integers(0, n_dim, per).astype(np.int64).tolist(),
                    "amt": rng.uniform(1.0, 100.0, per).tolist(),
                }
            ),
            os.path.join(fact_root, f"part-{i:02d}.parquet"),
        )
    cio.write_parquet(
        ColumnBatch.from_pydict(
            {
                "ok": np.arange(n_dim, dtype=np.int64).tolist(),
                "dt": rng.integers(0, 10_000, n_dim).tolist(),
            }
        ),
        os.path.join(dim_root, "part-00.parquet"),
    )

    prev = os.environ.get("HYPERSPACE_APPROX")
    os.environ["HYPERSPACE_APPROX"] = "1"
    res: dict = {"rows": n, "dim_rows": n_dim}
    try:
        hs = Hyperspace(session)
        t0 = time.time()
        hs.create_index(
            session.read.parquet(fact_root),
            CoveringIndexConfig("apx_fact_idx", ["fk"], ["amt"]),
        )
        hs.create_index(
            session.read.parquet(dim_root),
            CoveringIndexConfig("apx_dim_idx", ["ok"], ["dt"]),
        )
        res["index_build_s"] = round(time.time() - t0, 2)

        def q():
            f = session.read.parquet(fact_root)
            d = session.read.parquet(dim_root)
            return (
                f.join(d, col("fk") == col("ok"))
                .filter(col("dt") < 5000)
                .agg(Sum(col("amt")).alias("rev"), Count(lit(1)).alias("n"))
            )

        def bits(dd):
            return {
                k: [x.hex() if isinstance(x, float) else x for x in v]
                for k, v in dd.items()
            }

        session.disable_hyperspace()
        raw = q().to_pydict()
        session.enable_hyperspace()
        exact = q().to_pydict()
        os.environ["HYPERSPACE_APPROX"] = "0"
        exact_off = q().to_pydict()
        os.environ["HYPERSPACE_APPROX"] = "1"
        # tier idle == tier absent, bit for bit; index == raw to tolerance
        match = bits(exact) == bits(exact_off)
        match = match and all(
            abs(float(exact[k][0]) - float(raw[k][0]))
            <= 1e-6 * max(1.0, abs(float(raw[k][0])))
            for k in exact
        )
        t_exact, exact_stats = _timed(lambda: q().collect(), repeats)
        res["exact_ms"] = round(t_exact * 1000, 1)
        res["exact_stats"] = exact_stats

        legs: dict = {}
        best_speedup = 0.0
        for frac in sorted(sample_store.sample_fractions(), reverse=True):
            with plan_stats.collect_scope() as cap:
                with sampling.approx_scope(frac):
                    est = q().to_pydict()
            info = (cap.summary() or {}).get("approx") or {}
            outs = info.get("outputs") or {}
            leg: dict = {"engaged": bool(outs)}
            if not outs:
                leg["reason"] = info.get("reason")
            else:
                with sampling.approx_scope(frac):
                    t_s, s_stats = _timed(lambda: q().collect(), repeats)
                leg["sampled_ms"] = round(t_s * 1000, 1)
                leg["sampled_stats"] = s_stats
                leg["speedup_vs_exact"] = (
                    round(t_exact / t_s, 3) if t_s > 0 else 0.0
                )
                best_speedup = max(best_speedup, leg["speedup_vs_exact"])
                covered = True
                rel_errs, rel_cis = [], []
                for name in ("rev", "n"):
                    ex = float(exact[name][0])
                    err = abs(float(est[name][0]) - ex)
                    ci = float(outs[name]["ci95_max"])
                    covered = covered and err <= ci
                    rel_errs.append(err / max(1.0, abs(ex)))
                    rel_cis.append(ci / max(1.0, abs(ex)))
                leg["rel_err_max"] = round(max(rel_errs), 5)
                leg["ci_rel_max"] = round(max(rel_cis), 5)
                leg["ci_covers_exact"] = covered
                match = match and covered
            legs[f"f{frac:g}"] = leg
        res["sampled"] = legs
        res["best_sampled_speedup"] = best_speedup
        res["speedup_ok"] = best_speedup >= 5.0

        sched = serve.QueryScheduler(max_concurrent=2, queue_depth=64)
        try:
            label = "bench-approx-join"
            for _ in range(3):  # teach the cost model the exact-tier wall
                sched.submit(lambda: q().collect(), label=label).result(
                    timeout=600
                )
            predicted = qos.COST_MODEL.predict(label)
            deadline = max(0.005, predicted / 5.0)
            t0 = time.time()
            h = sched.submit(
                lambda: q().collect(), label=label, deadline_s=deadline
            )
            h.result(timeout=600)
            wall = time.time() - t0
            res["degrade"] = {
                "predicted_exact_s": round(predicted, 4),
                "deadline_s": round(deadline, 4),
                "degraded_fraction": h.ctx.approx_fraction,
                "degraded_ms": round(wall * 1000, 1),
                "speedup_vs_exact": (
                    round(t_exact / wall, 3) if wall > 0 else 0.0
                ),
                "within_deadline": wall <= deadline,
            }
        finally:
            sched.shutdown()
    finally:
        if prev is None:
            os.environ.pop("HYPERSPACE_APPROX", None)
        else:
            os.environ["HYPERSPACE_APPROX"] = prev
        session.disable_hyperspace()
    res["results_match"] = match
    return res


def _qps_stats(latencies: list[float]) -> dict:
    """p50/p99/min/max over per-query latencies (submission → result)."""
    xs = sorted(latencies)
    n = len(xs)
    if not n:
        return {"n": 0}
    return {
        "p50_ms": round(xs[n // 2] * 1000, 1),
        "p99_ms": round(xs[min(n - 1, (n * 99) // 100)] * 1000, 1),
        "min_ms": round(xs[0] * 1000, 1),
        "max_ms": round(xs[-1] * 1000, 1),
        "n": n,
    }


def _measure_sustained_qps(session, ws: str) -> dict:
    """Sustained multi-query throughput through the serving layer
    (serve/scheduler.py) over the TPC-H mix, host tier.

    Closed loop: C client threads (C in 1/4/8) each run the whole mix
    BENCH_QPS_PASSES times back-to-back through ONE shared scheduler
    (max_concurrent=C) — the classic saturating-clients shape; aggregate
    QPS and per-query p50/p99 latency (queue wait included) per C, with
    the 1-client run as the serial baseline QPS. Every served result is
    verified bit-identical (`float.hex()`) to a serial reference computed
    up front, so `results_match` here feeds the artifact's top-level
    `results_match_raw`.

    Open loop: queries submitted on a fixed arrival schedule at ~1.5x the
    4-client closed-loop rate regardless of completion (the overload
    shape); reports offered vs achieved QPS, latency percentiles, and
    admission rejections (bounded run queue shedding load).

    BENCH_QPS=0 skips the section; BENCH_QPS_CLIENTS / BENCH_QPS_PASSES
    override the sweep."""
    import threading as _threading

    from hyperspace_tpu import serve
    from hyperspace_tpu.benchmark import TPCH_QUERIES

    client_counts = [
        int(c)
        for c in os.environ.get("BENCH_QPS_CLIENTS", "1,4,8").split(",")
        if c.strip()
    ]
    passes = int(os.environ.get("BENCH_QPS_PASSES", 2))
    names = list(TPCH_QUERIES)
    session.enable_hyperspace()

    def _bits(d: dict) -> str:
        return repr(
            {
                k: [x.hex() if isinstance(x, float) else x for x in v]
                for k, v in d.items()
            }
        )

    # serial reference on the exact config the served runs use (also warms
    # caches so the measured sweep is the steady serving state)
    reference = {
        name: _bits(TPCH_QUERIES[name](session, ws).to_pydict())
        for name in names
    }
    match = {"ok": True}

    def _run_client(sched, tid: int, latencies: list) -> None:
        for p in range(passes):
            off = (tid + p) % len(names)
            for name in names[off:] + names[:off]:
                t0 = time.perf_counter()
                h = sched.submit_query(
                    TPCH_QUERIES[name](session, ws), label=name
                )
                got = h.result(timeout=600)
                latencies.append(time.perf_counter() - t0)
                if _bits(got.to_pydict()) != reference[name]:
                    match["ok"] = False

    from hyperspace_tpu.telemetry.attribution import LEDGER, phase_percentiles

    closed: dict[str, dict] = {}
    for c in client_counts:
        ledger_mark = LEDGER.last_seq()
        sched = serve.QueryScheduler(
            max_concurrent=c, queue_depth=max(64, c * len(names) * passes)
        )
        per_client: list[list] = [[] for _ in range(c)]
        threads = [
            _threading.Thread(
                target=_run_client, args=(sched, i, per_client[i]),
                name=f"bench-qps-{i}",
            )
            for i in range(c)
        ]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall = time.perf_counter() - t0
        sched.shutdown(wait=True)
        lat = [x for xs in per_client for x in xs]
        closed[f"c{c}"] = {
            "clients": c,
            "queries": len(lat),
            "wall_s": round(wall, 3),
            "qps": round(len(lat) / wall, 3) if wall > 0 else 0.0,
            **_qps_stats(lat),
            # mean/p99 per phase (plan/io/upload/dispatch/fetch/fold +
            # queue/total) over exactly this tier's serving window, from
            # the per-query attribution ledger
            "phases": phase_percentiles(
                LEDGER.recent_records(since_seq=ledger_mark)
            ),
        }

    # open loop at ~1.5x the best closed-loop rate: arrivals keep coming
    # regardless of completions, so queueing (and, past the bounded run
    # queue, load shedding) is part of the measurement
    base_qps = max(
        (e["qps"] for e in closed.values()), default=1.0
    )
    offered_qps = max(0.5, 1.5 * base_qps)
    interval = 1.0 / offered_qps
    n_submit = max(12, 2 * len(names))
    ledger_mark = LEDGER.last_seq()
    sched = serve.QueryScheduler(max_concurrent=4, queue_depth=len(names))
    handles: list = []
    rejected = 0
    t0 = time.perf_counter()
    for i in range(n_submit):
        target = t0 + i * interval
        delay = target - time.perf_counter()
        if delay > 0:
            time.sleep(delay)
        name = names[i % len(names)]
        try:
            handles.append(
                (name, time.perf_counter(),
                 sched.submit_query(TPCH_QUERIES[name](session, ws),
                                    label=f"open:{name}"))
            )
        except serve.AdmissionRejected:
            rejected += 1
    lat = []
    for name, t_submit, h in handles:
        got = h.result(timeout=600)
        lat.append(time.perf_counter() - t_submit)
        if _bits(got.to_pydict()) != reference[name]:
            match["ok"] = False
    wall = time.perf_counter() - t0
    sched.shutdown(wait=True)
    session.disable_hyperspace()

    out = {
        "closed": closed,
        "open": {
            "offered_qps": round(offered_qps, 3),
            "achieved_qps": round(len(lat) / wall, 3) if wall > 0 else 0.0,
            "submitted": n_submit,
            "completed": len(lat),
            "rejected": rejected,
            **_qps_stats(lat),
            "phases": phase_percentiles(
                LEDGER.recent_records(since_seq=ledger_mark)
            ),
        },
        "passes": passes,
        "results_match": match["ok"],
    }
    if "c1" in closed and "c4" in closed and closed["c1"]["qps"] > 0:
        out["qps_scaling_c4_vs_c1"] = round(
            closed["c4"]["qps"] / closed["c1"]["qps"], 3
        )
    return out


def _measure_multi_tenant(session, ws: str) -> dict:
    """Hog-vs-light tenant isolation through the QoS scheduler: ONE hog
    tenant floods heavy join queries ahead of BENCH_TENANT_LIGHT light
    tenants submitting a cheap aggregate, through one scheduler, twice —
    QoS off (everyone on the default tenant: the pre-QoS FIFO order) and
    QoS on (per-tenant weighted-fair queues). Reports hog/light queue-wait
    p50/p99 for both legs and their ratio; with QoS on the light tenants'
    p99 queue wait must drop (they stop waiting behind the whole hog
    backlog), while every served result stays bit-identical to the serial
    reference — verified into ``results_match``. BENCH_TENANT=0 skips."""
    from hyperspace_tpu import serve
    from hyperspace_tpu.benchmark import TPCH_QUERIES
    from hyperspace_tpu.serve.tenant import TENANTS

    n_hog = int(os.environ.get("BENCH_TENANT_HOG", 10))
    n_light = int(os.environ.get("BENCH_TENANT_LIGHT", 8))
    heavy_name, light_name = "q3", "q6"
    session.enable_hyperspace()

    def _bits(d: dict) -> str:
        return repr(
            {
                k: [x.hex() if isinstance(x, float) else x for x in v]
                for k, v in d.items()
            }
        )

    reference = {
        name: _bits(TPCH_QUERIES[name](session, ws).to_pydict())
        for name in (heavy_name, light_name)
    }
    match = {"ok": True}

    def _pctls(waits_ms: list) -> dict:
        xs = sorted(waits_ms)
        if not xs:
            return {"p50_ms": 0.0, "p99_ms": 0.0}
        return {
            "p50_ms": round(xs[len(xs) // 2], 3),
            "p99_ms": round(xs[min(len(xs) - 1, int(0.99 * len(xs)))], 3),
        }

    def run_leg(use_tenants: bool) -> dict:
        sched = serve.QueryScheduler(max_concurrent=1, queue_depth=512)
        try:
            hog_handles = [
                sched.submit_query(
                    TPCH_QUERIES[heavy_name](session, ws), label="hog",
                    tenant="hog" if use_tenants else None,
                )
                for _ in range(n_hog)
            ]
            light_handles = [
                sched.submit_query(
                    TPCH_QUERIES[light_name](session, ws), label=f"light{i}",
                    tenant=f"light{i}" if use_tenants else None,
                )
                for i in range(n_light)
            ]
            hog_waits, light_waits = [], []
            for h in hog_handles:
                if _bits(h.result(600).to_pydict()) != reference[heavy_name]:
                    match["ok"] = False
                hog_waits.append(h.queue_wait_s * 1000)
            for h in light_handles:
                if _bits(h.result(600).to_pydict()) != reference[light_name]:
                    match["ok"] = False
                light_waits.append(h.queue_wait_s * 1000)
            return {
                "hog": _pctls(hog_waits),
                "light": _pctls(light_waits),
            }
        finally:
            sched.shutdown(wait=True)

    off = run_leg(use_tenants=False)
    on = run_leg(use_tenants=True)
    TENANTS.reset_for_testing()
    session.disable_hyperspace()
    out = {
        "hog_queries": n_hog,
        "light_tenants": n_light,
        "heavy_query": heavy_name,
        "light_query": light_name,
        "off": off,
        "on": on,
        "light_p99_off_ms": off["light"]["p99_ms"],
        "light_p99_on_ms": on["light"]["p99_ms"],
        "light_p50_off_ms": off["light"]["p50_ms"],
        "light_p50_on_ms": on["light"]["p50_ms"],
        "results_match": match["ok"],
    }
    if on["light"]["p99_ms"] > 0:
        out["light_p99_isolation_x"] = round(
            off["light"]["p99_ms"] / on["light"]["p99_ms"], 3
        )
    return out


def _measure_spill_join(session, ws: str) -> dict:
    """Memory-adaptive spilling join: the TPC-H join queries re-run on the
    device tier at a deliberately tiny device-memory grant
    (BENCH_SPILL_BUDGET_MB, default 0.25 MB) so every band wave exceeds
    the ledger and must park/spill instead of declining to the host tier.
    Four configurations of the SAME engine must be bit-identical
    (float.hex): unconstrained adaptive (default grant), the
    HYPERSPACE_PIPELINE=0 barrier path, the constrained (spilling) run,
    and a CONCURRENT leg pushing 2 spilling joins through one scheduler
    sharing the single device ledger. The raw (hyperspace-off) reference
    is compared under the bench's standard float tolerance — together
    these feed the section's ``results_match_raw``. BENCH_SPILL=0 skips
    the section."""
    from hyperspace_tpu import serve
    from hyperspace_tpu import constants as C
    from hyperspace_tpu.benchmark import TPCH_QUERIES
    from hyperspace_tpu.serve import budget as serve_budget
    from hyperspace_tpu.telemetry.metrics import REGISTRY

    names = [n for n in ("q3", "q10") if n in TPCH_QUERIES]
    budget_mb = os.environ.get("BENCH_SPILL_BUDGET_MB", "0.25")

    def _bits(d: dict) -> str:
        return repr(
            {
                k: [x.hex() if isinstance(x, float) else x for x in v]
                for k, v in d.items()
            }
        )

    def _close(got: dict, want: dict) -> bool:
        return list(got.keys()) == list(want.keys()) and all(
            len(got[k]) == len(want[k])
            and all(
                (abs(a - b) <= 1e-6 * max(1.0, abs(b)))
                if isinstance(a, float)
                else a == b
                for a, b in zip(got[k], want[k])
            )
            for k in got
        )

    session.disable_hyperspace()
    raw = {name: TPCH_QUERIES[name](session, ws).to_pydict() for name in names}
    session.enable_hyperspace()
    session.set_conf(C.EXEC_TPU_ENABLED, True)
    prior_budget = os.environ.get("HYPERSPACE_DEVICE_BUDGET_MB")
    prior_pipeline = os.environ.get("HYPERSPACE_PIPELINE")
    bit_ok = True
    raw_ok = True
    try:
        os.environ["HYPERSPACE_PIPELINE"] = "1"
        # ---- unconstrained adaptive: the no-spill reference --------------
        serve_budget.reset_device_budget()
        reference = {}
        t_un = 0.0
        for name in names:
            got = TPCH_QUERIES[name](session, ws).to_pydict()
            reference[name] = _bits(got)
            raw_ok = raw_ok and _close(got, raw[name])
            t, _ = _timed(lambda: TPCH_QUERIES[name](session, ws).collect(), 1)
            t_un += t
        # ---- barrier path (PIPELINE=0) at the default grant --------------
        os.environ["HYPERSPACE_PIPELINE"] = "0"
        for name in names:
            bit_ok = bit_ok and (
                _bits(TPCH_QUERIES[name](session, ws).to_pydict())
                == reference[name]
            )
        os.environ["HYPERSPACE_PIPELINE"] = "1"
        # ---- constrained: every wave over-budget -> park/spill ------------
        os.environ["HYPERSPACE_DEVICE_BUDGET_MB"] = budget_mb
        serve_budget.reset_device_budget()
        parks0 = REGISTRY.counter("join.spill.parks").value
        spills0 = REGISTRY.counter("join.spill.spills").value
        t_con = 0.0
        for name in names:
            bit_ok = bit_ok and (
                _bits(TPCH_QUERIES[name](session, ws).to_pydict())
                == reference[name]
            )
            t, _ = _timed(lambda: TPCH_QUERIES[name](session, ws).collect(), 1)
            t_con += t
        parks = REGISTRY.counter("join.spill.parks").value - parks0
        spills = REGISTRY.counter("join.spill.spills").value - spills0
        # ---- concurrent leg: 2 spilling joins share the one ledger --------
        cparks0 = REGISTRY.counter("join.spill.parks").value
        sched = serve.QueryScheduler(max_concurrent=2, queue_depth=8)
        try:
            handles = [
                sched.submit_query(
                    TPCH_QUERIES[names[0]](session, ws), label=f"spill:{i}"
                )
                for i in range(2)
            ]
            for h in handles:
                bit_ok = bit_ok and (
                    _bits(h.result(timeout=600).to_pydict())
                    == reference[names[0]]
                )
        finally:
            sched.shutdown(wait=True)
        concurrent_parks = REGISTRY.counter("join.spill.parks").value - cparks0
        acct = serve_budget.device_budget()
        ledger_drained = acct.held_bytes() == 0 and acct.check_consistency()
    finally:
        if prior_budget is None:
            os.environ.pop("HYPERSPACE_DEVICE_BUDGET_MB", None)
        else:
            os.environ["HYPERSPACE_DEVICE_BUDGET_MB"] = prior_budget
        if prior_pipeline is None:
            os.environ.pop("HYPERSPACE_PIPELINE", None)
        else:
            os.environ["HYPERSPACE_PIPELINE"] = prior_pipeline
        serve_budget.reset_device_budget()
        session.set_conf(C.EXEC_TPU_ENABLED, False)
        session.disable_hyperspace()
    return {
        "device_budget_mb": float(budget_mb),
        "queries": names,
        "unconstrained_ms": round(t_un * 1000, 1),
        "constrained_ms": round(t_con * 1000, 1),
        "spill_overhead_pct": round(100.0 * (t_con - t_un) / t_un, 1)
        if t_un > 0
        else 0.0,
        "parks": parks,
        "spills": spills,
        "concurrent_parks": concurrent_parks,
        "spilling_engaged": parks > 0 and spills > 0,
        "ledger_drained": ledger_drained,
        "bit_identical": bit_ok,
        "results_match_raw": bool(raw_ok and bit_ok and ledger_drained),
    }


def _measure_adaptive(session, ws: str) -> dict:
    """Mid-query adaptive re-optimization (HYPERSPACE_ADAPTIVE): two legs.

    TPC-H leg: the join queries re-run adaptive-on vs adaptive-off at the
    default grant. Honest footer stats mean no switch should fire, and
    adaptive-on must stay bit-identical (float.hex) to static and within
    tolerance of the raw reference — the monitoring is pure overhead
    accounting here, reported as ``adaptive_overhead_pct``.

    Planted leg: a dedicated 150k-row join fixture whose footer byte
    stats are tampered 64x low under a 2 MB grant. The static banded
    plan reserves pow2-padded band waves (~2x the decoded bytes) and
    parks on the device ledger; the adaptive run observes decoded
    actuals per bucket pair, flips banded->split (``adaptive.replan``),
    and must finish with strictly fewer parks+spills and the exact
    static bits. BENCH_ADAPT=0 skips the section."""
    import numpy as np

    from hyperspace_tpu import CoveringIndexConfig, Hyperspace
    from hyperspace_tpu import constants as C
    from hyperspace_tpu.benchmark import TPCH_QUERIES
    from hyperspace_tpu.columnar import io as cio
    from hyperspace_tpu.columnar.table import ColumnBatch
    from hyperspace_tpu.plan import Count, Max, Min, col, lit
    from hyperspace_tpu.plan import join_memory
    from hyperspace_tpu.serve import budget as serve_budget
    from hyperspace_tpu.telemetry.metrics import REGISTRY

    names = [n for n in ("q3", "q10") if n in TPCH_QUERIES]

    def _bits(d: dict) -> str:
        return repr(
            {
                k: [x.hex() if isinstance(x, float) else x for x in v]
                for k, v in d.items()
            }
        )

    def _close(got: dict, want: dict) -> bool:
        return list(got.keys()) == list(want.keys()) and all(
            len(got[k]) == len(want[k])
            and all(
                (abs(a - b) <= 1e-6 * max(1.0, abs(b)))
                if isinstance(a, float)
                else a == b
                for a, b in zip(got[k], want[k])
            )
            for k in got
        )

    def _cnt(name: str) -> float:
        return REGISTRY.counter(name).value

    def _switches() -> float:
        return (
            _cnt("adaptive.replan")
            + _cnt("adaptive.reorder")
            + _cnt("adaptive.abort")
        )

    session.disable_hyperspace()
    raw = {name: TPCH_QUERIES[name](session, ws).to_pydict() for name in names}
    session.enable_hyperspace()
    session.set_conf(C.EXEC_TPU_ENABLED, True)
    prior_env = {
        k: os.environ.get(k)
        for k in (
            "HYPERSPACE_ADAPTIVE",
            "HYPERSPACE_DEVICE_BUDGET_MB",
            "HYPERSPACE_JOIN_BROADCAST_ROWS",
            "HYPERSPACE_PARK_WAIT_MS",
            "HYPERSPACE_ADAPTIVE_WARMUP_CHUNKS",
        )
    }
    prior_buckets = session.conf.num_buckets
    real_estimates = join_memory._bucket_estimates
    raw_ok = True
    try:
        # ---- TPC-H leg: honest stats, default grant ----------------------
        os.environ["HYPERSPACE_ADAPTIVE"] = "0"
        reference = {}
        t_static = 0.0
        for name in names:
            got = TPCH_QUERIES[name](session, ws).to_pydict()
            reference[name] = _bits(got)
            raw_ok = raw_ok and _close(got, raw[name])
            t, _ = _timed(lambda: TPCH_QUERIES[name](session, ws).collect(), 1)
            t_static += t
        os.environ["HYPERSPACE_ADAPTIVE"] = "1"
        sw0 = _switches()
        tpch_bits = True
        t_adapt = 0.0
        for name in names:
            tpch_bits = tpch_bits and (
                _bits(TPCH_QUERIES[name](session, ws).to_pydict())
                == reference[name]
            )
            t, _ = _timed(lambda: TPCH_QUERIES[name](session, ws).collect(), 1)
            t_adapt += t
        tpch = {
            "queries": names,
            "static_ms": round(t_static * 1000, 1),
            "adaptive_ms": round(t_adapt * 1000, 1),
            "adaptive_overhead_pct": round(
                100.0 * (t_adapt - t_static) / t_static, 1
            )
            if t_static > 0
            else 0.0,
            "switches": _switches() - sw0,
            "bit_identical": tpch_bits,
        }

        # ---- planted leg: tampered footer stats, tight grant -------------
        rng = np.random.default_rng(7)
        n_join = 150_000
        cio.write_parquet(
            ColumnBatch.from_pydict(
                {
                    "k": rng.integers(0, 600, n_join).tolist(),
                    "p": rng.uniform(0, 100, n_join).tolist(),
                }
            ),
            os.path.join(ws, "adapt_l", "l.parquet"),
        )
        cio.write_parquet(
            ColumnBatch.from_pydict(
                {
                    "rk": list(range(500)),
                    "w": rng.uniform(size=500).tolist(),
                }
            ),
            os.path.join(ws, "adapt_r", "r.parquet"),
        )
        hs = Hyperspace(session)
        session.set_conf(C.INDEX_NUM_BUCKETS, 4)
        hs.create_index(
            session.read.parquet(os.path.join(ws, "adapt_l")),
            CoveringIndexConfig("bench_adapt_l", ["k"], ["p"]),
        )
        hs.create_index(
            session.read.parquet(os.path.join(ws, "adapt_r")),
            CoveringIndexConfig("bench_adapt_r", ["rk"], ["w"]),
        )
        join_memory._bucket_estimates = lambda side, b: (
            lambda r, nb: (r, nb / 64.0)
        )(*real_estimates(side, b))
        os.environ["HYPERSPACE_JOIN_BROADCAST_ROWS"] = "10"
        os.environ["HYPERSPACE_DEVICE_BUDGET_MB"] = "2.0"
        # A parked wave waits the full HYPERSPACE_PARK_WAIT_MS for other
        # queries' releases before the zero-holder force grant, so the knob
        # IS the wall-clock price of a park on this single-query fixture.
        # Model a contended serving window rather than the near-free 1 ms
        # the smoke test uses to stay fast.
        park_wait_ms = 2000
        os.environ["HYPERSPACE_PARK_WAIT_MS"] = str(park_wait_ms)
        os.environ["HYPERSPACE_ADAPTIVE_WARMUP_CHUNKS"] = "1"
        serve_budget.reset_device_budget()

        def planted_q():
            l = session.read.parquet(os.path.join(ws, "adapt_l")).select(
                "k", "p"
            )
            r = session.read.parquet(os.path.join(ws, "adapt_r")).select(
                "rk", "w"
            )
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

        # Per mode: one cold run brackets the park/spill/flip counter deltas
        # (exactly one execution between the reads — _timed would warm first
        # and double-count), then one warm run is timed so neither leg is
        # charged for first-shape compilation.
        os.environ["HYPERSPACE_ADAPTIVE"] = "0"
        parks0, spills0 = _cnt("join.spill.parks"), _cnt("join.spill.spills")
        static_got = planted_q()
        static_parks = _cnt("join.spill.parks") - parks0
        static_spills = _cnt("join.spill.spills") - spills0
        t0 = time.time()
        planted_q()
        t_pstatic = time.time() - t0

        os.environ["HYPERSPACE_ADAPTIVE"] = "1"
        parks0, spills0 = _cnt("join.spill.parks"), _cnt("join.spill.spills")
        flips0 = _cnt("adaptive.replan")
        adaptive_got = planted_q()
        adapt_parks = _cnt("join.spill.parks") - parks0
        adapt_spills = _cnt("join.spill.spills") - spills0
        flips = _cnt("adaptive.replan") - flips0
        t0 = time.time()
        planted_q()
        t_padapt = time.time() - t0

        planted_bits = _bits(adaptive_got) == _bits(static_got)
        fewer = (adapt_parks + adapt_spills) < (static_parks + static_spills)
        planted = {
            "rows": n_join,
            "device_budget_mb": 2.0,
            "park_wait_ms": park_wait_ms,
            "static_ms": round(t_pstatic * 1000, 1),
            "adaptive_ms": round(t_padapt * 1000, 1),
            "adaptive_speedup": round(t_pstatic / max(t_padapt, 1e-9), 2),
            "flips": flips,
            "static_parks": static_parks,
            "static_spills": static_spills,
            "adaptive_parks": adapt_parks,
            "adaptive_spills": adapt_spills,
            "bit_identical": planted_bits,
            "fewer_parks_and_spills": fewer,
        }
    finally:
        join_memory._bucket_estimates = real_estimates
        session.set_conf(C.INDEX_NUM_BUCKETS, prior_buckets)
        for k, v in prior_env.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        serve_budget.reset_device_budget()
        session.set_conf(C.EXEC_TPU_ENABLED, False)
        session.disable_hyperspace()
    return {
        "tpch": tpch,
        "planted": planted,
        "results_match_raw": bool(
            raw_ok
            and tpch_bits
            and planted_bits
            and fewer
            and flips >= 1
        ),
    }


def _measure_cached_qps(session, ws: str) -> dict:
    """Repeat-heavy serving with the snapshot-keyed result cache
    (cache/result_cache.py): the dashboard-workload shape where the same
    query templates repeat against a slowly-advancing lake.

    Two closed-loop tiers through one scheduler over the TPC-H mix:
    ``cold`` (HYPERSPACE_RESULT_CACHE=0 — every repeat re-executes, the
    PR-8 serving baseline) and ``warm`` (=1 — a populate pass, then the
    measured repeats hit the cache). Every served result, hit or computed,
    is verified bit-identical to the cold reference and ANDed into the
    artifact's ``results_match_raw``. The headline is repeat-query p50
    warm vs cold plus the measured hit ratio.

    A freshness leg then runs a small append stream into a dedicated live
    table WITH the cache on: a prober polls a fold-eligible count through
    the serving path, so the recorded freshness lag proves caching does
    not delay visibility (every publish changes the snapshot key; the
    incremental-view path answers at delta cost — ``folds`` counts it
    engaging). BENCH_CACHED=0 skips the section."""
    import threading as _threading

    import numpy as np

    from hyperspace_tpu import CoveringIndexConfig, Hyperspace, ingest, serve
    from hyperspace_tpu.benchmark import TPCH_QUERIES
    from hyperspace_tpu.cache.result_cache import RESULT_CACHE
    from hyperspace_tpu.cache.view_maintenance import refresh_idle
    from hyperspace_tpu.columnar import io as cio
    from hyperspace_tpu.columnar.table import ColumnBatch
    from hyperspace_tpu.plan import Count, col, lit
    from hyperspace_tpu.telemetry.metrics import REGISTRY

    clients = int(os.environ.get("BENCH_CACHED_CLIENTS", 4))
    passes = int(os.environ.get("BENCH_CACHED_PASSES", 3))
    batches = int(os.environ.get("BENCH_CACHED_BATCHES", 4))
    batch_rows = int(os.environ.get("BENCH_CACHED_ROWS", 10_000))
    names = list(TPCH_QUERIES)
    session.enable_hyperspace()
    prev_mode = os.environ.get("HYPERSPACE_RESULT_CACHE")

    def _bits(d: dict) -> str:
        return repr(
            {
                k: [x.hex() if isinstance(x, float) else x for x in v]
                for k, v in d.items()
            }
        )

    def _val(n: str) -> float:
        m = REGISTRY.get(n)
        return 0 if m is None else m.value

    match = {"ok": True}
    os.environ["HYPERSPACE_RESULT_CACHE"] = "0"
    reference = {
        name: _bits(TPCH_QUERIES[name](session, ws).to_pydict())
        for name in names
    }

    def _run_tier(mode: str) -> dict:
        os.environ["HYPERSPACE_RESULT_CACHE"] = mode
        RESULT_CACHE.clear()
        sched = serve.QueryScheduler(
            max_concurrent=clients,
            queue_depth=max(64, clients * len(names) * (passes + 1)),
        )
        if mode == "1":
            # populate pass: the first run of each template is the miss
            for name in names:
                sched.submit_query(
                    TPCH_QUERIES[name](session, ws), label=f"pop:{name}"
                ).result(timeout=600)
        h0, m0 = _val("cache.result.hits"), _val("cache.result.misses")
        lat: list[float] = []
        lock = _threading.Lock()

        def client(tid: int) -> None:
            for p in range(passes):
                off = (tid + p) % len(names)
                for name in names[off:] + names[:off]:
                    t0 = time.perf_counter()
                    h = sched.submit_query(
                        TPCH_QUERIES[name](session, ws), label=name
                    )
                    got = h.result(timeout=600)
                    dt = time.perf_counter() - t0
                    with lock:
                        lat.append(dt)
                    if _bits(got.to_pydict()) != reference[name]:
                        match["ok"] = False

        threads = [
            _threading.Thread(target=client, args=(i,), name=f"bench-rc-{i}")
            for i in range(clients)
        ]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall = time.perf_counter() - t0
        sched.shutdown(wait=True)
        hits = _val("cache.result.hits") - h0
        misses = _val("cache.result.misses") - m0
        looked = hits + misses
        return {
            "queries": len(lat),
            "wall_s": round(wall, 3),
            "qps": round(len(lat) / wall, 3) if wall > 0 else 0.0,
            **_qps_stats(lat),
            "hits": int(hits),
            "misses": int(misses),
            "hit_ratio": round(hits / looked, 4) if looked else 0.0,
        }

    cold = _run_tier("0")
    warm = _run_tier("1")

    # --- freshness under ingest WITH the cache on -------------------------
    def _batch(seed: int) -> dict:
        r = np.random.default_rng(900 + seed)
        return {
            "k": r.integers(0, 128, batch_rows).tolist(),
            "v": r.integers(0, 10_000, batch_rows).tolist(),
        }

    ev = os.path.join(ws, "events_cached")
    cio.write_parquet(
        ColumnBatch.from_pydict(_batch(0)), os.path.join(ev, "part0.parquet")
    )
    hs = Hyperspace(session)
    hs.create_index(
        session.read.parquet(ev), CoveringIndexConfig("ev_cached", ["k"], ["v"])
    )
    folds0 = _val("cache.result.folds")
    sched = serve.QueryScheduler(max_concurrent=2, queue_depth=64)
    publishes: list[tuple[float, int]] = []
    observed: list[tuple[float, int]] = []
    total0 = batch_rows
    ingest_done = _threading.Event()

    def ingester() -> None:
        try:
            for k in range(1, batches + 1):
                ingest.append_batch(session, "ev_cached", _batch(k))
                publishes.append((time.perf_counter(), total0 + k * batch_rows))
        finally:
            ingest_done.set()

    def prober() -> None:
        """Counts the latest stable snapshot's rows through the serving
        path WITH the cache on (reading the entry's recorded file listing,
        never a directory mid-write): every publish changes the snapshot
        key, so a cached plane must still see fresh rows immediately —
        the foldable count answers each advance at delta cost."""
        target = total0 + batches * batch_rows
        while True:
            entry = ingest.latest_stable_entry(session, "ev_cached")
            files = [f.name for f in entry.relation.content.file_infos()]
            df = session.read.parquet(files)
            # the (always-true) filter makes this the rewritable
            # filter-aggregate fragment: the probe runs over the INDEX,
            # pins the snapshot, and folds across appends
            h = sched.submit_query(
                df.filter(df["k"] >= 0).agg(Count(lit(1)).alias("n")),
                label="cached:freshness",
            )
            n = int(h.result(timeout=600).to_pydict()["n"][0])
            observed.append((time.perf_counter(), n))
            if ingest_done.is_set() and n >= target:
                return

    ing = _threading.Thread(target=ingester, name="bench-rc-ingester")
    probe = _threading.Thread(target=prober, name="bench-rc-prober")
    ing.start()
    probe.start()
    ing.join()
    probe.join()
    sched.drain(timeout=120)
    sched.shutdown(wait=True)
    deadline = time.monotonic() + 120
    while time.monotonic() < deadline and not (
        refresh_idle() and ingest.maintenance_idle()
    ):
        time.sleep(0.05)
    lags: list[float] = []
    for t_pub, total in publishes:
        seen = [t for t, n in observed if n >= total and t >= t_pub]
        if seen:
            lags.append(min(seen) - t_pub)
    lag_stats = _qps_stats(lags)
    folds = _val("cache.result.folds") - folds0

    RESULT_CACHE.clear()
    if prev_mode is None:
        os.environ.pop("HYPERSPACE_RESULT_CACHE", None)
    else:
        os.environ["HYPERSPACE_RESULT_CACHE"] = prev_mode
    session.disable_hyperspace()

    out = {
        "clients": clients,
        "passes": passes,
        "cold": cold,
        "warm": warm,
        "cold_p50_ms": cold.get("p50_ms"),
        "warm_p50_ms": warm.get("p50_ms"),
        "hit_ratio": warm["hit_ratio"],
        "freshness_p50_ms": lag_stats.get("p50_ms"),
        "freshness_max_ms": lag_stats.get("max_ms"),
        "freshness_samples": len(lags),
        "folds": int(folds),
        "results_match": match["ok"],
    }
    if cold.get("p50_ms") and warm.get("p50_ms"):
        out["repeat_speedup_p50"] = round(
            cold["p50_ms"] / max(warm["p50_ms"], 1e-9), 3
        )
    return out


def _measure_ingest_rw(session, ws: str) -> dict:
    """Mixed read/write serving: sustained ingest into a live covering
    index while concurrent TPC-H queries run through the scheduler.

    An ingester thread appends ``BENCH_INGEST_BATCHES`` seeded batches
    (``BENCH_INGEST_ROWS`` rows each) into a dedicated ``events`` table via
    ``ingest.append_batch`` — each append an atomic snapshot publish, with
    background compaction + refcount-gated vacuum riding the shared IO
    pool. Meanwhile ``BENCH_INGEST_CLIENTS`` closed-loop clients run a
    TPC-H query mix through one QueryScheduler; the same client load runs
    once WITHOUT ingest first, so the artifact carries query p50/p99 both
    ways (the cost of writes under the read path). A freshness prober
    polls the latest stable snapshot and counts its rows through the index:
    per batch, freshness lag = commit -> first query whose result contains
    the batch. Compaction/vacuum engagement lands as ingest.* counter
    deltas. BENCH_INGEST=0 skips the section."""
    import threading as _threading

    import numpy as np

    from hyperspace_tpu import CoveringIndexConfig, Hyperspace, ingest, serve
    from hyperspace_tpu.benchmark import TPCH_QUERIES
    from hyperspace_tpu.columnar import io as cio
    from hyperspace_tpu.columnar.table import ColumnBatch
    from hyperspace_tpu.plan import Count, col, lit
    from hyperspace_tpu.telemetry.metrics import REGISTRY

    batches = int(os.environ.get("BENCH_INGEST_BATCHES", 8))
    batch_rows = int(os.environ.get("BENCH_INGEST_ROWS", 25_000))
    clients = int(os.environ.get("BENCH_INGEST_CLIENTS", 2))
    mix = [n for n in ("q1", "q6", "q14") if n in TPCH_QUERIES]

    def _batch(seed: int) -> dict:
        r = np.random.default_rng(500 + seed)
        return {
            "k": r.integers(0, 256, batch_rows).tolist(),
            "v": r.integers(0, 10_000, batch_rows).tolist(),
            "w": r.random(batch_rows).tolist(),
        }

    ev = os.path.join(ws, "events")
    cio.write_parquet(
        ColumnBatch.from_pydict(_batch(0)), os.path.join(ev, "part0.parquet")
    )
    hs = Hyperspace(session)
    hs.create_index(
        session.read.parquet(ev), CoveringIndexConfig("ev_ingest", ["k"], ["v", "w"])
    )
    session.enable_hyperspace()

    def _counters() -> dict:
        return {
            k: v
            for k, v in REGISTRY.snapshot().items()
            if k.startswith("ingest.") and isinstance(v, (int, float))
        }

    def _run_clients(sched, stop: "_threading.Event | None", passes: int):
        """Closed-loop TPC-H mix; with ``stop``, loop until it fires."""
        lat: list[float] = []
        lock = _threading.Lock()

        def client(tid: int) -> None:
            p = 0
            while True:
                if stop is not None and stop.is_set():
                    return
                if stop is None and p >= passes:
                    return
                name = mix[(tid + p) % len(mix)]
                t0 = time.perf_counter()
                h = sched.submit_query(
                    TPCH_QUERIES[name](session, ws), label=f"rw:{name}"
                )
                h.result(timeout=600)
                with lock:
                    lat.append(time.perf_counter() - t0)
                p += 1

        threads = [
            _threading.Thread(target=client, args=(i,), name=f"bench-rw-{i}")
            for i in range(clients)
        ]
        for t in threads:
            t.start()
        return threads, lat

    # --- baseline: same client load, no ingest ---------------------------
    sched = serve.QueryScheduler(max_concurrent=clients, queue_depth=64)
    threads, base_lat = _run_clients(sched, None, passes=3)
    for t in threads:
        t.join()
    sched.shutdown(wait=True)

    # --- under ingest -----------------------------------------------------
    sched = serve.QueryScheduler(max_concurrent=clients + 1, queue_depth=64)
    c0 = _counters()
    publishes: list[tuple[float, int]] = []  # (t_commit, cumulative rows)
    observed: list[tuple[float, int]] = []  # (t_result, visible rows)
    ingest_done = _threading.Event()
    total0 = batch_rows  # the seed part

    def ingester() -> None:
        try:
            for k in range(1, batches + 1):
                ingest.append_batch(session, "ev_ingest", _batch(k))
                publishes.append((time.perf_counter(), total0 + k * batch_rows))
        finally:
            ingest_done.set()

    def prober() -> None:
        """Counts the latest stable snapshot's rows THROUGH the serving
        path; each sample is (completion time, rows the query saw)."""
        while not (ingest_done.is_set() and observed and
                   observed[-1][1] >= total0 + batches * batch_rows):
            entry = ingest.latest_stable_entry(session, "ev_ingest")
            files = [f.name for f in entry.relation.content.file_infos()]
            df = session.read.parquet(files)
            h = sched.submit_query(
                df.agg(Count(lit(1)).alias("n")), label="rw:freshness"
            )
            n = int(h.result(timeout=600).to_pydict()["n"][0])
            observed.append((time.perf_counter(), n))
            if ingest_done.is_set() and n >= total0 + batches * batch_rows:
                return

    threads, ingest_lat = _run_clients(sched, ingest_done, passes=0)
    ing = _threading.Thread(target=ingester, name="bench-rw-ingester")
    probe = _threading.Thread(target=prober, name="bench-rw-prober")
    t_start = time.perf_counter()
    ing.start()
    probe.start()
    ing.join()
    probe.join()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t_start
    sched.drain(timeout=120)
    sched.shutdown(wait=True)

    # drain background maintenance so the counter deltas are complete
    deadline = time.monotonic() + 120
    while time.monotonic() < deadline and not ingest.maintenance_idle():
        time.sleep(0.05)
    delta = {
        k: round(v - c0.get(k, 0), 3)
        for k, v in _counters().items()
        if v != c0.get(k, 0)
    }
    session.disable_hyperspace()

    # freshness lag per batch: commit -> first probe that saw its rows
    lags: list[float] = []
    for t_pub, total in publishes:
        seen = [t for t, n in observed if n >= total and t >= t_pub]
        if seen:
            lags.append(min(seen) - t_pub)
    lag_stats = _qps_stats(lags)
    return {
        "batches": batches,
        "batch_rows": batch_rows,
        "clients": clients,
        "wall_s": round(wall, 3),
        "rows_ingested": batches * batch_rows,
        "ingest_rows_per_s": (
            round(batches * batch_rows / wall, 1) if wall > 0 else 0.0
        ),
        "freshness_p50_ms": lag_stats.get("p50_ms"),
        "freshness_max_ms": lag_stats.get("max_ms"),
        "freshness_samples": len(lags),
        "baseline_p50_ms": _qps_stats(base_lat).get("p50_ms"),
        "baseline_p99_ms": _qps_stats(base_lat).get("p99_ms"),
        "under_ingest_p50_ms": _qps_stats(ingest_lat).get("p50_ms"),
        "under_ingest_p99_ms": _qps_stats(ingest_lat).get("p99_ms"),
        "queries_under_ingest": len(ingest_lat),
        "counters": delta,
    }


def _measure_hybrid_refresh(session, hs, ws: str, repeats: int) -> dict:
    """BASELINE.md config 4: append parquet files to lineitem, run Q3 with
    Hybrid Scan serving the stale index (appended rows re-bucketed on the
    fly), then time the incremental refresh and the post-refresh query."""
    import numpy as np

    from hyperspace_tpu import constants as C
    from hyperspace_tpu.benchmark import TPCH_QUERIES
    from hyperspace_tpu.columnar import io as cio
    from hyperspace_tpu.columnar.table import ColumnBatch

    rng = np.random.default_rng(7)
    n = 50_000
    append = {
        "l_orderkey": rng.integers(0, 1_000_000, n).tolist(),
        "l_partkey": rng.integers(0, 10_000, n).tolist(),
        "l_suppkey": rng.integers(0, 2_500, n).tolist(),
        "l_quantity": rng.integers(1, 51, n).astype(float).tolist(),
        "l_extendedprice": rng.uniform(900, 105_000, n).tolist(),
        "l_discount": np.round(rng.uniform(0, 0.1, n), 2).tolist(),
        "l_tax": np.round(rng.uniform(0, 0.08, n), 2).tolist(),
        "l_returnflag": rng.choice(["A", "N", "R"], n).tolist(),
        "l_linestatus": rng.choice(["O", "F"], n).tolist(),
        "l_shipdate": rng.integers(8035, 10590, n).astype("int32").tolist(),
    }
    cio.write_parquet(
        ColumnBatch.from_pydict(append),
        os.path.join(ws, "lineitem", "part-append.parquet"),
    )
    session.set_conf(C.HYBRID_SCAN_ENABLED, True)
    session.enable_hyperspace()
    q3 = lambda: TPCH_QUERIES["q3"](session, ws).collect()
    t_hybrid, hybrid_stats = _timed(q3, repeats)
    from hyperspace_tpu.exceptions import NoChangesError

    t0 = time.time()
    for name in ("li_orderkey", "od_orderkey"):
        try:
            hs.refresh_index(name, "incremental")
        except NoChangesError:
            pass  # orders unchanged: expected; real failures must surface
    refresh_s = time.time() - t0
    t_after, after_stats = _timed(q3, repeats)
    session.disable_hyperspace()
    session.set_conf(C.HYBRID_SCAN_ENABLED, False)
    return {
        "q3_hybrid_ms": round(t_hybrid * 1000, 1),
        "q3_hybrid_stats": hybrid_stats,
        "refresh_incremental_s": round(refresh_s, 2),
        "q3_after_refresh_ms": round(t_after * 1000, 1),
        "q3_after_refresh_stats": after_stats,
    }


def _measure_bloom_skipping(session, ws: str, rows: int, repeats: int) -> dict:
    """BASELINE.md config 5: BloomFilterSketch data skipping over a
    store_sales-shaped table (high-cardinality int keys across many files);
    point lookups skip files whose bloom filter rejects the key."""
    import numpy as np

    from hyperspace_tpu import BloomFilterSketch, DataSkippingIndexConfig, Hyperspace
    from hyperspace_tpu.columnar import io as cio
    from hyperspace_tpu.columnar.table import ColumnBatch
    from hyperspace_tpu.plan import Count, Sum, col, lit

    rng = np.random.default_rng(11)
    # sized so the raw side is signal (>=100ms), capped so scale runs stay
    # bounded. 256 files is the shape the sketch exists for: the raw side
    # pays a footer read + stats check per file, the bloom index drops the
    # files BEFORE any IO (ref: BloomFilterSketch.scala:47-87 targets
    # many-file tables).
    n = max(2_000_000, min(rows, 16_000_000))
    n_files = 256
    per = n // n_files
    ss = os.path.join(ws, "store_sales")
    for i in range(n_files):
        data = {
            # item keys are file-local ranges: realistic ingest clustering,
            # so bloom filters reject most files for a point key
            "ss_item_sk": rng.integers(i * 100_000, (i + 1) * 100_000, per).tolist(),
            "ss_net_paid": rng.uniform(1, 300, per).tolist(),
        }
        cio.write_parquet(
            ColumnBatch.from_pydict(data), os.path.join(ss, f"part-{i:02d}.parquet")
        )
    hs = Hyperspace(session)
    df = session.read.parquet(ss)
    t0 = time.time()
    hs.create_index(
        df,
        DataSkippingIndexConfig(
            "ss_bloom", [BloomFilterSketch("ss_item_sk", per, 0.01)]
        ),
    )
    build_s = time.time() - t0
    key = int(rng.integers(3 * 100_000, 4 * 100_000))
    q = lambda: (
        session.read.parquet(ss)
        .filter(col("ss_item_sk") == key)
        .agg(Sum(col("ss_net_paid")).alias("s"), Count(lit(1)).alias("n"))
        .collect()
    )
    t_raw, raw_stats = _timed(q, repeats)
    session.enable_hyperspace()
    t_idx, idx_stats = _timed(q, repeats)
    session.disable_hyperspace()
    return {
        "rows": n,
        "files": n_files,
        "index_build_s": round(build_s, 2),
        "raw_ms": round(t_raw * 1000, 1),
        "raw_stats": raw_stats,
        "indexed_ms": round(t_idx * 1000, 1),
        "indexed_stats": idx_stats,
        "speedup": round(t_raw / t_idx, 3) if t_idx > 0 else 0.0,
    }


def main() -> None:
    t_start = time.time()
    # kernel-audit every cache miss by default: a clean artifact must
    # report zero jaxpr hazards and zero retrace-storm warnings
    # (BENCH_KERNEL_AUDIT=0 opts out; audit never alters kernel behavior)
    if os.environ.get("BENCH_KERNEL_AUDIT", "1") == "1":
        os.environ.setdefault("HYPERSPACE_KERNEL_AUDIT", "1")
    # verify every optimized plan's structural invariants: a violation
    # raises PlanInvariantError (failing the bench loudly), so a finished
    # artifact proves plan_violations == 0 (BENCH_VERIFY_PLAN=0 opts out)
    if os.environ.get("BENCH_VERIFY_PLAN", "1") == "1":
        os.environ.setdefault("HYPERSPACE_VERIFY_PLAN", "1")
    # audit lock acquisition order by default: a nesting that closes a
    # cycle in the order graph raises LockOrderError (failing the bench
    # loudly), so a finished artifact proves lock_violations == 0
    # (BENCH_LOCK_AUDIT=0 opts out; the audit never alters behavior)
    if os.environ.get("BENCH_LOCK_AUDIT", "1") == "1":
        os.environ.setdefault("HYPERSPACE_LOCK_AUDIT", "1")
    # a device failure raises instead of degrading to the host tier, so a
    # finished artifact's device numbers were measured on the device
    os.environ.setdefault("HYPERSPACE_DEVICE_STRICT", "1")
    rows = int(os.environ.get("BENCH_ROWS", 4_000_000))
    repeats = int(os.environ.get("BENCH_REPEATS", 3))

    # the device belongs to this process: JAX initializes here, once, and
    # an init that fails ends the run
    import jax

    from hyperspace_tpu.utils.backend import enable_compile_cache

    devices = jax.devices()
    backend = devices[0].platform
    device = {
        "platform": backend,
        "device_kind": devices[0].device_kind,
        "count": len(devices),
    }
    compile_cache_dir = enable_compile_cache()

    # --profile: trace every query into a JSONL artifact (one span per line;
    # read with tools/trace_report.py). Timings measured under --profile
    # carry the (small) tracing overhead — the artifact says so.
    profile_path = None
    if "--profile" in sys.argv:
        from hyperspace_tpu.telemetry import trace as _trace

        profile_path = os.environ.get("BENCH_PROFILE_FILE", "BENCH_profile.jsonl")
        if os.path.exists(profile_path):
            os.remove(profile_path)
        _trace.enable(_trace.JsonlTraceSink(profile_path))

    import tempfile

    from hyperspace_tpu import Hyperspace, HyperspaceSession
    from hyperspace_tpu import constants as C
    from hyperspace_tpu.benchmark import TPCH_QUERIES, generate_tpch, tpch_indexes

    ws = tempfile.mkdtemp(prefix="hs_bench_")
    sizes = generate_tpch(ws, rows_lineitem=rows, seed=42)
    source_mb = sum(sizes.values()) / 1e6

    session = HyperspaceSession(warehouse_dir=ws)
    session.set_conf(C.INDEX_NUM_BUCKETS, 8)
    session.set_conf(C.EXEC_TPU_ENABLED, False)  # host paths first
    session.set_conf(C.ZORDER_TARGET_SOURCE_BYTES_PER_PARTITION, 8 * 1024 * 1024)
    index_format = os.environ.get("BENCH_INDEX_FORMAT", "parquet")
    session.set_conf(C.INDEX_FORMAT, index_format)
    build_budget_mb = os.environ.get("BENCH_MAX_BUILD_MB")
    if build_budget_mb:  # scale runs force streaming file-group builds
        session.set_conf(
            C.BUILD_MAX_BYTES_IN_MEMORY, int(build_budget_mb) * 1024 * 1024
        )
    hs = Hyperspace(session)

    t0 = time.time()
    tpch_indexes(session, hs, ws)
    build_s = time.time() - t0
    # bytes actually indexed: lineitem is sliced by four indexes
    indexed_bytes = 4 * sizes["lineitem"] + sizes["orders"] + sizes["part"]
    build_gbps = indexed_bytes / build_s / 1e9

    from hyperspace_tpu.benchmark.external import PANDAS_TPCH

    # ---- host-path measurements (no device dependency) -------------------
    results: dict[str, dict] = {}
    correct = True
    expected_results = {}
    for name, q in TPCH_QUERIES.items():
        with _bench_span(f"host:{name}"):
            session.disable_hyperspace()
            expected = q(session, ws).to_pydict()
            expected_results[name] = expected
            t_raw, raw_stats = _timed(lambda: q(session, ws).collect(), repeats)
            session.enable_hyperspace()
            got, prune_delta = _prefix_counter_delta(
                lambda: q(session, ws).to_pydict(), "pruning."
            )
            t_idx, idx_stats = _timed(lambda: q(session, ws).collect(), repeats)
            session.disable_hyperspace()
            t_ext, ext_stats = _timed(lambda: PANDAS_TPCH[name](ws), repeats)
        ok = list(got.keys()) == list(expected.keys()) and all(
            len(got[k]) == len(expected[k])
            and all(
                (abs(a - b) <= 1e-6 * max(1.0, abs(b)))
                if isinstance(a, float)
                else a == b
                for a, b in zip(got[k], expected[k])
            )
            for k in got
        )
        correct = correct and ok
        results[name] = {
            "raw_ms": round(t_raw * 1000, 1),
            "raw_stats": raw_stats,
            "indexed_hostexec_ms": round(t_idx * 1000, 1),
            "indexed_hostexec_stats": idx_stats,
            "external_pandas_ms": round(t_ext * 1000, 1),
            "external_stats": ext_stats,
        }
        if prune_delta:
            # per-query index-pruning engagement (files/row groups kept vs
            # total, bytes never decoded) — diffed by tools/bench_compare.py
            results[name]["pruning"] = prune_delta

    # ---- device sections -------------------------------------------------
    # BEFORE the hybrid-refresh section, which MUTATES lineitem (appends +
    # incremental refresh) — device runs must see the same dataset the host
    # expectations were computed on. A device failure fails the run.
    host_wall_s = round(time.time() - t_start, 1)
    session.set_conf(C.EXEC_TPU_ENABLED, True)
    for name, q in TPCH_QUERIES.items():
        entry = results[name]
        # same-engine, same-tier correctness: the index must not
        # change answers. (Cross-tier f32-vs-f64 accumulation is a
        # documented property of the device tier — see
        # hyperspace.tpu.exec.exactF64Aggregates.)
        with _bench_span(f"device:{name}"):
            session.disable_hyperspace()
            expected_dev = q(session, ws).to_pydict()
            t_raw_dev, _ = _timed(lambda: q(session, ws).collect(), 1)
            entry["raw_device_ms"] = round(t_raw_dev * 1000, 1)
            session.enable_hyperspace()
            got = q(session, ws).to_pydict()
            t_dev, dev_stats = _timed(
                lambda: q(session, ws).collect(), repeats
            )
            rpc = _rpc_delta(lambda: q(session, ws).collect())
            if name in ("q3", "q10", "q17", "q18"):
                # join-pipeline engagement for this query: bucket
                # pairs streamed, band waves, splits, pad savings
                entry["join_pipeline"] = _join_counter_delta(
                    lambda: q(session, ws).collect()
                )
        session.disable_hyperspace()
        ok = list(got.keys()) == list(expected_dev.keys()) and all(
            len(got[k]) == len(expected_dev[k])
            and all(
                (abs(a - b) <= 1e-6 * max(1.0, abs(b)))
                if isinstance(a, float)
                else a == b
                for a, b in zip(got[k], expected_dev[k])
            )
            for k in got
        )
        correct = correct and ok
        entry["device_match"] = ok
        entry["indexed_device_ms"] = round(t_dev * 1000, 1)
        entry["indexed_device_stats"] = dev_stats
        entry["device_rpc"] = rpc
    session.set_conf(C.EXEC_TPU_ENABLED, False)

    # ---- index-pruning point lookup (non-mutating) -----------------------
    with _bench_span("point_lookup"):
        point = _measure_point_lookup(session, ws, repeats)

    # ---- per-row-group sketch pruning on non-sort columns (own table; ----
    # non-mutating for TPC-H inputs) ---------------------------------------
    sketch = None
    if os.environ.get("BENCH_SKETCH", "1") == "1":
        with _bench_span("sketch_prune"):
            sketch = _measure_sketch_prune(session, ws, rows, repeats)
        correct = correct and sketch["results_match"]

    # ---- sustained QPS under concurrent serving (non-mutating; must run --
    # BEFORE the hybrid-refresh section mutates lineitem) ------------------
    qps = None
    if os.environ.get("BENCH_QPS", "1") == "1":
        with _bench_span("sustained_qps"):
            qps = _measure_sustained_qps(session, ws)
        correct = correct and qps["results_match"]

    # ---- multi-tenant QoS: hog-vs-light isolation (non-mutating) ---------
    tenant_qos = None
    if os.environ.get("BENCH_TENANT", "1") == "1":
        with _bench_span("multi_tenant"):
            tenant_qos = _measure_multi_tenant(session, ws)
        correct = correct and tenant_qos["results_match"]

    # ---- memory-adaptive spilling join: over-budget device grant ---------
    # (non-mutating; device tier — must run BEFORE hybrid-refresh mutates)
    spill = None
    if os.environ.get("BENCH_SPILL", "1") == "1":
        with _bench_span("spill_join"):
            spill = _measure_spill_join(session, ws)
        correct = correct and spill["results_match_raw"]

    # ---- mid-query adaptive re-optimization: static vs adaptive legs -----
    # (device tier; writes only the dedicated adapt_l/adapt_r tables)
    adaptive = None
    if os.environ.get("BENCH_ADAPT", "1") == "1":
        with _bench_span("adaptive"):
            adaptive = _measure_adaptive(session, ws)
        correct = correct and adaptive["results_match_raw"]

    # ---- repeat-heavy serving through the result cache (non-mutating on
    # TPC-H; its freshness leg writes only the events_cached table) --------
    cached = None
    if os.environ.get("BENCH_CACHED", "1") == "1":
        with _bench_span("cached_qps"):
            cached = _measure_cached_qps(session, ws)
        correct = correct and cached["results_match"]

    # ---- mixed read/write serving: sustained ingest + concurrent queries -
    # (writes only the dedicated events table; TPC-H inputs untouched)
    ingest_rw = None
    if os.environ.get("BENCH_INGEST", "1") == "1":
        with _bench_span("ingest_rw"):
            ingest_rw = _measure_ingest_rw(session, ws)

    # ---- approximate query tier: sampled execution with error bounds -----
    # (writes only the dedicated apx_fact/apx_dim tables; HYPERSPACE_APPROX
    # is restored on exit so no other section sees the tier)
    approx_tier = None
    if os.environ.get("BENCH_APPROX", "1") == "1":
        with _bench_span("approx_tier"):
            approx_tier = _measure_approx_tier(session, ws, rows, repeats)
        correct = correct and approx_tier["results_match"]

    # ---- BASELINE.md config 4 + 5 (mutating; after device sections) ------
    with _bench_span("hybrid_refresh"):
        hybrid = _measure_hybrid_refresh(session, hs, ws, repeats)
    with _bench_span("bloom_skipping"):
        bloom = _measure_bloom_skipping(session, ws, rows, repeats)

    # ---- tier choice + headline -----------------------------------------
    tier_counts = {"device_wins": 0, "host_wins": 0}
    for name, entry in results.items():
        t_host = entry["indexed_hostexec_ms"]
        t_dev = entry.get("indexed_device_ms")
        raw_candidates = [entry["raw_ms"]] + (
            [entry["raw_device_ms"]] if "raw_device_ms" in entry else []
        )
        entry["raw_best_ms"] = min(raw_candidates)
        if t_dev is not None:
            entry["exec_tier"] = "device" if t_dev <= t_host else "host"
            tier_counts[
                "device_wins" if entry["exec_tier"] == "device" else "host_wins"
            ] += 1
            entry["indexed_ms"] = min(t_dev, t_host)
        else:
            entry["indexed_ms"] = t_host
        t_idx = entry["indexed_ms"]
        entry["speedup_self"] = (
            round(entry["raw_best_ms"] / t_idx, 3) if t_idx > 0 else 0.0
        )
        entry["speedup_vs_external"] = (
            round(entry["external_pandas_ms"] / t_idx, 3) if t_idx > 0 else 0.0
        )

    q3_speedup = results["q3"]["speedup_self"]
    q3_vs_external = results["q3"]["speedup_vs_external"]
    out = {
        "metric": "tpch_q3_join_speedup",
        "value": q3_speedup,
        "unit": "x",
        # BASELINE.md's denominator (32-core Spark-CPU) is not in this image;
        # pandas is the independently-implemented external engine standing in
        "vs_baseline": round(q3_vs_external / 4.0, 3),
        "baseline_denominator": "pandas (external engine; see BASELINE.md note)",
        "queries": results,
        "point_lookup": point,
        "sketch_prune": sketch,
        "sustained_qps": qps,
        "multi_tenant": tenant_qos,
        "spill_join": spill,
        "adaptive": adaptive,
        "cached_qps": cached,
        "ingest_rw": ingest_rw,
        "approx_tier": approx_tier,
        "serving": _counter_stats("serve."),
        "ingest": _counter_stats("ingest."),
        "approx": _counter_stats("approx."),
        "hybrid_refresh": hybrid,
        "bloom_skipping": bloom,
        "index_build_gbps": round(build_gbps, 4),
        "rows": rows,
        "source_mb": round(source_mb, 1),
        "results_match_raw": correct,
        "backend": backend,
        "device": device,
        "compile_cache_dir": compile_cache_dir,
        "exec_tier_summary": tier_counts,
        "repeats": repeats,
        "host": _host_facts(),
        "build": {
            "max_bytes_in_memory": session.conf.build_max_bytes_in_memory,
            "streaming_forced": bool(build_budget_mb),
            "build_s": round(build_s, 1),
            "index_format": index_format,
        },
        "device_cache": _device_cache_stats(),
        "kernel_cache": _counter_stats("cache.kernel."),
        "pipeline": _counter_stats("pipeline."),
        "pruning": _counter_stats("pruning."),
        "staticcheck": _staticcheck_stats(),
        "robustness": _robustness_stats(),
        "estimator": _estimator_stats(),
        "workload": _workload_stats(),
        "host_wall_s": host_wall_s,
        "wall_s": round(time.time() - t_start, 1),
    }
    if profile_path is not None:
        from hyperspace_tpu.telemetry import trace as _trace
        from hyperspace_tpu.telemetry.metrics import REGISTRY

        _trace.disable()
        n_spans = sum(1 for _ in open(profile_path, encoding="utf-8"))
        out["profile"] = {
            "path": os.path.abspath(profile_path),
            "spans": n_spans,
            "note": "timings include tracing overhead; read with tools/trace_report.py",
            "metrics": {
                k: v
                for k, v in REGISTRY.snapshot().items()
                if not k.startswith("cache.")
            },
        }
    print(json.dumps(out))


def _prefix_counter_delta(fn, prefix: str):
    """(fn(), counter deltas under ``prefix``) for one run — the per-query
    view of an engine subsystem's engagement (join pipeline, index pruning)
    surfaced in the bench artifact and diffed per section by
    tools/bench_compare.py."""
    from hyperspace_tpu.telemetry.metrics import REGISTRY

    def snap() -> dict:
        return {
            k: v
            for k, v in REGISTRY.snapshot().items()
            if k.startswith(prefix) and isinstance(v, (int, float))
        }

    before = snap()
    out = fn()
    after = snap()
    return out, {
        k[len(prefix):]: after[k] - before.get(k, 0)
        for k in after
        if after[k] != before.get(k, 0)
    }


def _join_counter_delta(fn) -> dict:
    """``pipeline.join.*`` counter deltas across one run of ``fn``."""
    return _prefix_counter_delta(fn, "pipeline.join.")[1]


def _counter_stats(prefix: str) -> dict:
    """Registry counters under ``prefix`` (kernel-cache hit/miss/evict and
    pipeline chunk/abort counts land in the artifact so warm-cache repeats
    and streaming engagement are checkable from the JSON alone)."""
    try:
        from hyperspace_tpu.telemetry.metrics import REGISTRY

        snap = REGISTRY.snapshot()
        return {
            k[len(prefix):]: v for k, v in snap.items() if k.startswith(prefix)
        }
    except Exception:
        return {}


def _staticcheck_stats() -> dict:
    """Static-analysis gate counts for the artifact: a healthy warm run
    reports zero hazards, zero retrace-storm warnings, zero plan
    violations, and zero lock-order violations (tools/bench_compare.py
    diffs these per run; the ``concurrency`` sub-block carries the
    lock-order audit's registry/graph sizes)."""
    try:
        from hyperspace_tpu.staticcheck.concurrency import report as lock_report
        from hyperspace_tpu.telemetry.metrics import REGISTRY

        def val(name: str) -> int:
            m = REGISTRY.get(name)
            return 0 if m is None else int(m.value)

        locks = lock_report()
        return {
            "plan_runs": val("staticcheck.plan.runs"),
            "plan_violations": val("staticcheck.plan.violations"),
            "kernels_audited": val("staticcheck.kernel.audited"),
            "kernel_hazards": val("staticcheck.kernel.hazards"),
            "retrace_warnings": val("staticcheck.kernel.retrace_storm"),
            "audit_errors": val("staticcheck.kernel.audit_errors"),
            "lock_acquisitions": val("staticcheck.lock.acquisitions"),
            "lock_edges": val("staticcheck.lock.edges"),
            "lock_violations": val("staticcheck.lock.violations"),
            "lifecycle_acquires": val("staticcheck.lifecycle.acquires"),
            "lifecycle_releases": val("staticcheck.lifecycle.releases"),
            "lifecycle_leaks": val("staticcheck.lifecycle.leaks"),
            "concurrency": {
                "audit_enabled": locks["audit_enabled"],
                "registered_locks": len(locks["locks"]),
                "order_edges": len(locks["edges"]),
                "guarded_state": len(locks["guarded"]),
            },
        }
    except Exception:
        return {}


def _estimator_stats() -> dict:
    """Estimator-accuracy rollup for the artifact, flattened to scalars so
    tools/bench_compare.py diffs them row by row: per-estimator q-error
    count/mean/max (1.0 = perfect estimates) plus the observation and
    correction-key totals."""
    try:
        from hyperspace_tpu.telemetry.plan_stats import ACCURACY

        snap = ACCURACY.snapshot()
        out = {
            "observations": snap["observations"],
            "correction_keys": snap["correction_keys"],
        }
        for est, h in sorted(snap["qerror"].items()):
            if not h.get("count"):
                continue
            out[f"qerror.{est}.count"] = h["count"]
            out[f"qerror.{est}.mean"] = h.get("mean", 0.0)
            out[f"qerror.{est}.max"] = h.get("max", 0.0)
        return out
    except Exception:
        return {}


def _workload_stats() -> dict:
    """Workload-intelligence rollup for the artifact, flattened to scalars
    so tools/bench_compare.py diffs them row by row. With
    HYPERSPACE_WORKLOAD_DIR unset (the default bench run) everything is
    zero — any drift means the disabled plane did work."""
    try:
        from hyperspace_tpu.telemetry import workload
        from hyperspace_tpu.telemetry.index_ledger import INDEX_LEDGER
        from hyperspace_tpu.telemetry.metrics import REGISTRY

        snap = REGISTRY.snapshot()

        def val(name):
            return snap.get(name, 0)

        totals = INDEX_LEDGER.totals()
        drift = workload.DRIFT.snapshot()
        return {
            "enabled": workload.enabled(),
            "journal_records": val("workload.journal.records"),
            "journal_rotations": val("workload.journal.rotations"),
            "journal_errors": val("workload.journal.errors"),
            "index_applied": val("workload.index.applied"),
            "benefit_bytes": round(totals["benefit_bytes"], 1),
            "bytes_skipped": totals["bytes_skipped"],
            "maintenance_actions": totals["maintenance_actions"],
            "maintenance_s": round(totals["maintenance_s"], 3),
            "indexes_tracked": len(INDEX_LEDGER.report()),
            "drift_series": drift["series"],
            "drift_regressions": len(drift["regressions"]),
        }
    except Exception:
        return {}


def _robustness_stats() -> dict:
    """Failure-hardening counts for the artifact: a clean bench run shows
    zero injections (HYPERSPACE_FAULTS unset), zero retries, a CLOSED
    breaker, and a no-op recovery pass — any drift here means the clean
    path hit the failure machinery (tools/bench_compare.py diffs these)."""
    try:
        from hyperspace_tpu.telemetry.metrics import REGISTRY
        from hyperspace_tpu.utils import faults
        from hyperspace_tpu.utils.backend import breaker_snapshot

        def val(name: str) -> int:
            m = REGISTRY.get(name)
            return 0 if m is None else int(m.value)

        return {
            "faults_armed": faults.armed(),
            "faults_injected": val("faults.injected"),
            "io_retry_attempts": val("io.retry.attempts"),
            "io_retry_gave_up": val("io.retry.gave_up"),
            "action_retry_attempts": val("action.retry.attempts"),
            "breaker": breaker_snapshot(),
            "recovery": {
                "runs": val("recovery.runs"),
                "rolled_back": val("recovery.rolled_back"),
                "staging_removed": val("recovery.staging_removed"),
                "orphan_versions": val("recovery.orphan_versions"),
                "temp_files": val("recovery.temp_files"),
                "pointer_fixed": val("recovery.pointer_fixed"),
            },
        }
    except Exception:
        return {}


def _device_cache_stats() -> dict:
    try:
        from hyperspace_tpu.utils.device_cache import DEVICE_CACHE, HOST_DERIVED_CACHE

        return {
            "device_hits": DEVICE_CACHE.hits,
            "device_misses": DEVICE_CACHE.misses,
            "host_derived_hits": HOST_DERIVED_CACHE.hits,
            "host_derived_misses": HOST_DERIVED_CACHE.misses,
        }
    except Exception:
        return {}


if __name__ == "__main__":
    main()
