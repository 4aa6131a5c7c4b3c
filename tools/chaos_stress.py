#!/usr/bin/env python
"""Chaos-stress gate: deterministic fault injection across queries and
maintenance actions must never produce a wrong answer or an unrecoverable
warehouse.

Two sweeps, one contract ("bit-identical or typed error, never wrong
answers" — docs/robustness.md):

1. **Query sweep** — each armed ``HYPERSPACE_FAULTS`` spec (transient IO
   errors, OOMs, device failures, compile failures; nth-hit and
   seeded-probabilistic triggers) runs the full TPC-H query set against a
   warmed indexed warehouse. Every single run must either match the clean
   reference at ``float.hex()`` bit precision (retries / the device
   breaker / host fallback absorbed the fault) or raise a typed
   ``HyperspaceError`` — a bare builtin or a silently wrong result fails
   the gate.

2. **Crash matrix** — maintenance actions (create / refresh / optimize /
   delete) run with ``InjectedCrash`` armed before and after every
   ``log.write`` and ``data.publish`` they perform, in a fresh warehouse
   per cell. After each simulated death, ``recover(force=True)`` must
   return the index to a stable state with no orphans: stable (or empty)
   log tail, no ``_staging`` dirs, no ``.tmp-*`` spool files, no data
   version unreferenced by the log. The action then re-runs and the final
   query must match a never-crashed twin warehouse bit-for-bit.

After both sweeps every bounded cache must pass ``check_consistency()``.
Prints one JSON line (per-spec outcomes, per-point injection counts,
retry/breaker/recovery counters); exit 0 iff all gates hold.

    timeout 600 env JAX_PLATFORMS=cpu python tools/chaos_stress.py

Env: SMOKE_ROWS (30000), CHAOS_CELL_ROWS (4000).
"""

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


# fault specs swept over the query set: transient kinds only (crash kinds
# simulate process death and belong to the crash matrix)
QUERY_SPECS = [
    "io.read_file:ioerror:n=1",
    "io.read_file:ioerror:n=3",
    "io.read_file:ioerror:p=0.02,seed=7",
    "io.read_file:oom:n=2",
    "io.footer:ioerror:n=1",
    "device.upload:ioerror:n=1",
    "device.dispatch:ioerror:n=1",
    "device.dispatch:oom:n=1",
    "device.fetch:ioerror:n=1",
    "device.*:ioerror:p=0.05,seed=3",
    "kernel.compile:ioerror:n=1",
]

# (action, fault specs): every log.write / data.publish the action performs,
# killed immediately before and immediately after the atomic step
_LOG_CRASHES = [
    "log.write:crash_before:n=1",   # begin() transient entry never lands
    "log.write:crash_after:n=1",    # transient entry lands, op never runs
    "log.write:crash_before:n=2",   # end() final entry never lands
    "log.write:crash_after:n=2",    # final entry lands, pointer rewrite lost
]
_PUBLISH_CRASHES = [
    "data.publish:crash_before:n=1",  # staged build never promoted
    "data.publish:crash_after:n=1",   # version live, final log.write lost
]
_APPEND_CRASHES = [
    "ingest.append:crash_before:n=1",  # staging created, delta never built
    "ingest.append:crash_after:n=1",   # delta published, final log.write lost
]
_COMPACT_CRASHES = [
    "ingest.compact:crash_before:n=1",  # staging created, merge never ran
    "ingest.compact:crash_after:n=1",   # compacted version live, log lost
]
CRASH_MATRIX = [
    ("create", _LOG_CRASHES + _PUBLISH_CRASHES),
    ("refresh", _LOG_CRASHES + _PUBLISH_CRASHES),
    ("optimize", _LOG_CRASHES + _PUBLISH_CRASHES),
    ("delete", _LOG_CRASHES),  # delete moves no data, only log entries
    ("append", _LOG_CRASHES + _PUBLISH_CRASHES + _APPEND_CRASHES),
    ("compact", _LOG_CRASHES + _PUBLISH_CRASHES + _COMPACT_CRASHES),
]


def main() -> int:
    # NOT strict: the breaker's degrade-to-host path is part of what this
    # gate verifies. Small chunks so the streamed executor engages.
    os.environ.setdefault("HYPERSPACE_STREAM_CHUNK_MB", "0.5")
    if os.environ.get("STRESS_LIFECYCLE_AUDIT", "1") == "1":
        os.environ.setdefault("HYPERSPACE_LIFECYCLE_AUDIT", "1")
    import jax

    jax.config.update("jax_platforms", os.environ.get("JAX_PLATFORMS", "cpu"))
    import tempfile

    from hyperspace_tpu import Hyperspace, HyperspaceSession
    from hyperspace_tpu import constants as C
    from hyperspace_tpu.benchmark import TPCH_QUERIES, generate_tpch, tpch_indexes
    from hyperspace_tpu.columnar import io as cio
    from hyperspace_tpu.exceptions import HyperspaceError
    from hyperspace_tpu.meta.data_manager import IndexDataManager
    from hyperspace_tpu.meta.log_manager import IndexLogManager, STABLE_STATES
    from hyperspace_tpu.plan import kernel_cache as kc
    from hyperspace_tpu.staticcheck import lifecycle as lc
    from hyperspace_tpu.telemetry.metrics import REGISTRY
    from hyperspace_tpu.utils import backend, device_cache as dc, faults

    rows = int(os.environ.get("SMOKE_ROWS", 30_000))
    cell_rows = int(os.environ.get("CHAOS_CELL_ROWS", 4_000))

    def val(n: str) -> int:
        m = REGISTRY.get(n)
        return 0 if m is None else int(m.value)

    failures: list = []

    # ---- sweep 1: queries under transient faults -------------------------
    ws = tempfile.mkdtemp(prefix="hs_chaos_q_")
    generate_tpch(ws, rows_lineitem=rows, seed=11)
    session = HyperspaceSession(warehouse_dir=ws)
    session.set_conf(C.INDEX_NUM_BUCKETS, 8)
    session.set_conf(C.EXEC_TPU_ENABLED, True)
    hs = Hyperspace(session)
    tpch_indexes(session, hs, ws)
    session.enable_hyperspace()
    names = list(TPCH_QUERIES)
    clean = {n: _bits(TPCH_QUERIES[n](session, ws).to_pydict()) for n in names}
    # second reference with the device tier off: a degraded run must equal
    # EITHER the full device answer or the full host recompute — the same
    # bits the engine produces with the tier disabled. Anything else is a
    # torn/partial result and fails the gate.
    session.set_conf(C.EXEC_TPU_ENABLED, False)
    clean_host = {n: _bits(TPCH_QUERIES[n](session, ws).to_pydict()) for n in names}
    session.set_conf(C.EXEC_TPU_ENABLED, True)

    def clear_engine_caches() -> None:
        """Warm caches absorb most injection points (a cached chunk never
        re-reads, a cached kernel never re-compiles); each spec starts cold
        so its point actually gets hit."""
        cio._INDEX_CHUNK_CACHE.clear()
        cio._SOURCE_COL_CACHE.clear()
        cio._ROWGROUP_STATS_CACHE.clear()
        dc.DEVICE_CACHE.clear()
        dc.HOST_DERIVED_CACHE.clear()
        for cache in (kc.KERNEL_CACHE, kc.JOIN_CACHE, kc.TOPK_CACHE, kc.SORT_CACHE):
            cache.clear()

    query_sweep = []
    point_fired: dict = {p: 0 for p in faults.POINTS}
    for spec in QUERY_SPECS:
        clear_engine_caches()
        rules = faults.arm(spec)
        outcomes = {"identical": 0, "degraded_identical": 0, "typed_error": 0}
        try:
            for n in names:
                try:
                    got = _bits(TPCH_QUERIES[n](session, ws).to_pydict())
                except faults.InjectedCrash:
                    raise  # crash kinds never belong in this sweep
                except HyperspaceError:
                    outcomes["typed_error"] += 1
                    continue
                except MemoryError as e:
                    # an unabsorbed OOM injection is typed (InjectedOOMError
                    # is a HyperspaceError); a bare MemoryError is a bug
                    if isinstance(e, HyperspaceError):
                        outcomes["typed_error"] += 1
                        continue
                    failures.append(f"query {n} under {spec!r}: bare {e!r}")
                    continue
                except Exception as e:
                    failures.append(f"query {n} under {spec!r}: untyped {e!r}")
                    continue
                if got == clean[n]:
                    outcomes["identical"] += 1
                elif got == clean_host[n]:
                    outcomes["degraded_identical"] += 1
                else:
                    failures.append(f"query {n} under {spec!r}: WRONG RESULT")
        finally:
            snap = faults.snapshot()
            faults.disarm()
        fired = sum(r["fired"] for r in snap)
        for r in snap:
            base = r["point"][:-2] if r["point"].endswith(".*") else r["point"]
            for p in point_fired:
                if p == r["point"] or (r["point"].endswith(".*") and p.startswith(base)):
                    point_fired[p] += r["fired"]
        query_sweep.append({"spec": spec, "fired": fired, **outcomes})
        # a transient device fault legitimately opens the breaker; runs are
        # independent experiments, so re-arm the device tier between specs
        backend._reset_for_testing()

    # ---- sweep 2: crash matrix over maintenance actions ------------------
    import numpy as np
    import pyarrow as pa
    import pyarrow.parquet as pq

    from hyperspace_tpu.models.covering import CoveringIndexConfig

    def write_part(src: str, part: int, n: int) -> None:
        rng = np.random.default_rng(100 + part)
        t = pa.table(
            {
                "k": rng.integers(0, 50, n),
                "v": rng.random(n),
                "w": rng.integers(0, 1000, n),
            }
        )
        pq.write_table(t, os.path.join(src, f"part{part}.parquet"))

    def fresh_session(root: str):
        s = HyperspaceSession(warehouse_dir=root)
        s.set_conf(C.INDEX_NUM_BUCKETS, 4)
        return s, Hyperspace(s)

    def run_action(h, s, root: str, action: str, phase: str) -> None:
        """phase 'setup' brings the warehouse to the action's precondition;
        phase 'act' performs the action under test."""
        src = os.path.join(root, "src")
        if phase == "setup":
            os.makedirs(src)
            write_part(src, 0, cell_rows)
            write_part(src, 1, cell_rows)
            if action != "create":
                h.create_index(
                    s.read.parquet(src), CoveringIndexConfig("cidx", ["k"], ["v", "w"])
                )
            if action == "optimize":
                # an incremental refresh adds a second small file per bucket
                # so quick-optimize has compaction work
                write_part(src, 2, cell_rows)
                h.refresh_index("cidx", C.REFRESH_MODE_INCREMENTAL)
            if action == "compact":
                # an ingest append gives every bucket a second (delta) run
                write_part(src, 2, cell_rows)
                h.append("cidx", s.read.parquet(os.path.join(src, "part2.parquet")))
            return
        if action == "create":
            h.create_index(
                s.read.parquet(src), CoveringIndexConfig("cidx", ["k"], ["v", "w"])
            )
        elif action == "refresh":
            write_part(src, 2, cell_rows)
            h.refresh_index("cidx", C.REFRESH_MODE_FULL)
        elif action == "optimize":
            h.optimize_index("cidx")
        elif action == "append":
            # the source part is written ONCE (act may re-run to converge
            # after a crash: an already-appended file must look unchanged
            # so the retry no-ops instead of double-indexing its rows)
            p2 = os.path.join(src, "part2.parquet")
            if not os.path.exists(p2):
                write_part(src, 2, cell_rows)
            h.append("cidx", s.read.parquet(p2))
        elif action == "compact":
            h.compact_index("cidx", min_runs=2)
        elif action == "delete":
            h.delete_index("cidx")

    def query_bits(s, root: str) -> str:
        df = s.read.parquet(os.path.join(root, "src"))
        out = (
            df.filter(df["k"] == 7).select("v", "w").collect().to_pydict()
        )
        return _bits(out)

    def index_debris(root: str) -> list:
        """Orphan report for every index under the warehouse's system dir."""
        bad = []
        sys_dir = os.path.join(root, C.INDEXES_DIR)
        if not os.path.isdir(sys_dir):
            return bad
        for name in os.listdir(sys_dir):
            ip = os.path.join(sys_dir, name)
            if not os.path.isdir(ip):
                continue
            lm = IndexLogManager(ip)
            dm = IndexDataManager(ip)
            latest = lm.get_latest_log()
            if latest is not None and latest.state not in STABLE_STATES:
                bad.append(f"{name}: unstable log tail {latest.state}")
            if dm.staged_versions():
                bad.append(f"{name}: staging dirs {dm.staged_versions()}")
            if lm.stale_temp_files():
                bad.append(f"{name}: stale .tmp files")
            from hyperspace_tpu.index_manager import IndexCollectionManager

            refs = IndexCollectionManager._referenced_versions(lm)
            if latest is not None and latest.state == "DOESNOTEXIST":
                refs = set()
            orphans = [v for v in dm.get_all_versions() if v not in refs]
            if orphans:
                bad.append(f"{name}: orphan data versions {orphans}")
        return bad

    crash_matrix = []
    twin_bits: dict = {}
    for action, specs in CRASH_MATRIX:
        # never-crashed twin (one per action; cells reuse its reference bits)
        twin = tempfile.mkdtemp(prefix=f"hs_chaos_twin_{action}_")
        ts, th = fresh_session(twin)
        run_action(th, ts, twin, action, "setup")
        run_action(th, ts, twin, action, "act")
        ts.enable_hyperspace()
        twin_bits[action] = query_bits(ts, twin)

        for spec in specs:
            cell = tempfile.mkdtemp(prefix=f"hs_chaos_{action}_")
            s, h = fresh_session(cell)
            run_action(h, s, cell, action, "setup")
            crashed = False
            faults.arm(spec)
            try:
                run_action(h, s, cell, action, "act")
            except faults.InjectedCrash:
                crashed = True
            finally:
                snap = faults.snapshot()
                faults.disarm()
            fired = sum(r["fired"] for r in snap)
            # a fresh manager (the "restarted process") repairs the debris
            s2, h2 = fresh_session(cell)
            h2.recover(force=True)
            debris = index_debris(cell)
            if debris:
                failures.append(f"{action} under {spec!r}: {debris}")
            # converge to the twin's logical end state, then compare
            try:
                run_action(h2, s2, cell, action, "act")
            except HyperspaceError:
                # already completed before the crash (e.g. final entry
                # landed); the state assertions below still apply
                pass  # hslint: HS402 — convergence retry; debris check is the gate
            s2.enable_hyperspace()
            got = query_bits(s2, cell)
            identical = got == twin_bits[action]
            if not identical:
                failures.append(f"{action} under {spec!r}: post-recovery result diverges")
            crash_matrix.append(
                {
                    "action": action,
                    "spec": spec,
                    "fired": fired,
                    "crashed": crashed,
                    "recovered_clean": not debris,
                    "identical": identical,
                }
            )

    # ---- global invariants ----------------------------------------------
    consistency = {
        "io.index_chunk": cio._INDEX_CHUNK_CACHE.check_consistency(),
        "io.source_col": cio._SOURCE_COL_CACHE.check_consistency(),
        "io.rowgroup_stats": cio._ROWGROUP_STATS_CACHE.check_consistency(),
        "device": dc.DEVICE_CACHE.check_consistency(),
        "host_derived": dc.HOST_DERIVED_CACHE.check_consistency(),
        "kernel": kc.KERNEL_CACHE.check_consistency(),
        "kernel_join": kc.JOIN_CACHE.check_consistency(),
        "kernel_topk": kc.TOPK_CACHE.check_consistency(),
        "kernel_sort": kc.SORT_CACHE.check_consistency(),
    }

    # quiescence: every injected fault unwound through cleanup; any handle
    # still live (pin, budget stream, ledger wave, scope, in-flight marker)
    # is a leak the crash/fault paths failed to release
    leaks = [h.describe() for h in lc.check_quiescent(raise_on_leak=False)]
    lifecycle = lc.report()

    injected = val("faults.injected")
    crashes_fired = sum(c["fired"] for c in crash_matrix)
    ok = (
        not failures
        and all(consistency.values())
        and injected > 0
        and crashes_fired > 0
        and all(c["crashed"] or c["fired"] == 0 for c in crash_matrix)
        and not leaks
    )
    out = {
        "rows": rows,
        "cell_rows": cell_rows,
        "query_specs": len(QUERY_SPECS),
        "query_runs": len(QUERY_SPECS) * len(names),
        "query_sweep": query_sweep,
        "crash_cells": len(crash_matrix),
        "crash_matrix": crash_matrix,
        "point_fired": point_fired,
        "injected_total": injected,
        "io_retry_attempts": val("io.retry.attempts"),
        "io_retry_gave_up": val("io.retry.gave_up"),
        "breaker": backend.breaker_snapshot(),
        "recovery_rolled_back": val("recovery.rolled_back"),
        "recovery_orphan_versions": val("recovery.orphan_versions"),
        "recovery_staging_removed": val("recovery.staging_removed"),
        "recovery_pointer_fixed": val("recovery.pointer_fixed"),
        "cache_consistency": consistency,
        "lifecycle_audit": lifecycle["audit_enabled"],
        "lifecycle_acquires": lifecycle["acquires"],
        "lifecycle_releases": lifecycle["releases"],
        "lifecycle_leaks": leaks[:10],
        "failures": failures[:20],
        "ok": ok,
    }
    print(json.dumps(out))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
