#!/usr/bin/env python
"""Chip smoke: the indexed query path on one TPU, at TPC-H SF10 scale.

Generates TPC-H-shaped data from ``--seed`` (``--rows`` lineitem rows,
default 60M = SF10; orders and part scale with it), builds the standard
index set through ``Hyperspace.create_index``, and runs Q6 (fused Pallas
filter-sum), Q1 (grouped aggregate), Q3 and Q17 (bucketed device joins)
and a point lookup with hyperspace on, each once through ``DataFrame`` and
once through ``QueryScheduler.submit_query``. Every answer is checked
against the raw-scan plan on the host tier, every query's route (from
``telemetry/plan_stats``) must be ``device`` or ``pipelined``, and the
device tier must not have degraded. The session runs strict
(``HYPERSPACE_DEVICE_STRICT=1``): a device failure raises instead of
falling back to the host.

``--chips 4`` runs only the mesh path (mesh-partitioned index build,
mesh aggregates, mesh-placed bucketed joins) and the one-chip answers it
is compared with.

There is no CPU path: without a TPU the script exits non-zero and prints
no result. The last line of a passing run is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import math
import os
import sys
import tempfile
import time

SF10_ROWS = 60_000_000
QUERIES = ("q6", "q1", "q3", "q17", "point")
DEVICE_ROUTES = frozenset({"device", "pipelined"})


def log(msg: str) -> None:
    print(f"chip_smoke: {msg}", flush=True)


def float_rtol(rows: int) -> float:
    """Relative tolerance of a device float aggregate against the host's
    f64 answer. The device tier holds float columns in f32 (each value
    rounds by at most 2^-24 relative) and accumulates in f32: the fused
    Pallas kernels keep 1024 lane partials, each a running sum of
    m = rows / 1024 same-sign terms, whose rounding walks ~sqrt(m) * 2^-24
    relative. At 60M rows that is sqrt(58594) * 2^-24 = 1.4e-5; a factor 8
    covers the worst lanes and the final tile reduction: 1.2e-4."""
    return max(1e-6, 8 * math.sqrt(rows / 1024) * 2.0**-24)


def point_lookup(session, root: str):
    """Index-pruned point lookup on the li_orderkey covering index."""
    from hyperspace_tpu.plan import Count, Sum, col, lit

    return (
        session.read.parquet(os.path.join(root, "lineitem"))
        .filter(col("l_orderkey") == 12345)
        .agg(Sum(col("l_extendedprice")).alias("s"), Count(lit(1)).alias("n"))
    )


def query(name: str, session, root: str):
    from hyperspace_tpu.benchmark import TPCH_QUERIES

    if name == "point":
        return point_lookup(session, root)
    return TPCH_QUERIES[name](session, root)


def mismatch(got: dict, want: dict, rtol: float) -> str | None:
    """None when ``got`` matches ``want``: same columns, same row count,
    keys and counts (ints, strings) exactly equal, floats within
    ``rtol`` relative. Otherwise a description of the first difference."""
    if list(got) != list(want):
        return f"columns {list(got)} != {list(want)}"
    for c in want:
        if len(got[c]) != len(want[c]):
            return f"{c}: {len(got[c])} rows != {len(want[c])}"
        for i, (a, b) in enumerate(zip(got[c], want[c])):
            if isinstance(b, float):
                if a is None or abs(a - b) > rtol * max(abs(b), 1e-30):
                    return f"{c}[{i}]: {a!r} vs {b!r} (rtol {rtol:.3g})"
            elif a != b:
                return f"{c}[{i}]: {a!r} != {b!r}"
    return None


class CompileClock:
    """Seconds JAX spent compiling (persistent-cache lookups included) and
    persistent-cache hits, from JAX's own monitoring events."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.cache_hits = 0

        def on_duration(event, secs, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.seconds += secs

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)


class Analyzed:
    """What ``QueryScheduler.submit_query`` needs of a DataFrame (its
    ``collect``), with the query's plan statistics captured on the
    scheduler's worker thread."""

    def __init__(self, df):
        self.df = df
        self.stats = None

    def collect(self):
        from hyperspace_tpu.telemetry import plan_stats

        with plan_stats.collect_scope() as stats:
            out = self.df.collect()
        self.stats = stats
        return out


def routes(stats) -> set:
    """The non-host routes of the executed plan nodes: Sort/Limit/Project
    over a finished aggregate stay on the host by design."""
    return {
        ns.route for ns in stats.nodes.values()
        if ns.executed and ns.route != "host"
    }


def new_session(ws: str, system_path: str | None = None, mesh_devices: int = 0):
    from hyperspace_tpu import Hyperspace, HyperspaceSession
    from hyperspace_tpu import constants as C

    conf = {C.SYSTEM_PATH: system_path} if system_path else None
    session = HyperspaceSession(warehouse_dir=ws, conf=conf)
    session.set_conf(C.EXEC_TPU_ENABLED, True)
    session.set_conf(C.EXEC_MESH_DEVICES, mesh_devices)
    return session, Hyperspace(session)


def generate(ws: str, rows: int, seed: int) -> None:
    from hyperspace_tpu.benchmark import generate_tpch

    t0 = time.perf_counter()
    sizes = generate_tpch(ws, rows_lineitem=rows, seed=seed)
    log(
        f"phase generate: {time.perf_counter() - t0:.3f}s, {rows} lineitem "
        f"rows, {sum(sizes.values())} parquet bytes"
    )


def build(ws: str, system_path: str | None = None, mesh_devices: int = 0):
    from hyperspace_tpu.benchmark import tpch_indexes

    session, hs = new_session(ws, system_path, mesh_devices)
    t0 = time.perf_counter()
    tpch_indexes(session, hs, ws)
    label = f"build (mesh {mesh_devices})" if mesh_devices else "build"
    log(f"phase {label}: {time.perf_counter() - t0:.3f}s")
    return session


def host_reference(session, ws: str) -> dict:
    """Every query's answer from the raw-scan plan on the host tier."""
    from hyperspace_tpu import constants as C

    session.disable_hyperspace()
    session.set_conf(C.EXEC_TPU_ENABLED, False)
    try:
        t0 = time.perf_counter()
        out = {name: query(name, session, ws).to_pydict() for name in QUERIES}
        log(f"phase host reference: {time.perf_counter() - t0:.3f}s")
        return out
    finally:
        session.set_conf(C.EXEC_TPU_ENABLED, True)


def run_queries(session, ws: str, reference: dict, rtol: float,
                clock: CompileClock | None = None) -> bool:
    """Each query once through DataFrame (the first run: compiles) and
    once through the serving scheduler (warm), answers and routes checked.
    True iff every check passed."""
    from hyperspace_tpu.serve.scheduler import QueryScheduler
    from hyperspace_tpu.telemetry import plan_stats

    session.enable_hyperspace()
    ok = True
    scheduler = QueryScheduler()
    try:
        for name in QUERIES:
            c0 = clock.seconds if clock else 0.0
            t0 = time.perf_counter()
            with plan_stats.collect_scope() as stats:
                got = query(name, session, ws).to_pydict()
            first_s = time.perf_counter() - t0
            compile_s = (clock.seconds - c0) if clock else 0.0
            wrapped = Analyzed(query(name, session, ws))
            t0 = time.perf_counter()
            served = scheduler.submit_query(wrapped, label=name).result()
            warm_s = time.perf_counter() - t0
            for via, answer, st in (
                ("dataframe", got, stats),
                ("scheduler", served.to_pydict(), wrapped.stats),
            ):
                diff = mismatch(answer, reference[name], rtol)
                r = routes(st)
                passed = diff is None and bool(r) and r <= DEVICE_ROUTES
                ok = ok and passed
                log(
                    f"query {name} via {via}: routes {sorted(r)} "
                    f"match {diff is None}"
                    + (f" ({diff})" if diff else "")
                    + ("" if passed else " FAIL")
                )
            log(
                f"phase query {name}: first {first_s:.3f}s (compile "
                f"{compile_s:.3f}s), warm (scheduler) {warm_s:.3f}s"
            )
    finally:
        scheduler.shutdown()
    return ok


@contextlib.contextmanager
def placement_on(n_devices: int):
    """Skew-aware bucket->device placement over ``n_devices``
    (``HYPERSPACE_MESH=1``, capped by ``HYPERSPACE_MESH_DEVICES``)."""
    saved = {k: os.environ.get(k)
             for k in ("HYPERSPACE_MESH", "HYPERSPACE_MESH_DEVICES")}
    os.environ["HYPERSPACE_MESH"] = "1"
    os.environ["HYPERSPACE_MESH_DEVICES"] = str(n_devices)
    try:
        yield
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def mesh_phase(ws: str, n_devices: int, rtol: float) -> bool:
    """The mesh path users select with ``hyperspace.tpu.exec.meshDevices``
    and ``HYPERSPACE_MESH=1``, each leg compared with the one-chip answers
    on the same data: a mesh-partitioned index build (exchange
    all_to_all) queried with mesh aggregates (psum) and the mesh join
    probe, then bucketed joins placed across the devices. Every device
    must have held work."""
    import jax

    from hyperspace_tpu.telemetry import trace

    session = build(ws)
    session.enable_hyperspace()
    t0 = time.perf_counter()
    one_chip = {name: query(name, session, ws).to_pydict() for name in QUERIES}
    log(f"phase one-chip queries: {time.perf_counter() - t0:.3f}s")

    ok = True

    def check(leg: str, names, sess, want_spans: set) -> None:
        nonlocal ok
        sess.enable_hyperspace()
        t0 = time.perf_counter()
        with trace.capture() as cap:
            for name in names:
                diff = mismatch(query(name, sess, ws).to_pydict(),
                                one_chip[name], rtol)
                ok = ok and diff is None
                log(f"mesh {leg} {name}: match one-chip {diff is None}"
                    + (f" ({diff})" if diff else ""))
        seen = {s.name for s in cap.sink.spans}
        ordinals = {s.attrs.get("device") for s in cap.sink.spans
                    if s.name == "mesh:dispatch"}
        missing = want_spans - seen
        ok = ok and not missing
        log(f"mesh {leg}: {time.perf_counter() - t0:.3f}s, spans missing "
            f"{sorted(missing)}, placed on devices {sorted(ordinals)}")
        if leg == "placed":
            ok = ok and ordinals == set(range(n_devices))

    with trace.capture() as cap:
        mesh_session = build(ws, os.path.join(ws, "indexes_mesh"), n_devices)
    built = "kernel:mesh_partition" in {s.name for s in cap.sink.spans}
    ok = ok and built
    log(f"mesh build exchanged over the mesh: {built}")
    check("aggregates+join", QUERIES, mesh_session,
          {"kernel:mesh_agg", "kernel:mesh_join_probe"})
    with placement_on(n_devices):
        check("placed", ("q3", "q17"), session, {"mesh:dispatch"})

    peaks = [
        (d.memory_stats() or {}).get("peak_bytes_in_use")
        for d in jax.devices()[:n_devices]
    ]
    log(f"peak_bytes_in_use per device: {peaks}")
    if all(p is not None for p in peaks):  # the CPU reports none
        ok = ok and all(p > 0 for p in peaks)
    return ok


def run(ws: str, rows: int, seed: int, chips: int,
        clock: CompileClock | None = None) -> bool:
    """Every phase of one smoke run in ``ws``; True iff all checks pass,
    the device tier never degraded to the host and the breaker is closed."""
    import jax

    from hyperspace_tpu.telemetry.metrics import REGISTRY
    from hyperspace_tpu.utils.backend import breaker_state
    from hyperspace_tpu.utils.device_cache import DEVICE_CACHE

    degrades0 = REGISTRY.counter("device.degrades").value
    rtol = float_rtol(rows)
    log(f"rows {rows} (SF10 = {SF10_ROWS}), float rtol {rtol:.3g}")
    generate(ws, rows, seed)
    if chips > 1:
        ok = mesh_phase(ws, chips, rtol)
    else:
        session = build(ws)
        reference = host_reference(session, ws)
        ok = run_queries(session, ws, reference, rtol, clock)
    degrades = REGISTRY.counter("device.degrades").value - degrades0
    state = breaker_state()
    log(f"device.degrades {degrades}, breaker {state}")
    peak = (jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use")
    log(f"peak_bytes_in_use {peak}, device cache bytes "
        f"{DEVICE_CACHE.occupancy_bytes}")
    held = peak is None or peak > 0  # the CPU reports no memory stats
    return ok and degrades == 0 and state == "closed" and held


def parse_args(argv):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--rows", type=int, default=SF10_ROWS,
                   help="lineitem rows (default: SF10)")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--chips", type=int, choices=(1, 4), default=1,
                   help="4: only the mesh path and its one-chip comparison")
    p.add_argument("--workdir", default=None,
                   help="where the data and indexes go (default: a new "
                        "temporary directory, removed at exit)")
    return p.parse_args(argv)


def main(argv=None) -> int:
    args = parse_args(argv)
    os.environ["HYPERSPACE_DEVICE_STRICT"] = "1"
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (platform {devices[0].platform!r}); "
              "there is no CPU path", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but {len(devices)} devices",
              file=sys.stderr)
        return 2
    from hyperspace_tpu.utils.backend import enable_compile_cache

    log(f"compile cache {enable_compile_cache()}")
    clock = CompileClock()
    t0 = time.perf_counter()
    with contextlib.ExitStack() as stack:
        ws = args.workdir or stack.enter_context(
            tempfile.TemporaryDirectory(prefix="hs_chip_smoke_"))
        ok = run(ws, args.rows, args.seed, args.chips, clock)
    log(f"compile {clock.seconds:.3f}s in all, {clock.cache_hits} persistent "
        f"cache hits; total {time.perf_counter() - t0:.3f}s")
    if not ok:
        log("FAILED")
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
