#!/usr/bin/env python3
"""One run of one benchmark cell on the chip.

    python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up (timed from the start of this process): JAX and the chip, TPC-H
data generated from ``--seed``, the configuration's index set built
through ``Hyperspace.create_index``, and every parameter set the run drew
from ``--seed`` run once through the scheduler, so that every kernel the
window uses is compiled or loaded from the persistent cache before it
opens. These warm-up queries run with the program's plan statistics on,
and their plans are checked for an index scan and for device routes; the
window runs without them, as users do.

Window: the traffic's closed-loop clients submit through
``QueryScheduler.submit_query`` (``max_concurrent`` = clients), with
hyperspace on, the device tier strict, exact answers (``allow_approx``
False), every audit off, and tracing off unless ``--trace 1``, which
records a profiler trace of the window and reports the per-layer metrics
instead of the end-to-end ones. A client stops submitting when
``--seconds`` have passed; the window closes when the last answer is in.

Afterwards every answer of the window is compared with the plain numpy
reference in the query's file, and the device tier must not have degraded
in the window. Each number compared is printed with its limit, as the last
lines of standard error and last in the result line.

Without a TPU, or with fewer chips than the cell asks for, the run exits 2
and prints no result.
"""

import time

_T0 = time.perf_counter()  # set-up is timed from the start of the process

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import threading  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from perfbench import cell as cells  # noqa: E402
from perfbench import check, devtrace, traffic, tpch_data  # noqa: E402

# every audit and tracing off, as users run it (ROADMAP A3); the
# configuration's own settings are applied on top
SERVED_ENV = {
    "HYPERSPACE_TRACE": "0",
    "HYPERSPACE_LOCK_AUDIT": "0",
    "HYPERSPACE_KERNEL_AUDIT": "0",
    "HYPERSPACE_VERIFY_PLAN": "0",
    "HYPERSPACE_LIFECYCLE_AUDIT": "0",
    "HYPERSPACE_PLAN_STATS": "0",
}
ANSWER_WAIT_S = 60.0  # how long past the close an answer may still come


class NoChip(RuntimeError):
    pass


def require_chips(n: int) -> list:
    """The first ``n`` TPU devices; ``NoChip`` without them."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX runs on {devices[0].platform!r}")
    if len(devices) < n:
        raise NoChip(f"the cell needs {n} chips, JAX sees {len(devices)}")
    return devices[:n]


class Compiles:
    """Compilations (persistent-cache loads included), from JAX's own
    monitoring events."""

    def __init__(self):
        import jax

        self.n = 0
        self.seconds = 0.0

        def on_duration(event, secs, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.n += 1
                self.seconds += secs

        jax.monitoring.register_event_duration_secs_listener(on_duration)


class GcPauses:
    """Garbage-collector pauses of the process while open, from
    ``gc.callbacks``."""

    def __init__(self):
        self.pauses: list = []
        self._t0 = 0.0

    def _note(self, phase, info):
        if phase == "start":
            self._t0 = time.perf_counter()
        else:
            self.pauses.append((info["generation"],
                                time.perf_counter() - self._t0))

    def __enter__(self):
        gc.callbacks.append(self._note)
        return self

    def __exit__(self, *exc):
        gc.callbacks.remove(self._note)
        return False


class Analyzed:
    """What ``submit_query`` needs of a DataFrame (its ``collect``), with the
    executed plan's statistics kept for the route and index checks."""

    def __init__(self, df):
        self.df = df
        self.stats = None

    def collect(self):
        from hyperspace_tpu.telemetry import plan_stats

        with plan_stats.collect_scope() as stats:
            out = self.df.collect()
        self.stats = stats
        return out


class Answer:
    __slots__ = ("query", "params", "out", "latency_s", "error", "stats")

    def __init__(self, query, params):
        self.query, self.params = query, params
        self.out = self.latency_s = self.error = self.stats = None


def new_session(ws: str, config: dict):
    from hyperspace_tpu import HyperspaceSession

    session = HyperspaceSession(warehouse_dir=ws)
    for key, value in config["session"].items():
        session.set_conf(key, value)
    return session


def serve(sched, cell, session, ws, query, params, deadline=None,
          span=contextlib.nullcontext, analyze=False):
    """One query through the scheduler, as a client submits it; with
    ``analyze``, the executed plan's statistics are kept."""
    a = Answer(query, params)
    try:
        with span("bench:build"):
            df = cell.queries[query].dataframe(session, ws, params)
            if analyze:
                df = Analyzed(df)
        t0 = time.perf_counter()
        with span("bench:submit"):
            h = sched.submit_query(df, label=query, allow_approx=False)
        wait = None if deadline is None else max(
            1.0, deadline + ANSWER_WAIT_S - time.perf_counter())
        try:
            with span("bench:wait"):
                a.out = h.result(timeout=wait)
        finally:
            a.latency_s = time.perf_counter() - t0
        a.stats = df.stats if analyze else None
    except Exception as e:  # counted as failed, judged by check.judge
        a.error = f"{type(e).__name__}: {e}"
    return a


def window(sched, cell, session, ws, pool, seed, seconds, span):
    """The measured window: ``(answers, seconds it lasted)``."""
    clients = int(cell.traffic["clients"])
    lists = [[] for _ in range(clients)]
    start = time.perf_counter()
    deadline = start + seconds

    def client(i: int) -> None:
        seq = traffic.client_sequence(cell.traffic, pool, seed, i)
        while time.perf_counter() < deadline:
            with span("bench:draw"):
                query, params = next(seq)
            lists[i].append(serve(sched, cell, session, ws, query, params,
                                  deadline, span))

    with span(devtrace.WINDOW):
        threads = [threading.Thread(target=client, args=(i,),
                                    name=f"bench-client-{i}")
                   for i in range(clients)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    return [a for lst in lists for a in lst], time.perf_counter() - start


def warm_up(sched, cell, session, ws, pool) -> list:
    """Every drawn parameter set of the mix once, so that every kernel the
    window uses is compiled or loaded before it opens; the answers, with
    their plans' statistics."""
    out = []
    for query in sorted(pool):
        for params in pool[query]:
            a = serve(sched, cell, session, ws, query, params, analyze=True)
            if a.error:
                raise RuntimeError(f"warm-up {query} {params}: {a.error}")
            out.append(a)
    return out


class Measured:
    """What a per-layer reader reads: the window's query count, deltas of
    the program's histograms, counters and RPC meter, the trace's
    reduction, the bytes the queries must read and the chip's peaks."""

    def __init__(self, n, totals0, totals1, meter, trace, must_read, kind):
        self.n = n
        self._t0, self._t1 = totals0, totals1
        self.meter = meter
        self.trace = trace
        self.must_read_bytes = must_read
        self._kind = kind

    @property
    def peaks(self) -> dict:
        return cells.peaks(self._kind)

    def _delta(self, kind: str, name: str) -> float:
        return float(self._t1[kind].get(name, 0.0)
                     - self._t0[kind].get(name, 0.0))

    def hist_sum(self, name: str) -> float:
        """The window's sum of a histogram; 0 for one never registered."""
        return self._delta("histogram", name)

    def counter(self, name: str) -> float:
        """The window's increase of a counter; 0 for one never registered."""
        return self._delta("counter", name)


def _totals() -> dict:
    """``{"histogram": {name: sum}, "counter": {name: value}}`` of the
    program's metrics registry, taken on each side of the window."""
    from hyperspace_tpu.telemetry.metrics import REGISTRY

    out: dict = {"histogram": {}, "counter": {}}
    for name, kind, v in REGISTRY.export():
        if kind in out:
            out[kind][name] = v["sum"] if kind == "histogram" else v
    return out


def memory_peak(devices) -> int | None:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def run(cell, seed: int, seconds: float, trace: bool, devices) -> dict:
    """Set-up, window and comparison of one run; the result line's dict."""
    import jax

    from hyperspace_tpu.serve.scheduler import QueryScheduler
    from hyperspace_tpu.telemetry.metrics import REGISTRY
    from hyperspace_tpu.utils.backend import breaker_state, enable_compile_cache
    from hyperspace_tpu.utils.rpc_meter import METER

    enable_compile_cache()
    compiles = Compiles()
    cfg = cell.config
    span = jax.profiler.TraceAnnotation if trace else contextlib.nullcontext
    with GcPauses() as gc_pauses, tempfile.TemporaryDirectory(
            prefix="perfbench_") as tmp:
        ws = os.path.join(tmp, "warehouse")
        data = tpch_data.generate(ws, cfg, seed, keep=cell.tables())
        session = new_session(ws, cfg)
        tpch_data.build_indexes(session, ws, cfg)
        session.enable_hyperspace()
        pool = traffic.pools(cell.traffic, cell.queries,
                             tpch_data.domain(cfg), seed)
        sched = QueryScheduler(max_concurrent=int(cell.traffic["clients"]))
        try:
            warmed = warm_up(sched, cell, session, ws, pool)
            # every window starts from the same collector state, so a seed
            # cannot move a full collection into or out of it
            gc.collect()
            setup_s = time.perf_counter() - _T0
            degrades0 = REGISTRY.counter("device.degrades").value
            totals0, meter0, c0 = _totals(), METER.snapshot(), compiles.n
            g0 = len(gc_pauses.pauses)
            trace_dir = os.path.join(tmp, "trace")
            if trace:
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                opts.host_tracer_level = 1
                jax.profiler.start_trace(trace_dir, profiler_options=opts)
            try:
                answers, window_s = window(sched, cell, session, ws, pool,
                                           seed, seconds, span)
            finally:
                if trace:
                    jax.profiler.stop_trace()
            in_window = compiles.n - c0
            gcs = gc_pauses.pauses[g0:]
            meter = METER.delta_since(meter0)
            totals1 = _totals()
            degrades = REGISTRY.counter("device.degrades").value - degrades0
        finally:
            sched.shutdown()
        peak = memory_peak(devices)
        reduced = devtrace.reduce_dir(trace_dir) if trace else None
        t_ref = time.perf_counter()
        checks = check.judge(cell, data, answers, warmed, degrades,
                             breaker_state())
        t_ref = time.perf_counter() - t_ref

    ok = [a for a in answers if a.error is None]
    lat_ms = sorted(a.latency_s * 1000.0 for a in answers)
    dev = devices[0]
    out = {
        "correct": all(c["value"] <= c["limit"] for c in checks.values()),
        "attempted": len(answers),
        "failed": len(answers) - len(ok),
        "metrics": {},
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(devices), "memory_peak_bytes": peak},
    }
    if trace:
        must_read = sum(cell.queries[a.query].must_read_bytes(data, a.params)
                        for a in ok)
        m = Measured(len(answers), totals0, totals1, meter, reduced,
                     must_read, dev.device_kind)
        for metric in cell.per_layer:
            v = cell.reader(metric["name"])(m)
            if v is not None:
                out["metrics"][metric["name"]] = {"value": v,
                                                  "unit": metric["unit"]}
        if reduced:
            out["device"]["busy_s"] = reduced["busy_s"]
            out["device"]["window_s"] = reduced["window_s"]
            out["breakdown"] = {"device_ops": reduced["device_ops"],
                                "idle_gaps": reduced["idle_gaps"]}
    else:
        e2e = {
            "qps": len(ok) / window_s,
            "p50_ms": statistics.median(lat_ms) if lat_ms else None,
            "p95_ms": (statistics.quantiles(lat_ms, n=20)[18]
                       if len(lat_ms) >= 2 else None),
            "setup_s": setup_s,
        }
        for metric in cell.end_to_end:
            v = e2e[cells.base(metric["name"])]
            if v is not None:
                out["metrics"][metric["name"]] = {"value": v,
                                                  "unit": metric["unit"]}
    print(f"perfbench: {cell.name} seed {seed}: {len(answers)} queries in "
          f"{window_s:.3f}s, {in_window} compiles in the window, "
          f"{compiles.n} in all ({compiles.seconds:.3f}s); reference and "
          f"comparison {t_ref:.3f}s; {len(gcs)} gc pauses in the window, "
          f"{sum(d for _, d in gcs):.3f}s, longest "
          f"{max((d for _, d in gcs), default=0.0):.3f}s (gen2: "
          f"{sum(1 for g, _ in gcs if g == 2)})", file=sys.stderr)
    for query in sorted(cell.queries):
        mine = sorted(a.latency_s * 1000.0 for a in answers
                      if a.query == query)
        if mine:
            med = statistics.median(mine)
            stalls = sum(1 for x in mine if x > 3 * med)
            print(f"perfbench: {query}: {len(mine)} queries, median "
                  f"{med:.3f} ms, max {mine[-1]:.3f} ms, {stalls} over 3x "
                  f"the median", file=sys.stderr)
    out["checks"] = checks
    return out


def parse_args(argv):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return p.parse_args(argv)


def main(argv=None) -> int:
    args = parse_args(argv)
    cell = cells.Cell(args.workload)
    os.environ.update(SERVED_ENV)
    os.environ.update({k: str(v) for k, v in cell.config["env"].items()})
    try:
        devices = require_chips(cell.chips)
    except NoChip as e:
        print(f"perfbench: {e}; no result", file=sys.stderr)
        return 2
    out = run(cell, args.seed, args.seconds, bool(args.trace), devices)
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
