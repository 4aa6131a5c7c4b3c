"""Device-RPC accounting: dispatches, fetches, and transfer bytes.

Every jitted-kernel dispatch and every blocking fetch pays a fixed host
round trip on top of its bytes, so the device tier's economics are decided
by COUNTS as much as bytes. The meter makes those
counts first-class: execution paths record each kernel dispatch, each
``device_get``, and each host->device transfer; benchmarks snapshot the
counters around a query and publish the deltas (VERDICT r3 item 1: "record
per-query RPC/transfer counts in the artifact so losses are attributable").

Thread-safe; negligible overhead (a lock + integer adds per event, against
milliseconds-scale device work).
"""

from __future__ import annotations

from ..staticcheck.concurrency import TrackedLock


def _tree_nbytes(value) -> int:
    if isinstance(value, (tuple, list)):
        return sum(_tree_nbytes(v) for v in value)
    if isinstance(value, dict):
        return sum(_tree_nbytes(v) for v in value.values())
    return getattr(value, "nbytes", 0)


class RpcMeter:
    def __init__(self) -> None:
        self._lock = TrackedLock("rpc_meter")
        self.dispatches = 0  # jitted kernel calls (async enqueue RPCs)
        self.fetches = 0  # blocking device_get round trips
        self.uploads = 0  # host->device array transfers
        self.upload_bytes = 0
        self.fetch_bytes = 0

    def record_dispatch(self, n: int = 1) -> None:
        # the one funnel every jitted-kernel dispatch passes through right
        # before the call — which makes it the `device.dispatch` injection
        # point: an armed fault raises here, inside the caller's
        # record_device_failure try block, exactly like a device error
        from . import faults

        faults.fire("device.dispatch")
        with self._lock:
            self.dispatches += n

    def record_chip_dispatch(self, leaves, chips: tuple) -> None:
        """Count one kernel call of a query on a deployment of several
        chips (``chips``, the deployment's devices) in
        ``device.dispatch.d<k>``, k the chip it runs on: the device its
        first committed operand among ``leaves`` lives on, which JAX runs
        it on, else the calling thread's default device (the query's home
        chip), where uncommitted operands go."""
        import jax

        device = None
        for leaf in leaves:
            if isinstance(leaf, jax.Array) and leaf.committed:
                device = next(iter(leaf.devices()))
                break
        if device is None:
            device = jax.config.jax_default_device
        if device in chips and self is METER:
            from ..telemetry.metrics import REGISTRY

            REGISTRY.counter(f"device.dispatch.d{chips.index(device)}").inc()

    def record_upload(self, nbytes: int, n: int = 1) -> None:
        # `device.upload` injection point: every REAL host->device transfer
        # (monolithic, chunk-streamed, join, mesh) meters through here —
        # a device-cache hit moves no bytes, so it never faults either
        from . import faults

        faults.fire("device.upload")
        with self._lock:
            self.uploads += n
            self.upload_bytes += nbytes
        if self is METER:
            from ..telemetry.metrics import REGISTRY

            REGISTRY.counter("rpc.upload_bytes").inc(nbytes)

    def record_fetch(self, nbytes: int, n: int = 1) -> None:
        with self._lock:
            self.fetches += n
            self.fetch_bytes += nbytes
        if self is METER:
            from ..telemetry.metrics import REGISTRY

            REGISTRY.counter("rpc.fetch_bytes").inc(nbytes)

    def snapshot(self) -> dict:
        # all five counters read under the SAME lock acquisition the writers
        # hold, so a snapshot is a consistent point-in-time cut — reading the
        # public attributes directly can interleave with a concurrent
        # record_upload and pair a new `uploads` with an old `upload_bytes`
        with self._lock:
            return {
                "dispatches": self.dispatches,
                "fetches": self.fetches,
                "uploads": self.uploads,
                "upload_bytes": self.upload_bytes,
                "fetch_bytes": self.fetch_bytes,
            }

    @staticmethod
    def delta(before: dict, after: dict) -> dict:
        return {k: after[k] - before[k] for k in before}

    def delta_since(self, before: dict) -> dict:
        return self.delta(before, self.snapshot())

    def measure(self) -> "MeterDelta":
        """Context manager capturing the meter delta around a block:

            with METER.measure() as m:
                run_query()
            print(m.delta["dispatches"])

        Replaces the snapshot-subtract pattern each caller re-implemented.
        """
        return MeterDelta(self)


class MeterDelta:
    def __init__(self, meter: RpcMeter):
        self._meter = meter
        self._before: dict = {}
        self.delta: dict = {}

    def __enter__(self) -> "MeterDelta":
        self._before = self._meter.snapshot()
        return self

    def __exit__(self, *exc) -> bool:
        self.delta = self._meter.delta_since(self._before)
        return False


METER = RpcMeter()


def device_get(tree):
    """``jax.device_get`` with fetch accounting — use this in execution
    paths instead of calling jax directly so every blocking round trip
    lands in the meter (and, when tracing is on, in a `fetch` span). The
    one funnel every blocking fetch passes through, so it is also the
    serving query's chokepoint for two phases: ``device_wait`` (the device
    finishing what the fetch reads) and ``fetch`` (the copy of ready
    arrays)."""
    import jax

    from ..telemetry import attribution, trace
    from . import faults

    with trace.span("fetch", annotate=False):
        faults.fire("device.fetch")
        with attribution.phase("device_wait"):
            jax.block_until_ready(tree)
        with attribution.phase("fetch"):
            out = jax.device_get(tree)
        nbytes = _tree_nbytes(out)
        METER.record_fetch(nbytes)
        trace.add_attr("nbytes", nbytes)
    return out
