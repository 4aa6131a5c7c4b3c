"""The global streaming byte budget: one ledger for every read-ahead stream.

Before the serving layer, each streaming consumer accounted
``HYPERSPACE_IO_BUDGET_MB`` privately: the pipelined scan streamer
(columnar/io.iter_chunks) and the bucketed-join pair loader
(bucket_join._iter_bucket_pairs) each held their own counter, so a query
that both streamed a scan and ran a bucketed join reserved the budget
twice, and N concurrent queries multiplied it by N. The
``BudgetAccountant`` below is the single process-wide ledger both paths —
and any number of concurrent queries — now reserve through, bounded by
``HYPERSPACE_GLOBAL_BUDGET_MB``.

Deadlock freedom by construction: reservations NEVER block.

- A stream holding zero bytes is always granted, even past the limit (the
  *progress guarantee*: every admitted stream can always decode its next
  chunk, so no admission order can wedge).
- A stream already holding bytes is granted only while the global total
  stays within the limit; otherwise ``try_reserve`` returns False and the
  stream simply stops pumping read-ahead until its own deliveries free
  bytes.

Backpressure therefore stalls exactly the streams that already hold decoded
bytes — the hungriest streams wait the longest — and a stalled stream can
never block another stream's first chunk, which is how a saturated
low-priority scan cannot starve a freshly admitted high-priority query.

Cancellation integration: ``BudgetStream.close()`` (wired into the
streamers' ``finally`` blocks) returns every outstanding byte to the
ledger, so a cancelled query's reservations release the moment its stream
generator unwinds.

Tenant partitioning (multi-tenant QoS): every stream opened under a
serving query carries its tenant, and while bytes are held by more than
one tenant each tenant is additionally capped at its share of the limit
(``budget_fraction`` when configured on the tenant, else its
weight-proportional share among the tenants currently holding bytes) —
a stalled hog tenant saturates only its own partition, never the whole
ledger (``serve.budget.tenant_stalls`` counts the partition stalls). The
zero-holder progress grant is untouched, and the check is only consulted
when ≥2 tenants hold bytes, so single-tenant behavior is bit-identical.

Second ledger — DEVICE-resident bytes: the same accountant class, bounded
by ``HYPERSPACE_DEVICE_BUDGET_MB``, accounts the padded upload footprint of
in-flight bucketed-join band waves (``plan/device_join._BandScheduler``
reserves a wave before dispatch and releases it when the wave's results
have been fetched back to the host). Instead of declining to the host tier
when a build side exceeds device memory, the join *parks* the wave —
spilling already-dispatched waves' results to the host to drain its own
reservations — and re-admits it when reservations drain; the identical
zero-holder force-grant rule keeps N concurrent spilling joins deadlock-
free on the shared ledger. ``wait_for_release`` is the park path's bounded
wait primitive: parked consumers sleep on the release condition instead of
spinning, and every release/close wakes them.
"""

from __future__ import annotations

import threading

from typing import Optional

from ..staticcheck.concurrency import TrackedLock, guarded_by
from ..staticcheck.lifecycle import release_resource, tracked_resource
from ..utils import env
from .context import current_query


class BudgetStream:
    """One consumer's handle on the global ledger (a scan stream, a join
    pair loader). Not thread-safe across consumers by design — each stream
    is pumped from exactly one consumer thread; the accountant's lock
    serializes the shared ledger. ``tenant`` is the owning serving
    tenant's name (None outside the scheduler) — the key the per-tenant
    budget partition stalls on."""

    __slots__ = ("_acct", "label", "query_id", "tenant", "held", "_closed",
                 "_lc")

    def __init__(self, acct: "BudgetAccountant", label: str, query_id,
                 tenant: "str | None" = None):
        self._acct = acct
        self.label = label
        self.query_id = query_id
        self.tenant = tenant
        self.held = 0
        self._closed = False
        self._lc = tracked_resource(
            "budget.stream", f"{acct.name}/{label}", query=query_id,
            tenant=tenant,
        )

    def try_reserve(self, nbytes: int) -> bool:
        """Reserve ``nbytes`` for one in-flight chunk; False = over budget
        (stop pumping read-ahead and retry after the next delivery)."""
        return self._acct._reserve(self, nbytes)

    def release(self, nbytes: int) -> None:
        """Return ``nbytes`` on chunk delivery."""
        self._acct._release(self, nbytes)

    def close(self) -> None:
        """Return any outstanding reservation (abort/cancel path included);
        idempotent."""
        if not self._closed:
            self._closed = True
            self._acct._close(self)
            release_resource(self._lc)

    def __enter__(self) -> "BudgetStream":
        return self

    def __exit__(self, *exc) -> bool:
        self.close()
        return False


class BudgetAccountant:
    """Process-wide byte ledger. All mutation under one TrackedLock so the
    lock-order audit covers it; metric emission stays outside the lock."""

    def __init__(self, max_bytes: int, name: str = "serve.budget"):
        self.max_bytes = max_bytes
        self.name = name  # metric prefix: <name>.{reservations,...}, <name>_bytes
        self._lock = TrackedLock(name)
        self._held = 0
        self._streams: dict[int, BudgetStream] = {}
        # release notification for parked consumers (plain leaf Condition:
        # never held while acquiring anything else, so it skips the audit
        # by the same rule as the per-metric value locks)
        self._released = threading.Condition(threading.Lock())

    # --- stream lifecycle -------------------------------------------------

    def stream(self, label: str, query=None, tenant=None) -> BudgetStream:
        """Open a consumer handle; ``query``/``tenant`` default to the
        thread's current serving context (None outside the scheduler)."""
        if query is None:
            ctx = current_query()
            if ctx is not None:
                query = ctx.query_id
                if tenant is None:
                    tenant = getattr(ctx, "tenant", None)
        s = BudgetStream(self, label, query, tenant)
        with self._lock:
            self._streams[id(s)] = s
        return s

    def _tenant_over_share_locked(self, s: BudgetStream, nbytes: int) -> bool:
        """Per-tenant partition of the ledger: while bytes are held by MORE
        THAN ONE tenant, each tenant is capped at its share of the limit —
        ``budget_fraction`` when configured, else weight-proportional among
        the tenants currently holding bytes — so one stalled hog tenant
        cannot pin the whole ledger. With zero or one tenant in play (the
        whole pre-QoS world, and any single-tenant process) this is never
        consulted, keeping that path bit-identical."""
        if s.tenant is None:
            return False
        holders = {
            st.tenant
            for st in self._streams.values()
            if st.held > 0 and st.tenant is not None
        }
        holders.add(s.tenant)
        if len(holders) <= 1:
            return False
        from .tenant import TENANTS

        tenants = {name: TENANTS.get(name) for name in holders}
        total_weight = sum(
            max(1e-6, t.weight) for t in tenants.values()
        )
        mine = tenants[s.tenant]
        share = (
            mine.budget_fraction
            if mine.budget_fraction is not None
            else max(1e-6, mine.weight) / total_weight
        )
        limit = self.max_bytes * max(0.0, min(1.0, share))
        held_t = sum(
            st.held for st in self._streams.values()
            if st.tenant == s.tenant
        )
        return held_t + nbytes > limit

    def _reserve(self, s: BudgetStream, nbytes: int) -> bool:
        forced = False
        tenant_stall = False
        with self._lock:
            if s.held > 0 and self._held + nbytes > self.max_bytes:
                granted = False
            elif s.held > 0 and self._tenant_over_share_locked(s, nbytes):
                granted = False
                tenant_stall = True
            else:
                granted = True
                forced = self._held + nbytes > self.max_bytes
                s.held += nbytes
                self._held += nbytes
            occupancy = self._held
        from ..telemetry.metrics import REGISTRY

        if granted:
            REGISTRY.counter(f"{self.name}.reservations").inc()
            if forced:
                # zero-holder progress grant past the limit
                REGISTRY.counter(f"{self.name}.force_grants").inc()
            REGISTRY.gauge(f"{self.name}_bytes").set(occupancy)
        else:
            REGISTRY.counter(f"{self.name}.stalls").inc()
            if tenant_stall:
                REGISTRY.counter(f"{self.name}.tenant_stalls").inc()
        return granted

    def _release(self, s: BudgetStream, nbytes: int) -> None:
        with self._lock:
            n = min(nbytes, s.held)
            s.held -= n
            self._held -= n
            occupancy = self._held
        self._notify_released()
        from ..telemetry.metrics import REGISTRY

        REGISTRY.gauge(f"{self.name}_bytes").set(occupancy)

    def _close(self, s: BudgetStream) -> None:
        with self._lock:
            self._held -= s.held
            s.held = 0
            self._streams.pop(id(s), None)
            occupancy = self._held
        self._notify_released()
        from ..telemetry.metrics import REGISTRY

        REGISTRY.gauge(f"{self.name}_bytes").set(occupancy)

    def _notify_released(self) -> None:
        with self._released:
            self._released.notify_all()

    def wait_for_release(self, timeout: float) -> None:
        """Block until some stream releases/closes or ``timeout`` elapses —
        the parked-consumer wait primitive. Callers MUST loop (a wakeup is
        a hint, not a grant) and poll cancellation between waits; the
        bounded timeout is what keeps the park path deadlock-free even if
        every other holder is itself parked."""
        with self._released:
            self._released.wait(timeout)

    # --- introspection ----------------------------------------------------

    def held_bytes(self) -> int:
        with self._lock:
            return self._held

    def state(self) -> dict:
        """Aggregate + per-stream + per-tenant occupancy for hs.profile /
        serve_state."""
        with self._lock:
            streams = [
                {"label": s.label, "query": s.query_id,
                 "tenant": s.tenant, "held_bytes": s.held}
                for s in self._streams.values()
            ]
            held = self._held
        tenants: dict[str, int] = {}
        for s in streams:
            if s["tenant"] is not None and s["held_bytes"]:
                tenants[s["tenant"]] = (
                    tenants.get(s["tenant"], 0) + s["held_bytes"]
                )
        return {
            "limit_bytes": self.max_bytes,
            "held_bytes": held,
            "streams": streams,
            "tenants": tenants,
        }

    def check_consistency(self) -> bool:
        """Ledger invariant at quiescence: the total equals the per-stream
        sum, and nothing is held once every stream closed (smoke gates)."""
        with self._lock:
            return self._held == sum(s.held for s in self._streams.values())


def configured_budget_bytes() -> int:
    """``HYPERSPACE_GLOBAL_BUDGET_MB`` in bytes. Migration fallback: an
    explicitly-set legacy ``HYPERSPACE_IO_BUDGET_MB`` (the old per-stream
    knob) keeps meaning when the global knob is unset, so existing
    deployments' memory ceilings carry over — now enforced globally instead
    of per stream."""
    raw = env.read_raw("HYPERSPACE_GLOBAL_BUDGET_MB")
    if raw is None:
        legacy = env.read_raw("HYPERSPACE_IO_BUDGET_MB")
        if legacy is not None:
            raw = legacy
    try:
        if raw is not None:
            return int(float(raw) * 2**20)
    except ValueError:
        pass
    return int(env.knob("HYPERSPACE_GLOBAL_BUDGET_MB").default * 2**20)


_global_lock = TrackedLock("serve.budget_singleton")  # guards the swap only
_GLOBAL: Optional[BudgetAccountant] = None


def global_budget() -> BudgetAccountant:
    """The process-wide accountant every streaming consumer reserves
    through. Budget size is read once at first use; tests swap it with
    ``reset_global_budget()`` after changing the knob."""
    global _GLOBAL
    with _global_lock:
        if _GLOBAL is None:
            _GLOBAL = BudgetAccountant(configured_budget_bytes())
        return _GLOBAL


def reset_global_budget() -> BudgetAccountant:
    """Re-read the knob and install a fresh ledger (tests; never mid-query)."""
    global _GLOBAL
    with _global_lock:
        _GLOBAL = BudgetAccountant(configured_budget_bytes())
        return _GLOBAL


# ---------------------------------------------------------------------------
# the device-resident ledger (memory-adaptive spilling joins)
# ---------------------------------------------------------------------------


def configured_device_budget_bytes() -> int:
    """``HYPERSPACE_DEVICE_BUDGET_MB`` in bytes; 0 disables the ledger
    (joins keep the pre-adaptive fixed-threshold behavior)."""
    try:
        return int(env.env_float("HYPERSPACE_DEVICE_BUDGET_MB") * 2**20)
    except ValueError:
        return int(env.knob("HYPERSPACE_DEVICE_BUDGET_MB").default * 2**20)


def _device_budget_name(ordinal: int) -> str:
    # ordinal 0 keeps the historical metric prefix EXACTLY so one-chip
    # telemetry (and every existing dashboard/test) is byte-for-byte
    # unchanged; the other chips of a deployment suffix .d<N>
    return (
        "serve.device_budget" if ordinal == 0
        else f"serve.device_budget.d{ordinal}"
    )


# keyed by mesh device ordinal; every lookup/install is under _global_lock
_DEVICES: dict[int, BudgetAccountant] = guarded_by(
    {}, _global_lock, name="serve.budget._DEVICES"
)


def device_budget(ordinal: int = 0) -> BudgetAccountant:
    """The DEVICE-byte accountant for one mesh device ordinal — every
    bucketed-join band scheduler reserves wave footprints through these
    (N concurrent spilling joins share each device's ledger). Ordinal 0
    is the historical single-device ledger; on a deployment of several
    chips (``hyperspace.tpu.exec.meshDevices``) a wave on chip d, the
    query's home chip, reserves through ordinal d, so each chip's joins
    park on its own ledger.
    Each ledger is sized by ``HYPERSPACE_DEVICE_BUDGET_MB`` (the knob is
    per device: a mesh multiplies the fleet budget by its size) at first
    use; ``reset_device_budget()`` re-reads the knob (tests/bench)."""
    with _global_lock:
        acct = _DEVICES.get(ordinal)
        if acct is None:
            acct = BudgetAccountant(
                configured_device_budget_bytes(),
                name=_device_budget_name(ordinal),
            )
            _DEVICES[ordinal] = acct
        return acct


def device_budgets() -> dict[int, BudgetAccountant]:
    """Snapshot of every instantiated per-device accountant (telemetry
    rollups; ordinals appear lazily as placement first targets them)."""
    with _global_lock:
        return dict(_DEVICES)


def reset_device_budget() -> BudgetAccountant:
    """Re-read the knob and install fresh device ledgers (tests/bench;
    never mid-query). Drops every mesh ordinal and returns the fresh
    ordinal-0 ledger."""
    with _global_lock:
        _DEVICES.clear()
        acct = BudgetAccountant(
            configured_device_budget_bytes(), name=_device_budget_name(0)
        )
        _DEVICES[0] = acct
        return acct
