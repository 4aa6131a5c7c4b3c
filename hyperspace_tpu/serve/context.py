"""Per-query serving context: identity, priority, cooperative cancellation.

Every query the scheduler admits runs its whole execution (plan → streamers
→ kernels) on one worker thread under a ``QueryContext`` installed via
``query_scope``. The context is what makes a query addressable while it
runs: the budget accountant tags reservations with it, the trace layer's
``serve:query`` span carries its id, and ``cancel()`` flips the one flag
every streaming loop polls.

Cancellation is cooperative and chunk-granular: ``check_cancelled()`` sits
inside the ordered chunk/pair streamers (columnar/io.iter_chunks,
bucket_join._iter_bucket_pairs), the pipelined fold loops (plan/tpu_exec),
and the per-node executor walk (plan/executor.execute_plan), so a cancelled
query unwinds at the next chunk boundary. The unwind path is the streams'
existing ``finally`` blocks — read-ahead futures cancel, IO pools release,
and budget reservations return to the global accountant — which is exactly
the "releases everything within a scheduler tick" contract tests pin.

``QueryCancelledError`` deliberately derives from ``BaseException`` (the
``InjectedCrash`` precedent in utils/faults.py): the device tier wraps its
streamed execution in ``except Exception`` handlers that degrade to a host
re-run via the breaker, and a swallowed cancellation would *re-execute* the
query on the host instead of stopping it. No ``except Exception`` on the
way out may absorb a cancel.
"""

from __future__ import annotations

import contextvars
import itertools
import threading
from typing import Optional


class QueryCancelledError(BaseException):
    """The running query was cancelled via its handle. BaseException so the
    device tier's ``except Exception`` degrade-to-host handlers can never
    swallow a cancel into a breaker event + host re-execution (see module
    docstring); catch it explicitly via ``QueryHandle.result()``."""


_ids = itertools.count(1)


class QueryContext:
    """Identity + cancellation flag of one admitted query. ``tenant`` is
    the owning tenant's name (the QoS dimension: per-tenant queues, budget
    partitions, and ledger rollups all key on it; "default" when the
    submitter never said otherwise) and ``deadline_s`` the optional SLO
    the admission door checked against. ``device_home`` is the ordinal of
    the chip the query runs on when its session deploys on several
    (``hyperspace.tpu.exec.meshDevices``): the scheduler's
    ``home_chooser`` picks it (tenant-weighted occupancy argmin) when the
    query first reaches the device (``query_home``); None outside the
    scheduler or on one chip."""

    __slots__ = ("query_id", "label", "priority", "tenant", "deadline_s",
                 "device_home", "home_chooser", "approx_fraction",
                 "_cancelled")

    def __init__(self, label: str = "query", priority: int = 0,
                 tenant: str = "default",
                 deadline_s: Optional[float] = None):
        self.query_id = next(_ids)
        self.label = label
        self.priority = priority
        self.tenant = tenant
        self.deadline_s = deadline_s
        self.device_home: Optional[int] = None
        self.home_chooser = None  # (chips) -> ordinal, set at dispatch
        # sampling fraction the QoS degrade policy selected for this query
        # (serve/scheduler.py); None = exact. plan/sampling.py reads it at
        # collect time and engages the sampled tier when eligible.
        self.approx_fraction: Optional[float] = None
        self._cancelled = threading.Event()

    def cancel(self) -> None:
        self._cancelled.set()

    @property
    def cancelled(self) -> bool:
        return self._cancelled.is_set()

    def __repr__(self) -> str:  # pragma: no cover - debugging aid
        return f"QueryContext(id={self.query_id}, label={self.label!r})"


# the running query of the current thread (None outside the serving layer);
# a contextvar so nested scopes restore correctly on exit
_current: contextvars.ContextVar = contextvars.ContextVar(
    "hyperspace_serve_query", default=None
)


def current_query() -> Optional[QueryContext]:
    """The QueryContext this thread is executing under, or None (direct
    ``collect()`` callers outside any scheduler)."""
    return _current.get()


class query_scope:
    """Install ``ctx`` as the thread's current query for the duration."""

    __slots__ = ("_ctx", "_token")

    def __init__(self, ctx: QueryContext):
        self._ctx = ctx
        self._token = None

    def __enter__(self) -> QueryContext:
        self._token = _current.set(self._ctx)
        return self._ctx

    def __exit__(self, *exc) -> bool:
        _current.reset(self._token)
        return False


def check_cancelled() -> None:
    """Raise ``QueryCancelledError`` when the current query was cancelled.
    One contextvar read + one Event check — cheap enough for per-chunk and
    per-plan-node call sites; a no-op outside the serving layer."""
    ctx = _current.get()
    if ctx is not None and ctx.cancelled:
        raise QueryCancelledError(
            f"query {ctx.query_id} ({ctx.label}) cancelled"
        )


def query_home(session) -> "tuple | None":
    """The home chip of the running served query on ``session``'s
    deployment, as ``(ordinal, devices)`` for ``utils.backend.on_home``:
    chosen by the scheduler on first use and kept for the query. None
    outside the scheduler or on a deployment of one chip."""
    ctx = _current.get()
    if ctx is None or ctx.home_chooser is None:
        return None
    from ..parallel.placement import mesh_devices

    devices = mesh_devices(session)
    if not devices:
        return None
    if ctx.device_home is None:
        ctx.device_home = ctx.home_chooser(len(devices))
    return ctx.device_home, tuple(devices)
