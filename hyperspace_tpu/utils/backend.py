"""JAX backend resolution and the device-execution circuit breaker.

``platform()`` and ``device_count()`` resolve the backend once per process
through plain ``jax.default_backend()`` / ``jax.devices()``. A backend init
that fails raises to the caller: no query moves to the host because the
device was never reached.
"""

from __future__ import annotations

import contextlib
import contextvars
import os
import time

from ..staticcheck.concurrency import TrackedLock, guarded_by
from . import env

_lock = TrackedLock("backend.state")
_state: dict = guarded_by(
    {"platform": None, "devices": None}, _lock, name="utils.backend._state"
)


def platform() -> str:
    """The default JAX backend's platform name (``tpu``, ``cpu``, ...)."""
    with _lock:
        if _state["platform"] is None:
            import jax

            _state["platform"] = jax.default_backend()
        return _state["platform"]


def device_count() -> int:
    """``len(jax.devices())`` of the default backend."""
    with _lock:
        if _state["devices"] is None:
            import jax

            _state["devices"] = len(jax.devices())
        return _state["devices"]


# a fixed path: a directory named after a temp name, a pid or the time is
# new, and empty, on every run
_CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)


def enable_compile_cache() -> str:
    """Turn JAX's persistent compilation cache on for an entry point
    (chip_smoke.py, bench.py) before its first compile; returns the cache
    directory. Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it
    itself and no path is set here; otherwise the cache lives at
    ``<checkout>/.jax_cache``. Never called at import or by the tests."""
    import jax

    # hslint: HS301 — JAX's own variable, not a hyperspace knob
    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        cache_dir = _CHECKOUT_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    # the query kernels compile in about a second each: cache all of them
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return cache_dir


# ---------------------------------------------------------------------------
# home chips
# ---------------------------------------------------------------------------
# A session deployed on N chips (``hyperspace.tpu.exec.meshDevices`` = N)
# holds a replica of its device state on each chip, and the serving
# scheduler runs each query on one of them, its home chip: the query's
# thread runs with that chip as JAX's default device, so its uploads,
# kernels and device caches all live there.

_home: contextvars.ContextVar = contextvars.ContextVar("hs_home", default=None)


def home() -> "tuple | None":
    """``(ordinal, devices)`` of the running query's home chip, or None
    outside a homed query."""
    return _home.get()


@contextlib.contextmanager
def on_home(h: "tuple | None"):
    """Run the block on home chip ``h`` (``(ordinal, devices)``, as
    ``home()`` gives it) as JAX's default device; None runs it as is."""
    if h is None:
        yield
        return
    import jax

    token = _home.set(h)
    try:
        with jax.default_device(h[1][h[0]]):
            yield
    finally:
        _home.reset(token)


def _reset_for_testing() -> None:
    with _lock:
        _state.update(platform=None, devices=None)
        _breaker.update(
            state=CLOSED, opened_at=0.0, cooldown=0.0, reopens=0, last_kind=None
        )
    _set_breaker_gauge(CLOSED)


# ---------------------------------------------------------------------------
# device-execution circuit breaker
# ---------------------------------------------------------------------------
# The query rewrite is fail-open in the reference (ApplyHyperspace.scala:60-64);
# the device tier extends that to EXECUTION: a device kernel failing mid-query
# (e.g. a device runtime error) degrades that query to the host executor
# instead of failing it. What happens NEXT depends on the failure kind:
#
#   permanent (compile/lowering/shape errors — deterministic, re-failing
#   forever)                  -> LATCHED: device tier off for the process,
#                                the original always-latch behavior
#   transient (runtime errors, timeouts, RESOURCE_EXHAUSTED/OOM — the device
#   may come back)            -> OPEN: device tier off for a cooldown
#                                (HYPERSPACE_BREAKER_COOLDOWN, default 30 s),
#                                then ONE query probes it (HALF_OPEN); a
#                                probe success closes the breaker, a probe
#                                failure reopens with doubled cooldown
#                                (capped at 16x)
#
# HYPERSPACE_DEVICE_STRICT=1 re-raises instead (set by the test harness and
# chip_smoke.py so device bugs surface rather than hide behind host
# fallbacks).
# State is surfaced through the `breaker.state` gauge, `breaker.*` counters,
# and `hs.profile`; the clock is injectable so tests drive cooldowns without
# sleeping.

import logging

_logger = logging.getLogger(__name__)

CLOSED, OPEN, HALF_OPEN, LATCHED = "closed", "open", "half_open", "latched"
_STATE_CODES = {CLOSED: 0, OPEN: 1, HALF_OPEN: 2, LATCHED: 3}
_MAX_COOLDOWN_FACTOR = 16

_breaker: dict = guarded_by(
    {"state": CLOSED, "opened_at": 0.0, "cooldown": 0.0, "reopens": 0,
     "last_kind": None},
    _lock,
    name="utils.backend._breaker",
)

_clock = time.monotonic


def _set_clock_for_testing(fn) -> None:
    """Inject a fake monotonic clock (tests drive cooldown expiry)."""
    global _clock
    _clock = fn


def _count(event: str) -> None:
    from ..telemetry.metrics import REGISTRY

    REGISTRY.counter(f"breaker.{event}").inc()


def _set_breaker_gauge(state: str) -> None:
    from ..telemetry.metrics import REGISTRY

    REGISTRY.gauge("breaker.state").set(_STATE_CODES[state])


def classify_device_failure(err: BaseException) -> str:
    """"permanent" for deterministic compile/lowering/shape errors (retrying
    the same query re-fails forever — latch, exactly the old behavior);
    "transient" for runtime/transport errors that a healthy device would not
    produce (the tier deserves a recovery probe). Unknown exception types
    default to transient: an unclassified runtime error wrongly latching
    the tier off forever is the costlier mistake."""
    if isinstance(err, (TypeError, ValueError, NotImplementedError)):
        return "permanent"  # tracing/shape errors are deterministic
    if isinstance(err, (OSError, ConnectionError, TimeoutError, MemoryError)):
        return "transient"
    msg = str(err).lower()
    if any(
        s in msg
        for s in ("lowering", "compilation", "invalid argument",
                  "unimplemented", "tracer", "unsupported")
    ):
        return "permanent"
    return "transient"


def breaker_state() -> str:
    """Current breaker state WITHOUT side effects (reports, hs.profile)."""
    with _lock:
        return _breaker["state"]


def breaker_snapshot() -> dict:
    """Report block for bench/chaos artifacts."""
    from ..telemetry.metrics import REGISTRY

    def val(name: str) -> int:
        m = REGISTRY.get(name)
        return 0 if m is None else int(m.value)

    with _lock:
        state = _breaker["state"]
        kind = _breaker["last_kind"]
    return {
        "state": state,
        "last_failure_kind": kind,
        "opened": val("breaker.opened"),
        "reopened": val("breaker.reopened"),
        "probes": val("breaker.probes"),
        "recovered": val("breaker.recovered"),
        "latched": val("breaker.latched"),
    }


def device_healthy() -> bool:
    """Gate every device-tier entry point. CLOSED admits everything (the
    fast path is one unlocked dict read). OPEN admits nothing until the
    cooldown elapses, then flips to HALF_OPEN and admits exactly the
    flipping caller as the recovery probe; other callers stay on the host
    tier until the probe resolves via record_device_success/_failure."""
    if _breaker["state"] == CLOSED:  # racy read: worst case one extra lock
        return True
    with _lock:
        state = _breaker["state"]
        if state == CLOSED:
            return True
        if state in (LATCHED, HALF_OPEN):
            return False
        # OPEN: probe when the cooldown has elapsed
        if _clock() - _breaker["opened_at"] >= _breaker["cooldown"]:
            _breaker["state"] = HALF_OPEN
            _count("probes")
            _set_breaker_gauge(HALF_OPEN)
            return True
        return False


def device_strict() -> bool:
    return env.env_bool("HYPERSPACE_DEVICE_STRICT")


def record_device_success() -> None:
    """Signal one successful device execution: a HALF_OPEN probe succeeding
    closes the breaker and resets the cooldown ladder. No-op when CLOSED
    (the common case — one unlocked read)."""
    if _breaker["state"] == CLOSED:
        return
    with _lock:
        if _breaker["state"] != HALF_OPEN:
            return
        _breaker.update(state=CLOSED, reopens=0, cooldown=0.0, last_kind=None)
    _count("recovered")
    _set_breaker_gauge(CLOSED)
    _logger.warning("device tier recovered; breaker closed")


def record_device_failure(err: BaseException) -> None:
    if device_strict():
        raise err
    kind = classify_device_failure(err)
    # every degrade-to-host occurrence (not just state TRANSITIONS like
    # breaker.opened): the per-query attribution ledger charges this to the
    # query that hit the failure, and /healthz derives its rolling degrade
    # rate from it
    from ..telemetry.metrics import REGISTRY

    REGISTRY.counter("device.degrades").inc()
    with _lock:
        prev = _breaker["state"]
        _breaker["last_kind"] = kind
        if kind == "permanent":
            _breaker["state"] = LATCHED
        else:
            base = env.env_float("HYPERSPACE_BREAKER_COOLDOWN")
            reopens = _breaker["reopens"] + 1 if prev in (OPEN, HALF_OPEN) else 0
            factor = min(2 ** reopens, _MAX_COOLDOWN_FACTOR)
            _breaker.update(
                state=OPEN, opened_at=_clock(), cooldown=base * factor,
                reopens=reopens,
            )
        new = _breaker["state"]
    if new == LATCHED:
        _count("latched")
        if prev != LATCHED:
            _logger.warning(
                "device execution failed permanently (%s); host paths take "
                "over for this process", err,
            )
    else:
        _count("reopened" if prev in (OPEN, HALF_OPEN) else "opened")
        if prev == CLOSED:
            _logger.warning(
                "device execution failed (%s); breaker open, host paths "
                "take over until the cooldown probe", err,
            )
    _set_breaker_gauge(new)
