"""Device-resident array cache.

Repeated queries over the same index chunks re-shipped every column to the
device on every execution; each upload costs host->device bandwidth plus
a round trip, which dominates sub-second queries. This cache keeps the device copy alive keyed by the *source numpy
array's object identity* — the columnar chunk cache (columnar/io.py) serves
shallow copies whose underlying ``.data`` buffers are shared and immutable,
so object identity is a sound content key.

Safety against id() reuse: each entry holds a weakref to the source array
and a lookup only hits when the weakref still resolves to the *same object*
(a dead or rebound ref is evicted). Mutated/derived arrays get fresh ids and
therefore fresh entries. Eviction is least-recently-used by device bytes
(``HYPERSPACE_DEVICE_CACHE_MB``, default 6144; 0 disables).
"""

from __future__ import annotations

import weakref
from collections import OrderedDict
from typing import Callable


from ..staticcheck.concurrency import TrackedLock
from . import env
from .rpc_meter import _tree_nbytes  # one canonical tree-size walker


def _budget_bytes(env_name: str, default_mb: str) -> int:
    return int(env.env_float(env_name, float(default_mb)) * 2**20)


def _cache_counter(name: str, event: str, n: int = 1) -> None:
    from ..telemetry.metrics import REGISTRY

    REGISTRY.counter(f"cache.{name}.{event}").inc(n)


def _cache_gauge(name: str, value: float) -> None:
    from ..telemetry.metrics import REGISTRY

    REGISTRY.gauge(f"cache.{name}.bytes").set(value)


class DeviceArrayCache:
    # default budget sized for a v5e chip (16 GB HBM): 6 GB of resident
    # columns keeps a 50M-row query working set (≈1.8 GB) plus the join
    # indexes hot without re-uploading them on every repeat
    def __init__(self, budget_env: str = "HYPERSPACE_DEVICE_CACHE_MB", default_mb: str = "6144") -> None:
        self._budget_env = budget_env
        self._default_mb = default_mb
        self._metric = "device" if budget_env == "HYPERSPACE_DEVICE_CACHE_MB" else "host_derived"
        self._d: OrderedDict = OrderedDict()
        self._bytes = 0
        self._lock = TrackedLock(f"device_cache.{self._metric}")
        self.hits = 0
        self.misses = 0
        self.evictions = 0
        self.evicted_bytes = 0

    def get_or_put(self, src, key_extra, builder: Callable, meter: bool = True):
        """The device copy of ``src`` (a numpy array) under derivation
        ``key_extra``, built by ``builder()`` on miss. ``builder`` returns a
        device array or a tuple of device arrays."""
        return self.get_or_put_multi((src,), key_extra, builder, meter=meter)

    def get_or_put_multi(self, srcs, key_extra, builder: Callable, meter: bool = True):
        """Like get_or_put but keyed on SEVERAL source arrays at once (e.g. a
        stacked per-join upload derived from every bucket's key buffer): the
        entry hits only while EVERY weakref still resolves to its original
        object, so id reuse on any constituent invalidates the whole stack.
        ``meter=False`` for builders that only derive device-side state from
        arrays already in HBM (the pipeline's chunk concatenation) — device
        bytes without a host->device transfer."""
        budget = _budget_bytes(self._budget_env, self._default_mb)
        if budget <= 0:
            value = builder()
            if meter and self is DEVICE_CACHE:  # cache off: still uploads
                from .rpc_meter import METER

                METER.record_upload(_tree_nbytes(value))
            return value
        srcs = tuple(srcs)
        key = (tuple(id(s) for s in srcs), key_extra)
        with self._lock:
            entry = self._d.get(key)
            if entry is not None:
                refs, value, nbytes = entry
                if all(r() is s for r, s in zip(refs, srcs)):
                    self._d.move_to_end(key)
                    self.hits += 1
                    _cache_counter(self._metric, "hits")
                    return value
                # an id was reused by a different array — stale entry
                del self._d[key]
                self._bytes -= nbytes
            self.misses += 1
        _cache_counter(self._metric, "misses")
        value, nbytes = self._build(key_extra, builder, meter)
        if nbytes > budget:
            return value
        try:
            refs = tuple(weakref.ref(s) for s in srcs)
        except TypeError:  # un-weakref-able source: don't cache
            return value
        with self._lock:
            existing = self._d.get(key)
            if existing is not None:
                # lost a concurrent build race: serve the already-cached
                # object so every caller holds THE resident copy (downstream
                # caches key on buffer identity); our duplicate upload is
                # dropped. The entry's refs are live — we hold srcs, so
                # their ids cannot have been reused.
                value = existing[1]
            else:
                self._d[key] = (refs, value, nbytes)
                self._bytes += nbytes
            evicted_n = evicted_b = 0
            while self._bytes > budget and self._d:
                _, (_r, _v, nb) = self._d.popitem(last=False)
                self._bytes -= nb
                evicted_n += 1
                evicted_b += nb
            self.evictions += evicted_n
            self.evicted_bytes += evicted_b
            occupancy = self._bytes
        if evicted_n:
            _cache_counter(self._metric, "evictions", evicted_n)
            _cache_counter(self._metric, "evicted_bytes", evicted_b)
        _cache_gauge(self._metric, occupancy)
        return value

    def _build(self, key_extra, builder: Callable, meter: bool = True):
        """Run the builder; a DEVICE_CACHE miss IS a host->device transfer,
        so it meters an upload and (when tracing) lands in an `upload` span."""
        if self is not DEVICE_CACHE or not meter:
            value = builder()
            return value, _tree_nbytes(value)
        from ..telemetry import attribution, trace
        from .rpc_meter import METER

        with trace.span("upload", annotate=False, key=str(key_extra)), \
                attribution.phase("upload"):
            value = builder()
            nbytes = _tree_nbytes(value)
            METER.record_upload(nbytes)
            trace.add_attr("nbytes", nbytes)
        return value, nbytes

    def get_or_put_keyed(self, key, builder: Callable):
        """Budgeted LRU entry under an explicit hashable ``key`` (no source
        buffer to validate — for deterministic values like padded masks)."""
        budget = _budget_bytes(self._budget_env, self._default_mb)
        if budget <= 0:
            value = builder()
            if self is DEVICE_CACHE:
                from .rpc_meter import METER

                METER.record_upload(_tree_nbytes(value))
            return value
        full_key = ("keyed", key)
        with self._lock:
            entry = self._d.get(full_key)
            if entry is not None:
                self._d.move_to_end(full_key)
                self.hits += 1
                _cache_counter(self._metric, "hits")
                return entry[1]
            self.misses += 1
        _cache_counter(self._metric, "misses")
        value, nbytes = self._build(key, builder)
        if nbytes > budget:
            return value
        with self._lock:
            if full_key not in self._d:
                self._d[full_key] = (None, value, nbytes)
                self._bytes += nbytes
            evicted_n = evicted_b = 0
            while self._bytes > budget and self._d:
                _, (_r, _v, nb) = self._d.popitem(last=False)
                self._bytes -= nb
                evicted_n += 1
                evicted_b += nb
            self.evictions += evicted_n
            self.evicted_bytes += evicted_b
            occupancy = self._bytes
        if evicted_n:
            _cache_counter(self._metric, "evictions", evicted_n)
            _cache_counter(self._metric, "evicted_bytes", evicted_b)
        _cache_gauge(self._metric, occupancy)
        return value

    @property
    def occupancy_bytes(self) -> int:
        return self._bytes

    def check_consistency(self) -> bool:
        """Byte accounting invariant: the occupancy counter equals the sum
        of the resident entries' sizes (race-stress gate)."""
        with self._lock:
            return self._bytes == sum(e[2] for e in self._d.values())

    def clear(self) -> None:
        with self._lock:
            self._d.clear()
            self._bytes = 0
        _cache_gauge(self._metric, 0)


# process-wide caches shared by every executor path: device uploads charge
# the device budget; cheap-to-recompute host derivations (argsorts,
# factorize results) get their own budget so they cannot evict transfers
DEVICE_CACHE = DeviceArrayCache()
HOST_DERIVED_CACHE = DeviceArrayCache("HYPERSPACE_HOST_DERIVED_CACHE_MB", "512")
