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
(``HYPERSPACE_DEVICE_CACHE_MB``, default 6144; 0 disables), a budget per
chip: on a deployment of several chips each home chip keeps its own
entries.
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
    """LRU of derived arrays under a byte budget. With ``per_chip`` (the
    device cache) each home chip of a deployment (``utils.backend``) has
    an LRU of its own, keyed and budgeted apart: a query reads and fills
    the entries of the chip it runs on, and each chip evicts under its own
    budget. Off a deployment everything lives in one LRU, as on one chip."""

    # default budget sized for a v5e chip (16 GB HBM): 6 GB of resident
    # columns keeps a 50M-row query working set (≈1.8 GB) plus the join
    # indexes hot without re-uploading them on every repeat
    def __init__(self, budget_env: str = "HYPERSPACE_DEVICE_CACHE_MB",
                 default_mb: str = "6144", per_chip: bool = False) -> None:
        self._budget_env = budget_env
        self._default_mb = default_mb
        self._per_chip = per_chip
        self._metric = "device" if budget_env == "HYPERSPACE_DEVICE_CACHE_MB" else "host_derived"
        self._chips: dict = {}  # chip id (None: no home chip) -> OrderedDict
        self._chip_bytes: dict = {}  # chip id -> resident bytes
        self._lock = TrackedLock(f"device_cache.{self._metric}")
        self.hits = 0
        self.misses = 0
        self.evictions = 0
        self.evicted_bytes = 0

    def _chip(self):
        """The LRU the calling query reads and fills: its home chip's."""
        if not self._per_chip:
            return None
        from .backend import home

        h = home()
        return None if h is None else h[1][h[0]].id

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
        chip = self._chip()
        key = (tuple(id(s) for s in srcs), key_extra)
        with self._lock:
            lru = self._chips.setdefault(chip, OrderedDict())
            entry = lru.get(key)
            if entry is not None:
                refs, value, nbytes = entry
                if all(r() is s for r, s in zip(refs, srcs)):
                    lru.move_to_end(key)
                    self.hits += 1
                    _cache_counter(self._metric, "hits")
                    return value
                # an id was reused by a different array — stale entry
                del lru[key]
                self._chip_bytes[chip] -= nbytes
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
            existing = lru.get(key)
            if existing is not None:
                # lost a concurrent build race: serve the already-cached
                # object so every caller holds THE resident copy (downstream
                # caches key on buffer identity); our duplicate upload is
                # dropped. The entry's refs are live — we hold srcs, so
                # their ids cannot have been reused.
                value = existing[1]
            else:
                lru[key] = (refs, value, nbytes)
                self._chip_bytes[chip] = self._chip_bytes.get(chip, 0) + nbytes
            evicted = self._evict_locked(chip, budget)
        self._note_evictions(evicted)
        return value

    def _evict_locked(self, chip, budget: int) -> tuple:
        """Evict ``chip``'s least recently used entries down to ``budget``;
        ``(entries, bytes)`` evicted and the occupancy of every chip."""
        lru = self._chips[chip]
        n = b = 0
        while self._chip_bytes.get(chip, 0) > budget and lru:
            _, (_r, _v, nb) = lru.popitem(last=False)
            self._chip_bytes[chip] -= nb
            n += 1
            b += nb
        self.evictions += n
        self.evicted_bytes += b
        return n, b, sum(self._chip_bytes.values())

    def _note_evictions(self, evicted: tuple) -> None:
        n, b, occupancy = evicted
        if n:
            _cache_counter(self._metric, "evictions", n)
            _cache_counter(self._metric, "evicted_bytes", b)
        _cache_gauge(self._metric, occupancy)

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
        chip = self._chip()
        full_key = ("keyed", key)
        with self._lock:
            lru = self._chips.setdefault(chip, OrderedDict())
            entry = lru.get(full_key)
            if entry is not None:
                lru.move_to_end(full_key)
                self.hits += 1
                _cache_counter(self._metric, "hits")
                return entry[1]
            self.misses += 1
        _cache_counter(self._metric, "misses")
        value, nbytes = self._build(key, builder)
        if nbytes > budget:
            return value
        with self._lock:
            if full_key not in lru:
                lru[full_key] = (None, value, nbytes)
                self._chip_bytes[chip] = self._chip_bytes.get(chip, 0) + nbytes
            evicted = self._evict_locked(chip, budget)
        self._note_evictions(evicted)
        return value

    @property
    def occupancy_bytes(self) -> int:
        with self._lock:
            return sum(self._chip_bytes.values())

    def chip_bytes(self) -> dict:
        """Resident bytes by chip id (None: entries of queries without a
        home chip)."""
        with self._lock:
            return {c: b for c, b in self._chip_bytes.items() if b}

    def check_consistency(self) -> bool:
        """Byte accounting invariant: each chip's occupancy counter equals
        the sum of its resident entries' sizes (race-stress gate)."""
        with self._lock:
            return all(
                self._chip_bytes.get(c, 0) == sum(e[2] for e in lru.values())
                for c, lru in self._chips.items()
            )

    def clear(self) -> None:
        with self._lock:
            self._chips.clear()
            self._chip_bytes.clear()
        _cache_gauge(self._metric, 0)


# process-wide caches shared by every executor path: device uploads charge
# the device budget; cheap-to-recompute host derivations (argsorts,
# factorize results) get their own budget so they cannot evict transfers
DEVICE_CACHE = DeviceArrayCache(per_chip=True)
HOST_DERIVED_CACHE = DeviceArrayCache("HYPERSPACE_HOST_DERIVED_CACHE_MB", "512")
