"""Skew-aware bucket→device placement for mesh-sharded execution.

The covering indexes bucketize both join sides identically, so bucket work
units (band-wave items in ``plan/device_join``) are independent: any device
may compute any unit and the host-side fold reassembles results in bucket
order, bit-identical to single-device execution. That independence is what
this module exploits — it only decides *where* each unit runs, never *what*
runs.

Placement policy (JSPIM-style skew awareness): the join memory planner's
per-bucket footer-stat estimates predict each bucket's decoded bytes. A
bucket predicted to exceed the per-device fair share is split into as many
ranges as shares it covers (its probe chunks then rotate through those
ranges), and all ranges are largest-first bin packed onto the least-loaded
device. Buckets with no stats fall back to deterministic round-robin —
counted in ``mesh.placement.fallbacks`` so a stats-starved workload is
visible. Everything is a pure function of the estimates dict and the
device count, so placement is deterministic for a fixed dataset.

Default-off behind ``HYPERSPACE_MESH``; locally the path is driven with
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` on CPU, where
placement, balance, and bit-identity are all provable at nproc=1.
"""

from __future__ import annotations

import math
from typing import Optional

from ..telemetry import trace
from ..telemetry.metrics import REGISTRY
from ..utils import env


def mesh_enabled() -> bool:
    """``HYPERSPACE_MESH=1`` — the scale-out placement master switch."""
    return env.env_bool("HYPERSPACE_MESH")


def mesh_devices() -> list:
    """The devices placement may target: ``[]`` when the knob is off or
    fewer than two devices are visible (a 1-device mesh is just the default
    device with extra bookkeeping)."""
    if not mesh_enabled():
        return []
    try:
        cap = env.env_int("HYPERSPACE_MESH_DEVICES")
    except ValueError:
        cap = 0
    from .mesh import visible_devices

    devices = visible_devices(cap)
    return devices if len(devices) >= 2 else []


def mesh_size() -> int:
    return len(mesh_devices())


def _query_offset() -> int:
    """The serving scheduler's home-device assignment for the current
    query (tenant-weighted occupancy argmin) — placement rotates its
    round-robin and tie-breaks from here so concurrent queries spread
    instead of all packing from ordinal 0."""
    from ..serve.context import current_query

    q = current_query()
    home = getattr(q, "device_home", None) if q is not None else None
    return int(home) if home is not None else 0


class Placement:
    """An immutable bucket→device assignment. ``chunk`` indexes a split
    bucket's probe chunks: a bucket planned into k ranges rotates its
    chunks through the k packed ordinals; unplanned buckets round-robin
    deterministically from the query's home offset."""

    __slots__ = ("devices", "_units", "_offset")

    def __init__(self, devices: list, units: dict, offset: int):
        self.devices = devices
        self._units = units  # bucket -> tuple[ordinal, ...] in range order
        self._offset = offset

    def ordinal_for(self, bucket: int, chunk: int = 0) -> int:
        ords = self._units.get(bucket)
        if ords is None:
            REGISTRY.counter("mesh.placement.fallbacks").inc()
            return (bucket + chunk + self._offset) % len(self.devices)
        return ords[chunk % len(ords)]

    def device_for(self, bucket: int, chunk: int = 0):
        return self.devices[self.ordinal_for(bucket, chunk)]

    def slot_for(self, bucket: int, chunk: int = 0) -> tuple:
        """The ``(ordinal, device)`` pair band schedulers thread through
        ``_BandScheduler.add`` — hashable, so it doubles as the wave
        grouping key."""
        o = self.ordinal_for(bucket, chunk)
        return o, self.devices[o]


def plan_bucket_placement(
    estimates: dict, devices: "list | None" = None, offset: int = 0
) -> Optional[Placement]:
    """Largest-first bin packing of predicted per-bucket decoded bytes
    onto the mesh. ``estimates`` maps bucket -> predicted bytes (buckets
    absent from it take the round-robin fallback at lookup time). None
    when no mesh is on."""
    if devices is None:
        devices = mesh_devices()
    ndev = len(devices)
    if ndev < 2:
        return None
    loads = [0.0] * ndev
    units: dict[int, tuple] = {}
    total = float(sum(estimates.values()))
    if estimates and total > 0:
        share = total / ndev
        # one work unit per fair share the bucket covers: a skewed bucket
        # becomes several ranges its split chunks rotate through, so ONE
        # hot bucket can no longer pin the balance to a single device
        work = []
        for b in sorted(estimates):
            nbytes = float(estimates[b])
            k = max(1, min(ndev, math.ceil(nbytes / share))) if nbytes > 0 else 1
            for i in range(k):
                work.append((nbytes / k, int(b), i))
        work.sort(key=lambda u: (-u[0], u[1], u[2]))
        placed: dict[int, list] = {}
        for nbytes, b, i in work:
            o = min(
                range(ndev), key=lambda d: (loads[d], (d - offset) % ndev)
            )
            loads[o] += nbytes
            placed.setdefault(b, []).append((i, o))
        units = {
            b: tuple(o for _i, o in sorted(pairs)) for b, pairs in placed.items()
        }
    REGISTRY.counter("mesh.placement.buckets").inc(len(estimates))
    used = [l for l in loads if l > 0]
    ratio = (max(used) / (sum(used) / len(used))) if used else 1.0
    if estimates:
        REGISTRY.gauge("mesh.placement.devices_used").set(len(used))
        REGISTRY.gauge("mesh.placement.bytes_imbalance_ratio").set(ratio)
    if trace.enabled():
        # zero-width marker carrying the packing outcome (join:resume idiom)
        with trace.span(
            "mesh:place", buckets=len(estimates), devices=ndev,
            devices_used=len(used), imbalance=round(ratio, 3),
        ):
            pass
    return Placement(devices, units, offset)


def plan_for_strategy(strategy) -> Optional[Placement]:
    """A Placement for one bucketed join, driven by the memory planner's
    footer-stat estimates (a stable read-only map — ``observe_actual``
    writes a separate observed-actuals ledger)."""
    devices = mesh_devices()
    if len(devices) < 2:
        return None
    estimates = {}
    if strategy is not None:
        estimates = {b: est[1] for b, est in strategy.estimates.items()}
    return plan_bucket_placement(estimates, devices, _query_offset())
