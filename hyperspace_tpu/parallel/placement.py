"""The chips of a deployment, and where a served query's join bands run.

A session deploys on N chips with ``hyperspace.tpu.exec.meshDevices`` = N.
SF-scale indexes fit on one chip, so the chips hold replicas: the serving
scheduler runs each query on one of them, its home chip
(``serve/context.query_home``). ``hyperspace.tpu.exec.bandPlacement``
says where the bands of its bucketed joins run: ``home`` (the default)
runs every band there; ``spread`` packs them over all the chips with the
skew-aware placer below.

The covering indexes bucketize both join sides identically, so bucket work
units (band-wave items in ``plan/device_join``) are independent: any chip
may compute any unit and the host-side fold reassembles results in bucket
order, bit-identical to one chip. The placer only decides *where* each
unit runs, never *what* runs.

Placement policy (JSPIM-style skew awareness): the join memory planner's
per-bucket footer-stat estimates predict each bucket's decoded bytes. A
bucket predicted to exceed the per-chip fair share is split into as many
ranges as shares it covers (its probe chunks then rotate through those
ranges), and all ranges are largest-first bin packed onto the least-loaded
chip. Buckets with no stats fall back to deterministic round-robin —
counted in ``mesh.placement.fallbacks`` so a stats-starved workload is
visible. Everything is a pure function of the estimates dict and the chip
count, so placement is deterministic for a fixed dataset.

Locally the path is driven with
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` on CPU.
"""

from __future__ import annotations

import math
from typing import Optional

from ..telemetry import trace
from ..telemetry.metrics import REGISTRY


def mesh_devices(session) -> list:
    """The chips ``session`` deploys on: the first ``meshDevices`` of
    ``jax.devices()``, or ``[]`` with the conf at most 1 (a 1-device mesh
    is just the default device with extra bookkeeping). More chips than
    are visible raises: a deployment never silently shrinks."""
    n = session.conf.exec_mesh_devices if session is not None else 0
    if n <= 1:
        return []
    import jax

    devices = jax.devices()
    if len(devices) < n:
        raise ValueError(
            f"hyperspace.tpu.exec.meshDevices={n} but only {len(devices)} "
            "devices are visible"
        )
    return list(devices[:n])


class Placement:
    """An immutable bucket→chip assignment. ``chunk`` indexes a split
    bucket's probe chunks: a bucket planned into k ranges rotates its
    chunks through the k packed ordinals; unplanned buckets round-robin
    deterministically from the query's home chip."""

    __slots__ = ("devices", "_units", "_offset")

    def __init__(self, devices, units: dict, offset: int):
        self.devices = tuple(devices)
        self._units = units  # bucket -> tuple[ordinal, ...] in range order
        self._offset = offset

    def ordinal_for(self, bucket: int, chunk: int = 0) -> int:
        ords = self._units.get(bucket)
        if ords is None:
            REGISTRY.counter("mesh.placement.fallbacks").inc()
            return (bucket + chunk + self._offset) % len(self.devices)
        return ords[chunk % len(ords)]

    def slot_for(self, bucket: int, chunk: int = 0) -> tuple:
        """The chip as ``(ordinal, devices)``, the form
        ``utils.backend.on_home`` takes; band schedulers thread it through
        ``_BandScheduler.add``, and it doubles as the wave grouping key."""
        return self.ordinal_for(bucket, chunk), self.devices


def plan_bucket_placement(
    estimates: dict, devices, offset: int = 0
) -> Optional[Placement]:
    """Largest-first bin packing of predicted per-bucket decoded bytes
    onto ``devices``. ``estimates`` maps bucket -> predicted bytes
    (buckets absent from it take the round-robin fallback at lookup
    time); ``offset``, the home chip, breaks load ties. None for fewer
    than two chips."""
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
        # hot bucket can no longer pin the balance to a single chip
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


def plan_for_strategy(strategy, session) -> Optional[Placement]:
    """A Placement for one bucketed join of a query homed on a deployment
    whose session spreads join bands (``bandPlacement`` = ``spread``),
    driven by the memory planner's footer-stat estimates (a stable
    read-only map — ``observe_actual`` writes a separate observed-actuals
    ledger). None otherwise: every band runs on the home chip."""
    from ..utils.backend import home

    h = home()
    if h is None or session is None \
            or session.conf.exec_band_placement != "spread":
        return None
    estimates = {}
    if strategy is not None:
        estimates = {b: est[1] for b, est in strategy.estimates.items()}
    return plan_bucket_placement(estimates, h[1], h[0])
