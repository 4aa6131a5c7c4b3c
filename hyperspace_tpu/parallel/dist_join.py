"""Distributed co-partitioned merge join over a device mesh.

The scaled form of the Exchange-free sort-merge join that covering join
indexes buy (ref: covering/JoinIndexRule.scala:635-720 + Spark's bucketed
SMJ, execution/BucketUnionExec.scala:52-121): both sides are pre-bucketed on
the join key, bucket b lives on shard b % n, so every device probes ITS
buckets against ITS buckets with ZERO inter-chip communication — the
sharding already is the shuffle. One shard_map call serves a whole wave of
buckets; no collective appears in the body because co-partitioning makes
the join embarrassingly shard-local (the ICI stays idle by design, unlike
the raw-table join whose all_to_all it replaces).

The probe phase (per-left-row lower bound + match count over the sorted
right keys) is static-shaped and runs on device; run expansion to pair
indices is dynamic-sized and stays on the host, exactly like the
single-device plain join (plan/device_join.py), so results are
bit-identical to the host merge join.
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .mesh import SHARD_AXIS
from ..plan.kernel_cache import MESH_CACHE, mesh_probe_fingerprint

# alias kept for tests/tools poking cache state directly
_PROBE_CACHE = MESH_CACHE


def _build_probe(mesh: Mesh, axis: str):
    def body(lk, rk, n_r):
        # [1, padL] / [1, padR] / [1] per shard — purely local, no psum
        lo = jnp.searchsorted(rk[0], lk[0], side="left")
        hi = jnp.searchsorted(rk[0], lk[0], side="right")
        n = n_r[0]
        lo = jnp.minimum(lo, n)
        hi = jnp.minimum(hi, n)
        return lo[None, :].astype(jnp.int32), (hi - lo)[None, :].astype(jnp.int32)

    fn = shard_map(
        body,
        mesh=mesh,
        in_specs=(P(axis), P(axis), P(axis)),
        out_specs=(P(axis), P(axis)),
        check_vma=False,
    )
    return jax.jit(fn)  # hslint: HS201 — builder runs via MESH_CACHE.get_or_build


def mesh_join_probe(
    mesh: Mesh,
    lk_stack: np.ndarray,
    rk_stack: np.ndarray,
    n_r: np.ndarray,
    axis: str = SHARD_AXIS,
) -> tuple[np.ndarray, np.ndarray]:
    """Probe a wave of co-partitioned buckets, one per mesh shard.

    lk_stack: [S, padL] sorted left keys per bucket (padded with the dtype
    maximum); rk_stack: [S, padR] sorted right keys; n_r: [S] real right
    row counts. Returns host (starts [S, padL], counts [S, padL]) int64.
    """
    key = mesh_probe_fingerprint(
        id(mesh), axis, lk_stack.shape, rk_stack.shape, str(lk_stack.dtype)
    )
    fn = MESH_CACHE.get_or_build(
        key, lambda: _build_probe(mesh, axis), "mesh_probe"
    )
    shard = NamedSharding(mesh, P(axis))
    from ..telemetry import trace
    from ..utils.rpc_meter import METER, device_get as metered_get

    with trace.span(
        "kernel:mesh_join_probe", buckets=int(lk_stack.shape[0])
    ):
        METER.record_upload(lk_stack.nbytes + rk_stack.nbytes + n_r.nbytes, n=3)
        METER.record_dispatch()
        lo, cnt = metered_get(
            fn(
                jax.device_put(jnp.asarray(lk_stack), shard),
                jax.device_put(jnp.asarray(rk_stack), shard),
                jax.device_put(jnp.asarray(n_r.astype(np.int32)), shard),
            )
        )
    return np.asarray(lo).astype(np.int64), np.asarray(cnt).astype(np.int64)
