"""all_to_all bucket exchange — the TPU-native replacement for Spark's hash
shuffle in bucketed index builds.

Reference behavior replaced: `repartition(numBuckets, indexedCols)` +
bucketed sorted write (covering/CoveringIndex.scala:56-71,
DataFrameWriterExtensions.scala:50-68) ran as a full JVM shuffle through
Spark's block manager. Here every device holds a row chunk, computes
destination shards from the shared hash (ops/hashing.py), and one
`lax.all_to_all` over the mesh axis moves rows across ICI (or DCN when the
mesh spans hosts); a per-device segmented sort finishes the bucket layout.

Static-shape contract (XLA requires fixed shapes): each device sends at most
`capacity` rows to each destination, padding with a validity mask. The kernel
also returns the true per-(src,dst) max count so the host can detect overflow
and re-launch with a larger capacity (size-class recompilation, one cache
entry per power-of-two capacity).
"""

from __future__ import annotations

from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from .mesh import SHARD_AXIS


def _exchange_body(axis: str, n_dest: int, capacity: int, cols, dest):
    """Per-device body under shard_map. cols: pytree of [N] arrays;
    dest: [N] int32 in [0, n_dest). Returns (pytree of [n_dest*capacity],
    valid mask, overflow_max)."""
    n = dest.shape[0]
    order = jnp.argsort(dest)
    dest_sorted = dest[order]
    counts = jnp.bincount(dest_sorted, length=n_dest)
    offsets = jnp.concatenate([jnp.zeros(1, jnp.int32), jnp.cumsum(counts)[:-1].astype(jnp.int32)])
    max_count = counts.max()

    # slot (d, m) <- sorted row at offsets[d] + m when m < counts[d]
    d_idx = jax.lax.broadcasted_iota(jnp.int32, (n_dest, capacity), 0)
    m_idx = jax.lax.broadcasted_iota(jnp.int32, (n_dest, capacity), 1)
    src_pos = offsets[d_idx] + m_idx
    valid = m_idx < counts[d_idx]
    src_pos = jnp.clip(src_pos, 0, n - 1)

    def build_send(col):
        return col[order][src_pos]  # [n_dest, capacity]

    send = jax.tree.map(build_send, cols)
    recv = jax.tree.map(
        lambda s: jax.lax.all_to_all(s, axis, split_axis=0, concat_axis=0, tiled=True),
        send,
    )
    valid_recv = jax.lax.all_to_all(valid, axis, split_axis=0, concat_axis=0, tiled=True)
    flat = jax.tree.map(lambda r: r.reshape(n_dest * capacity), recv)
    # overflow signal: global max of per-device max count
    overflow = jax.lax.pmax(max_count, axis)
    return flat, valid_recv.reshape(n_dest * capacity), overflow


def bucket_exchange(
    mesh: Mesh,
    cols: Any,
    dest: jnp.ndarray,
    capacity: int,
    axis: str = SHARD_AXIS,
):
    """Exchange rows so all rows with dest==d land on shard d.

    cols: pytree of arrays with leading dim = total rows (sharded over mesh);
    dest: int32 array aligned with cols (values in [0, num_shards));
    capacity: static per-(src,dst) row budget.

    Returns (cols_out, valid, overflow) where cols_out arrays have
    num_shards*capacity rows per shard (padded; valid marks real rows) and
    overflow is the true max per-(src,dst) count — if overflow > capacity the
    result is truncated and the caller must retry with a larger capacity.
    """
    n_dest = mesh.shape[axis]
    body = partial(_exchange_body, axis, n_dest, capacity)
    fn = shard_map(
        body,
        mesh=mesh,
        in_specs=(jax.tree.map(lambda _: P(axis), cols), P(axis)),
        out_specs=(jax.tree.map(lambda _: P(axis), cols), P(axis), P()),
        check_vma=False,
    )
    return fn(cols, dest)


def exchange_with_retry(mesh, cols, dest, rows_per_shard: int, axis: str = SHARD_AXIS):
    """Host wrapper: start from a balanced-capacity guess, grow by powers of
    two on overflow (skewed buckets). Each capacity is a separate compile
    cache entry."""
    from ..telemetry import trace
    from ..utils.rpc_meter import METER

    n = mesh.shape[axis]
    capacity = max(64, int(2 ** np.ceil(np.log2(max(1, 2 * rows_per_shard // n)))))
    while True:
        with trace.span("kernel:bucket_exchange", capacity=capacity):
            METER.record_dispatch()
            out, valid, overflow = bucket_exchange(mesh, cols, dest, capacity, axis)
            overflow = int(overflow)  # blocking read inside the span
        if overflow <= capacity:
            return out, valid
        capacity = int(2 ** np.ceil(np.log2(overflow)))


def partition_batch_mesh(batch, bucket_columns, num_buckets: int, mesh: Mesh, axis: str = SHARD_AXIS):
    """Bucket partition of a production index build, computed ON the mesh:
    key words shard across devices, the bucket hash runs on device with the
    exact arithmetic of the host path (ops/hashing), and one all_to_all
    moves (bucket, row-id) pairs so shard s owns every bucket ≡ s (mod D).

    Returns the same structure as ops.bucketize.partition_batch — per-bucket
    row indices in original row order, so downstream sort+write produce a
    bit-identical bucket layout — or None when the batch cannot shard
    (fewer rows than devices) and the host path should take over.

    Ref: the Spark hash shuffle behind repartition(numBuckets, cols)
    (covering/CoveringIndex.scala:56-71); here the shuffle decision — hash,
    placement, exchange — runs on the device mesh, and the host materializes
    each bucket's rows for the parquet write.
    """
    from jax.sharding import NamedSharding

    from ..ops.bucketize import key_hash_words
    from ..ops.hashing import _words_np, bucket_ids_jnp

    from .mesh import is_hierarchical

    if is_hierarchical(mesh):
        # build row-exchange is intra-slice by design: all_to_all must ride
        # ICI, never DCN (rows are the big payload). On a hierarchical mesh
        # the host partitioner takes over; multi-slice builds partition
        # sources per slice upstream.
        return None
    D = mesh.shape[axis]
    n = batch.num_rows
    if n < D:
        return None
    padded = ((n + D - 1) // D) * D

    def pad32(a: np.ndarray) -> np.ndarray:
        out = np.zeros(padded, np.int32)
        out[:n] = a.view(np.int32) if a.dtype == np.uint32 else a.astype(np.int32)
        return out

    # decompose keys into uint32 words exactly as the host hash does (int64
    # and float64 split into two words; strings hash by value host-side and
    # ship their word), transported as int32 (no x64 on device)
    words: list[np.ndarray] = []
    for c in bucket_columns:
        for w in _words_np(np.asarray(key_hash_words(batch.column(c)))):
            words.append(pad32(w))
    row_id = np.full(padded, -1, np.int32)
    row_id[:n] = np.arange(n, dtype=np.int32)

    from ..telemetry import trace
    from ..utils.rpc_meter import METER

    with trace.span("kernel:mesh_partition", rows=n, buckets=num_buckets) as sp:
        shard = NamedSharding(mesh, P(axis))
        METER.record_upload(
            sum(w.nbytes for w in words) + row_id.nbytes, n=len(words) + 1
        )
        words_d = [jax.device_put(jnp.asarray(w), shard) for w in words]
        row_d = jax.device_put(jnp.asarray(row_id), shard)
        # each transported word is one single-word hash column; mixing order
        # matches hash32_np's word order, so placement is bit-identical
        bucket_d = bucket_ids_jnp(words_d, num_buckets)
        dest_d = bucket_d % jnp.int32(D)
        out, valid = exchange_with_retry(
            mesh, {"b": bucket_d, "r": row_d}, dest_d, padded // D, axis
        )

    b_np = np.asarray(out["b"])
    r_np = np.asarray(out["r"])
    sel = np.asarray(valid) & (r_np >= 0)
    if int(sel.sum()) != n:
        return None  # lost rows would corrupt the index: host path instead
    b_sel, r_sel = b_np[sel], r_np[sel]
    # stable by bucket: rows arrive shard-major / source-major, which is the
    # original row order within each bucket (same contract as the host
    # counting-sort partition)
    order = np.argsort(b_sel, kind="stable")
    b_sorted, r_sorted = b_sel[order], r_sel[order]
    bounds = np.searchsorted(b_sorted, np.arange(num_buckets + 1))
    return [
        (b, r_sorted[bounds[b]: bounds[b + 1]])
        for b in range(num_buckets)
        if bounds[b + 1] > bounds[b]
    ]
