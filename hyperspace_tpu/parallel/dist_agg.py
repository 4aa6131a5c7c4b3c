"""Distributed filter-aggregate over a device mesh.

The scaled form of the fused query kernel: columns live sharded across the
mesh (one shard per device, ICI within a slice / DCN across slices — jax
inserts the collectives either way), each shard runs the fused
filter+aggregate locally, and a `psum` tree combines the partials. This is
what an accelerated Q6 looks like when the index chunks exceed one chip's
HBM — the analogue of Spark's partial→final aggregation over executors,
minus the shuffle (only scalars cross the interconnect).
"""

from __future__ import annotations

from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .mesh import mesh_row_axes
from ..ops.intsum import blocked_segment_sum, int_chunk_sums


def _row_axis(mesh: Mesh, axis):
    """Resolve the data axis: explicit, or every axis of the mesh. On a
    hierarchical (dcn, ici) mesh the collectives run over the axis TUPLE —
    XLA lowers psum(('dcn','ici')) as an intra-slice ICI reduction followed
    by a cross-slice DCN combine of the already-reduced partials, so row
    data never crosses DCN."""
    if axis is not None:
        return axis
    return mesh_row_axes(mesh)


def distributed_filter_aggregate(
    mesh: Mesh,
    cols: dict[str, jnp.ndarray],
    mask: jnp.ndarray,
    pred_fn: Callable[[dict[str, jnp.ndarray]], jnp.ndarray],
    agg_fns: dict[str, Callable[[dict[str, jnp.ndarray], jnp.ndarray], jnp.ndarray]],
    axis: "str | tuple[str, ...] | None" = None,
) -> dict[str, jnp.ndarray]:
    """Run pred_fn + per-shard reductions under shard_map, psum the results.

    cols/mask: arrays sharded on the leading dim over `axis`;
    pred_fn(cols) -> bool array; agg_fns: name -> fn(cols, final_mask) ->
    scalar partial (summed across shards).
    Returns {name: replicated scalar}.
    """
    axis = _row_axis(mesh, axis)

    def body(cols_shard, mask_shard):
        m = mask_shard & pred_fn(cols_shard)
        out = {}
        for name, fn in agg_fns.items():
            out[name] = jax.lax.psum(fn(cols_shard, m), axis)
        return out

    fn = shard_map(
        body,
        mesh=mesh,
        in_specs=(jax.tree.map(lambda _: P(axis), cols), P(axis)),
        out_specs=jax.tree.map(lambda _: P(), dict(agg_fns)),
        check_vma=False,
    )
    from ..telemetry import trace
    from ..utils.rpc_meter import METER

    with trace.span("kernel:dist_filter_agg", aggs=len(agg_fns)):
        METER.record_dispatch()
        # Utility API keyed by caller-supplied closures (pred_fn/agg_fns):
        # no sound automatic fingerprint exists, so this jits per call.
        # The query path caches its mesh kernels via KERNEL_CACHE instead
        # (tpu_exec mesh route + build_distributed_grouped_kernel below).
        # hslint: HS201 — per-call closures; no cacheable fingerprint
        return jax.jit(fn)(cols, mask)


def build_distributed_grouped_kernel(
    mesh: Mesh,
    pred_fn: Callable | None,
    agg_list: list[tuple[str, Callable]],
    seg_pad: int,
    axis: "str | tuple[str, ...] | None" = None,
):
    """Build (and jit once — callers cache) a mesh kernel for grouped
    aggregation: every shard segment-reduces its rows (group ids are global,
    factorized host-side), then a psum/pmin/pmax tree combines per-group
    partials — only [seg_pad]-sized vectors cross the interconnect, never
    rows. Global aggregates are the seg_pad-with-one-group special case.

    agg_list: (kind, value_fn(cols)->vals) with kind in
    sum/count/min/max/avg. Kernel returns (counts, first_masked,
    tuple(outputs)), replicated — first_masked is the GLOBAL row index of
    each group's first predicate-passing row (pmin over shard-local
    minima), so assembly orders output rows exactly like the host tier."""
    axis = _row_axis(mesh, axis)
    axes = axis if isinstance(axis, (tuple, list)) else (axis,)
    axis_sizes = dict(zip(mesh.axis_names, mesh.devices.shape))

    def body(cols_shard, gids_shard, mask_shard):
        m = mask_shard
        if pred_fn is not None:
            m = m & pred_fn(cols_shard)
        g = jnp.where(m, gids_shard, seg_pad - 1)
        counts = jax.lax.psum(
            jax.ops.segment_sum(jnp.ones_like(g, dtype=jnp.int32), g, num_segments=seg_pad),
            axis,
        )
        # global row index = linear shard index * shard length + local row
        shard_idx = jnp.int32(0)
        for a in axes:
            shard_idx = shard_idx * axis_sizes[a] + jax.lax.axis_index(a)
        local_idx = jnp.arange(g.shape[0], dtype=jnp.int32)
        global_idx = shard_idx * jnp.int32(g.shape[0]) + local_idx
        first_masked = jax.lax.pmin(
            jax.ops.segment_min(
                jnp.where(m, global_idx, jnp.int32(2**31 - 1)),
                g,
                num_segments=seg_pad,
            ),
            axis,
        )
        out = []
        for kind, fn in agg_list:
            if kind == "count":
                out.append(counts)
                continue
            vals = fn(cols_shard)
            int_vals = jnp.issubdtype(vals.dtype, jnp.integer)
            if kind == "sum":
                if int_vals:
                    # exact int accumulation: psum each 8-bit chunk's
                    # per-shard segment sums; the caller's global row cap
                    # keeps every psum total within int32, and the host
                    # recombines into int64 exactly (tiers must agree)
                    out.append(
                        tuple(
                            jax.lax.psum(c, axis)
                            for c in int_chunk_sums(vals, g, seg_pad)
                        )
                    )
                else:
                    out.append(
                        jax.lax.psum(blocked_segment_sum(vals, g, seg_pad), axis)
                    )
            elif kind == "min":
                out.append(
                    jax.lax.pmin(jax.ops.segment_min(vals, g, num_segments=seg_pad), axis)
                )
            elif kind == "max":
                out.append(
                    jax.lax.pmax(jax.ops.segment_max(vals, g, num_segments=seg_pad), axis)
                )
            elif kind == "avg":  # the sum only: the host divides
                if int_vals:  # exact chunked sums
                    out.append(
                        tuple(
                            jax.lax.psum(c, axis)
                            for c in int_chunk_sums(vals, g, seg_pad)
                        )
                    )
                else:
                    out.append(
                        jax.lax.psum(blocked_segment_sum(vals, g, seg_pad), axis)
                    )
        return counts, first_masked, tuple(out)

    def wrapper(cols, gids, mask):
        inner = shard_map(
            body,
            mesh=mesh,
            in_specs=(jax.tree.map(lambda _: P(axis), cols), P(axis), P(axis)),
            out_specs=(P(), P(), tuple(P() for _ in agg_list)),
            check_vma=False,
        )
        return inner(cols, gids, mask)

    # hslint: HS201 — builder runs via KERNEL_CACHE.get_or_build (tpu_exec)
    return jax.jit(wrapper)


def shard_columns(
    mesh: Mesh, cols: dict, axis: "str | tuple[str, ...] | None" = None
) -> tuple[dict, "jnp.ndarray"]:
    """Pad to a multiple of the mesh size and place each column sharded on
    the leading dimension. Returns (cols, mask)."""
    import numpy as np

    from .mesh import num_shards

    axis = _row_axis(mesh, axis)
    n = len(next(iter(cols.values())))
    d = num_shards(mesh, axis)
    padded = ((n + d - 1) // d) * d
    sharding = NamedSharding(mesh, P(axis))
    from ..utils.rpc_meter import METER

    out = {}
    nbytes = 0
    for name, arr in cols.items():
        a = np.asarray(arr)
        if padded != n:
            a = np.pad(a, (0, padded - n))
        out[name] = jax.device_put(jnp.asarray(a), sharding)
        nbytes += a.nbytes
    mask = jax.device_put(
        jnp.asarray(np.arange(padded) < n), sharding
    )
    METER.record_upload(nbytes + mask.nbytes, n=len(out) + 1)
    return out, mask
