"""Per-group reductions where every group is one contiguous run of rows.

A covering index is hash-bucketed on its key and sorted within each bucket,
so a grouping on that key sees each group as one run of rows. A reduction
over runs needs no scatter: a segmented inclusive scan that resets at each
run start leaves each group's result at its run's last row, and one gather
of those rows reads them out. This is the sort-based aggregate a bucketed,
sorted index is built for.

The scan is Hillis-Steele (log-shift) in two levels: 128 rows a line along
the lanes, then the lines' tails, with the carry added up to each line's
first run start. Each output is a tree of depth about log2 of its run's
length, so a float sum over millions of rows stays within float32 tree
rounding, as ``intsum.blocked_segment_sum`` does, not running-sum rounding.
The shifts are along whole lanes, never even/odd strided slices, which the
TPU's (8, 128) tiling would relayout.
"""

from __future__ import annotations

import jax.numpy as jnp

LANES = 128


def _shift(x, s: int, fill):
    """``x`` moved ``s`` places up its last axis, ``fill`` in front."""
    pad = jnp.full(x.shape[:-1] + (s,), fill, x.dtype)
    return jnp.concatenate([pad, x[..., :-s]], axis=-1)


def _lane_scan(v, starts, op, identity):
    """Segmented inclusive scan of ``op`` along the last axis; also whether
    a run starts at or before each place of its line."""
    s = 1
    while s < v.shape[-1]:
        v = jnp.where(starts, v, op(_shift(v, s, identity), v))
        starts = starts | _shift(starts, s, False)
        s *= 2
    return v, starts


def segmented_scan(v, starts, op, identity):
    """Inclusive scan of ``op`` over ``v`` that restarts wherever ``starts``
    is true. ``v`` is 1-D, of at most ``LANES`` rows or a multiple of it;
    ``identity`` is ``op``'s identity in ``v``'s dtype."""
    n = v.shape[0]
    if n <= LANES:
        return _lane_scan(v, starts, op, identity)[0]
    if n % LANES:
        raise ValueError(f"{n} rows are not whole lines of {LANES}")
    lines, started = _lane_scan(
        v.reshape(-1, LANES), starts.reshape(-1, LANES), op, identity
    )
    tails = segmented_scan(lines[:, -1], started[:, -1], op, identity)
    carry = _shift(tails, 1, identity)[:, None]
    return jnp.where(started, lines, op(carry, lines)).reshape(n)


def run_starts(gids):
    """True at each row whose group differs from the row before it."""
    return jnp.concatenate(
        [jnp.ones(1, bool), gids[1:] != gids[:-1]]
    )


def run_reduce(v, starts, last_rows, op, identity):
    """``op`` over each run, read at the runs' last rows: ``last_rows`` holds
    one row a group, negative for a group with no rows, which reads
    ``identity``."""
    scanned = segmented_scan(v, starts, op, identity)
    at = scanned.at[jnp.maximum(last_rows, 0)].get(mode="promise_in_bounds")
    return jnp.where(last_rows >= 0, at, identity)
