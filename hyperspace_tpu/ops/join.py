"""Device join building blocks for the co-partitioned, shuffle-free path.

Reference behavior replaced: the bucketed sort-merge join that JoinIndexRule
arranges by swapping both sides for equally-bucketed indexes — Spark then
runs SMJ with no Exchange (covering/JoinIndexRule.scala:635-687). On TPU,
bucket b of both indexes lives on shard b, so the join is embarrassingly
parallel per shard; within a shard both sides are sorted by key, and the
match structure comes from one probe of the left keys into the right keys
(``probe_ranks``): a sort-merge rank, or a binary search where the left
side is much smaller than the right.

XLA's static shapes make "materialize all match pairs" awkward (dynamic
output), so the primitives here favor the patterns index-accelerated queries
actually lower to:
  - counts/offsets of matches (host decides materialization),
  - fused join+aggregate where the output is keyed by the join key
    (segment-sum then sorted lookup), which is the hot shape of TPC-H Q3-like
    queries and stays entirely on device.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax


def exact_key32(a: np.ndarray):
    """Exact 32-bit device representation of an order/match-deciding key
    column, or None. Shared contract for every path where the key decides
    result STRUCTURE (join matches, sort order): int64 within int32 range
    casts, f64 always declines (a lossy downcast could collapse distinct
    keys or reorder near-ties vs the host), f32 declines on NaNs."""
    if a.dtype == np.int64:
        if len(a) and (a.min() < -(2**31) or a.max() >= 2**31):
            return None
        return a.astype(np.int32)
    if a.dtype in (np.int32, np.int16, np.int8):
        return a.astype(np.int32)
    if a.dtype == np.float32:
        return None if np.isnan(a).any() else a
    return None


# The merge form sorts pad_l + pad_r keys twice; the search form runs
# ceil(log2 pad_r) + 1 dependent gather rounds over pad_l probes. On one
# v5e the merge was faster up to a right side 128 times the left and
# slower from 256 times (PERF.md section 6, PR 24), so the search form
# stays only past this ratio.
_SEARCH_RATIO = 128


def merge_probe(pad_l: int, pad_r: int) -> bool:
    """Whether ``probe_ranks`` takes the merge form at these static pads."""
    return pad_r <= _SEARCH_RATIO * pad_l


def probe_temp_bytes(pad_l: int, pad_r: int) -> int:
    """Device temporaries of one merge-form probe: six int32 words a row
    of the merged sequence (key and position through the first sort; the
    run scans; position, lo and hi through the compaction sort)."""
    return 24 * (pad_l + pad_r) if merge_probe(pad_l, pad_r) else 0


def probe_ranks(rk, lk, n_r):
    """(lo, hi) for every left key: its left and right insertion ranks in
    the sorted 1-D right keys ``rk`` (np.searchsorted's two sides), each
    clamped to ``n_r``, the real right rows ahead of the pads. ``lk``
    need not be sorted. The form follows the static pads (``merge_probe``):
    both give the same integers."""
    if merge_probe(lk.shape[0], rk.shape[0]):
        lo, hi = _merge_ranks(rk, lk)
    else:
        lo = jnp.searchsorted(rk, lk, side="left")
        hi = jnp.searchsorted(rk, lk, side="right")
    return jnp.minimum(lo, n_r), jnp.minimum(hi, n_r)


def _merge_ranks(rk, lk):
    """Ranks by merging: one stable sort of the right keys followed by the
    left keys (so a right row precedes a left row of the same key), scans
    over the merged order, and a sort by position that returns the left
    rows to their own order. No gather and no loop. Float keys need no
    canonical zero: the sort's comparator and the run test both take
    -0.0 for 0.0, as np.searchsorted does."""
    n_r = rk.shape[0]
    keys = jnp.concatenate([rk, lk])
    pos = lax.iota(jnp.int32, keys.shape[0])
    keys, pos = lax.sort((keys, pos), num_keys=1, is_stable=True)
    is_r = (pos < n_r).astype(jnp.int32)
    hi = jnp.cumsum(is_r)  # right rows at or before: all keys <= this one
    run = jnp.concatenate([jnp.ones(1, bool), keys[1:] != keys[:-1]])
    # right rows before the run of this key: all keys < this one
    lo = lax.cummax(jnp.where(run, hi - is_r, 0))
    _, lo, hi = lax.sort((pos, lo, hi), num_keys=1)
    return lo[n_r:], hi[n_r:]


def segment_sum_by_sorted_key(keys_sorted, values, unique_keys):
    """Sum `values` per key, for a pre-sorted key column, emitting sums
    aligned with `unique_keys` (also sorted). Static shapes throughout."""
    starts = jnp.searchsorted(keys_sorted, unique_keys, side="left")
    ends = jnp.searchsorted(keys_sorted, unique_keys, side="right")
    csum = jnp.concatenate([jnp.zeros(1, values.dtype), jnp.cumsum(values)])
    return csum[ends] - csum[starts]


def lookup_sorted(table_keys_sorted, table_values, queries, default):
    """Exact-match gather: for each query key return the table value (first
    match) or `default`. table_keys_sorted ascending."""
    pos = jnp.searchsorted(table_keys_sorted, queries, side="left")
    pos_c = jnp.clip(pos, 0, table_keys_sorted.shape[0] - 1)
    found = table_keys_sorted[pos_c] == queries
    return jnp.where(found, table_values[pos_c], default), found


def expand_runs(starts: np.ndarray, counts: np.ndarray) -> np.ndarray:
    """Vectorized expansion of [start_i, start_i + count_i) runs into one
    index array (no per-run Python loop)."""
    total = int(counts.sum())
    cum = (
        np.concatenate([[0], np.cumsum(counts)[:-1]])
        if len(counts)
        else np.empty(0, np.int64)
    )
    within = np.arange(total, dtype=np.int64) - np.repeat(cum, counts)
    return np.repeat(starts, counts) + within


def host_merge_join_indices(left_sorted: np.ndarray, right_sorted: np.ndarray):
    """Host merge join on sorted keys -> (left_idx, right_idx), fully
    vectorized."""
    starts = np.searchsorted(right_sorted, left_sorted, side="left")
    ends = np.searchsorted(right_sorted, left_sorted, side="right")
    counts = ends - starts
    li = np.repeat(np.arange(len(left_sorted)), counts)
    return li, expand_runs(starts, counts)
