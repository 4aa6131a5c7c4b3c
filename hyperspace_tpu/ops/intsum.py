"""Exact integer summation on a 32-bit device, and blocked float sums.

The device disables x64, so a naive int sum accumulates in int32 (wraps) or
f32 (rounds past 2^24). Instead v decomposes as
v = b3*2^24 + b2*2^16 + b1*2^8 + b0 with b0..b2 in [0,256) and b3 in
[-128,128): each chunk's sum stays within int32 for up to 2^23 rows, and the
host recombines into int64 exactly (the host executor emits int64 sums, and
cross-tier equality must be exact). The same bound keeps a psum over mesh
shards exact: the psum total equals the global chunk sum, which the row cap
already bounds within int32.

Reference parity: Spark accumulates long sums on the JVM with no such cap
(sum codegen); the cap is the honest price of 32-bit devices, and capped
queries decline to the host path rather than degrade.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

_INT_SUM_ROW_CAP = 1 << 23


def int_chunk_sums(v, seg=None, num_segments: int = 0):
    """Per-chunk sums of an int32 vector: global (seg=None) or segmented."""
    v = v.astype(jnp.int32)
    chunks = (v & 0xFF, (v >> 8) & 0xFF, (v >> 16) & 0xFF, v >> 24)
    if seg is None:
        return tuple(c.sum() for c in chunks)
    return tuple(
        jax.ops.segment_sum(c, seg, num_segments=num_segments) for c in chunks
    )


# most rows x groups the partials of blocked_segment_sum may hold (4 MB f32)
_BLOCK_PARTIALS = 1 << 20


def blocked_segment_sum(v, seg, num_segments: int):
    """Per-segment float sums accumulated in up to 1024 row blocks whose
    partials then reduce as a tree. One running segment sum of millions of
    f32 rows rounds at every add once a group's total passes 2^24 (on the
    CPU and the TPU alike: 1e-4 relative at 6M rows); a block of n/1024
    rows stays far below that."""
    n = v.shape[0]
    blocks = 1
    while (
        blocks < 1024
        and n % (2 * blocks) == 0
        and 2 * blocks * num_segments <= _BLOCK_PARTIALS
    ):
        blocks *= 2
    if blocks == 1:
        return jax.ops.segment_sum(v, seg, num_segments=num_segments)
    partials = jax.vmap(
        lambda vb, sb: jax.ops.segment_sum(vb, sb, num_segments=num_segments)
    )(v.reshape(blocks, -1), seg.reshape(blocks, -1))
    return partials.sum(axis=0)


def combine_int_chunks(parts) -> np.ndarray:
    """Host-side exact recombination of chunk sums into int64."""
    total = np.zeros(np.asarray(parts[0]).shape, dtype=np.int64)
    for k, p in enumerate(parts):
        total += np.asarray(p).astype(np.int64) << (8 * k)
    return total
