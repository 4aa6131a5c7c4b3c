"""Pallas TPU kernels for the hottest single-chip loops.

These are the custom-kernel tier beneath the generic fused-XLA path
(plan/tpu_exec.py): where XLA's fusion is already optimal we let it be, and
where a hand-rolled pass helps — the filter+reduce over index column chunks
that every accelerated Q6-style query bottoms out in — the kernel streams
VMEM blocks once and accumulates elementwise partials in a resident
register-tile.

Mosaic lowering requires output block shapes whose last two dims are
(8k, 128m) or the whole array, so every kernel here accumulates into a
single full-block (8, 128)-shaped buffer (index_map is constant, the TPU
grid is sequential, so the block stays resident in VMEM across steps) and
the final cheap reduction of that one tile happens outside the pallas_call.
Kernels run in interpreter mode on the CPU only (tests on the CPU mesh) and
compiled by Mosaic on real TPU hardware.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# VPU-friendly block: 8 sublanes x 128 lanes of float32
_BLOCK_ROWS = 8
_LANES = 128
_BLOCK = _BLOCK_ROWS * _LANES


def _interpret() -> bool:
    """Interpret mode on the CPU (the test harness) only: on any other
    platform the kernels compile through Mosaic or fail loudly."""
    from ..utils.backend import platform

    return platform() == "cpu"


def _pad_blocks(*arrs):
    """Pad 1-D arrays to a whole number of (8,128) blocks and reshape 2-D."""
    n = arrs[0].shape[0]
    padded = ((n + _BLOCK - 1) // _BLOCK) * _BLOCK
    if padded != n:
        arrs = tuple(jnp.pad(a, (0, padded - n)) for a in arrs)
    steps = padded // _BLOCK
    shape2d = (steps * _BLOCK_ROWS, _LANES)
    return steps, tuple(a.reshape(shape2d) for a in arrs)


_IN_SPEC = pl.BlockSpec((_BLOCK_ROWS, _LANES), lambda i: (i, 0))
_ACC_SPEC = pl.BlockSpec((_BLOCK_ROWS, _LANES), lambda i: (0, 0))
_ACC_SHAPE = (_BLOCK_ROWS, _LANES)


def _filter_sum_kernel(pred_ref, x_ref, y_ref, rev_ref, cnt_ref):
    """One grid step: accumulate pred*x*y and pred elementwise into the
    resident (8,128) tiles. Counts stay integer — float32 rounds above
    2^24 matching rows."""
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        rev_ref[...] = jnp.zeros(_ACC_SHAPE, jnp.float32)
        cnt_ref[...] = jnp.zeros(_ACC_SHAPE, jnp.int32)

    predf = pred_ref[...].astype(jnp.float32)
    rev_ref[...] += predf * x_ref[...] * y_ref[...]
    cnt_ref[...] += pred_ref[...].astype(jnp.int32)


@partial(jax.jit, static_argnames=())  # hslint: HS201 — module-level jit singleton; traced once per shape
def filter_weighted_sum(pred: jnp.ndarray, x: jnp.ndarray, y: jnp.ndarray):
    """sum(x*y where pred) and count(pred) over 1-D arrays.

    Inputs are padded to a whole number of (8,128) blocks; the predicate is
    already masked for padding (False rows contribute nothing).
    Returns (revenue f32, count i32 scalar)."""
    if pred.shape[0] == 0:
        return jnp.float32(0), jnp.int32(0)
    steps, (pred2, x2, y2) = _pad_blocks(
        pred, x.astype(jnp.float32), y.astype(jnp.float32)
    )
    rev, cnt = pl.pallas_call(
        _filter_sum_kernel,
        grid=(steps,),
        in_specs=[_IN_SPEC, _IN_SPEC, _IN_SPEC],
        out_specs=[_ACC_SPEC, _ACC_SPEC],
        out_shape=[
            jax.ShapeDtypeStruct(_ACC_SHAPE, jnp.float32),
            jax.ShapeDtypeStruct(_ACC_SHAPE, jnp.int32),
        ],
        interpret=_interpret(),
    )(pred2, x2, y2)
    return rev.sum(), cnt.sum()


def _filter_plain_sum_kernel(pred_ref, x_ref, s_ref, cnt_ref):
    """One grid step: accumulate pred*x and pred elementwise."""
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        s_ref[...] = jnp.zeros(_ACC_SHAPE, jnp.float32)
        cnt_ref[...] = jnp.zeros(_ACC_SHAPE, jnp.int32)

    predf = pred_ref[...].astype(jnp.float32)
    s_ref[...] += predf * x_ref[...]
    cnt_ref[...] += pred_ref[...].astype(jnp.int32)


@partial(jax.jit, static_argnames=())  # hslint: HS201 — module-level jit singleton; traced once per shape
def filter_sum(pred: jnp.ndarray, x: jnp.ndarray):
    """sum(x where pred) and count(pred) over 1-D arrays — the
    single-measure sibling of filter_weighted_sum (the Q6-without-product
    shape). Returns (sum f32, count i32 scalar)."""
    if pred.shape[0] == 0:
        return jnp.float32(0), jnp.int32(0)
    steps, (pred2, x2) = _pad_blocks(pred, x.astype(jnp.float32))
    s, cnt = pl.pallas_call(
        _filter_plain_sum_kernel,
        grid=(steps,),
        in_specs=[_IN_SPEC, _IN_SPEC],
        out_specs=[_ACC_SPEC, _ACC_SPEC],
        out_shape=[
            jax.ShapeDtypeStruct(_ACC_SHAPE, jnp.float32),
            jax.ShapeDtypeStruct(_ACC_SHAPE, jnp.int32),
        ],
        interpret=_interpret(),
    )(pred2, x2)
    return s.sum(), cnt.sum()


_MAX_PALLAS_GROUPS = 16


@partial(jax.jit, static_argnames=("num_groups",))  # hslint: HS201 — module-level jit singleton; traced once per shape
def filter_grouped_sum(
    pred: jnp.ndarray, gids: jnp.ndarray, x: jnp.ndarray, num_groups: int
):
    """Per-group sum(x where pred) and count(pred) for a SMALL group domain
    (num_groups <= 16) — the grouped Q1-fragment shape (GROUP BY low-
    cardinality keys) as a single Pallas streaming pass: per-group (8,128)
    accumulator slabs reduce to scalars outside the kernel. The predicate
    must already mask padding rows. Returns (sums[G] f32, counts[G] i32)."""
    sums, counts, _first = filter_grouped_multi_sum(pred, gids, (x,), num_groups)
    return sums[0], counts


# a group's first passing row when no row of it passes
_NO_ROW = 2**31 - 1


def _grouped_multi_sum_kernel_body(num_groups: int, num_vals: int):
    acc_shape = (num_groups * _BLOCK_ROWS, _LANES)

    def kernel(*refs):
        pred_ref, gid_ref = refs[0], refs[1]
        x_refs = refs[2 : 2 + num_vals]
        s_refs = refs[2 + num_vals : 2 + 2 * num_vals]
        c_ref, f_ref = refs[2 + 2 * num_vals], refs[3 + 2 * num_vals]
        i = pl.program_id(0)

        @pl.when(i == 0)
        def _init():
            for s_ref in s_refs:
                s_ref[...] = jnp.zeros(acc_shape, jnp.float32)
            c_ref[...] = jnp.zeros(acc_shape, jnp.int32)
            f_ref[...] = jnp.full(acc_shape, _NO_ROW, jnp.int32)

        pred = pred_ref[...]
        gids = gid_ref[...]
        # the global row index of each element of this step's (8,128) tile
        row = (
            i * _BLOCK_ROWS + jax.lax.broadcasted_iota(jnp.int32, _ACC_SHAPE, 0)
        ) * _LANES + jax.lax.broadcasted_iota(jnp.int32, _ACC_SHAPE, 1)
        # static unroll over the (small) group domain: every measure, the
        # count and the first passing row accumulate in the SAME streaming
        # pass — pred/gids are read from HBM once per block regardless of
        # how many sums the fragment has
        for g in range(num_groups):
            m = pred & (gids == g)
            lo, hi = g * _BLOCK_ROWS, (g + 1) * _BLOCK_ROWS
            for x_ref, s_ref in zip(x_refs, s_refs):
                s_ref[lo:hi, :] += jnp.where(m, x_ref[...], jnp.float32(0))
            c_ref[lo:hi, :] += m.astype(jnp.int32)
            f_ref[lo:hi, :] = jnp.minimum(
                f_ref[lo:hi, :], jnp.where(m, row, jnp.int32(_NO_ROW))
            )

    return kernel


@partial(jax.jit, static_argnames=("num_groups",))  # hslint: HS201 — module-level jit singleton; traced once per shape
def filter_grouped_multi_sum(pred, gids, xs, num_groups: int):
    """Per-group sums of each value column in ``xs``, the shared
    count(pred) and each group's first row passing ``pred``, all in ONE
    streaming pass (a k-measure Q1 fragment costs one HBM read of
    pred/gids, not k). ``xs`` may be empty (count-only). A group with no
    passing row keeps 2**31 - 1 as its first row, as a segment min does.
    Returns (tuple of sums[G] f32, counts[G] i32, first rows[G] i32)."""
    xs = tuple(xs)
    if pred.shape[0] == 0:
        return (
            tuple(jnp.zeros((num_groups,), jnp.float32) for _ in xs),
            jnp.zeros((num_groups,), jnp.int32),
            jnp.full((num_groups,), _NO_ROW, jnp.int32),
        )
    num_vals = len(xs)
    steps, blocks = _pad_blocks(
        pred, gids.astype(jnp.int32), *(x.astype(jnp.float32) for x in xs)
    )
    acc_shape = (num_groups * _BLOCK_ROWS, _LANES)
    acc_spec = pl.BlockSpec(acc_shape, lambda i: (0, 0))
    outs = pl.pallas_call(
        _grouped_multi_sum_kernel_body(num_groups, num_vals),
        grid=(steps,),
        in_specs=[_IN_SPEC] * (2 + num_vals),
        out_specs=[acc_spec] * (num_vals + 2),
        out_shape=[jax.ShapeDtypeStruct(acc_shape, jnp.float32)] * num_vals
        + [jax.ShapeDtypeStruct(acc_shape, jnp.int32)] * 2,
        interpret=_interpret(),
    )(*blocks)
    slabs = [o.reshape(num_groups, _BLOCK_ROWS, _LANES) for o in outs]
    sums = tuple(o.sum(axis=(1, 2)) for o in slabs[:num_vals])
    counts = slabs[num_vals].sum(axis=(1, 2))
    first = slabs[num_vals + 1].min(axis=(1, 2))
    return sums, counts, first


def _minmax_kernel(x_ref, valid_ref, mn_ref, mx_ref):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        mn_ref[...] = jnp.full(_ACC_SHAPE, jnp.inf, jnp.float32)
        mx_ref[...] = jnp.full(_ACC_SHAPE, -jnp.inf, jnp.float32)

    v = valid_ref[...]
    x = x_ref[...]
    mn_ref[...] = jnp.minimum(mn_ref[...], jnp.where(v, x, jnp.inf))
    mx_ref[...] = jnp.maximum(mx_ref[...], jnp.where(v, x, -jnp.inf))


@jax.jit  # hslint: HS201 — module-level jit singleton; traced once per shape
def masked_min_max(x: jnp.ndarray, valid: jnp.ndarray):
    """Per-chunk min/max of valid rows — the sketch-build reduction for one
    file chunk as a Pallas pass. Returns (min f32, max f32)."""
    if x.shape[0] == 0:
        return jnp.float32(jnp.inf), jnp.float32(-jnp.inf)
    steps, (x2, v2) = _pad_blocks(x.astype(jnp.float32), valid)
    mn, mx = pl.pallas_call(
        _minmax_kernel,
        grid=(steps,),
        in_specs=[_IN_SPEC, _IN_SPEC],
        out_specs=[_ACC_SPEC, _ACC_SPEC],
        out_shape=[
            jax.ShapeDtypeStruct(_ACC_SHAPE, jnp.float32),
            jax.ShapeDtypeStruct(_ACC_SHAPE, jnp.float32),
        ],
        interpret=_interpret(),
    )(x2, v2)
    return mn.min(), mx.max()
