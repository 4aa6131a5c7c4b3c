"""Kernel-layer tests: hashing, bucketize, sketches, z-order, join prims.

Each device kernel has a host (numpy) reference; tests assert agreement, the
analogue of the reference's expression-level unit tests (e.g. ZOrderFieldTest
bit-level checks, BloomFilter sketch tests).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from hyperspace_tpu.ops import hashing as H
from hyperspace_tpu.ops import bucketize as B
from hyperspace_tpu.ops import sketch as SK
from hyperspace_tpu.ops import zorder as Z
from hyperspace_tpu.ops import join as J
from hyperspace_tpu.columnar.table import ColumnBatch


class TestHashing:
    def test_np_jnp_agree_int32(self):
        x = np.arange(-500, 500, dtype=np.int32)
        hn = H.hash32_np([x])
        hj = np.asarray(H.hash32_jnp([jnp.asarray(x)]))
        assert np.array_equal(hn, hj)

    def test_np_jnp_agree_float32(self):
        x = np.linspace(-1e6, 1e6, 1000).astype(np.float32)
        assert np.array_equal(
            H.hash32_np([x]), np.asarray(H.hash32_jnp([jnp.asarray(x)]))
        )

    def test_int64_words_agree_with_split(self):
        x = np.array([0, 1, -1, 2**40, -(2**40), 2**62], dtype=np.int64)
        lo, hi = H.split64_np(x)
        # hashing int64 directly must equal hashing its (lo, hi) words
        assert np.array_equal(H.hash32_np([x]), H.hash32_np([lo, hi]))
        assert np.array_equal(H.merge64_np(lo, hi, np.int64), x)

    def test_bucket_distribution(self):
        x = np.arange(100000, dtype=np.int64)
        b = H.bucket_ids_np([x], 8)
        counts = np.bincount(b, minlength=8)
        assert counts.min() > 100000 / 8 * 0.9  # roughly uniform

    def test_string_hash_stable_across_vocab_order(self):
        words1 = H.string_key_words(np.array([0, 1, 2]), ["a", "b", "c"])
        words2 = H.string_key_words(np.array([2, 1, 0]), ["c", "b", "a"])
        assert np.array_equal(words1, words2)

    def test_multi_column(self):
        a = np.array([1, 1, 2], dtype=np.int32)
        b = np.array([1, 2, 1], dtype=np.int32)
        h = H.hash32_np([a, b])
        assert h[0] != h[1] and h[0] != h[2] and h[1] != h[2]


class TestBucketize:
    def test_partition_covers_all_rows(self):
        batch = ColumnBatch.from_pydict(
            {"k": list(range(1000)), "v": [i * 2 for i in range(1000)]}
        )
        parts = B.partition_batch(batch, ["k"], 8)
        all_rows = np.concatenate([rows for _, rows in parts])
        assert sorted(all_rows.tolist()) == list(range(1000))
        ids = B.bucket_ids_for_batch(batch, ["k"], 8)
        for b, rows in parts:
            assert (ids[rows] == b).all()

    def test_string_bucket_keys(self):
        batch = ColumnBatch.from_pydict({"s": ["x", "y", "z", "x"]})
        ids = B.bucket_ids_for_batch(batch, ["s"], 4)
        assert ids[0] == ids[3]

    def test_sort_within(self):
        batch = ColumnBatch.from_pydict({"a": [3, 1, 2], "b": ["c", "a", "b"]})
        order = B.sort_indices_within(batch, ["a"])
        assert order.tolist() == [1, 2, 0]
        order2 = B.sort_indices_within(batch, ["b"])
        assert order2.tolist() == [1, 2, 0]


class TestSketch:
    def test_segment_min_max_agree(self):
        rng = np.random.default_rng(1)
        vals = rng.normal(size=1000).astype(np.float32)
        segs = rng.integers(0, 10, 1000)
        mn_np, mx_np = SK.segment_min_max_np(vals, segs, 10)
        mn_j, mx_j = SK.segment_min_max_jnp(
            jnp.asarray(vals), jnp.asarray(segs), 10
        )
        assert np.allclose(mn_np, np.asarray(mn_j))
        assert np.allclose(mx_np, np.asarray(mx_j))

    def test_bloom_no_false_negatives(self):
        bf = SK.BloomFilter.create(1000, 0.01)
        keys = np.arange(1000, dtype=np.int64)
        bf.add_words([keys])
        assert bf.might_contain_words([keys]).all()

    def test_bloom_fpp_reasonable(self):
        bf = SK.BloomFilter.create(1000, 0.01)
        bf.add_words([np.arange(1000, dtype=np.int64)])
        probe = np.arange(100000, 200000, dtype=np.int64)
        fp_rate = bf.might_contain_words([probe]).mean()
        assert fp_rate < 0.05

    def test_bloom_merge_and_serialize(self):
        a = SK.BloomFilter.create(100, 0.01)
        b = SK.BloomFilter.create(100, 0.01)
        a.add_words([np.array([1, 2, 3], dtype=np.int64)])
        b.add_words([np.array([100, 200], dtype=np.int64)])
        m = a.merge(b)
        assert m.might_contain_words([np.array([2, 200], dtype=np.int64)]).all()
        rt = SK.BloomFilter.from_dict(m.to_dict())
        assert rt == m

    def test_device_build_matches_host(self):
        keys32 = np.arange(500, dtype=np.int32)
        host = SK.BloomFilter.create(500, 0.01)
        host.add_words([keys32])
        unpacked = SK.bloom_build_bits_jnp(
            [jnp.asarray(keys32)], host.num_bits, host.num_hashes
        )
        packed = SK.pack_bits(np.asarray(unpacked))
        assert np.array_equal(packed, host.bits[: len(packed)])

    def test_device_probe(self):
        keys32 = np.arange(500, dtype=np.int32)
        m, k = SK.bloom_params(500, 0.01)
        bits = SK.bloom_build_bits_jnp([jnp.asarray(keys32)], m, k)
        hits = SK.bloom_probe_bits_jnp(bits, [jnp.asarray(keys32)], k)
        assert np.asarray(hits).all()


class TestZOrder:
    def test_two_field_interleave(self):
        # x=0b10, y=0b01 -> MSB-first round robin: x1 y0 x0 y1 = 0b1001
        x = np.array([0b10], dtype=np.uint64)
        y = np.array([0b01], dtype=np.uint64)
        z = Z.interleave_bits([(x, 2), (y, 2)])
        assert z[0] == 0b1001

    def test_uneven_bits(self):
        # a has 3 bits (0b111), b has 1 bit (0b1): a2 b0 a1 a0 -> 0b1111
        a = np.array([0b111], dtype=np.uint64)
        b = np.array([0b1], dtype=np.uint64)
        z = Z.interleave_bits([(a, 3), (b, 1)])
        assert z[0] == 0b1111

    def test_jnp_agrees(self):
        rng = np.random.default_rng(2)
        a = rng.integers(0, 256, 100).astype(np.uint64)
        b = rng.integers(0, 256, 100).astype(np.uint64)
        zn = Z.interleave_bits([(a, 8), (b, 8)])
        zj = Z.interleave_bits_jnp(
            [(jnp.asarray(a.astype(np.uint32)), 8), (jnp.asarray(b.astype(np.uint32)), 8)]
        )
        assert np.array_equal(zn.astype(np.uint32), np.asarray(zj))

    def test_locality(self):
        # points near each other in 2D should be near in z-order on average
        xs, ys = np.meshgrid(np.arange(16, dtype=np.uint64), np.arange(16, dtype=np.uint64))
        z = Z.interleave_bits([(xs.ravel(), 4), (ys.ravel(), 4)])
        assert len(np.unique(z)) == 256  # bijective

    def test_scale_min_max(self):
        v = np.array([0.0, 50.0, 100.0])
        s = Z.scale_min_max(v, 0.0, 100.0, 4)
        assert s[0] == 0 and s[2] == 15 and 6 <= s[1] <= 8

    def test_scale_percentile(self):
        v = np.array([1.0, 5.0, 100.0, 1000.0])
        bounds = np.array([2.0, 50.0, 500.0])  # 2 bits -> 4 buckets
        s = Z.scale_percentile(v, bounds, 2)
        assert s.tolist() == [0, 1, 2, 3]

    def test_too_many_bits_raises(self):
        from hyperspace_tpu.exceptions import HyperspaceError

        with pytest.raises(HyperspaceError):
            Z.interleave_bits([(np.zeros(1, np.uint64), 40), (np.zeros(1, np.uint64), 40)])


_I32 = np.iinfo(np.int32)


def _probe_case(name):
    """(right keys padded as the kernels pad them, real right rows, left
    keys) of one probe_ranks case."""
    i32 = lambda *a: np.array(a, np.int32)  # noqa: E731
    rng = np.random.default_rng(7)
    if name == "merge_match_counts":
        rk, n_r, lk = i32(2, 2, 3, 5, 5, 5), 6, i32(1, 2, 2, 5)
    elif name == "sorted_zero_tail":
        rk, n_r = i32(-3, 0, 0, 4, 9), 5
        lk = i32(-5, -3, 0, 4, 4, 8, 9, 12, 0, 0, 0)
    elif name == "unsorted_zero_tail":
        rk, n_r = np.sort(rng.integers(-20, 20, 40)).astype(np.int32), 40
        lk = np.concatenate([rng.integers(-25, 25, 50), np.zeros(14)]).astype(np.int32)
    elif name == "disjoint":
        rk, n_r = np.arange(100, 140, dtype=np.int32), 40
        lk = np.concatenate([np.arange(0, 20), np.arange(200, 220)]).astype(np.int32)
    elif name == "int32_extremes":
        # real keys at both ends of int32 beside the dtype-max pads, which
        # the clamp to n_r keeps out of every range
        rk, n_r = i32(_I32.min, _I32.min, 0, _I32.max - 1), 4
        lk = i32(_I32.max, _I32.max - 1, _I32.min, 0, _I32.max - 1, _I32.min)
    elif name == "float_signed_zero":
        rk = np.array([-1.5, -0.0, 0.0, 0.0, 2.0], np.float32)
        n_r = 5
        lk = np.array([0.0, -0.0, 2.0, -1.5, np.inf, -np.inf, 1.0], np.float32)
    elif name == "single_left":
        rk, n_r, lk = i32(1, 3, 3, 3, 8), 5, i32(3)
    elif name == "single_right":
        rk, n_r, lk = i32(3), 1, i32(0, 3, 3, 7, 3)
    elif name == "mixed_widths":  # int16 left keys into int32 right keys
        rk, n_r = i32(-40000, -7, -7, 0, 300, 70000), 6
        lk = np.array([-7, 0, 299, 300, 32767, -32768, 0], np.int16)
    else:  # random: duplicates on both sides
        rk = np.sort(rng.integers(0, 60, 300)).astype(np.int32)
        n_r, lk = 300, rng.integers(-5, 65, 257).astype(np.int32)
    pad = np.inf if rk.dtype.kind == "f" else _I32.max
    rk = np.concatenate([rk, np.full(3, pad, rk.dtype)])
    return rk, n_r, lk


def _expected_ranks(rk, n_r, lk):
    return (np.minimum(np.searchsorted(rk, lk, "left"), n_r),
            np.minimum(np.searchsorted(rk, lk, "right"), n_r))


@pytest.fixture(params=["merge", "search"])
def probe_form(request, monkeypatch):
    """Force probe_ranks' form: every pad ratio merges, or none does."""
    monkeypatch.setattr(J, "_SEARCH_RATIO", 1 << 40 if request.param == "merge" else 0)
    return request.param


class TestJoinPrims:
    @pytest.mark.parametrize("vmapped", [False, True], ids=["flat", "vmap"])
    @pytest.mark.parametrize(
        "case",
        ["merge_match_counts", "sorted_zero_tail", "unsorted_zero_tail",
         "disjoint", "int32_extremes", "float_signed_zero", "single_left",
         "single_right", "mixed_widths", "random"],
    )
    def test_probe_ranks_matches_searchsorted(self, probe_form, case, vmapped):
        rk, n_r, lk = _probe_case(case)
        assert J.merge_probe(len(lk), len(rk)) == (probe_form == "merge")
        if not vmapped:
            lo, hi = jax.jit(J.probe_ranks)(jnp.asarray(rk), jnp.asarray(lk), n_r)
            exp_lo, exp_hi = _expected_ranks(rk, n_r, lk)
            assert np.asarray(lo).tolist() == exp_lo.tolist()
            assert np.asarray(hi).tolist() == exp_hi.tolist()
            return
        # two rows: the case, and its left keys reversed against fewer
        # real right rows
        lks = np.stack([lk, lk[::-1]])
        rks = np.stack([rk, rk])
        n_rs = np.array([n_r, max(n_r - 1, 0)], np.int32)
        lo, hi = jax.jit(jax.vmap(J.probe_ranks))(
            jnp.asarray(rks), jnp.asarray(lks), jnp.asarray(n_rs)
        )
        for b in range(2):
            exp_lo, exp_hi = _expected_ranks(rks[b], n_rs[b], lks[b])
            assert np.asarray(lo[b]).tolist() == exp_lo.tolist()
            assert np.asarray(hi[b]).tolist() == exp_hi.tolist()

    def test_expand_kernel_matches_expand_runs(self, probe_form):
        from hyperspace_tpu.plan.device_join import _build_stacked_expand_kernel

        rng = np.random.default_rng(3)
        pad_l, out_pad = 64, 256
        starts = np.zeros((2, pad_l), np.int64)
        counts = np.zeros((2, pad_l), np.int64)
        for b, n_l in enumerate((50, 9)):
            # empty runs among the real rows, and none past n_l
            counts[b, :n_l] = rng.integers(0, 6, n_l) * (rng.random(n_l) < 0.7)
            starts[b, :n_l] = rng.integers(0, 40, n_l)
        offs = np.cumsum(counts, axis=1) - counts
        totals = counts.sum(axis=1)
        assert totals.max() <= out_pad
        li, ri = _build_stacked_expand_kernel(out_pad)(
            jnp.asarray(starts, jnp.int32), jnp.asarray(offs, jnp.int32),
            jnp.asarray(totals, jnp.int32),
        )
        for b in range(2):
            t = int(totals[b])
            assert np.asarray(ri[b, :t]).tolist() == J.expand_runs(
                starts[b], counts[b]).tolist()
            assert np.asarray(li[b, :t]).tolist() == np.repeat(
                np.arange(pad_l), counts[b]).tolist()
            assert not np.asarray(li[b, t:]).any() and not np.asarray(ri[b, t:]).any()

    def test_segment_sum_by_sorted_key(self):
        keys = jnp.asarray(np.array([1, 1, 2, 2, 2, 7], dtype=np.int32))
        vals = jnp.asarray(np.array([1.0, 2.0, 3.0, 4.0, 5.0, 6.0], dtype=np.float32))
        uniq = jnp.asarray(np.array([1, 2, 5, 7], dtype=np.int32))
        sums = J.segment_sum_by_sorted_key(keys, vals, uniq)
        assert np.asarray(sums).tolist() == [3.0, 12.0, 0.0, 6.0]

    def test_lookup_sorted(self):
        tk = jnp.asarray(np.array([1, 3, 5], dtype=np.int32))
        tv = jnp.asarray(np.array([10, 30, 50], dtype=np.int32))
        q = jnp.asarray(np.array([3, 4, 5, 0], dtype=np.int32))
        vals, found = J.lookup_sorted(tk, tv, q, jnp.int32(-1))
        assert np.asarray(vals).tolist() == [30, -1, 50, -1]
        assert np.asarray(found).tolist() == [True, False, True, False]

    def test_host_merge_join(self):
        li, ri = J.host_merge_join_indices(
            np.array([1, 2, 2, 5]), np.array([2, 2, 3, 5])
        )
        pairs = list(zip(li.tolist(), ri.tolist()))
        assert pairs == [(1, 0), (1, 1), (2, 0), (2, 1), (3, 3)]


class TestSegmentMinMaxNaN:
    def test_np_masks_nan(self):
        import numpy as np
        from hyperspace_tpu.ops.sketch import segment_min_max_np

        vals = np.array([1.0, np.nan, 3.0, np.nan], np.float32)
        segs = np.array([0, 0, 1, 1])
        mins, maxs = segment_min_max_np(vals, segs, 2)
        assert mins[0] == 1.0 and maxs[0] == 1.0
        assert mins[1] == 3.0 and maxs[1] == 3.0

    def test_jnp_matches_np(self):
        import numpy as np
        import jax.numpy as jnp
        from hyperspace_tpu.ops.sketch import segment_min_max_jnp, segment_min_max_np

        vals = np.array([1.0, np.nan, 3.0, 2.0, np.nan], np.float32)
        segs = np.array([0, 0, 1, 1, 1])
        mn, mx = segment_min_max_np(vals, segs, 2)
        jmn, jmx = segment_min_max_jnp(jnp.asarray(vals), jnp.asarray(segs), 2)
        np.testing.assert_array_equal(mn, np.asarray(jmn))
        np.testing.assert_array_equal(mx, np.asarray(jmx))

    def test_all_nan_segment_keeps_empty_bounds(self):
        import numpy as np
        from hyperspace_tpu.ops.sketch import segment_min_max_np

        vals = np.array([np.nan, np.nan], np.float32)
        segs = np.array([0, 0])
        mins, maxs = segment_min_max_np(vals, segs, 1)
        # inverted (empty) interval: no finite value matches, same as an
        # empty file — equality predicates correctly skip it
        assert mins[0] == np.inf and maxs[0] == -np.inf
