"""Compiles of the main-path kernels for a described TPU v5e, at SF10 width.

Nothing runs: the TPU compiler that ships with jax compiles for a chip that
is described, not attached, and refuses what the chip would refuse (tiling,
VMEM, HBM). The topology is described inside a module-scoped fixture only:
only one process may load the TPU library, so describing it at import
would give xdist workers different tests to collect.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

SF10_ROWS = 60_000_000


@pytest.fixture(scope="module")
def topo():
    import os

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def no_compile_cache():
    """A compile for a described chip lands in the persistent cache but
    cannot be read back without one: keep the cache off around them."""
    from jax.experimental.compilation_cache import compilation_cache

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


@pytest.fixture()
def mosaic(monkeypatch):
    """Kernels compiled through Mosaic, as on the chip."""
    from hyperspace_tpu.ops import pallas_kernels
    from hyperspace_tpu.plan import tpu_exec

    monkeypatch.setattr(pallas_kernels, "_interpret", lambda: False)
    monkeypatch.setattr(tpu_exec, "_pallas_route", lambda: True)


def _sds(sharding, dtype, n=SF10_ROWS):
    return jax.ShapeDtypeStruct((n,), dtype, sharding=sharding)


def _compile_text(fn, *args, **static):
    return jax.jit(fn, static_argnames=tuple(static)).lower(*args, **static).compile().as_text()


@pytest.mark.parametrize(
    "kernel",
    ["filter_weighted_sum", "filter_sum", "filter_grouped_multi_sum", "masked_min_max"],
)
def test_pallas_kernel_compiles(topo, one_chip, mosaic, kernel):
    from hyperspace_tpu.ops import pallas_kernels as K

    pred = _sds(one_chip, jnp.bool_)
    x = _sds(one_chip, jnp.float32)
    args = {
        "filter_weighted_sum": (K.filter_weighted_sum, (pred, x, x), {}),
        "filter_sum": (K.filter_sum, (pred, x), {}),
        # Q1's shape: 6 (returnflag, linestatus) groups x 4 measures
        "filter_grouped_multi_sum": (
            K.filter_grouped_multi_sum,
            (pred, _sds(one_chip, jnp.int32), (x,) * 4),
            {"num_groups": 6},
        ),
        "masked_min_max": (K.masked_min_max, (x, pred), {}),
    }
    fn, a, static = args[kernel]
    assert "tpu_custom_call" in _compile_text(fn, *a, **static)


def _lits(words):
    """A kernel's literal vector (``slot_literals``), as a shape."""
    return jax.ShapeDtypeStruct(np.shape(words), jnp.int32)


def test_q6_fused_kernel_compiles(topo, one_chip, mosaic):
    from hyperspace_tpu.plan.expr import col
    from hyperspace_tpu.plan.tpu_exec import _build_kernel, slot_literals

    pred = (
        (col("l_shipdate") >= 8766)
        & (col("l_shipdate") < 9131)
        & (col("l_discount") >= 0.05)
        & (col("l_discount") <= 0.07)
        & (col("l_quantity") < 24)
    )
    agg_list = [("sum", col("l_extendedprice") * col("l_discount")), ("count", None)]
    kinds = {"l_shipdate": "i", "l_discount": "f", "l_quantity": "f",
             "l_extendedprice": "f"}
    pred, proj, aggs, lits = slot_literals(pred, (), agg_list, kinds)
    kernel = _build_kernel(pred, proj, aggs)
    cols = {
        "l_shipdate": _sds(one_chip, jnp.int32),
        "l_discount": _sds(one_chip, jnp.float32),
        "l_quantity": _sds(one_chip, jnp.float32),
        "l_extendedprice": _sds(one_chip, jnp.float32),
    }
    text = kernel.lower(cols, _sds(one_chip, jnp.bool_), _lits(lits)).compile().as_text()
    assert "tpu_custom_call" in text


def test_q1_grouped_kernel_compiles_without_scatter(topo, one_chip, mosaic):
    """Q1's grouped kernel at 2^23 rows and 16 segments: one Pallas pass
    gives the sums, the counts and each group's first passing row, so the
    compiled program holds no XLA scatter."""
    from hyperspace_tpu.plan.expr import col, lit
    from hyperspace_tpu.plan.tpu_exec import _build_grouped_kernel, slot_literals

    disc_price = col("l_extendedprice") * (lit(1.0) - col("l_discount"))
    agg_list = [
        ("sum", col("l_quantity")),
        ("sum", col("l_extendedprice")),
        ("sum", disc_price),
        ("sum", disc_price * (lit(1.0) + col("l_tax"))),
        ("avg", col("l_quantity")),
        ("avg", col("l_extendedprice")),
        ("avg", col("l_discount")),
        ("count", None),
    ]
    kinds = {"l_shipdate": "i", "l_quantity": "f", "l_extendedprice": "f",
             "l_discount": "f", "l_tax": "f"}
    pred, proj, aggs, lits = slot_literals(
        col("l_shipdate") <= 10471, (), agg_list, kinds
    )
    kernel = _build_grouped_kernel(pred, proj, aggs, 16)
    rows = 1 << 23
    cols = {
        name: _sds(one_chip, jnp.int32 if kind == "i" else jnp.float32, rows)
        for name, kind in kinds.items()
    }
    text = kernel.lower(
        cols, _sds(one_chip, jnp.int32, rows), _sds(one_chip, jnp.bool_, rows),
        _lits(lits),
    ).compile().as_text()
    assert "tpu_custom_call" in text
    assert " scatter(" not in text


def test_q17_grouped_kernel_compiles_without_scatter(topo, one_chip, mosaic):
    """Q17's per-part average over ``li_partkey`` at SF1: 2^23 rows in 2^18
    segments, each part one run of the bucketed, sorted index. The run
    route's segmented scans and last-row gathers give the counts, the first
    rows and the sum, so the compiled program holds no XLA scatter."""
    from hyperspace_tpu.plan.expr import col
    from hyperspace_tpu.plan.tpu_exec import _build_grouped_kernel, slot_literals

    pred, proj, aggs, lits = slot_literals(
        None, (), [("avg", col("l_quantity"))], {"l_quantity": "f"}
    )
    seg_pad, rows = 1 << 18, 1 << 23
    kernel = _build_grouped_kernel(pred, proj, aggs, seg_pad, runs=True)
    text = kernel.lower(
        {"l_quantity": _sds(one_chip, jnp.float32, rows)},
        _sds(one_chip, jnp.int32, rows), _sds(one_chip, jnp.bool_, rows),
        _lits(lits), _sds(one_chip, jnp.int32, seg_pad),
    ).compile().as_text()
    assert " scatter(" not in text
    assert " gather(" in text  # each group read at its run's last row


# the order-detail lookup at SF10: one bucket of l_orderkey padded to 2^23
_SELECT_ROWS = 1 << 23


def test_selection_kernel_compiles(topo, one_chip):
    from hyperspace_tpu.plan.expr import col
    from hyperspace_tpu.plan.tpu_exec import (
        SELECT_CAP, _build_select_kernel, slot_literals)

    pred, _, _, lits = slot_literals(col("l_orderkey") == 600000001,
                                     col_kinds={"l_orderkey": "i"})
    kernel = _build_select_kernel(pred, SELECT_CAP)
    cols = {"l_orderkey": _sds(one_chip, jnp.int32, _SELECT_ROWS)}
    compiled = kernel.lower(cols, _sds(one_chip, jnp.bool_, _SELECT_ROWS),
                            _lits(lits)).compile()
    count, pos = compiled.out_info
    assert pos.shape == (SELECT_CAP,) and count.shape == ()


# Q3 at SF10: 60M lineitem rows over 8 buckets probe 15M orders keys,
# one band of 8 buckets in one dispatch
_SF10_BAND = (8, 1 << 23, 1 << 21)


def test_stacked_join_probe_compiles(topo, one_chip):
    from hyperspace_tpu.ops.join import merge_probe
    from hyperspace_tpu.plan.device_join import _build_stacked_probe_kernel

    buckets, pad_l, pad_r = _SF10_BAND
    assert merge_probe(pad_l, pad_r)
    kernel = _build_stacked_probe_kernel(pad_l, pad_r)
    mat = lambda pad: jax.ShapeDtypeStruct((buckets, pad), jnp.int32, sharding=one_chip)
    vec = jax.ShapeDtypeStruct((buckets,), jnp.int32, sharding=one_chip)
    compiled = kernel.lower(mat(pad_l), mat(pad_r), vec, vec).compile()
    assert compiled.memory_analysis() is not None
    assert " sort(" in compiled.as_text()  # the merge form


def test_stacked_join_expand_compiles(topo, one_chip):
    from hyperspace_tpu.ops.join import merge_probe
    from hyperspace_tpu.plan.device_join import _build_stacked_expand_kernel

    # the band's run expansion at one pair per left row
    buckets, pad_l, _ = _SF10_BAND
    assert merge_probe(pad_l, pad_l)
    kernel = _build_stacked_expand_kernel(pad_l)
    mat = jax.ShapeDtypeStruct((buckets, pad_l), jnp.int32, sharding=one_chip)
    vec = jax.ShapeDtypeStruct((buckets,), jnp.int32, sharding=one_chip)
    compiled = kernel.lower(mat, mat, vec).compile()
    assert " sort(" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 4 * 2**30


def test_bucket_exchange_compiles_on_four_chips(topo):
    from hyperspace_tpu.parallel.exchange import bucket_exchange

    mesh = Mesh(np.array(topo.devices[:4]), ("shards",))
    # compile time grows with the capacity (~50 s at SF10's 2^23), the
    # program's structure does not: 1M rows keeps this test near 15 s
    rows = 1 << 20
    shard = NamedSharding(mesh, P("shards"))
    cols = {"b": _sds(shard, jnp.int32, rows), "r": _sds(shard, jnp.int32, rows)}
    capacity = 1 << 19  # exchange_with_retry's guess for 2^18 rows per shard
    fn = jax.jit(lambda c, d: bucket_exchange(mesh, c, d, capacity))
    text = fn.lower(cols, _sds(shard, jnp.int32, rows)).compile().as_text()
    assert "all-to-all" in text
