"""TPC-H Q17, small-quantity-order revenue (spec 2.4.17): lines of the
parts of one brand and container whose quantity is under a fifth of that
part's average, joined back against lineitem. The covering indexes on
l_partkey and p_partkey are bucketed alike, so the device tier joins
bucket by bucket.

Parameters (spec 2.4.17.3): BRAND ``Brand#MN`` (``brand`` indexes into the
25) and CONTAINER (``container`` indexes into the 40).
"""

import os

import numpy as np

from perfbench.answers import BYTES_PER_VALUE, compare_rows
from perfbench.tpch_data import BRANDS, CONTAINERS

TABLES = {"lineitem": ("l_partkey", "l_quantity", "l_extendedprice"),
          "part": ("p_brand", "p_container")}
FLOATS = ("avg_yearly",)


def draw(rng, ranges, domain) -> dict:
    return {"brand": int(rng.integers(0, len(domain["brands"]))),
            "container": int(rng.integers(0, len(domain["containers"])))}


def in_range(p, ranges, domain) -> bool:
    return (0 <= p["brand"] < len(domain["brands"])
            and 0 <= p["container"] < len(domain["containers"]))


def dataframe(session, root, p):
    from hyperspace_tpu.plan import Avg, Sum, col, lit

    li = session.read.parquet(os.path.join(root, "lineitem"))
    pt = session.read.parquet(os.path.join(root, "part"))
    avg_qty = (
        li.select("l_partkey", "l_quantity")
        .group_by("l_partkey")
        .agg(Avg(col("l_quantity")).alias("avg_qty"))
        .select(col("l_partkey").alias("ap_partkey"), col("avg_qty"))
    )
    return (
        li.select("l_partkey", "l_quantity", "l_extendedprice")
        .join(pt.filter((col("p_brand") == BRANDS[p["brand"]])
                        & (col("p_container") == CONTAINERS[p["container"]]))
              .select("p_partkey"),
              col("l_partkey") == col("p_partkey"))
        .join(avg_qty, col("l_partkey") == col("ap_partkey"))
        .filter(col("l_quantity") < lit(0.2) * col("avg_qty"))
        .agg(Sum(col("l_extendedprice")).alias("total"))
        .select((col("total") / lit(7.0)).alias("avg_yearly"))
    )


def _parts(data, p):
    pt = data["part"]
    return (pt["p_brand"] == p["brand"]) & (pt["p_container"] == p["container"])


def reference(data, p) -> dict:
    li = data["lineitem"]
    key, qty = li["l_partkey"] - 1, li["l_quantity"]
    n = len(data["part"]["p_brand"])
    lines = np.bincount(key, minlength=n)
    avg = np.bincount(key, weights=qty, minlength=n) / np.maximum(lines, 1)
    m = _parts(data, p)[key] & (qty < 0.2 * avg[key])
    if not m.any():
        return {"avg_yearly": [None]}
    return {"avg_yearly": [float(np.sum(li["l_extendedprice"][m])) / 7.0]}


def compare(got, want):
    return compare_rows(got, want, FLOATS)


def must_read_bytes(data, p) -> int:
    """The brand and container's parts (key, brand, container) and their
    lines (key, quantity, price)."""
    mine = _parts(data, p)
    lines = mine[data["lineitem"]["l_partkey"] - 1]
    return (int(np.count_nonzero(mine)) * 3
            + int(np.count_nonzero(lines)) * 3) * BYTES_PER_VALUE
