"""TPC-H Q1, pricing summary report (spec 2.4.1): every lineitem shipped
up to DATE, grouped by return flag and line status, with four sums, three
averages and a count. The covering index on the group keys serves it; the
grouped histogram kernel aggregates.

Parameter (spec 2.4.1.3): DELTA days in ``delta``; DATE is 1998-12-01
minus DELTA.
"""

import os

import numpy as np

from perfbench.answers import BYTES_PER_VALUE, compare_rows, days
from perfbench.tpch_data import FLAGS, STATUSES

TABLES = {"lineitem": ("l_shipdate", "l_returnflag", "l_linestatus",
                       "l_quantity", "l_extendedprice", "l_discount",
                       "l_tax")}
FLOATS = ("sum_qty", "sum_base_price", "sum_disc_price", "sum_charge",
          "avg_qty", "avg_price", "avg_disc")


def draw(rng, ranges, domain) -> dict:
    return {"delta": int(rng.integers(ranges["delta"][0],
                                      ranges["delta"][1] + 1))}


def in_range(p, ranges, domain) -> bool:
    return ranges["delta"][0] <= p["delta"] <= ranges["delta"][1]


def _date(p):
    return days(1998, 12, 1) - p["delta"]


def dataframe(session, root, p):
    from hyperspace_tpu.plan import Avg, Count, Sum, col, lit

    li = session.read.parquet(os.path.join(root, "lineitem"))
    disc_price = col("l_extendedprice") * (lit(1.0) - col("l_discount"))
    return (
        li.filter(col("l_shipdate") <= _date(p))
        .select("l_returnflag", "l_linestatus", "l_quantity",
                "l_extendedprice", "l_discount", "l_tax")
        .group_by("l_returnflag", "l_linestatus")
        .agg(
            Sum(col("l_quantity")).alias("sum_qty"),
            Sum(col("l_extendedprice")).alias("sum_base_price"),
            Sum(disc_price).alias("sum_disc_price"),
            Sum(disc_price * (lit(1.0) + col("l_tax"))).alias("sum_charge"),
            Avg(col("l_quantity")).alias("avg_qty"),
            Avg(col("l_extendedprice")).alias("avg_price"),
            Avg(col("l_discount")).alias("avg_disc"),
            Count(lit(1)).alias("count_order"),
        )
        .sort("l_returnflag", "l_linestatus")
    )


def reference(data, p) -> dict:
    li = data["lineitem"]
    m = li["l_shipdate"] <= _date(p)
    g = (li["l_returnflag"][m].astype(np.int64) * len(STATUSES)
         + li["l_linestatus"][m])
    k = len(FLAGS) * len(STATUSES)
    price, qty, disc = (li[c][m] for c in ("l_extendedprice", "l_quantity",
                                           "l_discount"))
    disc_price = price * (1.0 - disc)

    def total(w):
        return np.bincount(g, weights=w, minlength=k)

    n = np.bincount(g, minlength=k)
    sq, sp, sd = total(qty), total(price), total(disc_price)
    sc, sdisc = total(disc_price * (1.0 + li["l_tax"][m])), total(disc)
    groups = sorted((FLAGS[i // len(STATUSES)], STATUSES[i % len(STATUSES)],
                     i) for i in range(k) if n[i])
    rows = [i for _, _, i in groups]
    return {
        "l_returnflag": [f for f, _, _ in groups],
        "l_linestatus": [s for _, s, _ in groups],
        "sum_qty": [float(sq[i]) for i in rows],
        "sum_base_price": [float(sp[i]) for i in rows],
        "sum_disc_price": [float(sd[i]) for i in rows],
        "sum_charge": [float(sc[i]) for i in rows],
        "avg_qty": [float(sq[i] / n[i]) for i in rows],
        "avg_price": [float(sp[i] / n[i]) for i in rows],
        "avg_disc": [float(sdisc[i] / n[i]) for i in rows],
        "count_order": [int(n[i]) for i in rows],
    }


def compare(got, want):
    return compare_rows(got, want, FLOATS)


def must_read_bytes(data, p) -> int:
    """The rows shipped up to DATE, each of the seven columns the query
    references."""
    s = data["lineitem"]["l_shipdate"]
    return int(np.count_nonzero(s <= _date(p))) * 7 * BYTES_PER_VALUE
