"""TPC-H Q3, shipping priority (spec 2.4.3): the orders of one market
segment placed before DATE with lines shipped after it, revenue per
order, the ten largest. The covering indexes on l_orderkey and o_orderkey
are bucketed alike, so the device tier joins lineitem and orders bucket by
bucket; the customers of the segment join on o_custkey.

Parameters (spec 2.4.3.3): SEGMENT one of the five (``segment`` indexes
into them); DATE a day of March 1995 (``day``).
"""

import os

import numpy as np

from perfbench.answers import BYTES_PER_VALUE, days, rel_err
from perfbench.tpch_data import SEGMENTS, order_index

TABLES = {"lineitem": ("l_orderkey", "l_extendedprice", "l_discount",
                       "l_shipdate"),
          "orders": ("o_custkey", "o_orderdate", "o_shippriority"),
          "customer": ("c_mktsegment",)}
FLOATS = ("revenue",)
TOP = 10


def draw(rng, ranges, domain) -> dict:
    return {"segment": int(rng.integers(0, len(domain["segments"]))),
            "day": int(rng.integers(ranges["day"][0], ranges["day"][1] + 1))}


def in_range(p, ranges, domain) -> bool:
    return (ranges["day"][0] <= p["day"] <= ranges["day"][1]
            and 0 <= p["segment"] < len(domain["segments"]))


def _date(p):
    return days(1995, 3, p["day"])


def dataframe(session, root, p):
    from hyperspace_tpu.plan import Sum, col, lit

    def read(t):
        return session.read.parquet(os.path.join(root, t))

    d = _date(p)
    cu = (read("customer").filter(col("c_mktsegment") == SEGMENTS[p["segment"]])
          .select("c_custkey"))
    od = (read("orders").filter(col("o_orderdate") < d)
          .select("o_orderkey", "o_custkey", "o_orderdate", "o_shippriority"))
    li = (read("lineitem").filter(col("l_shipdate") > d)
          .select("l_orderkey", "l_extendedprice", "l_discount"))
    return (
        li.join(od, col("l_orderkey") == col("o_orderkey"))
        .join(cu, col("o_custkey") == col("c_custkey"))
        .group_by("l_orderkey", "o_orderdate", "o_shippriority")
        .agg(Sum(col("l_extendedprice") * (lit(1.0) - col("l_discount")))
             .alias("revenue"))
        .select("l_orderkey", "revenue", "o_orderdate", "o_shippriority")
        .sort("revenue", "o_orderdate", ascending=[False, True])
        .limit(TOP)
    )


def _lines(data, p):
    """Mask of the lines that qualify, and their order rows."""
    li, od = data["lineitem"], data["orders"]
    row = order_index(li["l_orderkey"])
    seg = data["customer"]["c_mktsegment"][od["o_custkey"][row] - 1]
    d = _date(p)
    m = ((seg == p["segment"]) & (od["o_orderdate"][row] < d)
         & (li["l_shipdate"] > d))
    return m, row


def _revenue(data, p):
    """Revenue of every order (NaN for the orders with no qualifying
    line), by order row."""
    li = data["lineitem"]
    m, row = _lines(data, p)
    n = len(data["orders"]["o_orderdate"])
    rev = np.bincount(row[m], minlength=n,
                      weights=li["l_extendedprice"][m]
                      * (1.0 - li["l_discount"][m]))
    lines = np.bincount(row[m], minlength=n)
    return np.where(lines > 0, rev, np.nan)


def _key(row):
    row = np.asarray(row)
    return (row // 8) * 32 + row % 8 + 1


def reference(data, p) -> dict:
    od = data["orders"]
    rev = _revenue(data, p)
    has = np.flatnonzero(~np.isnan(rev))
    # revenue descending, then order date ascending
    top = has[np.lexsort((od["o_orderdate"][has], -rev[has]))][:TOP]
    return {
        "l_orderkey": [int(k) for k in _key(top)],
        "revenue": [float(rev[r]) for r in top],
        "o_orderdate": [int(od["o_orderdate"][r]) for r in top],
        "o_shippriority": [int(od["o_shippriority"][r]) for r in top],
        "_revenue": rev,
        "_orders": od,
    }


def compare(got, want):
    """A top-k answer is judged by what it says, not by how near-ties fell:
    every returned order must qualify, with its own date and priority and
    its reference revenue, the rows must come largest first, and the k
    revenues must be the reference's k largest."""
    cols = ["l_orderkey", "revenue", "o_orderdate", "o_shippriority"]
    if list(got) != cols or len(got["revenue"]) != len(want["revenue"]):
        return max(len(want["revenue"]), 1), 0.0
    rev, od = want["_revenue"], want["_orders"]
    wrong, err = 0, 0.0
    for k, r, d, s in zip(*(got[c] for c in cols)):
        row = int(order_index(k)) if k is not None and k >= 1 else -1
        ok = (0 <= row < len(rev) and int(_key(row)) == k
              and not np.isnan(rev[row]) and d == od["o_orderdate"][row]
              and s == od["o_shippriority"][row])
        e = rel_err(r, rev[row]) if ok else None
        wrong += e is None
        err = max(err, e or 0.0)
    if list(got["revenue"]) != sorted(got["revenue"], reverse=True):
        wrong += 1
    for r, w in zip(got["revenue"], want["revenue"]):
        e = rel_err(r, w)
        wrong += e is None
        err = max(err, e or 0.0)
    return wrong, err


def must_read_bytes(data, p) -> int:
    """The segment's customers (key, segment), the orders placed before
    DATE (key, customer, date, priority) and the lines shipped after it
    (key, price, discount, date)."""
    d = _date(p)
    seg = data["customer"]["c_mktsegment"] == p["segment"]
    od = data["orders"]["o_orderdate"] < d
    li = data["lineitem"]["l_shipdate"] > d
    return (int(np.count_nonzero(seg)) * 2 + int(np.count_nonzero(od)) * 4
            + int(np.count_nonzero(li)) * 4) * BYTES_PER_VALUE
