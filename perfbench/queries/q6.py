"""TPC-H Q6, forecasting revenue change (spec 2.4.6): a one-year range on
l_shipdate, a discount band and a quantity bound, and one global sum. The
z-order index on l_shipdate prunes, the fused filter-sum kernel sums.

Parameters (spec 2.4.6.3): DATE is 1 January of a year in ``year``;
DISCOUNT is ``cents``/100, the band DISCOUNT +- 0.01; QUANTITY in
``quantity``.
"""

import os

import numpy as np

from perfbench.answers import BYTES_PER_VALUE, compare_rows, days

TABLES = {"lineitem": ("l_shipdate", "l_discount", "l_quantity",
                       "l_extendedprice")}
FLOATS = ("revenue",)


def draw(rng, ranges, domain) -> dict:
    return {
        "year": int(rng.integers(ranges["year"][0], ranges["year"][1] + 1)),
        "cents": int(rng.integers(ranges["cents"][0], ranges["cents"][1] + 1)),
        "quantity": int(rng.integers(ranges["quantity"][0],
                                     ranges["quantity"][1] + 1)),
    }


def in_range(p, ranges, domain) -> bool:
    return all(ranges[k][0] <= p[k] <= ranges[k][1]
               for k in ("year", "cents", "quantity"))


def _bounds(p):
    # (c -+ 1) / 100 is the very float64 the generator's round(x, 2) makes
    return (days(p["year"], 1, 1), days(p["year"] + 1, 1, 1),
            (p["cents"] - 1) / 100, (p["cents"] + 1) / 100)


def dataframe(session, root, p):
    from hyperspace_tpu.plan import Sum, col

    lo, hi, dlo, dhi = _bounds(p)
    li = session.read.parquet(os.path.join(root, "lineitem"))
    return (
        li.filter((col("l_shipdate") >= lo) & (col("l_shipdate") < hi)
                  & (col("l_discount") >= dlo) & (col("l_discount") <= dhi)
                  & (col("l_quantity") < p["quantity"]))
        .select("l_shipdate", "l_extendedprice", "l_discount", "l_quantity")
        .agg(Sum(col("l_extendedprice") * col("l_discount")).alias("revenue"))
    )


def reference(data, p) -> dict:
    li = data["lineitem"]
    lo, hi, dlo, dhi = _bounds(p)
    d = li["l_discount"]
    m = ((li["l_shipdate"] >= lo) & (li["l_shipdate"] < hi) & (d >= dlo)
         & (d <= dhi) & (li["l_quantity"] < p["quantity"]))
    if not m.any():
        return {"revenue": [None]}
    return {"revenue": [float(np.sum(li["l_extendedprice"][m] * d[m]))]}


def compare(got, want):
    return compare_rows(got, want, FLOATS)


def must_read_bytes(data, p) -> int:
    """The rows in the shipdate year, each of the four columns the query
    references."""
    lo, hi, _, _ = _bounds(p)
    s = data["lineitem"]["l_shipdate"]
    return int(np.count_nonzero((s >= lo) & (s < hi))) * 4 * BYTES_PER_VALUE
