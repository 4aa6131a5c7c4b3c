"""An order-detail lookup: every line of one order by ``l_orderkey``, the
query an order-status or customer-service API sends. Hyperspace's
FilterIndexRule serves it from the covering index on ``l_orderkey``: the
bucket of the key, the row groups of its sorted run, and a device selection
of the matching rows, returned whole (1 to 7 lines an order).

Parameter: ``orderkey``, an existing order key drawn uniformly over all the
orders of the scale factor.
"""

import os

import numpy as np

from perfbench.answers import BYTES_PER_VALUE, compare_rows
from perfbench.tpch_data import VOCAB, order_index

COLUMNS = ("l_orderkey", "l_linenumber", "l_partkey", "l_suppkey",
           "l_quantity", "l_extendedprice", "l_discount", "l_tax",
           "l_returnflag", "l_linestatus", "l_shipdate", "l_commitdate",
           "l_receiptdate", "l_shipmode")
TABLES = {"lineitem": COLUMNS}
FLOATS = ("l_quantity", "l_extendedprice", "l_discount", "l_tax")


def _key(row):
    """Order key of an order row (the first 8 of every 32 keys)."""
    return (row // 8) * 32 + row % 8 + 1


def draw(rng, ranges, domain) -> dict:
    return {"orderkey": int(_key(int(rng.integers(0, domain["orders"]))))}


def in_range(p, ranges, domain) -> bool:
    row = int(order_index(p["orderkey"]))
    return 0 <= row < domain["orders"] and _key(row) == p["orderkey"]


def dataframe(session, root, p):
    from hyperspace_tpu.plan import col

    return (session.read.parquet(os.path.join(root, "lineitem"))
            .filter(col("l_orderkey") == p["orderkey"])
            .select(*COLUMNS))


def _lines(data, p):
    """Rows of the order's lines: lineitem is generated in order-key order,
    checked once per data set, so the lines are one run found by binary
    search."""
    li = data["lineitem"]
    keys = li["l_orderkey"]
    if li.get("_sorted") is not keys:
        if not np.all(keys[1:] >= keys[:-1]):
            raise ValueError("l_orderkey is not in order-key order")
        li["_sorted"] = keys
    lo = np.searchsorted(keys, p["orderkey"], side="left")
    hi = np.searchsorted(keys, p["orderkey"], side="right")
    return slice(int(lo), int(hi))


def reference(data, p) -> dict:
    li = data["lineitem"]
    rows = _lines(data, p)
    out = {}
    for c in COLUMNS:
        v = li[c][rows]
        out[c] = ([VOCAB[c][i] for i in v] if c in VOCAB else v.tolist())
    return out


def _by_line(answer: dict) -> dict:
    """The answer's rows ordered by line number (SQL fixes no row order)."""
    if list(answer) != list(COLUMNS):
        return answer
    order = np.argsort(np.asarray(answer["l_linenumber"]), kind="stable")
    return {c: [v[i] for i in order] for c, v in answer.items()}


def compare(got, want):
    return compare_rows(_by_line(got), _by_line(want), FLOATS)


def must_read_bytes(data, p) -> int:
    """The order's lines, each of the 14 columns returned."""
    rows = _lines(data, p)
    return (rows.stop - rows.start) * len(COLUMNS) * BYTES_PER_VALUE
