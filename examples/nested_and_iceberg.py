"""Nested columns and the Iceberg-style snapshot source.

Run: python examples/nested_and_iceberg.py

Covers two round-2 capabilities:
1. Indexing nested (struct) fields: struct leaves flatten to
   `__hs_nested.<path>` columns at the reader boundary (ref:
   util/ResolverUtils.scala's normalization) and bare dotted references
   like col("nested.cnt") resolve to them everywhere.
2. The Iceberg-shaped snapshot table: metadata files + manifest lists +
   manifests, random snapshot ids with parent ancestry, time travel by
   snapshot id or timestamp, and ancestry-based index-version matching.
"""

import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq

from hyperspace_tpu import CoveringIndexConfig, Hyperspace, HyperspaceSession
from hyperspace_tpu.columnar.table import ColumnBatch
from hyperspace_tpu.plan import col
from hyperspace_tpu.sources.iceberg import IcebergStyleTable

ws = tempfile.mkdtemp(prefix="hs_example_")
session = HyperspaceSession(warehouse_dir=ws)
hs = Hyperspace(session)

# --- 1. nested columns ------------------------------------------------------
rng = np.random.default_rng(0)
n = 10_000
nested_table = pa.table(
    {
        "id": pa.array(np.arange(n)),
        "nested": pa.StructArray.from_arrays(
            [pa.array(rng.integers(0, 100, n)), pa.array(rng.uniform(0, 1, n))],
            names=["cnt", "score"],
        ),
    }
)
src = os.path.join(ws, "events")
os.makedirs(src)
pq.write_table(nested_table, os.path.join(src, "part-0.parquet"))

df = session.read.parquet(src)
print("flattened schema:", df.schema.names)

# index the nested field by its dotted path; the index column is the
# normalized __hs_nested.nested.cnt
hs.create_index(df, CoveringIndexConfig("ev_cnt", ["nested.cnt"], ["id"]))
session.enable_hyperspace()
out = (
    session.read.parquet(src)
    .filter(col("nested.cnt") == 7)
    .select("id", "nested.cnt")
    .to_pydict()
)
print("rows with nested.cnt == 7:", len(out["id"]))
print(hs.why_not(session.read.parquet(src).select("id")))
session.disable_hyperspace()

# --- 2. iceberg-style snapshots --------------------------------------------
t = IcebergStyleTable(os.path.join(ws, "sales"))
s0 = t.commit(ColumnBatch.from_pydict({"k": [1, 2, 3], "v": [1.0, 2.0, 3.0]}))
s1 = t.commit(ColumnBatch.from_pydict({"k": [4], "v": [4.0]}))
print("snapshots:", s0, "->", s1, "(parent:", t.parent_of(s1), ")")

hs.create_index(t.scan(session), CoveringIndexConfig("sales_k", ["k"], ["v"]))
session.enable_hyperspace()
print("current rows:", t.scan(session).count())
print("time travel to first snapshot:", t.scan(session, snapshot_id=s0).count())
# the filter over the old snapshot still uses the index version recorded
# against an ancestor snapshot (ancestry-walk matching)
old = t.scan(session, snapshot_id=s0).filter(col("k") == 2).select("k", "v")
print("old-snapshot lookup:", old.to_pydict())
