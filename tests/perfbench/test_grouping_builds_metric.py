"""The reader of ``grouping_builds``: the window's groupings built per
query, and nothing from a program that caches no grouping."""

from perfbench import cell as cells
from perfbench.run import Measured


def test_grouping_builds_reads_the_builds_per_query_of_the_window(monkeypatch):
    from hyperspace_tpu.plan import tpu_exec

    read = cells._module(cells.ROOT, "metrics", "grouping_builds").read
    before = {"histogram": {}, "counter": {"pipeline.grouping.builds": 4,
                                           "pipeline.grouping.hits": 10}}
    after = {"histogram": {}, "counter": {"pipeline.grouping.builds": 6,
                                          "pipeline.grouping.hits": 40}}
    m = Measured(8, before, after, {}, None, 0, "TPU v5 lite")
    assert read(m) == 0.25  # 2 builds in 8 queries; hits are not builds
    assert read(Measured(0, before, after, {}, None, 0, "TPU v5 lite")) is None
    monkeypatch.delattr(tpu_exec, "_grouping")  # a program with no cache
    assert read(m) is None
