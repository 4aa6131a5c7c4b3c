"""The reader of ``grouped_scatters``: the window's XLA scatters of grouped
kernels per query, and nothing from a program that counts none."""

from perfbench import cell as cells
from perfbench.run import Measured


def test_grouped_scatters_reads_the_scatters_per_query_of_the_window(monkeypatch):
    from hyperspace_tpu.plan import tpu_exec

    read = cells._module(cells.ROOT, "metrics", "grouped_scatters").read
    before = {"histogram": {}, "counter": {"kernel.grouped.scatters": 30}}
    after = {"histogram": {}, "counter": {"kernel.grouped.scatters": 36}}
    m = Measured(6, before, after, {}, None, 0, "TPU v5 lite")
    assert read(m) == 1.0  # two generic kernels of 3 scatters in 6 queries
    empty = {"histogram": {}, "counter": {}}
    assert read(Measured(9, empty, empty, {}, None, 0, "TPU v5 lite")) == 0.0
    assert read(Measured(0, before, after, {}, None, 0, "TPU v5 lite")) is None
    monkeypatch.delattr(tpu_exec, "_grouped_scatters")  # a program with no count
    assert read(m) is None


def test_grouped_scatters_is_read_in_the_sf1_cells_only():
    for name, listed in (("sf1_scan_agg", True), ("sf1_join", True),
                         ("sf10_point", False)):
        names = [m["name"] for m in cells.Cell(name).per_layer]
        assert any(n.startswith("grouped_scatters.") for n in names) is listed
