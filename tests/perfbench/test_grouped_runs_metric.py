"""The reader of ``grouped_runs``: the window's run-route grouped dispatches
per query, and nothing from a program that has no run route."""

from perfbench import cell as cells
from perfbench.run import Measured


def test_grouped_runs_reads_the_run_dispatches_per_query_of_the_window(monkeypatch):
    from hyperspace_tpu.plan import tpu_exec

    read = cells._module(cells.ROOT, "metrics", "grouped_runs").read
    before = {"histogram": {}, "counter": {"kernel.grouped.runs": 10,
                                           "kernel.grouped.scatters": 30}}
    after = {"histogram": {}, "counter": {"kernel.grouped.runs": 12,
                                          "kernel.grouped.scatters": 30}}
    m = Measured(6, before, after, {}, None, 0, "TPU v5 lite")
    assert read(m) == 2 / 6  # two Q17s in a window of six queries
    empty = {"histogram": {}, "counter": {}}
    assert read(Measured(9, empty, empty, {}, None, 0, "TPU v5 lite")) == 0.0
    assert read(Measured(0, before, after, {}, None, 0, "TPU v5 lite")) is None
    monkeypatch.delattr(tpu_exec, "_run_route")  # a program with no run route
    assert read(m) is None


def test_grouped_runs_is_read_in_sf1_join_only():
    for name in ("sf1_join", "sf1_scan_agg", "sf10_point", "sf1_mesh"):
        names = [m["name"] for m in cells.Cell(name).per_layer]
        assert ("grouped_runs.join" in names) is (name == "sf1_join")
