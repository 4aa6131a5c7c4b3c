"""The reader of ``dispatch_imbalance``: kernel dispatches on the busiest
chip over the mean across the host's chips, and nothing from a program
that counts no dispatch by chip."""

import jax
import pytest

from perfbench import cell as cells
from perfbench.run import Measured

EMPTY = {"histogram": {}, "counter": {}}


def _read(before, after, n=12):
    read = cells._module(cells.ROOT, "metrics", "dispatch_imbalance").read
    return read(Measured(n, before, after, {}, None, 0, "TPU v5 lite"))


def test_dispatch_imbalance_reads_the_window_by_chip(monkeypatch):
    monkeypatch.setattr(jax, "device_count", lambda: 4)
    before = {"histogram": {}, "counter": {
        f"device.dispatch.d{k}": 100 for k in range(4)}}
    after = {"histogram": {}, "counter": {
        "device.dispatch.d0": 160, "device.dispatch.d1": 150,
        "device.dispatch.d2": 140, "device.dispatch.d3": 150}}
    assert _read(before, after) == pytest.approx(60 / 50)


def test_dispatches_before_the_window_do_not_count(monkeypatch):
    """Set-up's warm-up dispatches on chip 0 are outside the window."""
    monkeypatch.setattr(jax, "device_count", lambda: 4)
    before = {"histogram": {}, "counter": {"device.dispatch.d0": 900}}
    after = {"histogram": {}, "counter": {
        f"device.dispatch.d{k}": (900 if k == 0 else 0) + 30
        for k in range(4)}}
    assert _read(before, after) == 1.0


def test_a_program_that_counts_no_dispatch_by_chip_reads_nothing(monkeypatch):
    monkeypatch.setattr(jax, "device_count", lambda: 4)
    assert _read(EMPTY, EMPTY) is None
    parent = {"histogram": {}, "counter": {"cache.kernel.hits": 55}}
    assert _read(EMPTY, parent) is None


def test_the_mesh_cell_reports_the_seven_mesh_metrics():
    """The seven ``.mesh`` metrics the cell was specified with, and the
    accepted readers of the layers it runs besides (Q17's grouping and
    scatters, chunk IO, the device fetch)."""
    c = cells.Cell("sf1_mesh")
    assert c.chips == 4 and c.traffic["clients"] == 4
    got = {m["name"]: m for m in c.per_layer}
    assert set(got) == {
        "home_imbalance.mesh", "dispatch_imbalance.mesh", "compile_ms.mesh",
        "host_ms.mesh", "device_wait_ms.mesh", "plan_ms.mesh",
        "device_round_trips.mesh", "grouping_builds.mesh",
        "grouped_scatters.mesh", "io_ms.mesh", "fetch_ms.mesh"}
    assert got["home_imbalance.mesh"]["layer"] == "served path"
    assert got["dispatch_imbalance.mesh"]["layer"] == "device"
    for name in ("home_imbalance.mesh", "dispatch_imbalance.mesh"):
        assert got[name]["moves"] == "qps.join"
    assert c.config["session"]["hyperspace.tpu.exec.meshDevices"] == 4
    # sf1_join's mix under the names of the cell's query files, which
    # load q3.py's and q17.py's and fail at once on a program without
    # home chips
    join = cells.Cell("sf1_join")
    homed = {f"{q}_homed": q for q in join.traffic["mix"]}
    for key in ("mix", "distinct", "ranges"):
        assert c.traffic[key] == {
            h: join.traffic[key][q] for h, q in homed.items()}
    for h, q in homed.items():
        for fn in ("draw", "in_range", "dataframe", "reference", "compare",
                   "must_read_bytes"):
            got, want = (getattr(m, fn).__code__
                         for m in (c.queries[h], join.queries[q]))
            assert (got.co_filename, got.co_firstlineno) == (
                want.co_filename, want.co_firstlineno)
