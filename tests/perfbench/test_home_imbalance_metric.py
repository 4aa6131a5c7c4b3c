"""The reader of ``home_imbalance``: queries homed on the busiest chip over
the mean across the host's chips, and nothing from a program that homes no
query."""

import jax

from perfbench import cell as cells
from perfbench.run import Measured

EMPTY = {"histogram": {}, "counter": {}}


def _read(before, after, n=12):
    read = cells._module(cells.ROOT, "metrics", "home_imbalance").read
    return read(Measured(n, before, after, {}, None, 0, "TPU v5 lite"))


def test_home_imbalance_reads_the_busiest_chip_over_the_mean(monkeypatch):
    monkeypatch.setattr(jax, "device_count", lambda: 4)
    before = {"histogram": {}, "counter": {"serve.home.d0": 6,
                                           "serve.home.d1": 2}}
    after = {"histogram": {}, "counter": {
        "serve.home.d0": 12, "serve.home.d1": 5, "serve.home.d2": 3,
        "serve.home.d3": 4}}
    assert _read(before, after) == 6 / (16 / 4)
    even = {"histogram": {}, "counter": {
        f"serve.home.d{k}": 5 for k in range(4)}}
    assert _read(EMPTY, even) == 1.0


def test_a_chip_that_got_no_query_counts_in_the_mean(monkeypatch):
    monkeypatch.setattr(jax, "device_count", lambda: 4)
    after = {"histogram": {}, "counter": {"serve.home.d0": 8}}
    assert _read(EMPTY, after) == 4.0


def test_a_program_that_homes_no_query_reads_nothing(monkeypatch):
    monkeypatch.setattr(jax, "device_count", lambda: 4)
    assert _read(EMPTY, EMPTY) is None
    one_chip = {"histogram": {}, "counter": {"serve.admitted": 40}}
    assert _read(EMPTY, one_chip) is None


def test_home_imbalance_is_read_in_the_mesh_cell_only():
    for name, listed in (("sf1_mesh", True), ("sf1_join", False),
                         ("sf1_scan_agg", False), ("sf10_point", False)):
        names = [m["name"] for m in cells.Cell(name).per_layer]
        assert ("home_imbalance.mesh" in names) is listed
