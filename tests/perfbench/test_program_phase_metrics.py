"""The readers of the program's attribution phases (``host_ms``,
``device_wait_ms``, ``fetch_ms``, ``compile_ms``) on a fake run: the mean
per query of the window, and nothing where there is nothing to read."""

import pytest

from perfbench import cell as cells
from hyperspace_tpu.telemetry import attribution

READERS = ("host_ms", "device_wait_ms", "fetch_ms", "compile_ms")
PHASE = {"host_ms": "host", "device_wait_ms": "device_wait",
         "fetch_ms": "fetch", "compile_ms": "compile"}


class FakeRun:
    """What ``perfbench/run.py``'s ``Measured`` gives a reader."""

    def __init__(self, n, sums):
        self.n = n
        self._sums = sums

    def hist_sum(self, name):
        return self._sums.get(name, 0.0)


def _read(metric, run):
    return cells._module(cells.ROOT, "metrics", metric).read(run)


@pytest.mark.parametrize("metric", READERS)
def test_reads_the_mean_phase_per_query_of_the_window(metric):
    name = f"serve.query.phase.{PHASE[metric]}_ms"
    run = FakeRun(8, {name: 100.0, "serve.query.phase.plan_ms": 7.0})
    assert _read(metric, run) == pytest.approx(12.5)
    assert _read(metric, FakeRun(8, {})) == 0.0  # a phase no query entered


@pytest.mark.parametrize("metric", READERS)
def test_an_empty_window_reads_nothing(metric):
    assert _read(metric, FakeRun(0, {})) is None


@pytest.mark.parametrize("metric", READERS)
def test_a_program_without_the_split_reads_nothing(metric, monkeypatch):
    """The parent's phases: a ``fetch`` that holds the device wait, and
    no ``host``, ``device_wait`` or ``compile``."""
    monkeypatch.setattr(attribution, "PHASES", (
        "plan", "io", "upload", "dispatch", "fetch", "fold", "park"))
    run = FakeRun(8, {"serve.query.phase.fetch_ms": 40.0})
    assert _read(metric, run) is None


@pytest.mark.parametrize("workload", ["sf1_join", "sf1_scan_agg"])
def test_every_cell_reports_the_four_phase_metrics(workload):
    c = cells.Cell(workload)
    mix = {"sf1_join": "join", "sf1_scan_agg": "scan_agg"}[workload]
    got = {m["name"]: m for m in c.per_layer}
    for metric in READERS:
        m = got[f"{metric}.{mix}"]
        assert m["moves"] == f"p50_ms.{mix}" and m["unit"] == "ms"
        assert m["source"] == "program_counter"
