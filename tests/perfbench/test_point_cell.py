"""The order-detail lookup cell (``sf10_point``) run whole on the CPU at a
tiny size, as the driver runs it save the chip check: every lookup is
answered on the device route, builds no kernel in the window, and a broken
timed path comes out not correct. And its two readers of program counters."""

import contextlib
import json
import os
import shutil
from unittest import mock

import numpy as np
import pytest

from perfbench import cell as cells
from perfbench import run

SCALE = 0.02  # 30,000 orders, about 120,000 lines
DISTINCT = 64  # keys a tiny run warms up (the cell draws 1,024)


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    root = tmp_path_factory.mktemp("checkout")
    shutil.copytree(os.path.join(cells.ROOT, "perfbench"), root / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(cells.ROOT, "BENCHMARK.json"), root)
    path = root / "perfbench" / "configs" / "tpch_sf10.json"
    cfg = json.loads(path.read_text())
    cfg["scale_factor"], cfg["orders_per_file"] = SCALE, 10_000
    path.write_text(json.dumps(cfg))
    path = root / "perfbench" / "traffic" / "point.json"
    mix = json.loads(path.read_text())
    mix["distinct"]["point"] = DISTINCT
    path.write_text(json.dumps(mix))
    return str(root)


@contextlib.contextmanager
def served(cell):
    with mock.patch.dict(os.environ), mock.patch(
            "hyperspace_tpu.utils.backend.enable_compile_cache"):
        os.environ.update(run.SERVED_ENV)
        os.environ.update({k: str(v) for k, v in cell.config["env"].items()})
        yield


def _run(root, trace=False, seconds=0.6, seed=2**31 + 11):
    import jax

    cell = cells.Cell("sf10_point", root=root)
    with served(cell), mock.patch.object(
            cells, "peaks", lambda kind: {"hbm_bytes_per_s": 819e9}):
        return run.run(cell, seed, seconds, trace, jax.devices()[:1])


@pytest.mark.parametrize("trace", [False, True])
def test_every_lookup_is_exact_and_on_the_device(tiny_root, trace):
    out = _run(tiny_root, trace)
    assert out["correct"] is True, out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["checks"]["off_device"]["value"] == 0
    assert out["checks"]["float_rel_err"]["value"] == 0.0
    cell = cells.Cell("sf10_point", root=tiny_root)
    names = {m["name"] for m in (cell.per_layer if trace else cell.end_to_end)}
    if trace:
        got = {k: v["value"] for k, v in out["metrics"].items()}
        assert set(got) <= names
        assert got["kernel_builds.point"] == 0
        assert got["compile_ms.point"] == 0
        assert got["select_rows.point"] > 0
        assert got["device_round_trips.point"] == 2.0  # a dispatch, a fetch
    else:
        assert set(out["metrics"]) == names


def _stale(monkeypatch):
    from hyperspace_tpu.serve.scheduler import QueryHandle

    real, last = QueryHandle.result, []

    def result(self, timeout=None):
        last.append(real(self, timeout))
        return last[-2] if len(last) > 1 else last[-1]

    monkeypatch.setattr(QueryHandle, "result", result)


def _last_row_lost(monkeypatch):
    """The device selection drops the last row it matched."""
    from hyperspace_tpu.plan import tpu_exec

    real = tpu_exec._select_positions

    def positions(*a, **k):
        pos = real(*a, **k)
        return pos if pos is None or len(pos) < 2 else pos[:-1]

    monkeypatch.setattr(tpu_exec, "_select_positions", positions)


def _float32_values(monkeypatch):
    """The selected rows' float64 values come back through float32."""
    from hyperspace_tpu.plan import tpu_exec

    real = tpu_exec.try_execute_select

    def select(plan, session):
        out = real(plan, session)
        if out is not None:
            for c in out.columns.values():
                if c.data.dtype == np.float64:
                    c.data = c.data.astype(np.float32).astype(np.float64)
        return out

    monkeypatch.setattr(tpu_exec, "try_execute_select", select)


@pytest.mark.parametrize("fault", [_stale, _last_row_lost, _float32_values])
def test_a_broken_timed_path_is_not_correct(tiny_root, fault, monkeypatch):
    fault(monkeypatch)
    out = _run(tiny_root)
    assert out["correct"] is False
    assert (out["checks"]["wrong_rows"]["value"] > 0
            or out["checks"]["float_rel_err"]["value"]
            > out["checks"]["float_rel_err"]["limit"])


class FakeRun:
    def __init__(self, n, counters):
        self.n = n
        self._counters = counters

    def counter(self, name):
        return float(self._counters.get(name, 0.0))


def _reader(name):
    return cells._module(cells.ROOT, "metrics", name).read


def test_the_readers_take_their_counters_per_query_of_the_window():
    run_ = FakeRun(8, {"kernel.retrace": 2, "select.rows_scanned": 800})
    assert _reader("kernel_builds")(run_) == 0.25
    assert _reader("select_rows")(run_) == 100.0
    assert _reader("kernel_builds")(FakeRun(0, {})) is None
    assert _reader("select_rows")(FakeRun(0, {})) is None


def test_select_rows_reads_nothing_on_a_program_without_the_route(monkeypatch):
    from hyperspace_tpu.plan import tpu_exec

    monkeypatch.delattr(tpu_exec, "try_execute_select")
    assert _reader("select_rows")(FakeRun(8, {})) is None
