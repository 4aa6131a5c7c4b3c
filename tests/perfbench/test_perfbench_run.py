"""Whole runs of the harness on the CPU at a tiny size: the look for a chip
is skipped, everything else is the run the driver makes. The result line
keeps its schema, a host without a TPU is refused, and with the timed path
broken underneath ``correct`` comes out false."""

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


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    """A checkout whose configurations are cut to scale factor ``SCALE``."""
    root = tmp_path_factory.mktemp("checkout")
    shutil.copytree(os.path.join(cells.ROOT, "perfbench"), root / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(cells.ROOT, "BENCHMARK.json"), root)
    for path in (root / "perfbench" / "configs").iterdir():
        cfg = json.loads(path.read_text())
        cfg["scale_factor"], cfg["orders_per_file"] = SCALE, 10_000
        path.write_text(json.dumps(cfg))
    return str(root)


@contextlib.contextmanager
def served(cell):
    """The run's environment, restored afterwards; no compile cache in the
    checkout from a test."""
    with mock.patch.dict(os.environ), mock.patch(
            "hyperspace_tpu.utils.backend.enable_compile_cache"):
        os.environ.update(run.SERVED_ENV)
        os.environ.update({k: str(v) for k, v in cell.config["env"].items()})
        yield


def _run(root, workload, trace=False, seconds=0.6, seed=2**31 + 5):
    import jax

    cell = cells.Cell(workload, root=root)
    with served(cell), mock.patch.object(
            cells, "peaks", lambda kind: {"hbm_bytes_per_s": 819e9}):
        return run.run(cell, seed, seconds, trace, jax.devices()[:1])


def test_a_host_without_a_tpu_is_refused(capsys):
    with mock.patch.dict(os.environ):
        rc = run.main(["--workload", "sf1_join", "--seed", "1",
                       "--seconds", "1", "--trace", "0"])
    out = capsys.readouterr()
    assert rc == 2 and out.out == "" and "no TPU" in out.err


@pytest.mark.parametrize("trace", [False, True])
@pytest.mark.parametrize("workload", ["sf1_join", "sf1_scan_agg"])
def test_the_result_line_keeps_its_schema(tiny_root, workload, trace):
    out = _run(tiny_root, workload, trace)
    assert list(out)[-1] == "checks"
    assert list(out)[:5] == ["correct", "attempted", "failed", "metrics",
                             "device"]
    assert out["correct"] is True, out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    for c in out["checks"].values():
        assert set(c) == {"value", "limit"} and c["value"] <= c["limit"]
    cell = cells.Cell(workload, root=tiny_root)
    names = {m["name"] for m in (cell.per_layer if trace
                                 else cell.end_to_end)}
    assert set(out["metrics"]) <= names
    if not trace:
        assert set(out["metrics"]) == names
    else:
        assert {"plan_ms", "device_round_trips"} <= {
            cells.base(m) for m in out["metrics"]}
    for m in out["metrics"].values():
        assert set(m) == {"value", "unit"} and np.isfinite(m["value"])
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(
        out["device"])
    json.dumps(out)


def test_a_metric_file_reads_program_counters_of_the_window(tiny_root,
                                                            tmp_path):
    """A per-layer metric added by files alone reads the program's counters
    through ``Measured.counter``: the window hits the kernel cache, and
    builds no kernel that set-up did not."""
    root = tmp_path / "checkout"
    shutil.copytree(tiny_root, root)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    for name, counter in (("kernel_hits", "cache.kernel.hits"),
                          ("kernel_builds", "kernel.retrace")):
        (root / "perfbench" / "metrics" / f"{name}.py").write_text(
            f"def read(run):\n    return run.counter({counter!r})\n")
        bench["per_layer"].append({
            "name": f"{name}.scan_agg", "unit": "kernels", "better": "lower",
            "source": "program_counter", "layer": "kernels",
            "moves": "p50_ms.scan_agg", "workloads": ["sf1_scan_agg"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    out = _run(str(root), "sf1_scan_agg", trace=True)
    assert out["correct"] is True, out["checks"]
    assert out["metrics"]["kernel_hits.scan_agg"]["value"] > 0
    assert out["metrics"]["kernel_builds.scan_agg"]["value"] == 0


def _stale(monkeypatch):
    """The scheduler hands every query the previous query's answer."""
    from hyperspace_tpu.serve.scheduler import QueryHandle

    real, last = QueryHandle.result, []

    def result(self, timeout=None):
        last.append(real(self, timeout))
        return last[-2] if len(last) > 1 else last[-1]

    monkeypatch.setattr(QueryHandle, "result", result)


def _half_rows(monkeypatch):
    """Every parquet file reads back half its rows."""
    from hyperspace_tpu.columnar import io

    real = io._read_one_table_once

    def read(*a, **k):
        t = real(*a, **k)
        return t.slice(0, t.num_rows // 2)

    monkeypatch.setattr(io, "_read_one_table_once", read)


def _altered(monkeypatch):
    """Every float the device hands back, and every float an aggregate
    produces on the host, is off by a part in a thousand: the answer is
    altered where it is made (Q6 and Q1 sum on the device; spec Q3 and Q17
    sum on the host, after the device probe)."""
    import jax

    from hyperspace_tpu.plan import executor

    real_get, real_agg = jax.device_get, executor._exec_aggregate

    def get(tree):
        return jax.tree_util.tree_map(
            lambda x: x * 1.001 if np.asarray(x).dtype.kind == "f" else x,
            real_get(tree))

    def agg(plan, session):
        out = real_agg(plan, session)
        for c in out.columns.values():
            if c.data.dtype.kind == "f":
                c.data = c.data * 1.001
        return out

    monkeypatch.setattr(jax, "device_get", get)
    monkeypatch.setattr(executor, "_exec_aggregate", agg)


@pytest.mark.parametrize("fault", [_stale, _half_rows, _altered])
@pytest.mark.parametrize("workload", ["sf1_join", "sf1_scan_agg"])
def test_a_broken_timed_path_is_not_correct(tiny_root, workload, fault,
                                            monkeypatch):
    fault(monkeypatch)
    out = _run(tiny_root, workload)
    assert out["correct"] is False
    assert (out["checks"]["wrong_rows"]["value"] > 0
            or out["checks"]["float_rel_err"]["value"]
            > out["checks"]["float_rel_err"]["limit"])
