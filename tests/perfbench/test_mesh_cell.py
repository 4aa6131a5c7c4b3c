"""A whole traced run of the four-chip cell ``sf1_mesh`` on four of the
conftest's eight host devices, at a tiny size: the look for a chip is
skipped, everything else is the benchmark's own run. The answers are
``correct``, the four chips took the window's queries, and nothing
compiled in the window."""

import json
import os
import shutil
from unittest import mock

import jax
import pytest

from perfbench import cell as cells
from perfbench import run

SCALE = 0.01


@pytest.fixture(scope="module")
def mesh_root(tmp_path_factory):
    root = tmp_path_factory.mktemp("checkout")
    shutil.copytree(os.path.join(cells.ROOT, "perfbench"), root / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(cells.ROOT, "BENCHMARK.json"), root)
    path = root / "perfbench" / "configs" / "tpch_sf1_4chip.json"
    cfg = json.loads(path.read_text())
    cfg["scale_factor"], cfg["orders_per_file"] = SCALE, 5_000
    path.write_text(json.dumps(cfg))
    return str(root)


def test_a_traced_run_is_correct_on_four_chips(mesh_root):
    cell = cells.Cell("sf1_mesh", root=mesh_root)
    with mock.patch.dict(os.environ), mock.patch(
            "hyperspace_tpu.utils.backend.enable_compile_cache"), \
            mock.patch.object(jax, "device_count", lambda: 4):
        os.environ.update(run.SERVED_ENV)
        os.environ.update({k: str(v) for k, v in cell.config["env"].items()})
        out = run.run(cell, 2**31 + 5, 1.5, True, jax.devices()[:4])
    assert out["correct"] is True, json.dumps(out["checks"])
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["device"]["count"] == 4
    got = {k: v["value"] for k, v in out["metrics"].items()}
    assert set(got) == {m["name"] for m in cell.per_layer}
    assert got["compile_ms.mesh"] == 0.0
    assert 1.0 <= got["home_imbalance.mesh"] <= 4.0
    assert 1.0 <= got["dispatch_imbalance.mesh"] <= 4.0
