"""chip_smoke.py's phases at a tiny size on the 8-device CPU mesh, and its
refusal to run anywhere but on a TPU."""

import json

import pytest

import chip_smoke


@pytest.mark.parametrize("chips", [1, 4])
def test_phases_pass_on_cpu_mesh(tmp_path, capsys, chips):
    """chips=1: every query matches the host raw scan on a device route
    through DataFrame and the scheduler; chips=4: the mesh build, mesh
    aggregates and placed joins match the one-chip answers on 4 of the 8
    virtual devices."""
    assert chip_smoke.run(str(tmp_path), rows=100_000, seed=1, chips=chips)
    out = capsys.readouterr().out
    assert "FAIL" not in out
    if chips == 1:
        for name in chip_smoke.QUERIES:
            for via in ("dataframe", "scheduler"):
                assert f"query {name} via {via}: routes" in out
        assert "device.degrades 0, breaker closed" in out
    else:
        assert "mesh build exchanged over the mesh: True" in out
        assert "placed on devices [0, 1, 2, 3]" in out


def test_main_refuses_without_tpu(capsys):
    assert chip_smoke.main(["--rows", "1000"]) != 0
    out = capsys.readouterr().out
    assert '"ok": true' not in out
    for line in out.splitlines():
        with pytest.raises(json.JSONDecodeError):
            json.loads(line)


@pytest.mark.parametrize(
    "got,diff",
    [
        ({"k": [1, 2], "s": [1.0, 2.0]}, None),
        ({"k": [1, 2], "s": [1.0, 2.0 * (1 + 1e-5)]}, None),  # within rtol
        ({"k": [1, 2], "s": [1.0, 2.0 * (1 + 1e-3)]}, "s[1]"),
        ({"k": [1, 3], "s": [1.0, 2.0]}, "k[1]"),  # keys match exactly
        ({"k": [1], "s": [1.0]}, "k: 1 rows"),
    ],
)
def test_mismatch(got, diff):
    want = {"k": [1, 2], "s": [1.0, 2.0]}
    out = chip_smoke.mismatch(got, want, rtol=1e-4)
    assert out is None if diff is None else out.startswith(diff)


def test_float_rtol_at_sf10():
    assert 1e-4 < chip_smoke.float_rtol(chip_smoke.SF10_ROWS) < 2e-4
