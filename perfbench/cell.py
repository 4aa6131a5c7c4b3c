"""Finds everything a cell needs by the names in ``BENCHMARK.json``.

- the workload entry names its configuration and its traffic;
- ``configs[].file`` is the configuration (sizes, index set, settings);
- ``perfbench/traffic/<traffic>.json`` is the traffic mix;
- ``perfbench/cells/<workload>.json`` holds the limits of the numbers that
  decide ``correct``;
- ``perfbench/queries/<query>.py`` is one query shape: ``draw`` and
  ``in_range`` (its parameters, from and against the traffic file's
  ``ranges`` and the configuration's domain), ``dataframe`` (the program's
  query), ``reference`` and ``compare`` (the plain answer and the
  comparison) and ``must_read_bytes``;
- ``perfbench/metrics/<metric>.py`` reads one per-layer metric from what
  ``perfbench/run.py``'s ``Measured`` gives it (the window's deltas of the
  program's histograms and counters, its RPC meter, the trace's
  reduction); a metric split by cell (``plan_ms.join``,
  ``plan_ms.scan_agg``) is read by the file of its base name, the part
  before the first dot.

A later PR adds a cell, a mix, a query or a metric by adding files and
entries; nothing here changes.
"""

from __future__ import annotations

import importlib.util
import json
import os

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load(path: str) -> dict:
    with open(path, encoding="utf-8") as f:
        return json.load(f)


class Cell:
    """One workload of the benchmark, with its files loaded."""

    def __init__(self, name: str, root: str = ROOT):
        bench = _load(os.path.join(root, "BENCHMARK.json"))
        by_name = {w["name"]: w for w in bench["workloads"]}
        if name not in by_name:
            raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                           f"(have {sorted(by_name)})")
        self.name = name
        self.workload = by_name[name]
        self.chips = int(self.workload["chips"])
        entry = {c["name"]: c for c in bench["configs"]}[
            self.workload["config"]]
        self.config = _load(os.path.join(root, entry["file"]))
        here = os.path.join(root, "perfbench")
        self.root = root
        self.traffic = _load(os.path.join(
            here, "traffic", self.workload["traffic"] + ".json"))
        self.limits = _load(os.path.join(here, "cells", name + ".json"))[
            "limits"]
        self.queries = {q: _module(root, "queries", q)
                        for q in sorted(self.traffic["mix"])}
        self.end_to_end = [m for m in bench["end_to_end"] if self._has(m)]
        self.per_layer = [m for m in bench["per_layer"] if self._has(m)]

    def _has(self, metric: dict) -> bool:
        return self.name in metric.get("workloads", [self.name])

    def tables(self) -> dict:
        """``{table: columns}`` the reference needs for this cell's mix."""
        out: dict = {}
        for q in self.queries.values():
            for t, cols in q.TABLES.items():
                out.setdefault(t, set()).update(cols)
        return {t: sorted(c) for t, c in out.items()}

    def reader(self, metric: str):
        """The ``read`` function of ``perfbench/metrics/<base>.py``."""
        return _module(self.root, "metrics", base(metric)).read


def base(metric: str) -> str:
    """``plan_ms`` of ``plan_ms.join``: a metric split by cell."""
    return metric.split(".", 1)[0]


def _module(root: str, kind: str, name: str):
    """``<root>/perfbench/<kind>/<name>.py``, loaded by its path."""
    path = os.path.join(root, "perfbench", kind, name + ".py")
    spec = importlib.util.spec_from_file_location(
        f"perfbench_{kind}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def peaks(device_kind: str) -> dict:
    """The published peaks of one chip; an unknown kind is an error."""
    table = _load(os.path.join(ROOT, "perfbench", "peaks.json"))
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"perfbench/peaks.json")
    return table[device_kind]
