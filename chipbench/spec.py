"""Finds the benchmark's parts by the names ``BENCHMARK.json`` gives.

A cell is ``workloads/<cell>.json``; a configuration is
``configs/<config>.json`` with its plain reference in
``configs/<config>.py``; a per-layer metric is ``metrics/<metric>.py``
with a ``read(ctx)`` function; a kind of run is ``runners/<runner>.py``
with a ``run(...)`` function. Adding any of them means adding files and
entries, never editing one that is there.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path

HERE = Path(__file__).resolve().parent


class SpecError(Exception):
    pass


def _load_module(path: Path, name: str):
    if not path.is_file():
        raise SpecError(f"missing {path}")
    mod_spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


class Spec:
    def __init__(self, root: Path = HERE, benchmark: Path | None = None):
        self.root = Path(root)
        path = benchmark or self.root.parent / "BENCHMARK.json"
        if not path.is_file():
            raise SpecError(f"missing {path}")
        self.bench = json.loads(path.read_text())

    def _json(self, kind: str, name: str) -> dict:
        path = self.root / kind / f"{name}.json"
        if not path.is_file():
            raise SpecError(f"missing {path}")
        data = json.loads(path.read_text())
        if data.get("name") != name:
            raise SpecError(f"{path} names itself {data.get('name')!r}")
        return data

    def cell(self, name: str) -> dict:
        entries = [w for w in self.bench["workloads"] if w["name"] == name]
        if not entries:
            raise SpecError(f"no workload {name!r} in BENCHMARK.json")
        cell = self._json("workloads", name)
        entry = entries[0]
        for key in ("config", "chips"):
            if cell[key] != entry[key]:
                raise SpecError(f"{name}: {key} is {cell[key]!r} in its file "
                                f"and {entry[key]!r} in BENCHMARK.json")
        return cell

    def config(self, name: str) -> dict:
        return self._json("configs", name)

    def reference(self, name: str):
        return _load_module(self.root / "configs" / f"{name}.py",
                            f"chipbench_ref_{name}")

    def runner(self, name: str):
        return _load_module(self.root / "runners" / f"{name}.py",
                            f"chipbench_runner_{name}")

    def metric_reader(self, name: str):
        return _load_module(self.root / "metrics" / f"{name}.py",
                            f"chipbench_metric_{name}")

    def metrics_for(self, group: str, cell: str) -> list:
        """The ``end_to_end`` or ``per_layer`` entries that ``cell`` reports."""
        return [m for m in self.bench[group]
                if "workloads" not in m or cell in m["workloads"]]


def peaks(device_kind: str, path: Path = HERE / "peaks.json") -> dict:
    """Published peaks of one chip; a device that is not in the table is an
    error, never a default."""
    table = json.loads(Path(path).read_text())
    if device_kind not in table:
        raise SpecError(f"no peaks for device kind {device_kind!r}; known: "
                        f"{sorted(table)}")
    return table[device_kind]
