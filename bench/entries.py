"""Finds the benchmark's entries by name.

``BENCHMARK.json`` names the cells, configurations and metrics.  Each entry
is a file of its own, so a later change adds an entry by adding a file:

- a configuration: the ``file`` its ``configs`` entry names (JSON);
- a traffic mix: ``bench/traffic/<name>.json``;
- a per-layer metric: ``bench/metrics/<name>.py``, with ``read(ctx)``;
- a duet's work count: ``bench/work/<duet>.py``, with ``VERSIONS``,
  ``shapes(config)`` and ``work(shapes)``;
- a duet's plain reference: ``bench/reference/<duet>.py``, with
  ``inputs(key, shapes)`` (the duet's arguments from a key),
  ``reference(args, config)`` and ``control(args, config)``.

Nothing here names an entry.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path
from types import ModuleType
from typing import Dict, List

ROOT = Path(__file__).resolve().parents[1]


class EntryError(LookupError):
    """A name that `BENCHMARK.json` or a cell uses has no entry file."""


class Entries:
    """The benchmark rooted at `root` (the directory of BENCHMARK.json)."""

    def __init__(self, root: Path = ROOT):
        self.root = Path(root)
        self.dir = self.root / "bench"
        self.spec = json.loads((self.root / "BENCHMARK.json").read_text())
        self._modules: Dict[Path, ModuleType] = {}

    def _named(self, key: str, name: str) -> dict:
        for entry in self.spec[key]:
            if entry["name"] == name:
                return entry
        raise EntryError(f"BENCHMARK.json has no {key} entry {name!r}")

    def cell(self, name: str) -> dict:
        return self._named("workloads", name)

    def config(self, name: str) -> dict:
        path = self.root / self._named("configs", name)["file"]
        if not path.is_file():
            raise EntryError(f"configuration {name!r}: no file {path}")
        return json.loads(path.read_text())

    def traffic(self, name: str) -> dict:
        return json.loads(self._file("traffic", name, ".json").read_text())

    def metric(self, name: str) -> ModuleType:
        return self._module("metrics", name)

    def work(self, duet: str) -> ModuleType:
        return self._module("work", duet)

    def reference(self, duet: str) -> ModuleType:
        return self._module("reference", duet)

    def peaks(self, device_kind: str) -> dict:
        table = json.loads((self.dir / "peaks.json").read_text())
        if device_kind not in table:
            raise EntryError(f"no peaks for device kind {device_kind!r} in "
                             f"bench/peaks.json")
        return table[device_kind]

    def metrics_for(self, cell: str, kind: str) -> List[dict]:
        """The `kind` ("end_to_end" or "per_layer") metrics `cell` reports:
        those that list it, and those that list no cells."""
        return [m for m in self.spec[kind]
                if cell in m.get("workloads", [cell])]

    def _file(self, kind: str, name: str, suffix: str) -> Path:
        path = self.dir / kind / f"{name}{suffix}"
        if not path.is_file():
            raise EntryError(f"no {kind} entry {name!r}: {path} is missing")
        return path

    def _module(self, kind: str, name: str) -> ModuleType:
        path = self._file(kind, name, ".py")
        mod = self._modules.get(path)
        if mod is None:
            spec = importlib.util.spec_from_file_location(
                f"bench_{kind}_{name}".replace(".", "_").replace("-", "_"),
                path)
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            self._modules[path] = mod
        return mod
