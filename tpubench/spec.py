"""Finds the benchmark's files by the names `BENCHMARK.json` gives them.

Everything that belongs to one configuration, one traffic mix, one query
template, one data set, one entry point or one metric sits in a file of
its own under `tpubench/`; nothing here lists them.  `Spec(root)` reads from any
checkout root, so a test can point it at a copy with files added.
"""

from __future__ import annotations

import importlib.util
import json
import os

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class SpecError(ValueError):
    """A name in BENCHMARK.json or a data file does not resolve."""


def device_guard(config: dict, file: str = "the configuration") -> tuple:
    """(the counter a window has to bump, the counters it must leave at
    zero): the machine-read part of `guarantees.device`."""
    guard = config.get("guarantees", {}).get("device")
    if not (isinstance(guard, dict)
            and isinstance(guard.get("must_launch"), str)
            and isinstance(guard.get("must_be_zero"), list)
            and all(isinstance(c, str) for c in guard["must_be_zero"])):
        raise SpecError(
            f"{file}: guarantees.device needs \"must_launch\" (a counter "
            "name) and \"must_be_zero\" (a list of counter names)")
    return guard["must_launch"], guard["must_be_zero"]


class Spec:
    def __init__(self, root: str = ROOT):
        self.root = root
        self.dir = os.path.join(root, "tpubench")
        with open(os.path.join(root, "BENCHMARK.json")) as f:
            self.bench = json.load(f)

    # -- BENCHMARK.json entries ------------------------------------------
    def _entry(self, group: str, name: str) -> dict:
        for e in self.bench[group]:
            if e["name"] == name:
                return e
        known = sorted(e["name"] for e in self.bench[group])
        raise SpecError(f"no {group} entry named {name!r}; known: {known}")

    def cell(self, name: str) -> dict:
        return self._entry("workloads", name)

    def metrics_of(self, cell_name: str, kind: str) -> list[dict]:
        """The `end_to_end` or `per_layer` entries that apply to a cell:
        all of them, less those whose optional `workloads` leaves it out."""
        return [
            m for m in self.bench[kind]
            if "workloads" not in m or cell_name in m["workloads"]
        ]

    # -- files found by name ---------------------------------------------
    def _path(self, *parts: str) -> str:
        path = os.path.join(self.dir, *parts)
        if not os.path.isfile(path):
            raise SpecError(f"{os.path.relpath(path, self.root)} not found")
        return path

    def _json(self, *parts: str) -> dict:
        with open(self._path(*parts)) as f:
            return json.load(f)

    def config(self, name: str) -> dict:
        """The configuration as it is run: the file BENCHMARK.json names.
        It has to say how a run shows that the device did the work
        (`device_guard`), so no cell runs unguarded."""
        file = self._entry("configs", name)["file"]
        if not os.path.isfile(os.path.join(self.root, file)):
            raise SpecError(f"{file} not found")
        with open(os.path.join(self.root, file)) as f:
            doc = json.load(f)
        device_guard(doc, file)
        return doc

    def traffic(self, name: str) -> dict:
        return self._json("traffic", name + ".json")

    def query(self, query_dir: str, template: str) -> str:
        with open(self._path("queries", query_dir, template + ".sql")) as f:
            return " ".join(f.read().split())

    def _module(self, kind: str, name: str):
        path = self._path(kind, name + ".py")
        spec = importlib.util.spec_from_file_location(
            f"tpubench.{kind}.{name}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    def dataset(self, name: str):
        """`datasets/<name>.py`: `TABLES`, `generate`, `bind`, `Oracle`
        (see datasets/tpch_lineitem.py)."""
        return self._module("datasets", name)

    def entry(self, name: str):
        """`entries/<name>.py`: its `ENTRY`, a class built as
        `entries.Entry(device, engine_cfg, tables, spans)`."""
        return self._module("entries", name).ENTRY

    def metric_reader(self, name: str):
        """`metrics/<name>.py`: `read(run)` -> a number, or None where
        the run holds nothing for it to read."""
        return self._module("metrics", name).read
