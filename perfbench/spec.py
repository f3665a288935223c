"""BENCHMARK.json and the data files it names: cells, configurations,
traffic mixes and per-layer metrics, each found by NAME.

A later PR adds a cell with one ``workloads`` entry plus files of its own
(``cells/<cell>.json``, and where new ``configs/<config>.json``,
``traffic/<traffic>.json``, ``layer_metrics/<metric>.json``); nothing here
or in any existing file needs an edit.
"""

from __future__ import annotations

import json
import re
from dataclasses import dataclass, field
from pathlib import Path
from typing import Optional

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


class SpecError(Exception):
    """The benchmark's own files are inconsistent; no run is possible."""


def check_name(name: str, what: str) -> str:
    if not isinstance(name, str) or not NAME_RE.match(name):
        raise SpecError(f"{what} {name!r} is not a valid name")
    return name


def check_unit(unit: str, what: str) -> str:
    if not isinstance(unit, str) or not UNIT_RE.match(unit):
        raise SpecError(f"{what}: unit {unit!r} is not a valid unit")
    return unit


def _load(path: Path, what: str) -> dict:
    if not path.is_file():
        raise SpecError(f"{what}: no file {path}")
    try:
        return json.loads(path.read_text())
    except json.JSONDecodeError as e:
        raise SpecError(f"{what}: {path} is not JSON: {e}") from e


@dataclass
class Cell:
    """One entry of ``workloads`` with everything it names, loaded."""
    name: str
    chips: int
    config_name: str
    traffic_name: str
    config: dict            # configs/<config>.json
    traffic: dict           # traffic/<traffic>.json
    load: dict              # cells/<cell>.json: rate_rps or clients
    end_to_end: list        # the BENCHMARK.json entries this cell reports
    per_layer: list         # BENCHMARK.json entries merged with their file
    golden_path: Path = field(default=None)


class Benchmark:
    def __init__(self, root: Path = ROOT):
        """``root`` holds BENCHMARK.json. Data files are looked up under
        ``<root>/perfbench`` first and then beside this module, so a test
        can add a cell in a scratch root with files of its own only."""
        self.root = Path(root)
        self.dirs = [self.root / "perfbench"]
        if HERE.resolve() != self.dirs[0].resolve():
            self.dirs.append(HERE)
        self.doc = _load(self.root / "BENCHMARK.json", "benchmark")

    def _find(self, *parts: str) -> Path:
        for d in self.dirs:
            if d.joinpath(*parts).is_file():
                return d.joinpath(*parts)
        return self.dirs[0].joinpath(*parts)

    # -- lookups by name ----------------------------------------------------

    def cell_names(self) -> list:
        return [w["name"] for w in self.doc["workloads"]]

    def config(self, name: str) -> dict:
        check_name(name, "configuration")
        entry = next((c for c in self.doc["configs"] if c["name"] == name),
                     None)
        path = self._find("configs", f"{name}.json")
        if entry and (self.root / entry["file"]).is_file():
            path = self.root / entry["file"]
        cfg = _load(path, f"configuration {name}")
        for key in ("preset", "server_flags", "vocab_size"):
            if key not in cfg:
                raise SpecError(f"configuration {name}: {path} lacks {key!r}")
        return cfg

    def traffic(self, name: str) -> dict:
        check_name(name, "traffic mix")
        return _load(self._find("traffic", f"{name}.json"),
                     f"traffic mix {name}")

    def layer_metric(self, name: str) -> dict:
        check_name(name, "per-layer metric")
        spec = _load(self._find("layer_metrics", f"{name}.json"),
                     f"per-layer metric {name}")
        if "reader" not in spec:
            raise SpecError(f"per-layer metric {name}: no 'reader'")
        check_name(spec["reader"], f"reader of {name}")
        return spec

    @staticmethod
    def _in_cell(metric: dict, cell_name: str) -> bool:
        return "workloads" not in metric or cell_name in metric["workloads"]

    def cell(self, name: str) -> Cell:
        check_name(name, "workload")
        entry = next((w for w in self.doc["workloads"] if w["name"] == name),
                     None)
        if entry is None:
            raise SpecError(f"BENCHMARK.json has no workload {name!r}; it "
                            f"has {self.cell_names()}")
        e2e = [m for m in self.doc["end_to_end"] if self._in_cell(m, name)]
        layer = []
        for m in self.doc["per_layer"]:
            if not self._in_cell(m, name):
                continue
            spec = self.layer_metric(m["name"])
            for key in ("unit", "layer", "moves"):
                if key in spec and spec[key] != m[key]:
                    raise SpecError(
                        f"per-layer metric {m['name']}: {key} "
                        f"{spec[key]!r} in its file, {m[key]!r} in "
                        "BENCHMARK.json")
            layer.append({**spec, **m})
        return Cell(
            name=name, chips=int(entry["chips"]),
            config_name=entry["config"], traffic_name=entry["traffic"],
            config=self.config(entry["config"]),
            traffic=self.traffic(entry["traffic"]),
            load=_load(self._find("cells", f"{name}.json"),
                       f"load of cell {name}"),
            end_to_end=e2e, per_layer=layer,
            golden_path=self._find("configs",
                                   f"{entry['config']}.golden.json"))

    # -- whole-file checks (the tests run these) ----------------------------

    def validate(self) -> None:
        """Every name resolves, names and units use the allowed characters,
        every ``moves`` names an end-to-end metric reported wherever the
        per-layer metric is."""
        doc = self.doc
        e2e_names = {m["name"] for m in doc["end_to_end"]}
        seen = set()
        for group in ("configs", "workloads", "end_to_end", "per_layer"):
            for item in doc[group]:
                check_name(item["name"], group)
        for m in doc["end_to_end"] + doc["per_layer"]:
            if m["name"] in seen:
                raise SpecError(f"metric {m['name']} appears twice")
            seen.add(m["name"])
            check_unit(m["unit"], m["name"])
            if m["better"] not in ("lower", "higher"):
                raise SpecError(f"{m['name']}: better {m['better']!r}")
        if "setup_s" not in e2e_names:
            raise SpecError("no setup_s among the end-to-end metrics")
        for name in self.cell_names():
            cell = self.cell(name)
            mine = {m["name"] for m in cell.end_to_end}
            if len(mine - {"setup_s"}) < 1 or not cell.per_layer:
                raise SpecError(f"cell {name} reports too little")
            for m in cell.per_layer:
                if m["moves"] not in mine:
                    raise SpecError(
                        f"{m['name']} moves {m['moves']}, which cell "
                        f"{name} does not report")
