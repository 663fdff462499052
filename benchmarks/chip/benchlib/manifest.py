"""`BENCHMARK.json` and the files it names.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric is a file of its own, found by its name:

    configs/<config>.json      the configuration as it is run
    configs/<config>.py        its plain reference (imports no program code)
    archs/<architecture>.py    how the program runs the configuration's
                               "architecture": its ModelConfig, weights,
                               parameter tree and operation counts
    traffic/<traffic>.json     parameters of one traffic mix; "kind" names
                               the general driver that reads it
    metrics/<metric>.py        a reader: ``read(ctx) -> float | None``
    limits/<cell>.json         the correctness limits of one cell

So a cell, a configuration, an architecture, a traffic mix or a metric
is added with new files and new `BENCHMARK.json` entries only.
"""
from __future__ import annotations

import importlib.util
import json
import re
from dataclasses import dataclass
from pathlib import Path
from typing import Any, Dict, List, Optional

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parents[1]

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
E2E_SOURCES = ("device_trace", "host_clock")
TOP_KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}
CONFIG_KEYS = {"name", "source", "file", "reduced", "why"}
CELL_KEYS = {"name", "config", "traffic", "chips", "why"}
E2E_KEYS = {"name", "unit", "better", "bound", "source"}
LAYER_KEYS = {"name", "unit", "better", "source", "layer", "moves"}


class ManifestError(ValueError):
    """`BENCHMARK.json`, or a file it names, breaks the benchmark's rules."""


def _check_name(what: str, name: Any) -> str:
    if not isinstance(name, str) or not NAME_RE.match(name):
        raise ManifestError(f"{what}: bad name {name!r}")
    return name


def _check_line(what: str, text: Any) -> None:
    if (not isinstance(text, str) or not 1 <= len(text) <= 200
            or "\n" in text or "\t" in text):
        raise ManifestError(f"{what}: needs 1 to 200 characters on one line")


def _check_keys(what: str, entry: dict, allowed: set,
                optional: set = frozenset()) -> None:
    keys = set(entry)
    if not allowed <= keys or keys - allowed - optional:
        raise ManifestError(f"{what}: keys {sorted(keys)}, expected "
                            f"{sorted(allowed)} (+{sorted(optional)})")


def _check_metric(entry: dict, e2e: bool) -> None:
    what = f"metric {entry.get('name')!r}"
    _check_keys(what, entry, E2E_KEYS if e2e else LAYER_KEYS, {"workloads"})
    _check_name(what, entry["name"])
    if not isinstance(entry["unit"], str) or not UNIT_RE.match(entry["unit"]):
        raise ManifestError(f"{what}: bad unit {entry['unit']!r}")
    if entry["better"] not in ("lower", "higher"):
        raise ManifestError(f"{what}: better must be lower or higher")
    if entry["source"] not in (E2E_SOURCES if e2e else SOURCES):
        raise ManifestError(f"{what}: bad source {entry['source']!r}")
    if e2e:
        b = entry["bound"]
        if not isinstance(b, (int, float)) or not 0.01 <= b <= 0.25:
            raise ManifestError(f"{what}: bound {b!r} outside [0.01, 0.25]")
    else:
        _check_line(what, entry["layer"])


@dataclass(frozen=True)
class Cell:
    name: str
    config: str
    traffic: str
    chips: int
    why: str


class Manifest:
    """A validated `BENCHMARK.json`; ``bench_dir`` holds the files it names."""

    def __init__(self, data: dict, root: Path, bench_dir: Path):
        self.data = data
        self.root = root
        self.bench_dir = bench_dir
        self._validate()

    @classmethod
    def load(cls, root: Path = ROOT,
             bench_dir: Optional[Path] = None) -> "Manifest":
        path = root / "BENCHMARK.json"
        try:
            data = json.loads(path.read_text())
        except (OSError, json.JSONDecodeError) as e:
            raise ManifestError(f"cannot read {path}: {e}") from e
        return cls(data, root, bench_dir or root / "benchmarks" / "chip")

    # ------------------------------------------------------------ checks
    def _validate(self) -> None:
        d = self.data
        if set(d) != TOP_KEYS:
            raise ManifestError(f"top-level keys {sorted(d)}, expected "
                                f"{sorted(TOP_KEYS)}")
        rs = d["run_seconds"]
        if not isinstance(rs, int) or not 1 <= rs <= 51:
            raise ManifestError(f"run_seconds {rs!r} outside 1..51")
        configs = {}
        for c in d["configs"]:
            _check_keys("config", c, CONFIG_KEYS)
            _check_name("config", c["name"])
            _check_line(f"config {c['name']} source", c["source"])
            _check_line(f"config {c['name']} why", c["why"])
            for k in c["reduced"]:
                _check_name(f"config {c['name']} reduced", k)
            if c["name"] in configs:
                raise ManifestError(f"config {c['name']!r} twice")
            configs[c["name"]] = c
        self.configs: Dict[str, dict] = configs
        cells: Dict[str, Cell] = {}
        pairs = set()
        for w in d["workloads"]:
            _check_keys("workload", w, CELL_KEYS)
            for k in ("name", "config", "traffic"):
                _check_name(f"workload {k}", w[k])
            _check_line(f"workload {w['name']} why", w["why"])
            if w["config"] not in configs:
                raise ManifestError(f"workload {w['name']!r}: unknown "
                                    f"config {w['config']!r}")
            if w["chips"] not in (1, 4):
                raise ManifestError(f"workload {w['name']!r}: chips 1 or 4")
            if w["name"] in cells or (w["config"], w["traffic"]) in pairs:
                raise ManifestError(f"workload {w['name']!r} repeats")
            pairs.add((w["config"], w["traffic"]))
            cells[w["name"]] = Cell(**w)
        self.cells = cells
        names = set()
        for m in d["end_to_end"]:
            _check_metric(m, e2e=True)
        for m in d["per_layer"]:
            _check_metric(m, e2e=False)
        e2e = {m["name"] for m in d["end_to_end"]}
        for m in d["end_to_end"] + d["per_layer"]:
            if m["name"] in names:
                raise ManifestError(f"metric {m['name']!r} twice")
            names.add(m["name"])
            for w in m.get("workloads", []):
                if w not in cells:
                    raise ManifestError(f"metric {m['name']!r}: unknown "
                                        f"workload {w!r}")
        for m in d["per_layer"]:
            if m["moves"] not in e2e:
                raise ManifestError(f"metric {m['name']!r} moves unknown "
                                    f"{m['moves']!r}")
            for w in m.get("workloads", []):
                if m["moves"] not in self.end_to_end_names(w):
                    raise ManifestError(
                        f"metric {m['name']!r}: cell {w!r} does not report "
                        f"{m['moves']!r}")
        if "setup_s" not in e2e:
            raise ManifestError("no setup_s metric")

    # ----------------------------------------------------------- lookups
    def cell(self, name: str) -> Cell:
        try:
            return self.cells[name]
        except KeyError:
            raise ManifestError(f"unknown workload {name!r}") from None

    def end_to_end(self, cell: str) -> List[dict]:
        return [m for m in self.data["end_to_end"]
                if cell in m.get("workloads", [cell])]

    def end_to_end_names(self, cell: str) -> List[str]:
        return [m["name"] for m in self.end_to_end(cell)]

    def per_layer(self, cell: str) -> List[dict]:
        reported = set(self.end_to_end_names(cell))
        return [m for m in self.data["per_layer"]
                if (cell in m["workloads"] if "workloads" in m
                    else m["moves"] in reported)]

    def config(self, name: str) -> dict:
        entry = self.configs[name]
        path = self.root / entry["file"]
        return _load_json(path)

    def reference(self, config: str):
        """The configuration's plain reference module, beside its file."""
        path = (self.root / self.configs[config]["file"]).with_suffix(".py")
        return load_module(path, f"bench_ref_{config}")

    def architecture(self, config: str):
        """The module `archs/<architecture>.py` of the configuration's
        "architecture" key; None for a configuration that names none
        (one that runs no model)."""
        name = self.config(config).get("architecture")
        if name is None:
            return None
        _check_name(f"config {config} architecture", name)
        return load_architecture(name, self.bench_dir)

    def traffic(self, name: str) -> dict:
        return _load_json(self.bench_dir / "traffic" / f"{name}.json")

    def limits(self, cell: str) -> dict:
        return _load_json(self.bench_dir / "limits" / f"{cell}.json")

    def reader(self, metric: str):
        path = self.bench_dir / "metrics" / f"{metric}.py"
        return load_module(path, f"bench_metric_{metric}").read


def _load_json(path: Path) -> dict:
    try:
        return json.loads(path.read_text())
    except (OSError, json.JSONDecodeError) as e:
        raise ManifestError(f"cannot read {path}: {e}") from e


def load_architecture(name: str, bench_dir: Path = BENCH_DIR):
    """archs/<name>.py of the benchmark in bench_dir, loaded."""
    return load_module(bench_dir / "archs" / f"{name}.py",
                       f"bench_arch_{name}")


def load_module(path: Path, modname: str):
    """Import one file by path (names may hold '.' and '-')."""
    if not path.is_file():
        raise ManifestError(f"missing {path}")
    spec = importlib.util.spec_from_file_location(
        modname.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
