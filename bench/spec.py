"""Find everything a cell needs by name.

``BENCHMARK.json`` names each cell ``<config>.<traffic>``.  The pieces
live in files of their own under ``bench/``:

- ``configs/<config>.json``: one deployment (data, index, checks);
- ``traffic/<traffic>.json``: one traffic mix;
- ``data/<generator>.py``: one seeded data generator;
- ``metrics/<metric>.py``: one reader per metric, ``read(record)``; a
  metric ``<quantity>.<suffix>`` without a file of its own is read by
  ``metrics/<quantity>.py``;
- ``work/<op>.py``: operations and bytes of one kernel op;
- ``work/<estimator>.py``: the ops a launch runs, and a query's FLOPs;
- ``peaks.json``: chip peaks by ``device_kind``.

Adding a cell, a mix or a metric adds files and entries; no file that
exists is edited.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path
from types import ModuleType

ROOT = Path(__file__).resolve().parents[1]


class SpecError(ValueError):
    """A cell, file or entry that the benchmark cannot resolve."""


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark(root: Path = ROOT) -> dict:
    return load_json(root / "BENCHMARK.json")


def cell(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise SpecError(f"no workload {name!r} in BENCHMARK.json; have "
                    f"{[w['name'] for w in bench['workloads']]}")


def config(bench: dict, cell_: dict, root: Path = ROOT) -> dict:
    for c in bench["configs"]:
        if c["name"] == cell_["config"]:
            return load_json(root / c["file"])
    raise SpecError(f"workload {cell_['name']!r} names config "
                    f"{cell_['config']!r}, which BENCHMARK.json lacks")


def traffic(cell_: dict, root: Path = ROOT) -> dict:
    path = root / "bench" / "traffic" / f"{cell_['traffic']}.json"
    if not path.is_file():
        raise SpecError(f"no traffic file {path.relative_to(root)}")
    return load_json(path)


def module(kind: str, name: str, root: Path = ROOT) -> ModuleType:
    """``bench/<kind>/<name>.py`` as a module (names may hold dots).  A
    name with no file of its own falls back to the part before its first
    dot: ``idle_share.closed`` is read by ``idle_share.py``."""
    folder = root / "bench" / kind
    path = folder / f"{name}.py"
    if not path.is_file() and "." in name:
        path = folder / f"{name.split('.', 1)[0]}.py"
    if not path.is_file():
        raise SpecError(f"no {kind} file "
                        f"{(folder / f'{name}.py').relative_to(root)}")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metrics(bench: dict, cell_name: str, trace: bool) -> list:
    """The metric entries a run of this cell reports: its end-to-end
    metrics untraced, its per-layer metrics traced.  A metric without a
    ``workloads`` list belongs to every cell that reports the end-to-end
    metric it moves."""
    e2e = [m for m in bench["end_to_end"]
           if cell_name in m.get("workloads", [cell_name])]
    if not trace:
        return e2e
    mine = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"]
            if cell_name in m.get("workloads", [cell_name])
            and m["moves"] in mine]


def peaks(kind: str, root: Path = ROOT) -> dict:
    """The chip's peaks; an unknown ``device_kind`` is an error."""
    table = load_json(root / "bench" / "peaks.json")
    if kind not in table:
        raise SpecError(f"device kind {kind!r} is not in bench/peaks.json "
                        f"(have {sorted(table)}); add its published peaks")
    return table[kind]
