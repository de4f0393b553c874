"""``BENCHMARK.json`` and the files it names. Everything that belongs
to one configuration, one traffic mix or one per-layer metric is a
file found by NAME under the directories in ``paths``; nothing here
knows a cell."""

from __future__ import annotations

import importlib
import json
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def load(root: Path = ROOT) -> dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def _read(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def find(manifest: dict, kind: str, name: str, root: Path = ROOT) -> Path:
    """``<path>/<kind>/<name>.json`` under the first of ``paths`` that
    has it."""
    for p in manifest["paths"]:
        cand = root / p / kind / f"{name}.json"
        if cand.is_file():
            return cand
    raise FileNotFoundError(
        f"no {kind}/{name}.json under {manifest['paths']}"
    )


def cell(manifest: dict, name: str) -> dict:
    for w in manifest["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(
        f"no workload {name!r}; have {[w['name'] for w in manifest['workloads']]}"
    )


def config_of(manifest: dict, cell_: dict, root: Path = ROOT) -> dict:
    for c in manifest["configs"]:
        if c["name"] == cell_["config"]:
            return _read(root / c["file"])
    raise KeyError(f"no configuration {cell_['config']!r}")


def traffic_of(manifest: dict, cell_: dict, root: Path = ROOT) -> dict:
    return _read(find(manifest, "traffic", cell_["traffic"], root))


def metrics_of(manifest: dict, cell_name: str, group: str) -> list[dict]:
    """The ``end_to_end`` or ``per_layer`` metrics this cell reports."""
    return [
        m for m in manifest[group]
        if cell_name in m.get("workloads", [cell_name])
    ]


def resolve(dotted: str):
    """``package.module:function`` -> the function."""
    module, _, attr = dotted.partition(":")
    return getattr(importlib.import_module(module), attr)


def reader_of(manifest: dict, metric: str, root: Path = ROOT):
    return resolve(_read(find(manifest, "metrics", metric, root))["reader"])


def part_of(config: dict, key: str):
    """The function a configuration file names under ``key``
    (``engine``, ``builder``, ``reference``), found as a metric's
    reader is. A file that does not say is an error, never a default:
    a default would be one architecture's."""
    if key not in config:
        raise KeyError(
            f"configuration {config.get('name')!r} names no {key!r} "
            "(module:function; see chipbench/README.md)"
        )
    return resolve(config[key])
