"""A cell is found by name: ``BENCHMARK.json`` names its configuration and
its traffic mix, and each of those is a data file of its own
(``bench/configs/<config>.json``, ``bench/traffic/<mix>.json``); the
configuration names its model family (``harness.arch``)."""
from __future__ import annotations

import dataclasses
import json
from pathlib import Path

from harness import arch

BENCH = Path(__file__).resolve().parents[1]
CHECKOUT = BENCH.parent


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict          # the configuration file, as run
    family: object        # its harness.arch module
    traffic: dict         # the traffic file
    end_to_end: tuple     # this cell's end-to-end metric entries
    per_layer: tuple      # this cell's per-layer metric entries


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: Path = CHECKOUT) -> Cell:
    """The cell ``name`` of ``root/BENCHMARK.json``; raises ``KeyError``
    for an unknown cell, ``FileNotFoundError`` for a missing file and
    ``ValueError`` for a configuration that names no model family."""
    spec = json.loads((root / "BENCHMARK.json").read_text())
    work = {w["name"]: w for w in spec["workloads"]}[name]
    conf = {c["name"]: c for c in spec["configs"]}[work["config"]]
    config = json.loads((root / conf["file"]).read_text())
    traffic = json.loads(
        (root / "bench" / "traffic" / f"{work['traffic']}.json").read_text())
    e2e = tuple(m for m in spec["end_to_end"] if _applies(m, name))
    layer = tuple(m for m in spec["per_layer"] if _applies(m, name))
    return Cell(name=name, chips=int(work["chips"]), config=config,
                family=arch.family_of(config), traffic=traffic,
                end_to_end=e2e, per_layer=layer)
