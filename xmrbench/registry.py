"""Finds every item of the benchmark by its name, with a directory listing.

``BENCHMARK.json`` names the cells, configurations, traffic mixes and
metrics; each lives in a file of its own under ``xmrbench/``:

    configs/<config>.json      sizes, geometry, limits of ``correct``
    traffic/<mix>.json         the mix's parameters (see generator.py)
    workloads/<cell>.json      serving entry, offered rate, sample size
    e2e/<metric>.py            ``read(run)`` of an end-to-end metric
    metrics/<metric>.py        ``read(run)`` of a per-layer metric

A cell's configuration, mix, chips and ``why`` are said once, in its
``BENCHMARK.json`` entry; its workload file holds only what the entry
does not. A new cell, configuration, mix or metric is new files plus a new
entry in ``BENCHMARK.json``; no file already here changes.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import re
from typing import Callable, Dict, List, Optional

EXT = {"configs": ".json", "traffic": ".json", "workloads": ".json",
       "e2e": ".py", "metrics": ".py"}


def locate(root: str, kind: str, name: str) -> str:
    """Path of the ``kind`` file named ``name``, found by listing."""
    folder = os.path.join(root, "xmrbench", kind)
    for fn in sorted(os.listdir(folder)):
        if fn == name + EXT[kind]:
            return os.path.join(folder, fn)
    raise LookupError(f"no {kind} file named {name!r} in {folder}")


def load_json(root: str, kind: str, name: str) -> dict:
    with open(locate(root, kind, name)) as f:
        return json.load(f)


def load_reader(root: str, kind: str, name: str) -> Callable:
    """The ``read`` function of a metric file."""
    path = locate(root, kind, name)
    mod_name = "xmrbench_" + kind + "_" + re.sub(r"\W", "_", name)
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def benchmark(root: str) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    workload: dict
    config: dict
    mix: dict
    end_to_end: List[dict]
    per_layer: List[dict]


def _applies(metric: dict, cell: str, reported: Optional[set]) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return reported is None or metric["moves"] in reported


def cell(root: str, name: str) -> Cell:
    bench = benchmark(root)
    entries = [w for w in bench["workloads"] if w["name"] == name]
    if len(entries) != 1:
        raise LookupError(f"BENCHMARK.json has {len(entries)} workloads "
                          f"named {name!r}")
    entry = entries[0]
    e2e = [m for m in bench["end_to_end"] if _applies(m, name, None)]
    reported = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if _applies(m, name, reported)]
    return Cell(name=name, chips=int(entry["chips"]),
                workload=load_json(root, "workloads", name),
                config=load_json(root, "configs", entry["config"]),
                mix=load_json(root, "traffic", entry["traffic"]),
                end_to_end=e2e, per_layer=per_layer)


def readers(root: str, cell_: Cell) -> Dict[str, Dict[str, Callable]]:
    """The readers of every metric the cell reports, by kind."""
    return {
        "e2e": {m["name"]: load_reader(root, "e2e", m["name"])
                for m in cell_.end_to_end},
        "metrics": {m["name"]: load_reader(root, "metrics", m["name"])
                    for m in cell_.per_layer},
    }
