"""A tiny copy of the benchmark for tests on the CPU.

:func:`tiny_root` copies ``BENCHMARK.json`` and ``xmrbench/`` into a
temporary directory, links the program's ``src``, and adds a configuration
``tiny`` with the cells ``tiny.batch`` and ``tiny.online`` at a size the
Pallas-free CPU path serves in seconds, as new files and new entries only.
"""

from __future__ import annotations

import json
import os
import shutil
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

TINY_CONFIG = {
    "name": "tiny", "source": "tests", "d": 3000, "n_labels": 5000,
    "query_nnz": 40, "branching": 32, "beam": 10, "topk": 10,
    "score_mode": "prod", "method": "auto",
    "served_method": "mscm_pallas_grouped",
    "level_sizes": [5, 157, 5000], "col_nnz": [64, 64, 64],
    "sibling_overlap": 0.8, "reference": "xmr_tree",
    "reduced": [], "assumed": {},
}
with open(os.path.join(REPO, "xmrbench", "configs", "amazon-670k.json")) as _f:
    # The tiny cells are held to the limits the real cells are held to.
    TINY_CONFIG["limits"] = json.load(_f)["limits"]


def _dump(path: str, obj) -> None:
    with open(path, "w") as f:
        json.dump(obj, f, indent=1)


def tiny_root(tmp_path, pool: int = 512, rate_qps: float = 300.0,
              sample: int = 10 ** 6) -> str:
    """The copy's root. ``sample`` caps the answers a run checks; the
    default checks every answer, so a planted fault cannot be missed."""
    root = str(tmp_path / "checkout")
    os.makedirs(root)
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), root)
    shutil.copytree(os.path.join(REPO, "xmrbench"),
                    os.path.join(root, "xmrbench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(os.path.join(REPO, "src"), os.path.join(root, "src"))
    xb = os.path.join(root, "xmrbench")
    peaks_path = os.path.join(xb, "peaks.json")
    with open(peaks_path) as f:
        peaks = json.load(f)
    # The CPU has no published peak here; a stand-in lets a traced run on
    # the CPU go through the reduction. No number of it is a device metric.
    peaks["cpu"] = {"flops_per_s": 1e12, "bytes_per_s": 1e11}
    _dump(peaks_path, peaks)
    _dump(os.path.join(xb, "configs", "tiny.json"), TINY_CONFIG)
    for mix in ("blocks-uniform", "poisson-uniform"):
        with open(os.path.join(xb, "traffic", mix + ".json")) as f:
            spec = json.load(f)
        spec["pool"] = pool
        if spec["arrivals"]["kind"] == "blocks":
            spec["arrivals"]["block"] = pool
        _dump(os.path.join(xb, "traffic", f"tiny-{mix}.json"), spec)
    cells = {
        "tiny.batch": ("tiny-blocks-uniform",
                       {"sample": sample,
                        "serve": {"entry": "serve_batch",
                                  "engine_max_batch": 256}}),
        "tiny.online": ("tiny-poisson-uniform",
                        {"sample": sample, "rate_qps": rate_qps,
                         "serve": {"entry": "microbatcher",
                                   "engine_max_batch": 256, "max_batch": 16,
                                   "max_wait_ms": 2.0}}),
    }
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for name, (mix, wl) in cells.items():
        _dump(os.path.join(xb, "workloads", name + ".json"), wl)
        bench["workloads"].append({
            "name": name, "config": "tiny", "traffic": mix, "chips": 1,
            "why": "tiny CPU cell for the benchmark's own tests"})
    bench["configs"].append({"name": "tiny", "source": "tests",
                             "file": "xmrbench/configs/tiny.json",
                             "reduced": [], "why": "tests"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            base = [w for w in m["workloads"] if w.endswith(".batch")]
            m["workloads"] = m["workloads"] + (
                ["tiny.batch"] if base else ["tiny.online"])
    _dump(os.path.join(root, "BENCHMARK.json"), bench)
    return root
