"""BENCHMARK.json against its contract, and every item found by name."""

import json
import os
import re
import shutil

import pytest

from xmrbench_testkit import REPO, tiny_root  # noqa: F401  (sys.path)

from xmrbench import generator, registry

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.fixture(scope="module")
def bench():
    return registry.benchmark(REPO)


def test_keys_and_names(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["command"] == ["python3", "xmrbench/run.py"]
    assert 1 <= bench["run_seconds"] <= 51
    names = []
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("xmrbench/")
        names.append(c["name"])
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        names += [w["name"], w["config"], w["traffic"]]
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        names.append(m["name"])
    for m in bench["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    e2e = {m["name"] for m in bench["end_to_end"]}
    for m in bench["per_layer"]:
        assert m["moves"] in e2e and "\n" not in m["layer"]
    assert all(NAME.match(n) for n in names), names


@pytest.mark.parametrize("kind", ["configs", "workloads", "metrics"])
def test_every_item_resolves(bench, kind):
    if kind == "configs":
        for c in bench["configs"]:
            cfg = registry.load_json(REPO, "configs", c["name"])
            assert cfg["name"] == c["name"]
            assert registry.locate(REPO, "configs", c["name"]).endswith(
                c["file"])
    elif kind == "workloads":
        for w in bench["workloads"]:
            cell = registry.cell(REPO, w["name"])
            assert cell.config["name"] == w["config"]
            kind = cell.mix["arrivals"]["kind"]
            assert generator.ENTRIES[kind] == cell.workload["serve"]["entry"]
            # Said once, in BENCHMARK.json: never again in the file.
            assert not set(cell.workload) & {"config", "traffic", "chips",
                                             "why", "name"}
            assert any(m["name"] == "setup_s" for m in cell.end_to_end)
            assert len(cell.end_to_end) >= 2 and cell.per_layer
    else:
        for w in bench["workloads"]:
            readers = registry.readers(REPO, registry.cell(REPO, w["name"]))
            assert all(callable(r) for r in readers["e2e"].values())
            assert all(callable(r) for r in readers["metrics"].values())


def test_each_metric_lists_cells_that_report_what_it_moves(bench):
    cells = {w["name"] for w in bench["workloads"]}
    for m in bench["per_layer"]:
        for cell in m["workloads"]:
            assert cell in cells
            assert m["moves"] in {e["name"] for e in
                                  registry.cell(REPO, cell).end_to_end}


def test_new_files_and_entries_are_found_with_no_other_edit(tmp_path):
    root = tiny_root(tmp_path)
    before = {p: open(p, "rb").read() for p in _files(root)
              if not p.endswith("BENCHMARK.json")}
    xb = os.path.join(root, "xmrbench")
    with open(os.path.join(xb, "metrics", "tiny.probe_count.py"), "w") as f:
        f.write("def read(run):\n    return 42.0\n")
    shutil.copy(os.path.join(xb, "workloads", "tiny.batch.json"),
                os.path.join(xb, "workloads", "tiny.batch2.json"))
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["workloads"].append({"name": "tiny.batch2", "config": "tiny",
                               "traffic": "tiny-blocks-uniform", "chips": 1,
                               "why": "added"})
    bench["per_layer"].append({"name": "tiny.probe_count", "unit": "%",
                               "better": "higher", "source": "program_counter",
                               "layer": "device", "moves": "batch_qps",
                               "workloads": ["tiny.batch2"]})
    for m in bench["end_to_end"]:
        if m["name"] == "batch_qps":
            m["workloads"].append("tiny.batch2")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    cell = registry.cell(root, "tiny.batch2")
    assert [m["name"] for m in cell.per_layer] == ["tiny.probe_count"]
    assert registry.readers(root, cell)["metrics"]["tiny.probe_count"](None) == 42.0
    after = {p: open(p, "rb").read() for p in before}
    assert after == before


def test_unknown_names_are_errors():
    with pytest.raises(LookupError):
        registry.cell(REPO, "no-such-cell")
    with pytest.raises(LookupError):
        registry.locate(REPO, "metrics", "no_such_metric")


def _files(root):
    for dirpath, _, files in os.walk(os.path.join(root, "xmrbench")):
        for fn in files:
            if "__pycache__" not in dirpath:
                yield os.path.join(dirpath, fn)
