"""Whole runs of the harness on the CPU at a tiny size: sound runs come
out correct, and a timed path broken underneath comes out not correct."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from xmrbench_testkit import REPO, tiny_root

from xmrbench import harness


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny_root(tmp_path_factory.mktemp("xmrbench"))


def _run(root, workload, seed=77, trace=False, hook=None):
    return harness.run(workload, seed, 0.5, trace, root=root, allow_cpu=True,
                       cache=False, serve_hook=hook)


def test_run_py_exits_nonzero_without_a_chip():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "xmrbench/run.py", "--workload", "amazon-670k.batch",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    assert not proc.stdout.strip().startswith("{")


def test_a_checkout_without_the_program_cannot_run(tmp_path):
    bare = tmp_path / "bare"
    bare.mkdir()
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), bare)
    shutil.copytree(os.path.join(REPO, "xmrbench"), bare / "xmrbench")
    with pytest.raises(ImportError):
        harness.import_program(str(bare))


@pytest.mark.parametrize("workload,metrics", [
    ("tiny.batch", {"batch_qps", "setup_s"}),
    ("tiny.online", {"latency_p50_ms", "setup_s"}),
])
def test_sound_run_is_correct(root, workload, metrics):
    out = _run(root, workload, seed=2**31 + 5)
    assert out["correct"] and out["failed"] == 0 and out["attempted"] > 0
    assert set(out["metrics"]) == metrics
    assert all(v["value"] > 0 for v in out["metrics"].values())
    assert list(out)[-1] == "checks"
    assert out["checks"]["mismatched"]["limit"] == 0
    json.dumps(out)


def test_traced_run_reads_host_counters(root):
    out = _run(root, "tiny.online", trace=True)
    assert out["correct"]
    assert set(out["metrics"]) == {"batcher.queue_wait_p99_ms",
                                   "batcher.batch_fill"}
    assert 0 < out["metrics"]["batcher.batch_fill"]["value"] <= 100
    assert out["device"]["window_s"] > 0 and "breakdown" in out


def _alter_one_answer(engine):
    run = engine._run

    def altered(xi, xv, tier=0):
        s, l = run(xi, xv, tier=tier)
        return s, l.at[0, 0].set((l[0, 0] + 1) % engine.tree.n_labels)

    engine._run = altered


def _leave_out_half(engine):
    run = engine._run

    def half(xi, xv, tier=0):
        n = xi.shape[0] // 2
        if n:
            xi = xi.at[n:].set(engine.tree.d)
            xv = xv.at[n:].set(0.0)
        return run(xi, xv, tier=tier)

    engine._run = half


@pytest.mark.parametrize("workload", ["tiny.batch", "tiny.online"])
@pytest.mark.parametrize("fault", [_alter_one_answer, _leave_out_half])
def test_a_broken_timed_path_is_not_correct(root, workload, fault):
    out = _run(root, workload, hook=fault)
    assert not out["correct"]
    assert out["checks"]["mismatched"]["value"] > 0


NEW_MIXES = {
    # Open questions' mixes, each new data files and entries only.
    "tiny.batch-zipf": (
        {"arrivals": {"kind": "blocks", "block": 512},
         "targets": {"kind": "zipf", "a": 1.2}},
        {"serve": {"entry": "serve_batch", "engine_max_batch": 256}},
        "batch_qps"),
    "tiny.online-burst": (
        {"arrivals": {"kind": "bursts", "on_s": 0.2, "off_s": 0.3,
                      "on_x": 2.0, "off_x": 0.25}},
        {"rate_qps": 200.0,
         "serve": {"entry": "microbatcher", "engine_max_batch": 256,
                   "max_batch": 16, "max_wait_ms": 2.0}},
        "latency_p50_ms"),
    "tiny.serial": (
        {"arrivals": {"kind": "serial"}},
        {"serve": {"entry": "serve_online", "engine_max_batch": 256}},
        "latency_p50_ms"),
}


@pytest.mark.parametrize("name", sorted(NEW_MIXES))
def test_a_new_kind_of_mix_is_data_files_only(tmp_path, name):
    root = tiny_root(tmp_path)
    xb = os.path.join(root, "xmrbench")
    before = {p: open(p, "rb").read() for p in _files(xb)}
    mix_change, workload, e2e = NEW_MIXES[name]
    with open(os.path.join(xb, "traffic", "tiny-blocks-uniform.json")) as f:
        mix = json.load(f)
    mix["arrivals"] = mix_change["arrivals"]
    mix["queries"]["targets"] = mix_change.get("targets",
                                               mix["queries"]["targets"])
    _dump(os.path.join(xb, "traffic", name + ".json"), mix)
    _dump(os.path.join(xb, "workloads", name + ".json"),
          dict(workload, sample=10 ** 6))
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["workloads"].append({"name": name, "config": "tiny",
                               "traffic": name, "chips": 1, "why": "added"})
    for m in bench["end_to_end"]:
        if m["name"] == e2e:
            m["workloads"].append(name)
    _dump(os.path.join(root, "BENCHMARK.json"), bench)
    out = _run(root, name, seed=2**32 + 3)
    assert out["correct"] and out["failed"] == 0 and out["attempted"] > 0
    assert set(out["metrics"]) == {e2e, "setup_s"}
    assert {p: open(p, "rb").read() for p in before} == before


def _dump(path, obj):
    with open(path, "w") as f:
        json.dump(obj, f)


def _files(folder):
    for dirpath, _, files in os.walk(folder):
        if "__pycache__" not in dirpath:
            yield from (os.path.join(dirpath, fn) for fn in files)
