"""``BENCHMARK.json`` and the files it names keep to the benchmark's rules,
and a cell is added with data alone."""

import json
import os
import re
import shutil
import subprocess
import sys

import numpy as np
import pytest

import bench_testutil as bt
import harness
from ycsb import ScrambledZipfian

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
TOP_KEYS = {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"}


@pytest.fixture(scope="module")
def manifest():
    return harness.load_manifest(bt.ROOT)


def test_keys_names_and_units(manifest):
    assert set(manifest) == TOP_KEYS
    for c in manifest["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and all(NAME.match(k) for k in c["reduced"])
    for w in manifest["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"]) and w["chips"] in (1, 4)
        assert 1 <= len(w["why"]) <= 200
    for kind in ("end_to_end", "per_layer"):
        for m in manifest[kind]:
            assert NAME.match(m["name"]) and UNIT.match(m["unit"]), m
            assert m["better"] in ("lower", "higher")
    names = [x["name"] for k in ("configs", "workloads", "end_to_end", "per_layer")
             for x in manifest[k]]
    assert len(names) == len(set(names))
    assert 1 <= manifest["run_seconds"] <= 51


def test_bounds_and_sources(manifest):
    for m in manifest["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    assert any(m["name"] == "setup_s" and "workloads" not in m for m in manifest["end_to_end"])
    for m in manifest["per_layer"]:
        assert m["source"] in ("device_trace", "program_span", "program_counter", "host_clock")
        assert "bound" not in m and m["layer"] and "\n" not in m["layer"]


def test_moves_names_a_metric_every_listed_cell_reports(manifest):
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    cells = {w["name"] for w in manifest["workloads"]}
    for m in manifest["per_layer"]:
        moved = e2e[m["moves"]]
        for cell in m["workloads"]:
            assert cell in cells
            assert cell in moved.get("workloads", [cell]), (m["name"], cell)
    for cell in cells:
        spec = harness.resolve(manifest, cell, root=bt.ROOT)
        reported = {m["name"] for m in spec["end_to_end"]}
        assert "setup_s" in reported and len(reported) >= 2 and spec["per_layer"]


def test_files_named_exist(manifest):
    configs = {c["name"]: c for c in manifest["configs"]}
    for c in configs.values():
        assert c["file"].startswith("bench/") and os.path.exists(os.path.join(bt.ROOT, c["file"]))
        data = json.load(open(os.path.join(bt.ROOT, c["file"])))
        assert all(k in data for k in c["reduced"])
        assert os.path.exists(os.path.join(bt.BENCH, "references", data["reference"] + ".py"))
    for w in manifest["workloads"]:
        assert w["config"] in configs
        traffic = json.load(open(os.path.join(bt.BENCH, "traffic", w["traffic"] + ".json")))
        assert os.path.exists(os.path.join(bt.BENCH, "entries", traffic["entry"] + ".py"))
    for m in manifest["per_layer"]:
        assert os.path.exists(os.path.join(bt.BENCH, "metrics", m["name"] + ".py"))
    pairs = [(w["config"], w["traffic"]) for w in manifest["workloads"]]
    assert len(pairs) == len(set(pairs))
    assert {c["name"] for c in manifest["configs"]} == {w["config"] for w in manifest["workloads"]}


def test_at_most_half_the_cells_take_four_chips(manifest):
    four = sum(w["chips"] == 4 for w in manifest["workloads"])
    assert four <= max(1, len(manifest["workloads"]) // 2)


def test_command_and_paths(manifest):
    assert manifest["command"] == ["python3", "bench/run.py"]
    for p in manifest["paths"]:
        assert re.match(r"^[A-Za-z0-9_./\-]{1,200}$", p) and ".." not in p
        assert os.path.isdir(os.path.join(bt.ROOT, p))


def test_ycsb_generator_is_seeded_and_zipfian():
    gen = ScrambledZipfian(2**30, 0.99)
    a = gen.draw(np.random.default_rng(5), 200_000)
    assert np.array_equal(a, gen.draw(np.random.default_rng(5), 200_000))
    assert not np.array_equal(a, gen.draw(np.random.default_rng(6), 200_000))
    assert a.max() < 2**30
    _, counts = np.unique(a, return_counts=True)
    share = counts.max() / len(a)
    assert abs(share - 1 / 26.46902820178302) < 0.002  # 1 / zeta(10^10, 0.99)
    second = np.sort(counts)[-2] / len(a)
    assert abs(second - 0.5**0.99 / 26.46902820178302) < 0.002


def test_a_cell_is_added_with_data_alone(tmp_path):
    """A copy of the benchmark gains a cell through a new traffic file and
    a manifest entry; it runs with no existing file edited."""
    shutil.copytree(bt.BENCH, tmp_path / "bench", ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: open(p, "rb").read() for p in _files(tmp_path / "bench")}
    traffic = json.load(open(tmp_path / "bench" / "traffic" / "zipf.closed.json"))
    traffic["batch"] = 512
    json.dump(traffic, open(tmp_path / "bench" / "traffic" / "zipf.closed.half.json", "w"))
    manifest = harness.load_manifest(bt.ROOT)
    manifest["workloads"].append({
        "name": "kv.zipf.closed.half", "config": "ycsb-c-1024", "traffic": "zipf.closed.half",
        "chips": 1, "why": "half-size batches"})
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        if "kv.zipf.closed" in m.get("workloads", []):
            m["workloads"].append("kv.zipf.closed.half")
    json.dump(manifest, open(tmp_path / "BENCHMARK.json", "w"))
    out = bt.run("kv.zipf.closed.half", root=str(tmp_path))
    assert out["correct"] and "routed_per_s" in out["metrics"]
    assert all(open(p, "rb").read() == b for p, b in before.items())


def _files(root):
    return [os.path.join(d, f) for d, _, fs in os.walk(root) for f in fs]


def _run(args, cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, *args], cwd=cwd, env=env, capture_output=True,
                          text=True, timeout=300)


def test_refuses_without_a_tpu():
    r = _run(["bench/run.py", "--workload", "kv.zipf.closed", "--seed", "1", "--seconds", "1"],
             bt.ROOT)
    assert r.returncode != 0 and r.stdout.strip() == ""
    assert "no TPU" in r.stderr


def test_refuses_in_a_bare_benchmark_directory(tmp_path):
    shutil.copytree(bt.BENCH, tmp_path / "bench", ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copytree(os.path.join(bt.ROOT, "tests", "bench"), tmp_path / "tests" / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(bt.ROOT, "BENCHMARK.json"), tmp_path)
    r = _run(["bench/run.py", "--workload", "kv.zipf.closed", "--seed", "1", "--seconds", "1"],
             str(tmp_path))
    assert r.returncode != 0 and r.stdout.strip() == ""
