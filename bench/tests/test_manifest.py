"""BENCHMARK.json and the files it names keep to the benchmark's rules."""

import json
import os
import re
import shutil
import subprocess
import sys

import pytest

import run

ROOT = run.ROOT
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


@pytest.fixture(scope="module")
def bench():
    with open(ROOT / "BENCHMARK.json") as f:
        return json.load(f)


def test_keys_and_names(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    names = [c["name"] for c in bench["configs"]] + \
        [w["name"] for w in bench["workloads"]] + \
        [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    for n in names + [w["traffic"] for w in bench["workloads"]]:
        assert NAME.match(n), n
    for c in bench["configs"]:
        for k in c["reduced"]:
            assert NAME.match(k), k
    assert len(set(names)) == len(names)
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    for e in bench["end_to_end"]:
        assert e["source"] in ("host_clock", "device_trace")
        assert 0 < e["bound"] <= 0.25
    assert 1 <= bench["run_seconds"] <= 51


def test_every_metric_moves_what_its_cells_report(bench):
    cells = [w["name"] for w in bench["workloads"]]
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    reported = {c: {n for n, m in e2e.items()
                    if c in m.get("workloads", cells)} for c in cells}
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    for m in bench["per_layer"]:
        assert m["moves"] in e2e
        for c in m.get("workloads", cells):
            assert c in cells and m["moves"] in reported[c], (m, c)
        assert (run.BENCH / "metrics" / f"{m['name']}.py").is_file()
    for c in cells:
        assert len(reported[c]) >= 2
        assert any(c in m.get("workloads", cells) for m in bench["per_layer"])


def test_configs_cells_and_chips(bench):
    used = {w["config"] for w in bench["workloads"]}
    assert used == {c["name"] for c in bench["configs"]}
    assert sum(w["chips"] == 4 for w in bench["workloads"]) <= 1
    pairs = [(w["config"], w["traffic"]) for w in bench["workloads"]]
    assert len(set(pairs)) == len(pairs)
    for c in bench["configs"]:
        assert c["file"].startswith("bench/")
        with open(ROOT / c["file"]) as f:
            conf = json.load(f)
        assert sorted(conf["reduced"]) == sorted(c["reduced"])
        for k, (published, run_as) in conf["reduced"].items():
            assert conf["model"][k] == run_as != published
    for w in bench["workloads"]:
        assert w["chips"] in (1, 4)
        spec = run.load_spec(w["name"])
        assert (run.BENCH / f"{spec['mix']['kind']}_cell.py").is_file()
        assert len(w["why"]) <= 200


def test_command_stays_in_paths(bench):
    assert bench["paths"] == ["bench"]
    for word in bench["command"][1:]:
        assert not word.startswith("/") and ".." not in word
        assert word.startswith("bench/") or word.startswith("-")


def _run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "serve.qwen3-8b.chat",
         "--seed", "3", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120)


def test_refuses_a_host_without_a_tpu():
    p = _run(ROOT)
    assert p.returncode != 0
    assert "TPU" in p.stderr
    assert not p.stdout.strip()


def test_refuses_a_checkout_without_the_program(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0
    assert not p.stdout.strip()
