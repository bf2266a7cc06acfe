"""``BENCHMARK.json`` and the last line, pinned to the contract's form."""

import json
import os
import re
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def entries(bench):
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in bench[group]:
            yield group, e


def test_top_level_keys_are_exactly_the_contracts(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    assert isinstance(bench["run_seconds"], int)
    assert 1 <= bench["run_seconds"] <= 51
    assert len(bench["command"]) <= 32 and 1 <= len(bench["paths"]) <= 16
    for p in bench["paths"]:
        assert PATH.match(p) and not p.startswith("/") and ".." not in p
    # a full check: 2 + 14 x 24 cells runs of run_seconds + 60, 180 s a cell
    # to compile, 1200 s spare, inside 43200 s
    assert (2 + 14 * 24) * (bench["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200


def test_every_name_and_unit_is_of_the_allowed_characters(bench):
    for group, e in entries(bench):
        assert NAME.match(e["name"]), (group, e["name"])
        for key in ("config", "traffic"):
            if key in e:
                assert NAME.match(e[key]), (group, e)
        if "unit" in e:
            assert UNIT.match(e["unit"]), (group, e)
            assert e["better"] in ("lower", "higher")
            assert e["source"] in SOURCES
        for key in ("why", "layer", "source"):
            if key in e:
                assert 1 <= len(e[key]) <= 200 and "\n" not in e[key] \
                    and "\t" not in e[key], (group, e["name"], key)
    for group in ("configs", "workloads"):
        names = [e["name"] for e in bench[group]]
        assert len(set(names)) == len(names)
    metrics = [e["name"] for e in bench["end_to_end"] + bench["per_layer"]]
    assert len(set(metrics)) == len(metrics)


def test_entries_have_just_the_contracts_keys(bench):
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert len(c["reduced"]) <= 16
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4)
    for m in bench["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for m in bench["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["moves"] in {e["name"] for e in bench["end_to_end"]}
    assert "setup_s" in {m["name"] for m in bench["end_to_end"]}
    setup = next(m for m in bench["end_to_end"] if m["name"] == "setup_s")
    assert setup["bound"] == 0.1 and setup["unit"] == "s"
    for m in bench["per_layer"]:
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"


def test_cells_configs_and_files_line_up(bench):
    configs = {c["name"]: c for c in bench["configs"]}
    pairs = [(w["config"], w["traffic"]) for w in bench["workloads"]]
    assert len(set(pairs)) == len(pairs)
    assert {w["config"] for w in bench["workloads"]} == set(configs)
    assert 2 <= len(bench["workloads"]) <= 24
    four = sum(w["chips"] == 4 for w in bench["workloads"])
    assert four <= max(1, len(bench["workloads"]) // 4)
    files = [c["file"] for c in bench["configs"]]
    assert len(set(files)) == len(files)
    cells = {w["name"] for w in bench["workloads"]}
    for c in bench["configs"]:
        assert any(c["file"].startswith(p + "/") for p in bench["paths"])
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert os.path.isfile(os.path.join(ROOT, c["file"][:-5] + ".py"))
        assert os.path.isfile(os.path.join(
            BENCH, "reference", cfg["reference"] + ".py"))
        assert not c["reduced"]            # published sizes, nothing cut
    for w in bench["workloads"]:
        with open(os.path.join(BENCH, "traffic", w["traffic"] + ".json")) as f:
            traffic = json.load(f)
        assert os.path.isfile(os.path.join(BENCH, "feeds",
                                           traffic["feed"] + ".py"))
    for m in bench["per_layer"]:
        assert os.path.isfile(os.path.join(BENCH, "layer_metrics",
                                           m["name"] + ".py")), m["name"]
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert set(m.get("workloads", [])) <= cells
    # every cell reports setup_s, another end-to-end metric and a layer metric
    for cell in cells:
        e2e = [m["name"] for m in bench["end_to_end"]
               if cell in m.get("workloads", [cell])]
        assert "setup_s" in e2e and len(e2e) >= 2
        assert any(cell in m.get("workloads", [cell])
                   for m in bench["per_layer"])


def test_every_file_under_paths_is_named_from_name_characters(bench):
    for p in bench["paths"]:
        for d, dirs, files in os.walk(os.path.join(ROOT, p)):
            dirs[:] = [x for x in dirs if x not in (".cache", "out",
                                                    "__pycache__")]
            for f in files:
                rel = os.path.relpath(os.path.join(d, f), ROOT)
                assert PATH.match(rel), rel


def test_the_last_lines_key_set_is_the_contracts():
    sys.path.insert(0, BENCH)
    try:
        import run
    finally:
        sys.path.remove(BENCH)
    assert run.RESULT_KEYS == ("correct", "attempted", "failed", "metrics",
                               "device", "breakdown")


def test_no_cpu_mode(tmp_path):
    """With JAX_PLATFORMS=cpu, or alone in a directory, the command exits
    non-zero and prints no result."""
    import shutil
    import subprocess

    cmd = [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
           "bert_base_mlm.fit", "--seed", "0", "--seconds", "1", "--trace", "0"]
    p = subprocess.run(cmd, capture_output=True, text=True, timeout=120,
                       env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert p.returncode != 0 and p.stdout == ""
    alone = tmp_path / "alone"
    alone.mkdir()
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), alone)
    shutil.copytree(BENCH, alone / "benchmark", ignore=shutil.ignore_patterns(
        ".cache", "out", "__pycache__"))
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_PLATFORMS", "PYTHONPATH")}
    cmd[1] = str(alone / "benchmark" / "run.py")
    p = subprocess.run(cmd, capture_output=True, text=True, timeout=120,
                       env=env, cwd=alone)
    assert p.returncode != 0 and p.stdout == ""
    assert "not in this checkout" in p.stderr
