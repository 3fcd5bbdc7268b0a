"""BENCHMARK.json against the contract it is written to, and the files it
names."""
import copy
import json
import os
import pathlib
import re
import shutil
import subprocess
import sys


from bench import harness

ROOT = pathlib.Path(__file__).resolve().parents[2]
MAN = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CELLS = {w["name"]: w for w in MAN["workloads"]}
E2E = {m["name"]: m for m in MAN["end_to_end"]}


def test_keys_and_names():
    assert set(MAN) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    names = ([c["name"] for c in MAN["configs"]] + list(CELLS)
             + [m["name"] for m in MAN["end_to_end"] + MAN["per_layer"]]
             + [w["config"] for w in CELLS.values()]
             + [w["traffic"] for w in CELLS.values()]
             + [k for c in MAN["configs"] for k in c["reduced"]])
    for n in names:
        assert NAME.match(n), n
    for m in MAN["end_to_end"] + MAN["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    all_metrics = [m["name"] for m in MAN["end_to_end"] + MAN["per_layer"]]
    assert len(set(all_metrics)) == len(all_metrics)
    assert 1 <= MAN["run_seconds"] <= 51
    for p in MAN["paths"]:
        assert (ROOT / p).is_dir() and ".." not in p


def test_every_cell_reports_setup_another_end_to_end_and_a_layer():
    assert E2E["setup_s"]["bound"] <= 0.25
    for name in CELLS:
        e2e = [m for m in MAN["end_to_end"]
               if name in m.get("workloads", [name])]
        assert "setup_s" in [m["name"] for m in e2e] and len(e2e) >= 2
        assert harness.cell_metrics(MAN, CELLS[name], trace=True)
    for m in MAN["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")


def test_each_layer_metric_moves_a_metric_its_cells_report():
    for m in MAN["per_layer"]:
        moved = E2E[m["moves"]]
        for cell in m["workloads"]:
            assert cell in CELLS
            assert cell in moved.get("workloads", [cell]), (m, cell)
        assert (ROOT / "bench" / "metrics" / f"{m['name']}.py").is_file()


def test_configs_cells_and_chips():
    used = {w["config"] for w in CELLS.values()}
    assert {c["name"] for c in MAN["configs"]} == used
    four = sum(w["chips"] == 4 for w in CELLS.values())
    assert four <= max(1, len(CELLS) // 2)
    for w in CELLS.values():
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        assert (ROOT / "bench" / "traffic" / f"{w['traffic']}.json").is_file()
        assert (ROOT / "bench" / "cells" / f"{w['name']}.json").is_file()
    pairs = [(w["config"], w["traffic"]) for w in CELLS.values()]
    assert len(set(pairs)) == len(pairs)


def test_config_files_name_source_and_reduced():
    files = [c["file"] for c in MAN["configs"]]
    assert len(set(files)) == len(files)
    for c in MAN["configs"]:
        f = json.loads((ROOT / c["file"]).read_text())
        assert f["source"] == c["source"] and f["reduced"] == c["reduced"]
        assert c["file"].startswith("bench/")


def test_a_new_metric_is_a_new_file_and_a_new_entry(tmp_path):
    (tmp_path / "dummy_metric.py").write_text(
        "def read(ctx):\n    return 2.0 * ctx['x']\n")
    man = copy.deepcopy(MAN)
    cell = next(iter(CELLS))
    man["per_layer"].append({
        "name": "dummy_metric", "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "device",
        "moves": "setup_s", "workloads": [cell]})
    entry = harness.workload_entry(man, cell)
    names = [m["name"] for m in harness.cell_metrics(man, entry, True)]
    assert "dummy_metric" in names
    assert harness.reader("dummy_metric", tmp_path)({"x": 3.0}) == 6.0
    # the cell's end-to-end metrics are untouched by the new entry
    assert harness.cell_metrics(man, entry, False) == \
        harness.cell_metrics(MAN, entry, False)


def _run(cwd, env):
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", next(iter(CELLS)),
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=240)


def _cpu_env():
    env = {k: v for k, v in os.environ.items()
           if k not in ("PYTHONPATH", "ALLOW_MULTIPLE_LIBTPU_LOAD")}
    env["JAX_PLATFORMS"] = "cpu"
    return env


def test_run_without_a_tpu_exits_nonzero_and_prints_no_result():
    p = _run(ROOT, _cpu_env())
    assert p.returncode != 0
    assert "no CPU fallback" in p.stderr
    assert not any(line.startswith("{") for line in p.stdout.splitlines())


def test_run_without_the_program_exits_nonzero(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for p in MAN["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path, _cpu_env())
    assert p.returncode != 0
    assert not any(line.startswith("{") for line in p.stdout.splitlines())
