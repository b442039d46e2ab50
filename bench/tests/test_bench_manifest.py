"""`BENCHMARK.json` and the files it names: every piece loads, every name
and unit fits its characters, and a cell can be added by adding files."""
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

from bench import run
from bench.run import BENCH, ROOT, load_json

MANIFEST = load_json(ROOT / "BENCHMARK.json")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
E2E = {m["name"]: m for m in MANIFEST["end_to_end"]}
CELLS = [w["name"] for w in MANIFEST["workloads"]]


def test_top_level_keys_and_command():
    assert set(MANIFEST) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert MANIFEST["paths"] == ["bench"]
    assert MANIFEST["command"] == ["python3", "bench/run.py"]
    assert isinstance(MANIFEST["run_seconds"], int)
    assert 1 <= MANIFEST["run_seconds"] <= 51
    assert len(json.dumps(MANIFEST)) < 64 * 1024


def test_names_and_units():
    names = [c["name"] for c in MANIFEST["configs"]] + CELLS + \
        [m["name"] for m in MANIFEST["end_to_end"] + MANIFEST["per_layer"]]
    for n in names:
        assert NAME.match(n), n
    for kind in ("configs", "workloads"):
        assert len({x["name"] for x in MANIFEST[kind]}) == len(MANIFEST[kind])
    metrics = MANIFEST["end_to_end"] + MANIFEST["per_layer"]
    assert len({m["name"] for m in metrics}) == len(metrics)
    for m in metrics:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    for w in MANIFEST["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]


@pytest.mark.parametrize("entry", MANIFEST["configs"],
                         ids=lambda c: c["name"])
def test_config_files(entry):
    cfg = load_json(ROOT / entry["file"])
    assert entry["file"].startswith("bench/configs/")
    assert cfg["name"] == entry["name"]
    assert cfg["source"] == entry["source"]
    assert cfg["reduced"] == entry["reduced"]
    assert (BENCH / "reference" / f"{cfg['reference']}.py").is_file()
    for k in entry["reduced"]:
        assert NAME.match(k)
        assert not re.search(r"(_dim|_rank|size|hidden|heads)$", k), k
    used = {w["config"] for w in MANIFEST["workloads"]}
    assert entry["name"] in used


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_loads(cell):
    spec = run.load_cell(cell)
    assert spec["driver"].is_file()
    assert spec["workload"]["chips"] in (1, 4)
    e2e = {m["name"] for m in spec["end_to_end"]}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert spec["per_layer"]
    from bench import correct
    assert set(spec["limits"]) == set(correct.NUMBERS)
    for n in correct.NUMBERS:
        lim = spec["limits"][n]
        assert lim["lower"] < lim["limit"] < lim["upper"], (n, lim)
    for name, path in spec["readers"].items():
        mod = run.load_module(path, "reader_" + name.replace(".", "_"))
        assert callable(mod.read)


@pytest.mark.parametrize("metric", MANIFEST["per_layer"],
                         ids=lambda m: m["name"])
def test_per_layer_moves_an_end_to_end_metric_of_its_cells(metric):
    moved = E2E[metric["moves"]]
    for cell in metric.get("workloads", CELLS):
        assert cell in CELLS
        assert "workloads" not in moved or cell in moved["workloads"], \
            (metric["name"], cell)
    assert (BENCH / "metrics" / f"{metric['name']}.py").is_file()
    assert "\n" not in metric["layer"] and len(metric["layer"]) <= 200


def test_end_to_end_bounds():
    for m in MANIFEST["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    assert E2E["setup_s"]["bound"] <= 0.25


def test_four_chip_cells_are_at_most_half():
    four = [w for w in MANIFEST["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(CELLS) // 2)


def test_a_cell_is_added_by_adding_files(tmp_path):
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    manifest = json.loads(json.dumps(MANIFEST))
    before = {p: p.read_bytes() for p in (tmp_path / "bench").rglob("*")
              if p.is_file()}
    traffic = dict(load_json(BENCH / "traffic" / "ici4.json"), k_u=2)
    (tmp_path / "bench" / "traffic" / "ici4-k2.json").write_text(
        json.dumps(traffic))
    shutil.copy(BENCH / "limits" / "qwen2-0.5b.ici4.json",
                tmp_path / "bench" / "limits" / "qwen2-0.5b.ici4-k2.json")
    manifest["workloads"].append({"name": "qwen2-0.5b.ici4-k2",
                                  "config": "qwen2-0.5b",
                                  "traffic": "ici4-k2", "chips": 4,
                                  "why": "two shared steps a round"})
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        if "qwen2-0.5b.ici4" in m.get("workloads", ()):
            m["workloads"].append("qwen2-0.5b.ici4-k2")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(manifest))
    spec = run.load_cell("qwen2-0.5b.ici4-k2", root=tmp_path)
    assert spec["traffic"]["k_u"] == 2
    assert spec["driver"].name == "lm_train.py"
    assert {m["name"] for m in spec["per_layer"]} == {
        m["name"] for m in run.load_cell("qwen2-0.5b.ici4")["per_layer"]}
    after = {p: p.read_bytes() for p in before}
    assert after == before


def test_peaks_known_and_unknown():
    table = load_json(BENCH / "peaks.json")
    assert "TPU v5e" in table["source"]
    v5e = run.peaks_of("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        run.peaks_of("cpu")


def _run_bench(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("ALLOW_MULTIPLE_LIBTPU_LOAD", None)
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "qwen2-0.5b.ici4",
         "--seed", "3", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_a_run_without_a_tpu_exits_nonzero_and_prints_no_result():
    p = _run_bench(ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_a_run_without_the_program_exits_nonzero(tmp_path):
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    p = _run_bench(tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
