"""The harness without a chip: it refuses to measure on the CPU, and it
finds every configuration, cell and per-layer metric by name."""

import importlib.util
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())


def run_bench(cwd: Path, workload: str):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", workload, "--seed",
         str(2**33 + 1), "--seconds", "1", "--trace", "0"], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=300)


def test_no_tpu_exits_nonzero_without_a_result():
    p = run_bench(ROOT, SPEC["workloads"][0]["name"])
    assert p.returncode == 3, p.stderr[-2000:]
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr


def test_benchmark_files_alone_do_not_run(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = run_bench(tmp_path, SPEC["workloads"][0]["name"])
    assert p.returncode != 0
    assert p.stdout.strip() == ""


@pytest.mark.parametrize("workload", [w["name"] for w in SPEC["workloads"]])
def test_every_cell_is_found_by_name(workload):
    from bench import run as bench_run
    c = bench_run.load_cell(workload)
    assert c["cfg"]["name"] == workload.split(".")[0]
    assert c["cell"]["kind"] in ("arena", "trainer")
    assert c["cell"]["limits"]
    names = {m["name"] for m in c["e2e"]}
    assert "setup_s" in names and len(names) >= 2
    assert c["per_layer"]
    for m in c["per_layer"]:
        assert m["moves"] in names


READERS = sorted(p.stem for p in (ROOT / "bench" / "metrics").glob("*.py"))


def test_every_benchmark_metric_has_a_reader_file():
    assert {m["name"] for m in SPEC["per_layer"]} <= set(READERS)


@pytest.mark.parametrize("metric", READERS)
def test_every_per_layer_metric_has_a_reader(metric):
    path = ROOT / "bench" / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location("m", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    assert callable(mod.read)


@pytest.mark.parametrize("entry", SPEC["configs"], ids=lambda c: c["name"])
def test_every_config_file_parses(entry):
    cfg = json.loads((ROOT / entry["file"]).read_text())
    assert cfg["name"] == entry["name"]
    for key in entry["reduced"]:
        assert key in cfg
    assert cfg["hyper"]["V"] > 0 and cfg["hyper"]["lam"] > 0


def test_peaks_table_has_the_v5e():
    peaks = json.loads((ROOT / "bench" / "peaks.json").read_text())
    assert peaks["TPU v5 lite"]["bf16_flops_per_s"] == 197e12
    assert peaks["TPU v5 lite"]["hbm_bytes_per_s"] == 819e9
