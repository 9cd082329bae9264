"""Cells are found by name from files, their limits with them, and the
harness refuses to run where there is no chip or no program."""

import json
import os
import pathlib
import shutil
import subprocess
import sys

import pytest

from bench import cells, check, run
from bench.tests.conftest import SMOKE_LIMITS, SMOKE_MODELS, write_smoke_cell

BENCH = pathlib.Path(__file__).resolve().parents[1]
REPO = BENCH.parent


def test_benchmark_json_names_existing_files():
    bench = json.loads(cells.BENCHMARK_JSON.read_text())
    for conf in bench["configs"]:
        assert (REPO / conf["file"]).is_file()
    for w in bench["workloads"]:
        cell = cells.resolve(w["name"])
        assert cell.config["name"] == w["config"]
        assert (cells.TRAFFIC_DIR / f"{w['traffic']}.json").is_file()
        for name in cell.per_layer:
            assert (cells.METRIC_DIR / f"{name}.py").is_file()


def test_every_cell_has_its_limits():
    bench = json.loads(cells.BENCHMARK_JSON.read_text())
    for w in bench["workloads"]:
        limits = check.limits_for(cells.resolve(w["name"]))
        assert set(limits) == {"median_rank", "worst_request_rank"}, w["name"]
        for v in limits.values():
            assert type(v) in (int, float), (w["name"], limits)


@pytest.mark.parametrize("name", sorted(SMOKE_MODELS))
def test_cell_from_new_files_alone(tmp_path, name):
    """A configuration, a mix and a limits file that nothing in the
    repository names make a cell the harness judges by those limits."""
    dirs = tmp_path / "configs", tmp_path / "traffic", tmp_path / "checks"
    cell = cells.resolve(write_smoke_cell(*dirs, name), dirs[0], dirs[1],
                         check_dir=dirs[2])
    assert (cell.config["name"], cell.traffic, cell.chips) == (
        f"{name}-smoke", "chat", 1)
    assert check.limits_for(cell) == {
        k: v for k, v in SMOKE_LIMITS.items() if k != "why"}
    run.model_config(cell)  # the program takes the file as written


def test_reference_length_of_code_burst():
    """Padding the reference to the mix's longest request leaves
    code-burst's length where ``max_seq`` put it."""
    cell = cells.resolve("granite-3-8b.code-burst")
    assert check.padded_length(cell) == 4096 == cell.config["serve"]["max_seq"]


def test_new_traffic_file_makes_a_cell(tmp_path):
    cdir, tdir = tmp_path / "configs", tmp_path / "traffic"
    shutil.copytree(cells.CONFIG_DIR, cdir)
    shutil.copytree(cells.TRAFFIC_DIR, tdir)
    with pytest.raises(KeyError):
        cells.resolve("granite-3-8b.long-doc", cdir, tdir)
    mix = json.loads((tdir / "doc-qa-4k.json").read_text())
    mix["document"] = {"tokens": 2048}
    (tdir / "long-doc.json").write_text(json.dumps(mix))
    cell = cells.resolve("granite-3-8b.long-doc", cdir, tdir)
    assert (cell.traffic, cell.chips, cell.mix["document"]["tokens"]) == (
        "long-doc", 1, 2048)
    assert set(cell.per_layer) == {p.stem for p in cells.METRIC_DIR.glob("*.py")}


def _run(cwd, env_extra=None):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env.update(JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "granite-3-8b.code-burst",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300,
    )


def test_run_exits_nonzero_without_a_chip():
    out = _run(REPO)
    assert out.returncode != 0
    assert "no TPU" in out.stderr
    assert out.stdout.strip() == ""


def test_run_exits_nonzero_with_only_the_benchmark(tmp_path):
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    shutil.copy(cells.BENCHMARK_JSON, tmp_path / "BENCHMARK.json")
    out = _run(tmp_path)
    assert out.returncode != 0
    assert "program is not here" in out.stderr
    assert out.stdout.strip() == ""
