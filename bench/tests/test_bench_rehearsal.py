"""Each cell's driver end to end at a tiny size on the CPU, through the
harness's inner entry; the chip entry itself refuses the CPU."""

import os
import pathlib
import subprocess
import sys

import pytest

REPO = pathlib.Path(__file__).resolve().parents[2]


@pytest.mark.parametrize("workload", ["dna-seed-open", "proteins-build"])
def test_cell_runs_and_is_correct(run_cell, workload):
    rc, line, err = run_cell(workload)
    assert rc == 0, err
    assert line["correct"] is True
    assert line["attempted"] > 0 and line["failed"] == 0
    assert "setup_s" in line["metrics"]
    assert list(line)[-1] == "checks"
    assert all(v["value"] <= v["limit"] for v in line["checks"].values())
    assert line["device"]["platform"] == "cpu"
    # the compared numbers are the last lines of standard error
    tail = err.strip().splitlines()[-len(line["checks"]):]
    assert all(t.startswith("check ") for t in tail)


def test_serving_cell_reports_its_end_to_end_metrics(run_cell):
    rc, line, _ = run_cell("dna-seed-open")
    assert rc == 0
    m = line["metrics"]
    assert {"count_p99_ms", "locate_p99_ms", "lookup_qps"} <= set(m)
    assert m["lookup_qps"]["unit"] == "queries/s"


def test_traced_serving_run_reports_span_metrics(run_cell):
    rc, line, _ = run_cell("dna-seed-open", trace=True, seconds=1.0)
    assert rc == 0 and line["correct"] is True
    # the CPU trace has no device plane, so only host-span metrics exist
    assert "admit_wait_p99_ms.open" in line["metrics"]
    assert "flush_busy_share.open" in line["metrics"]
    assert "count_p99_ms" not in line["metrics"]


def test_chip_entry_refuses_the_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "proteins-build",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == "" or not p.stdout.strip().startswith("{")
    assert "no TPU" in p.stderr


def test_without_the_program_no_result(tmp_path):
    """A checkout that holds only BENCHMARK.json and bench/ exits
    non-zero and prints no result."""
    import shutil

    shutil.copytree(REPO / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "testdata"))
    shutil.copy(REPO / "BENCHMARK.json", tmp_path)
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "proteins-build",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, capture_output=True, text=True, timeout=120,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode != 0
    assert "{" not in p.stdout
