"""Shared helpers of the benchmark's CPU tests: they import the harness
from ``bench/`` and the program from ``src/``, and run cells at tiny
sizes through ``harness.run`` with the chip check off."""

import json
import pathlib
import sys
import time

import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
for p in (BENCH, BENCH.parent / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

# tiny shapes: a cell's own code paths at a size the CPU runs in seconds
TINY = {
    "dna-seed-open": {"config": {"n": 1 << 14, "index": {"serve_max_batch": 8}},
                      "traffic": {"rate_qps": 150, "pattern_len": [19, 32],
                                  "trace_start_s": 0.2,
                                  "trace_seconds": 0.6}},
    "proteins-build": {"config": {"n": 1 << 15}, "traffic": {"texts": 2}},
}


@pytest.fixture
def run_cell(capsys):
    """Run one cell on the CPU; returns (exit code, result line or None,
    standard error)."""
    import harness

    def go(workload, *, seed=2**31 + 11, seconds=1.0, trace=False,
           fault=None, overrides=None):
        ov = json.loads(json.dumps(TINY[workload]))
        harness._merge(ov, overrides or {})
        rc = harness.run(workload, seed, seconds, trace,
                         t_process=time.perf_counter(), require_tpu=False,
                         overrides=ov, fault=fault)
        out, err = capsys.readouterr()
        lines = out.strip().splitlines()
        line = json.loads(lines[-1]) if rc == 0 and lines else None
        return rc, line, err

    return go
