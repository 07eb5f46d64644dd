"""Benchmark harness: one section per paper table/figure + system ablations.

Prints ``name,us_per_call,derived`` CSV rows (plus per-bench headers).

  table2     paper Table 2 — ours vs Menon et al. competitor (wall time),
             plus fast-vs-seed build speedup; writes BENCH_build.json
  buildjson  machine-readable build trajectory from BENCH_build.json
  sortbench  DESIGN.md §4 sort-engine ablation (collective volume, derived;
             fused-key and radix local-sort variants)
  fmbench    FM-index serving throughput + rank_select kernel
  servebench async frontend load test (closed/open/overload); writes
             BENCH_serve.json
  compactbench  BWT-merge vs rebuild compaction (bit-identity asserted);
             writes BENCH_compact.json
  roofline   index-build + LM roofline terms (from dry-run JSONs, if present)
"""

from __future__ import annotations

import glob
import json
import os


def _roofline_section():
    out_dir = os.path.join(os.path.dirname(__file__), "..", "experiments",
                           "dryrun")
    files = sorted(glob.glob(os.path.join(out_dir, "*.json")))
    if not files:
        print("roofline,none,0,run `python -m repro.launch.dryrun` first")
        return
    print("roofline,cell,step_time_us,bottleneck;compute_s;memory_s;collective_s")
    for f in files:
        with open(f) as fh:
            r = json.load(fh)
        if r.get("status") != "compiled":
            continue
        roof = r.get("roofline", {})
        print(
            f"roofline,{r['arch']}__{r['shape']}__{r['mesh']},"
            f"{roof.get('step_time_s', 0) * 1e6:.0f},"
            f"{roof.get('bottleneck', '-')};{roof.get('compute_s', 0):.4f};"
            f"{roof.get('memory_s', 0):.4f};{roof.get('collective_s', 0):.4f}"
        )


def _build_json_section():
    from .table2_bwt import DEFAULT_JSON

    if not os.path.exists(DEFAULT_JSON):
        print("buildjson,none,0,table2 writes it")
        return
    with open(DEFAULT_JSON) as fh:
        payload = json.load(fh)
    print("buildjson,input,ours_s,build_speedup,rounds;skipped;active_frac0")
    for r in payload.get("rows", []):
        frac0 = r["active_frac"][0] if r["active_frac"] else 0.0
        print(
            f"buildjson,{r['input']},{r['ours_s']:.4f},"
            f"{r['build_speedup']:.2f},"
            f"{r['rounds_executed']};{r['rounds_skipped']};{frac0:.4f}"
        )


def main() -> None:
    from repro.launch import compile_cache

    from . import (
        compact_bench,
        fm_query_bench,
        serve_bench,
        sort_bench,
        table2_bwt,
    )

    compile_cache.enable()
    table2_bwt.main([])
    _build_json_section()
    sort_bench.main()
    fm_query_bench.main([])
    serve_bench.main([])
    compact_bench.main([])
    _roofline_section()


if __name__ == "__main__":
    main()
