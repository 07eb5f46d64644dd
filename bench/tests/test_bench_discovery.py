"""A later change adds a configuration, a traffic mix and a per-layer
metric as new files plus a ``workloads`` entry; the harness finds them by
name and no file that was there is edited."""

import hashlib
import json
import pathlib
import shutil
import time

import harness

REPO = pathlib.Path(__file__).resolve().parents[2]


def _digests(root: pathlib.Path) -> dict:
    return {str(p.relative_to(root)): hashlib.sha256(p.read_bytes())
            .hexdigest() for p in sorted(root.rglob("*")) if p.is_file()
            and "__pycache__" not in p.parts}


def test_new_files_are_found_by_name(tmp_path, capsys):
    shutil.copytree(REPO / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "testdata"))
    (tmp_path / "src").symlink_to(REPO / "src")
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    bench = tmp_path / "bench"
    before = _digests(bench)

    cfg = json.loads((bench / "configs" / "grch38_bwa_2p26.json").read_text())
    cfg.update(name="tiny_dna", n=1 << 13)
    cfg["index"]["serve_max_batch"] = 4
    (bench / "configs" / "tiny_dna.json").write_text(json.dumps(cfg))
    tr = json.loads((bench / "traffic" / "seed_open.json").read_text())
    tr.update(rate_qps=100, pattern_len=[20, 30], trace_start_s=0.1,
              trace_seconds=0.5)
    (bench / "traffic" / "slow_open.json").write_text(json.dumps(tr))
    (bench / "metrics" / "flushes.slow.py").write_text(
        'SOURCE = "program_span"\n\n\ndef read(ctx):\n'
        '    return float(len(ctx.spans)) or None\n')
    spec["configs"].append({"name": "tiny_dna", "source": "test",
                            "file": "bench/configs/tiny_dna.json",
                            "reduced": ["n"], "why": "test"})
    spec["workloads"].append({"name": "tiny-slow", "config": "tiny_dna",
                              "traffic": "slow_open", "chips": 1,
                              "why": "test"})
    for m in spec["end_to_end"]:
        if m.get("workloads") and "dna-seed-open" in m["workloads"]:
            m["workloads"].append("tiny-slow")
    spec["per_layer"].append({"name": "flushes.slow", "unit": "1",
                              "better": "lower", "source": "program_span",
                              "layer": "engine flush",
                              "moves": "count_p99_ms",
                              "workloads": ["tiny-slow"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))

    cell = harness.load_cell("tiny-slow", bench)
    assert cell.config["name"] == "tiny_dna"
    assert cell.traffic["rate_qps"] == 100
    assert [m["name"] for m in cell.per_layer] == ["flushes.slow"]
    rc = harness.run("tiny-slow", 5, 1.0, True,
                     t_process=time.perf_counter(), bench_dir=bench,
                     require_tpu=False)
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0 and line["correct"] is True
    assert line["metrics"]["flushes.slow"]["value"] > 0
    after = _digests(bench)
    assert {k: v for k, v in after.items() if k in before} == before
