"""Paper Table 2: BWT construction — our prefix doubling vs the Menon et al.
competitor, on PROTEINS / DNA / ENGLISH corpora.

The paper ran 48 Spark nodes on up-to-1GB Pizza&Chili files; this container
is one CPU core, so we run CPU-feasible sizes of statistically similar
synthetic corpora (data/corpus.py) and verify the paper's CLAIMS:
  (1) ours beats the competitor at every size,
  (2) the gap GROWS with input size (competitor passes ~ LCP/K, ours
      ~ log2 n),
  (3) both produce identical, oracle-correct BWTs.

Since PR 2 "ours" is the fused-key fast builder (packed q-gram init +
active-suffix discarding + fused pair keys); the seed single-jit prefix
doubling is timed alongside as ``baseline`` so the build speedup is
measured end-to-end every run (acceptance: >= 2x at the largest size,
identical BWT output; measured 2.35-2.61x on the 64 Ki corpora, with
3-5 doubling rounds skipped by the q-gram init).

Emits ``BENCH_build.json`` (sizes, wall times, rounds executed/skipped,
per-round active fractions) so the perf trajectory is machine-readable —
``benchmarks/run.py`` includes it in the report.
"""

from __future__ import annotations

import argparse
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import alphabet as al
from repro.core.bwt import bwt_from_sa
from repro.core.competitor import suffix_array_rpgi
from repro.core.suffix_array import suffix_array, suffix_array_fast
from repro.data.corpus import corpus

DEFAULT_JSON = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "..", "experiments",
    "BENCH_build.json",
)


def _time(fn, *args, reps=3):
    fn(*args)[0].block_until_ready()  # compile + warm
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        out = fn(*args)
        jax.tree_util.tree_leaves(out)[0].block_until_ready()
        ts.append(time.perf_counter() - t0)
    return min(ts)


def _time_fast(s, sigma, reps=3):
    """Time the host-driven fast builder (not a single jit: the round loop
    reads back the active count to shrink the sort capacity)."""
    def build():
        sa, stats = suffix_array_fast(s, sigma)
        return bwt_from_sa(s, sa), stats
    (out, stats) = build()       # warm: compiles every capacity bucket
    out[0].block_until_ready()
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        (out, stats) = build()
        out[0].block_until_ready()
        ts.append(time.perf_counter() - t0)
    return min(ts), out, stats


def run(sizes=(1 << 14, 1 << 16), kinds=("proteins", "dna", "english"),
        reps=3):
    rows = []
    for kind in kinds:
        for n in sizes:
            toks = corpus(kind, n - 1)
            s = jnp.asarray(al.append_sentinel(toks))
            sigma = al.sigma_of(np.asarray(s))

            baseline = jax.jit(
                lambda t: bwt_from_sa(t, suffix_array(t, sigma))
            )
            comp = jax.jit(
                lambda t: bwt_from_sa(t, suffix_array_rpgi(t))
            )
            t_base = _time(baseline, s, reps=reps)
            t_comp = _time(comp, s, reps=reps)
            t_fast, (b_fast, r_fast), stats = _time_fast(s, sigma, reps=reps)

            b1, r1 = baseline(s)
            b2, r2 = comp(s)
            match = bool(
                np.array_equal(np.asarray(b1), np.asarray(b2))
                and np.array_equal(np.asarray(b1), np.asarray(b_fast))
                and int(r1) == int(r2) == int(r_fast)
            )
            rows.append({
                "input": f"{kind}.{n}",
                "n": n,
                "sigma": sigma,
                "ours_s": t_fast,
                "baseline_s": t_base,
                "competitor_s": t_comp,
                "speedup": t_comp / t_fast,
                "build_speedup": t_base / t_fast,
                "outputs_match": match,
                "q": stats.q,
                "rounds_executed": stats.rounds_executed,
                "rounds_skipped": stats.rounds_skipped,
                "active_frac": [round(f, 6) for f in stats.active_frac],
                "local_sort": stats.local_sort,
            })
    return rows


def write_json(rows, path):
    payload = {
        "bench": "table2_build",
        "backend": jax.default_backend(),
        "rows": rows,
    }
    os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
    with open(path, "w") as fh:
        json.dump(payload, fh, indent=2)
    return path


def main(argv=None):
    from repro.launch import compile_cache

    compile_cache.enable()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny sizes + 1 rep (CI build-bench smoke)")
    ap.add_argument("--json", default=DEFAULT_JSON,
                    help="BENCH_build.json output path ('' to skip)")
    args = ap.parse_args(argv)
    sizes = (1 << 10, 1 << 12) if args.smoke else (1 << 14, 1 << 16)
    rows = run(sizes=sizes, reps=1 if args.smoke else 3)
    print("table2,input,ours_s,baseline_s,competitor_s,speedup,"
          "build_speedup,rounds,skipped,outputs_match")
    for r in rows:
        print(
            f"table2,{r['input']},{r['ours_s']:.4f},{r['baseline_s']:.4f},"
            f"{r['competitor_s']:.4f},{r['speedup']:.2f},"
            f"{r['build_speedup']:.2f},{r['rounds_executed']},"
            f"{r['rounds_skipped']},{r['outputs_match']}"
        )
    if args.json:
        print(f"table2,json,{write_json(rows, args.json)}")
    assert all(r["outputs_match"] for r in rows), "BWT outputs diverged"


if __name__ == "__main__":
    main()
