"""Compaction benchmark: rebuild-free BWT merges vs raw-token rebuild.

``SegmentedIndex.compact`` has three rebuild-free-capable strategies: the
pairwise interleave fold, the k-way interleave walk (one walk splices
every segment — no intermediate indexes), and the cost-model auto pick
(``strategy="merge"``, the serving default).  ``strategy="rebuild"``
re-sorts the run's raw tokens — the correctness oracle.  Each row of
``experiments/BENCH_compact.json`` times all of them over the same
catalog and asserts every strategy produces a bit-identical merged index
(``outputs_match``) and identical query answers (``answers_match``).
``speedup`` is rebuild time over the auto-picked strategy's time — the
regression gate (``scripts/check_bench_json.py``) fails any row where the
serving default loses to the rebuild.

``--smoke`` runs the 64 Ki scales at 2, 4, and 8 segments (the CI
regression gate rows).  The 2-segment row is the cold-start equal split;
the 4- and 8-segment rows use the steady-state serving shape — one large
accumulated segment plus a tail of fresh small appends (``SHAPES``),
which is the run ``maybe_compact`` actually folds between flushes.  The
shape matters: the sequential interleave walk visits every token *after*
the largest segment, so merges win exactly when the accumulated segment
dominates the run (and the k-way walk additionally avoids the pairwise
fold's per-intermediate splices as the tail widens).  An equal split at
high segment count is the merge-hostile case, and the cost model's job is
to route it to the rebuild instead — the planner's pick is recorded per
row as ``strategy``.  Full runs add more corpora and a 128 Ki scale.
Timings exclude compile: each strategy is warmed on a same-shape
throwaway catalog first, so the steady-state serving cost (the jit
programs are cached per power-of-two bucket) is what is measured.
"""

from __future__ import annotations

import argparse
import json
import os
import time

import jax
import numpy as np

from repro.core import alphabet as al
from repro.core.fm_index import PAD, fm_mismatch
from repro.core.segments import SegmentedIndex
from repro.data.corpus import corpus

DEFAULT_JSON = os.path.join(
    os.path.dirname(__file__), "..", "experiments", "BENCH_compact.json"
)

SAMPLE_RATE = 32
SA_SAMPLE_RATE = 16

STRATEGIES = ("rebuild", "pairwise", "kway", "merge")

# catalog split per segment count, as corpus fractions.  2 segments:
# cold-start equal halves.  4/8 segments: the serving steady state — one
# accumulated segment holding most of the corpus plus fresh small appends
# (each flush adds a small segment; maybe_compact folds the run).
SHAPES = {
    2: (1 / 2, 1 / 2),
    4: (3 / 4, 1 / 8, 1 / 16, 1 / 16),
    8: (3 / 4, 1 / 16) + (1 / 32,) * 6,
}


def build_catalog(kind: str, n: int, n_segments: int) -> SegmentedIndex:
    toks = corpus(kind, n)
    sigma = al.sigma_of(al.append_sentinel(toks))
    seg = SegmentedIndex(sigma, sample_rate=SAMPLE_RATE,
                         sa_sample_rate=SA_SAMPLE_RATE)
    shape = SHAPES[n_segments]
    bounds = np.round(np.cumsum((0.0,) + shape) * len(toks)).astype(int)
    for lo, hi in zip(bounds[:-1], bounds[1:]):
        seg.append(toks[lo:hi])
    return seg


def snapshot(seg: SegmentedIndex):
    return list(seg.segments), seg._next_id


def restore(seg: SegmentedIndex, snap) -> None:
    seg.segments, seg._next_id = list(snap[0]), snap[1]
    seg._stacked_cache = None


def time_strategy(seg: SegmentedIndex, snap, strategy: str, repeats: int):
    best, merged, plan = float("inf"), None, None
    for _ in range(repeats):
        restore(seg, snap)
        t0 = time.perf_counter()
        m = seg.compact(strategy=strategy)
        jax.block_until_ready(seg.segments[0].index.fm.bwt)
        best = min(best, time.perf_counter() - t0)
        assert m >= 1, strategy
        merged = seg.segments[0].index.fm
        plan = seg.compact_last_plan
    return best, merged, plan


def bench_scale(kind: str, n: int, n_segments: int, repeats: int,
                rng) -> dict:
    seg = build_catalog(kind, n, n_segments)
    snap = snapshot(seg)

    # warm the jit programs (snapshot-restore resets the catalog, so the
    # warmup compaction hits the same pow2 bucket shapes the timed runs do)
    for strategy in STRATEGIES:
        restore(seg, snap)
        seg.compact(strategy=strategy)

    times, fms, plans = {}, {}, {}
    for strategy in STRATEGIES:
        times[strategy], fms[strategy], plans[strategy] = time_strategy(
            seg, snap, strategy, repeats
        )
    outputs_match = all(
        not fm_mismatch(fms[s], fms["rebuild"]) for s in STRATEGIES[1:]
    )
    assert seg.compact_fallbacks == 0, seg.compact_last_fallback_reason

    # answers must also be invariant across the compaction itself
    restore(seg, snap)
    B, L = 16, 8
    toks = np.concatenate([s.tokens for s in seg.segments])
    pats = np.full((B, L), PAD, np.int32)
    for b in range(B):
        m = int(rng.integers(2, L + 1))
        st = int(rng.integers(0, len(toks) - m))
        pats[b, :m] = toks[st : st + m]
    before = seg.count(pats)
    seg.compact(strategy="merge")
    answers_match = bool(np.array_equal(seg.count(pats), before))

    plan = plans["merge"]
    row = {
        "scenario": f"{kind}.{n}.{n_segments}seg",
        "n": int(n),
        "segments": int(n_segments),
        "merge_s": times["merge"],
        "pairwise_s": times["pairwise"],
        "kway_s": times["kway"],
        "rebuild_s": times["rebuild"],
        "speedup": times["rebuild"] / times["merge"],
        "strategy": plan["strategy"],
        "est_walk_steps": int(plan["est_walk_steps"]),
        "actual_walk_steps": int(plan["actual_walk_steps"]),
        "outputs_match": bool(outputs_match),
        "answers_match": answers_match,
    }
    print(
        f"{row['scenario']}: auto[{row['strategy']}] "
        f"{times['merge'] * 1e3:.1f}ms (pairwise "
        f"{times['pairwise'] * 1e3:.1f}ms, kway "
        f"{times['kway'] * 1e3:.1f}ms) vs rebuild "
        f"{times['rebuild'] * 1e3:.1f}ms -> {row['speedup']:.2f}x "
        f"(bit-identical: {outputs_match})"
    )
    return row


def main(argv=None) -> None:
    from repro.launch import compile_cache

    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="64 Ki rows at 2/4/8 segments (the CI gate)")
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--json", default=DEFAULT_JSON,
                    help="output path ('' disables)")
    args = ap.parse_args(argv)

    rng = np.random.default_rng(0)
    scales = [("dna", 1 << 16, 2), ("dna", 1 << 16, 4), ("dna", 1 << 16, 8)]
    if not args.smoke:
        scales += [("english", 1 << 16, 2), ("dna", 1 << 17, 2)]
    rows = [bench_scale(kind, n, k, args.repeats, rng)
            for kind, n, k in scales]

    bad = [r["scenario"] for r in rows
           if not (r["outputs_match"] and r["answers_match"])]
    if bad:
        raise SystemExit(f"compact_bench: CORRECTNESS FAILURE in {bad}")

    if args.json:
        payload = {
            "bench": "compact",
            "backend": jax.default_backend(),
            "rows": rows,
        }
        path = os.path.abspath(args.json)
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(path, "w") as f:
            json.dump(payload, f, indent=2)
        print(f"wrote {path}")


if __name__ == "__main__":
    main()
