"""Serving-frontend load generator: closed-loop + open-loop arrival, mixed
count/locate, against the async admission-controlled frontend.

Three scenarios per scale, each a row of ``experiments/BENCH_serve.json``:

* ``closed``   — N client threads, each submits and waits (classic
  closed-loop saturation: measures sustained QPS and per-bucket p50/p99
  with backpressure from the clients themselves).
* ``open``     — requests arrive on a fixed-rate schedule regardless of
  completions (open-loop: what a cloud frontend actually sees).  The rate
  is set from the closed-loop measurement so the system runs near — but
  under — saturation.
* ``overload`` — open-loop far above capacity against a tiny admission
  queue: the frontend must shed (``Rejected``) rather than fall over, and
  every *admitted* request must still be answered correctly.

Every scenario cross-checks frontend answers against direct index calls
(``outputs_match`` — a fast wrong server must be loud), and rows carry
per-bucket p50/p99 plus flattened worst-bucket fields so
``scripts/check_bench_json.py`` can regression-compare smoke runs.

``--smoke`` shrinks the corpus and request counts for CI; smoke rows are
ALSO produced by full runs (suffix ``_smoke``) so the committed baseline
always contains the rows CI compares against.
"""

from __future__ import annotations

import argparse
import json
import os
import threading
import time

import jax
import numpy as np

from repro.core import alphabet as al
from repro.core.segments import SegmentedIndex
from repro.data.corpus import corpus
from repro.serving.engine import FMQueryServer
from repro.serving.frontend import AsyncQueryFrontend, Rejected

DEFAULT_JSON = os.path.join(
    os.path.dirname(__file__), "..", "experiments", "BENCH_serve.json"
)

LOCATE_FRAC = 0.2
LOCATE_K = 4


def build_segmented(kind: str, n: int, n_segments: int,
                    sample_rate=32, sa_sample_rate=16):
    """A segmented index over an n-token corpus (segment-parallel fan-out
    is the serving default), plus the raw sentinel-terminated text."""
    toks = corpus(kind, n)
    sigma = al.sigma_of(al.append_sentinel(toks))
    seg = SegmentedIndex(sigma, sample_rate=sample_rate,
                         sa_sample_rate=sa_sample_rate)
    bounds = np.linspace(0, len(toks), n_segments + 1).astype(int)
    for lo, hi in zip(bounds[:-1], bounds[1:]):
        seg.append(toks[lo:hi])
    return seg, toks


def make_requests(rng, toks, n_requests, buckets, locate_frac=LOCATE_FRAC):
    """Mixed workload: (pattern, kind) pairs with lengths spread across the
    server's jit buckets (patterns sampled from the corpus, so counts are
    nonzero often enough to exercise locate walks)."""
    reqs = []
    max_len = buckets[-1]
    for _ in range(n_requests):
        L = int(rng.integers(2, max_len + 1))
        st = int(rng.integers(0, len(toks) - L))
        kind = "locate" if rng.random() < locate_frac else "count"
        reqs.append((np.ascontiguousarray(toks[st : st + L]), kind))
    return reqs


def expected_results(index, reqs, k=LOCATE_K):
    """Direct (unqueued) answers for every request, via one padded batch
    per kind — the oracle for ``outputs_match``."""
    from repro.core.fm_index import PAD

    L = max(len(p) for p, _ in reqs)
    pats = np.full((len(reqs), L), PAD, np.int32)
    for i, (p, _) in enumerate(reqs):
        pats[i, : len(p)] = p
    counts = np.asarray(index.count(pats), np.int64)
    pos, _ = index.locate(pats, k)
    return counts, np.asarray(pos, np.int64)


def check_results(reqs, results, counts, pos, k=LOCATE_K):
    """True iff every non-shed frontend result equals the direct answer."""
    ok = True
    for i, ((_, kind), res) in enumerate(zip(reqs, results)):
        if isinstance(res, Rejected):
            continue
        if res.count != min(counts[i], k if kind == "locate" else counts[i]):
            ok = False
        if kind == "locate":
            want = pos[i][: res.count]
            if not np.array_equal(np.asarray(res.positions, np.int64), want):
                ok = False
    return ok


def run_closed(frontend, reqs, clients):
    """Closed loop: ``clients`` threads round-robin the request list, each
    waiting for its result before submitting the next."""
    results = [None] * len(reqs)
    t0 = time.perf_counter()

    def worker(start):
        for i in range(start, len(reqs), clients):
            pat, kind = reqs[i]
            k = LOCATE_K if kind == "locate" else None
            results[i] = frontend.submit(pat, kind, k=k).result(timeout=300)

    threads = [threading.Thread(target=worker, args=(c,))
               for c in range(clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return results, time.perf_counter() - t0


def run_open(frontend, reqs, target_qps=None):
    """Open loop: submit on a fixed-rate schedule (no waiting for results),
    then gather.  ``target_qps=None`` is an unpaced burst — every request
    arrives as fast as the producer can enqueue, the worst overload case.
    Falling behind the schedule is allowed — arrival times just bunch up,
    which is exactly the overload behaviour being measured."""
    futs = []
    interval = 1.0 / target_qps if target_qps else 0.0
    t0 = time.perf_counter()
    for i, (pat, kind) in enumerate(reqs):
        if interval:
            delay = t0 + i * interval - time.perf_counter()
            if delay > 0:
                time.sleep(delay)
        k = LOCATE_K if kind == "locate" else None
        futs.append(frontend.submit(pat, kind, k=k))
    results = [f.result(timeout=300) for f in futs]
    return results, time.perf_counter() - t0


def warm_shapes(server, rng, toks, buckets, sizes):
    """Compile every jit program the scenarios can hit — one direct flush
    per (kind, length bucket, pow2 batch bucket), so scenario latencies
    measure serving, not compilation (chunks of any size <= max(sizes) pad
    to one of these shapes)."""
    for size in sizes:
        for L in buckets:
            for kind in ("count", "locate"):
                for _ in range(size):
                    st = int(rng.integers(0, len(toks) - L))
                    server.submit(toks[st : st + L], kind,
                                  k=LOCATE_K if kind == "locate" else None)
                server.flush()


def _flatten_buckets(metrics):
    """Worst-bucket p50/p99 per kind, flattened for the regression check."""
    out = {}
    for kind in ("count", "locate"):
        rows = [b for key, b in metrics["buckets"].items()
                if key.startswith(kind + "/") and b["completed"]]
        if rows:
            out[f"{kind}_p50_ms"] = max(r["p50_ms"] for r in rows)
            out[f"{kind}_p99_ms"] = max(r["p99_ms"] for r in rows)
    return out


def bench_scale(label, kind, n, n_segments, n_requests, clients, cfg, rng):
    """All three scenarios at one corpus scale -> list of row dicts."""
    seg, toks = build_segmented(kind, n, n_segments)
    buckets = cfg.serve_length_buckets
    max_batch = cfg.serve_max_batch
    slo = {"count": cfg.serve_slo_p99_ms,
           "locate": cfg.serve_slo_p99_ms_locate}
    rows = []

    def frontend(max_queue, max_wait_ms=None):
        server = FMQueryServer(seg, length_buckets=buckets,
                               max_batch=max_batch, locate_k=LOCATE_K)
        return AsyncQueryFrontend(
            server, max_queue=max_queue, slo_p99_ms=slo,
            max_wait_ms=cfg.serve_max_wait_ms
            if max_wait_ms is None else max_wait_ms,
        )

    sizes = [1 << i for i in range((max_batch).bit_length())]  # 1..max_batch
    warm_shapes(FMQueryServer(seg, length_buckets=buckets,
                              max_batch=max_batch, locate_k=LOCATE_K),
                rng, toks, buckets, sizes)

    base = {"input": f"{kind}.{n}", "n": int(n), "segments": n_segments,
            "locate_frac": LOCATE_FRAC}

    # closed loop
    reqs = make_requests(rng, toks, n_requests, buckets)
    counts, pos = expected_results(seg, reqs)
    with frontend(1 << 16) as fe:
        results, wall = run_closed(fe, reqs, clients)
        m = fe.metrics()
    closed_qps = len(reqs) / wall
    rows.append({**base, "scenario": f"closed{label}", "mode": "closed",
                 "clients": clients, "requests": len(reqs),
                 "wall_s": wall, "qps": closed_qps,
                 "admitted": m["admitted"], "rejected": m["rejected"],
                 "shed_frac": m["shed_frac"],
                 "outputs_match": check_results(reqs, results, counts, pos),
                 **_flatten_buckets(m), "buckets": m["buckets"]})

    # open loop at ~70% of measured closed-loop capacity
    reqs = make_requests(rng, toks, n_requests, buckets)
    counts, pos = expected_results(seg, reqs)
    target = max(closed_qps * 0.7, 1.0)
    with frontend(1 << 16) as fe:
        results, wall = run_open(fe, reqs, target)
        m = fe.metrics()
    rows.append({**base, "scenario": f"open{label}", "mode": "open",
                 "target_qps": target, "requests": len(reqs),
                 "wall_s": wall, "qps": len(reqs) / wall,
                 "admitted": m["admitted"], "rejected": m["rejected"],
                 "shed_frac": m["shed_frac"],
                 "outputs_match": check_results(reqs, results, counts, pos),
                 **_flatten_buckets(m), "buckets": m["buckets"]})

    # overload: an unpaced burst into a tiny admission queue -> must shed,
    # not crash, and every admitted answer must still be exact
    reqs = make_requests(rng, toks, n_requests, buckets)
    counts, pos = expected_results(seg, reqs)
    with frontend(max_queue=max(clients, 8), max_wait_ms=0.5) as fe:
        results, wall = run_open(fe, reqs, None)
        m = fe.metrics()
    shed = sum(isinstance(r, Rejected) for r in results)
    # no "qps" on the overload row: admitted/wall there is a ratio of two
    # burst-timing artifacts (queue-depth slip vs 1-2 flush drains) and
    # regression-gating it across machines would flake; the row's signal
    # is shed_frac > 0 with outputs_match on the admitted remainder
    rows.append({**base, "scenario": f"overload{label}", "mode": "burst",
                 "target_qps": None, "requests": len(reqs),
                 "wall_s": wall, "drain_rate": (len(reqs) - shed) / wall,
                 "admitted": m["admitted"], "rejected": m["rejected"],
                 "shed_frac": m["shed_frac"],
                 "outputs_match": check_results(reqs, results, counts, pos),
                 **_flatten_buckets(m), "buckets": m["buckets"]})
    return rows


def main(argv=None):
    from repro.configs.bwt_index import CONFIG, reduced
    from repro.launch import compile_cache

    compile_cache.enable()

    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="seconds-scale run with assertions (CI)")
    ap.add_argument("--json", default=DEFAULT_JSON,
                    help="output path ('' skips the write)")
    args = ap.parse_args(argv)

    rng = np.random.default_rng(0)
    rows = []
    # smoke rows run in BOTH modes, so the committed full-run baseline
    # contains the rows CI's smoke run is compared against
    cfg = reduced().replace(serve_length_buckets=(4, 8), serve_max_batch=8)
    rows += bench_scale("_smoke", "dna", 1 << 12, 3, 160, 4, cfg, rng)
    if not args.smoke:
        cfg = CONFIG.replace(serve_length_buckets=(8, 16, 32),
                             serve_max_batch=32)
        rows += bench_scale("", "dna", 1 << 16, 8, 1536, 8, cfg, rng)

    payload = {"bench": "serve_frontend", "backend": jax.default_backend(),
               "rows": rows}
    for r in rows:
        rate = r.get("qps", r.get("drain_rate"))
        print(
            f"servebench,{r['scenario']},{r['input']},qps={rate:.0f},"
            f"shed={r['shed_frac']:.2f},match={r['outputs_match']}"
        )
    if args.smoke:
        assert all(r["outputs_match"] for r in rows), "frontend != direct"
        over = [r for r in rows if r["scenario"].startswith("overload")]
        assert all(r["rejected"] > 0 for r in over), "overload never shed"
        assert all(r["admitted"] == r["requests"] - r["rejected"]
                   for r in rows)
    if args.json:
        path = os.path.abspath(args.json)
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(path, "w") as f:
            json.dump(payload, f, indent=2)
        print(f"wrote {path}")


if __name__ == "__main__":
    main()
