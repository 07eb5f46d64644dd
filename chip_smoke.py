#!/usr/bin/env python3
"""Chip smoke test: the index's main path, end to end, on a TPU.

    python chip_smoke.py             # one chip: build -> serve -> append/compact
    python chip_smoke.py --chips 4   # the distributed build on four chips vs one
    python chip_smoke.py --size-probe 27 28   # largest DNA build that fits

One chip (the default) drives what ``launch/serve.py`` drives, through the
same entry points, at a genome-scale size:

  1. a 2^16 DNA build on the chip equals an independent numpy suffix sort
     (which itself equals ``core/bwt.bwt_naive`` at 2^10);
  2. a 2^26-token DNA index (``data/corpus.dna``) built through
     ``SegmentedIndex.append`` with the backend's default engine (the
     Pallas radix sort) has the BWT of a ``local_sort="compare"`` build;
  3. count and locate requests (``query_batch`` 1024, ``query_len`` 32 from
     ``configs/bwt_index.py``) go through ``FMQueryServer`` behind the
     ``AsyncQueryFrontend``; counts equal a numpy scan of the text and
     every located position matches;
  4. a 2^20-token tail is appended through the frontend, queried across
     both segments, then merge-compacted (the rank-kernel interleave walk
     of ``core/bwt_merge``); answers must not change;
  5. a 2^22-token proteins index (unpacked rank kernel) answers counts.

The compiled count step and the radix sort must hold a Mosaic kernel
(``tpu_custom_call``).  Any failed check or phase exits non-zero; so does a
machine without a TPU, or a directory without the repository's sources.
The last line of standard output is one JSON object with the device.
"""

from __future__ import annotations

import argparse
import collections
import contextlib
import json
import pathlib
import sys
import time

import numpy as np

ROOT = pathlib.Path(__file__).resolve().parent
SRC = ROOT / "src"
BATCHES = 4     # query batches (of the config's query_batch) per round
CHECKED = 48    # requests per kind checked against the numpy scan
ABSENT = 16     # requests per round for patterns the text does not hold
TAIL = 1 << 20  # appended tail tokens
PROTEINS = 1 << 22
SEED = 0


class CheckFailed(Exception):
    pass


def check(cond, what: str) -> None:
    if not cond:
        raise CheckFailed(what)


def log(msg: str) -> None:
    print(msg, flush=True)


# -- independent numpy references ---------------------------------------------


def numpy_suffix_array(s: np.ndarray) -> np.ndarray:
    """Suffix array by prefix doubling with ``np.lexsort`` (a shorter
    suffix sorts before a longer one it prefixes) — shares no code with
    the system under test."""
    n = len(s)
    rank = s.astype(np.int64)
    k = 1
    while True:
        second = np.full(n, -1, np.int64)
        second[: n - k] = rank[k:]
        sa = np.lexsort((second, rank))
        diff = (rank[sa][1:] != rank[sa][:-1]) | (second[sa][1:]
                                                   != second[sa][:-1])
        rank = np.empty(n, np.int64)
        rank[sa] = np.concatenate([[0], np.cumsum(diff)])
        if rank.max() == n - 1 or k >= n:
            return sa
        k *= 2


def numpy_bwt(s: np.ndarray) -> tuple[np.ndarray, int]:
    sa = numpy_suffix_array(s)
    return s[(sa - 1) % len(s)], int(np.argmin(sa))


class TextScan:
    """Occurrences of a pattern in a text, found by filtering candidate
    start positions one symbol at a time.  ``cuts`` are document starts
    in the text: no occurrence spans one."""

    def __init__(self, text: np.ndarray, cuts=()):
        self.text = text
        self.cuts = cuts
        self.by_first = {}

    def positions(self, pat: np.ndarray) -> np.ndarray:
        t, m = self.text, len(pat)
        first = int(pat[0])
        if first not in self.by_first:
            self.by_first[first] = np.nonzero(t == first)[0]
        cand = self.by_first[first]
        cand = cand[cand <= len(t) - m]
        for cut in self.cuts:
            cand = cand[(cand + m <= cut) | (cand >= cut)]
        for i in range(1, m):
            cand = cand[t[cand + i] == pat[i]]
        return cand


class CompileClock:
    """Seconds JAX spends compiling programs (or loading them from the
    persistent cache), summed from its backend-compile events by program
    name."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        from jax import monitoring

        self.by_fun = collections.Counter()
        monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if event == self.EVENT:
            self.by_fun[kw.get("fun_name", "?")] += duration

    def total(self) -> float:
        return sum(self.by_fun.values())


# -- helpers --------------------------------------------------------------------


def memory(dev) -> str:
    """The device's live bytes, its high-water mark since the process
    started (which no phase can lower) and its allocator limit, in GiB."""
    stats = dev.memory_stats() or {}
    return (f"in use {stats.get('bytes_in_use', 0) / 2**30} GiB, peak "
            f"{stats.get('peak_bytes_in_use', 0) / 2**30} GiB of "
            f"{stats.get('bytes_limit', 0) / 2**30} GiB")


def sample_patterns(rng, text: np.ndarray, count: int, lo: int, hi: int):
    """``count`` substrings of ``text`` with lengths in [lo, hi]."""
    out = []
    for _ in range(count):
        m = int(rng.integers(lo, hi + 1))
        st = int(rng.integers(0, len(text) - m))
        out.append(text[st : st + m].copy())
    return out


def absent_patterns(rng, scan: TextScan, count: int, m: int):
    """``count`` random length-``m`` strings over the text's symbols that
    the numpy scan finds nowhere in the text."""
    symbols = np.unique(scan.text[: 1 << 16])
    out = []
    while len(out) < count:
        p = rng.choice(symbols, m).astype(scan.text.dtype)
        if len(scan.positions(p)) == 0:
            out.append(p)
    return out


@contextlib.contextmanager
def timed_walks(names):
    """Time every call of the named ``core.bwt_merge`` walk programs to
    the end of its device work; yields the list the seconds go to."""
    import jax

    from repro.core import bwt_merge

    walls, saved = [], {w: getattr(bwt_merge, w) for w in names}

    def timed(fn):
        def call(*a, **kw):
            t0 = time.perf_counter()
            out = jax.block_until_ready(fn(*a, **kw))
            walls.append(time.perf_counter() - t0)
            return out
        return call

    for w, fn in saved.items():
        setattr(bwt_merge, w, timed(fn))
    try:
        yield walls
    finally:
        for w, fn in saved.items():
            setattr(bwt_merge, w, fn)


def has_kernel(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


def serve_round(fe, pats, kinds, label: str):
    """Submit every pattern through the async frontend; return the answers
    and the queries/s of the round (host clock, bring-up reading)."""
    from repro.serving.frontend import Rejected

    t0 = time.perf_counter()
    futs = [fe.submit(p, k) for p, k in zip(pats, kinds)]
    res = [f.result() for f in futs]
    dt = time.perf_counter() - t0
    check(not any(isinstance(r, Rejected) for r in res),
          f"{label}: requests shed")
    log(f"{label}: {len(pats)} requests in {dt:.3f}s "
        f"({len(pats) / dt:.0f} queries/s)")
    return res


def check_answers(res, pats, kinds, scan: TextScan, k: int, n_check: int,
                  label: str) -> None:
    """The first ``n_check`` requests of each kind against the numpy scan:
    counts, and the located positions themselves where the count is at
    most ``k``.  Every located position, checked or not, must be distinct
    and match the text."""
    checked = {"count": 0, "locate": 0}
    for r, p, kind in zip(res, pats, kinds):
        if kind == "locate":
            pos = np.sort(np.asarray(r.positions, np.int64))
            check(len(pos) == min(r.count, k),
                  f"{label}: locate returned {len(pos)} of {r.count}")
            check(len(np.unique(pos)) == len(pos),
                  f"{label}: locate returned a position twice")
            win = scan.text[pos[:, None] + np.arange(len(p))[None, :]]
            check(bool((win == p[None, :]).all()),
                  f"{label}: a located position does not match")
        if checked[kind] < n_check:
            want = scan.positions(p)
            if kind == "count":
                check(r.count == len(want),
                      f"{label}: count {r.count} != numpy {len(want)}")
            else:
                check(r.count == min(len(want), k),
                      f"{label}: locate {r.count} != numpy {len(want)}")
                if len(want) <= k:
                    check(np.array_equal(pos, want),
                          f"{label}: located positions != numpy")
            checked[kind] += 1


# -- phases ---------------------------------------------------------------------


def phase_small_build(dev) -> None:
    """2^16 DNA on the chip vs the numpy suffix sort; the numpy reference
    vs ``bwt_naive`` at 2^10."""
    from repro.core.bwt import bwt_naive
    from repro.core.pipeline import build_index, prepare_tokens
    from repro.data.corpus import corpus

    tiny, _ = prepare_tokens(corpus("dna", 1 << 10, seed=7), 64)
    naive_bwt, naive_row = bwt_naive(tiny)
    ref_bwt, ref_row = numpy_bwt(tiny)
    check(np.array_equal(naive_bwt, ref_bwt) and naive_row == ref_row,
          "numpy reference != bwt_naive at 2^10")

    toks = corpus("dna", 1 << 16, seed=7)
    t0 = time.perf_counter()
    idx = build_index(toks)
    got = np.asarray(idx.bwt)[: idx.length]
    dt = time.perf_counter() - t0
    s, _ = prepare_tokens(toks, 64)
    want, row = numpy_bwt(s)
    check(np.array_equal(got, want) and int(idx.row) == row,
          "2^16 BWT != numpy suffix-sort BWT")
    log(f"small: 2^16 DNA BWT == numpy reference (build {dt:.2f}s incl. "
        f"compile); numpy reference == bwt_naive at 2^10")


def phase_main(dev, args, icfg, clock: CompileClock) -> None:
    import jax
    import jax.numpy as jnp

    from repro.core import fm_index, keypack
    from repro.core.dist_suffix_array import DistSAConfig
    from repro.core.pipeline import build_index
    from repro.core.segments import SegmentedIndex
    from repro.data.corpus import corpus
    from repro.kernels import ops
    from repro.serving.engine import FMQueryServer
    from repro.serving.frontend import AsyncQueryFrontend

    n = 1 << args.n_log2
    toks = corpus("dna", n, seed=SEED)
    tail = corpus("dna", TAIL, seed=SEED + 1)
    sigma = int(max(toks.max(), tail.max())) + 1
    knobs = dict(engine=icfg.engine, capacity_factor=icfg.capacity_factor,
                 qgram=icfg.qgram, qgram_words=icfg.qgram_words,
                 discard=icfg.discard)

    # reference BWT first (compare engine = lax.sort), kept on the host
    t0 = time.perf_counter()
    ref = build_index(toks, sample_rate=icfg.sample_rate, sigma=sigma,
                      sa_sample_rate=0,
                      sa_config=DistSAConfig(local_sort="compare", **knobs))
    ref_bwt, ref_row = np.asarray(ref.bwt), int(ref.row)
    del ref
    log(f"main: compare-engine build of {n} tokens {time.perf_counter() - t0:.1f}s"
        f" (incl. compile); {memory(dev)}")

    # the served catalog, built the way launch/serve.py builds segments
    cat = SegmentedIndex.from_config(sigma, icfg)
    t0 = time.perf_counter()
    seg = cat.append(toks)
    fm = seg.index.fm
    jax.block_until_ready(fm.fused if fm.bits else fm.bwt)
    build_s = time.perf_counter() - t0
    engine = seg.index.build_stats.local_sort
    log(f"main: default-engine ({engine}) build of {n} tokens {build_s:.1f}s "
        f"(incl. compile); n_blocks={fm.n_blocks} bits={fm.bits} "
        f"sigma={fm.sigma}; {memory(dev)}")
    check(engine == "radix", f"build engine {engine!r}, expected radix")
    check(fm.bits == 4, f"DNA index not bit-packed (bits={fm.bits})")
    check(np.array_equal(np.asarray(seg.index.bwt), ref_bwt)
          and int(seg.index.row) == ref_row,
          "radix-engine BWT != compare-engine BWT")
    log("main: radix BWT == compare BWT")
    del ref_bwt

    # the kernels are really in the compiled programs
    q = icfg.qgram_words
    _, fpw, bits = keypack.qgram_params(fm.sigma, q)
    kb = min(32, fpw * bits)
    word = jax.ShapeDtypeStruct((fm.length,), jnp.uint32)
    sort_prog = ops.radix_sort.lower(
        (word,) * q + (jax.ShapeDtypeStruct((fm.length,), jnp.int32),),
        num_keys=q, key_bits=(kb,) * q).compile()
    pats_sds = jax.ShapeDtypeStruct((icfg.query_batch, icfg.query_len),
                                    jnp.int32)
    t0 = time.perf_counter()
    count_prog = fm_index.count.lower(fm, pats_sds).compile()
    log(f"main: count step compile {time.perf_counter() - t0:.2f}s")
    check(has_kernel(sort_prog), "radix sort program has no Mosaic kernel")
    check(has_kernel(count_prog), "count program has no Mosaic kernel")
    log("main: tpu_custom_call in the radix sort and the count step")

    rng = np.random.default_rng(SEED)
    scan = TextScan(toks)
    # global coordinates: the tail is a second document right after the
    # main text
    both = TextScan(np.concatenate([toks, tail]), cuts=(len(toks),))
    k = icfg.locate_k
    server = FMQueryServer.from_config(cat, icfg)
    n_req = icfg.query_batch * BATCHES
    # first the patterns found nowhere, alternately counted and located
    pats = absent_patterns(rng, both, ABSENT, icfg.query_len)
    pats += sample_patterns(rng, toks, n_req - ABSENT, 8, icfg.query_len)
    kinds = ["count" if i < ABSENT and i % 2 == 0
             else "locate" if i < ABSENT or i % 5 == 4 else "count"
             for i in range(n_req)]
    tail_pats = sample_patterns(rng, tail, icfg.query_batch, 8,
                                icfg.query_len)
    tail_kinds = ["locate" if i % 5 == 4 else "count"
                  for i in range(len(tail_pats))]

    with AsyncQueryFrontend.from_config(server, icfg) as fe:
        serve_round(fe, pats, kinds, "serve 1 segment, warm-up (compile)")
        res1 = serve_round(fe, pats, kinds, "serve 1 segment")
        check_answers(res1, pats, kinds, scan, k, CHECKED, "1 segment")
        log(f"serve: answers match numpy ({CHECKED} per kind checked, "
            f"all located positions verified); {memory(dev)}")

        info = fe.append(tail).result()
        log(f"append: {info}")
        check(info["segments"] == 2 and not info["compaction_quarantined"],
              f"append did not leave two healthy segments: {info}")
        serve_round(fe, pats + tail_pats, kinds + tail_kinds,
                    "serve 2 segments, warm-up (compile)")
        res2 = serve_round(fe, pats + tail_pats, kinds + tail_kinds,
                           "serve 2 segments")
        check_answers(res2, pats + tail_pats, kinds + tail_kinds, both, k,
                      CHECKED, "2 segments")
        m = fe.metrics()
    check(m["worker_restarts"] == 0 and m["quarantined_segments"] == 0,
          f"frontend took a recovery path: {m}")

    walks = ("_merge_walk", "_kway_walk")
    walk_c0 = sum(clock.by_fun[f"jit({w})"] for w in walks)
    with timed_walks(walks) as walk_wall:
        c0, t0 = clock.total(), time.perf_counter()
        merges = cat.compact(min_tokens=cat.total_tokens + 1,
                             strategy="merge")
        compact_s = time.perf_counter() - t0
    compile_s = clock.total() - c0
    walk_compile = sum(clock.by_fun[f"jit({w})"] for w in walks) - walk_c0
    walk_s = sum(walk_wall) - walk_compile
    plan = cat.compact_last_plan
    log(f"compact: {merges} merge, strategy={plan['strategy']} "
        f"walk_steps={plan['actual_walk_steps']} in {compact_s}s, of which "
        f"compile {compile_s}s; the walk {sum(walk_wall)}s, of which compile "
        f"{walk_compile}s, so the walk alone {walk_s}s; {memory(dev)}")
    check(merges == 1 and len(cat.segments) == 1, "compaction did not merge")
    check(plan["strategy"] in ("pairwise", "kway"),
          f"compaction fell back to {plan['strategy']}: {plan['reason']}")

    with AsyncQueryFrontend.from_config(server, icfg) as fe:
        serve_round(fe, pats + tail_pats, kinds + tail_kinds,
                    "serve merged, warm-up (compile)")
        res3 = serve_round(fe, pats + tail_pats, kinds + tail_kinds,
                           "serve merged")
        m = fe.metrics()
    check(m["worker_restarts"] == 0, f"frontend restarted: {m}")
    for a, b, kind in zip(res2, res3, kinds + tail_kinds):
        check(a.count == b.count, "a count changed across the compaction")
        if kind == "locate" and a.count < k:
            check(np.array_equal(np.sort(a.positions), np.sort(b.positions)),
                  "located positions changed across the compaction")
    check_answers(res3, pats + tail_pats, kinds + tail_kinds, both, k,
                  CHECKED, "merged")
    log(f"compact: answers unchanged across the merge;"
        f" {memory(dev)}")


def phase_proteins(dev, icfg) -> None:
    import jax
    import jax.numpy as jnp

    from repro.core import fm_index
    from repro.core.pipeline import build_index
    from repro.data.corpus import corpus
    from repro.serving.engine import FMQueryServer

    toks = corpus("proteins", PROTEINS, seed=SEED)
    t0 = time.perf_counter()
    idx = build_index(toks, sample_rate=icfg.sample_rate,
                      sa_sample_rate=icfg.sa_sample_rate)
    jax.block_until_ready(idx.fm.bwt)
    log(f"proteins: build of {len(toks)} tokens {time.perf_counter() - t0:.1f}s"
        f" (incl. compile); sigma={idx.sigma} bits={idx.fm.bits}")
    check(idx.fm.bits == 0, "proteins index unexpectedly bit-packed")
    prog = fm_index.count.lower(
        idx.fm, jax.ShapeDtypeStruct((icfg.query_batch, icfg.query_len),
                                     jnp.int32)).compile()
    check(has_kernel(prog), "unpacked count program has no Mosaic kernel")
    rng = np.random.default_rng(SEED)
    scan = TextScan(toks)
    pats = absent_patterns(rng, scan, ABSENT, icfg.query_len)
    pats += sample_patterns(rng, toks, icfg.query_batch - ABSENT, 8,
                            icfg.query_len)
    server = FMQueryServer.from_config(idx, icfg)
    server.count(pats)  # compile
    t0 = time.perf_counter()
    got = server.count(pats)
    dt = time.perf_counter() - t0
    for i in range(CHECKED):
        want = len(scan.positions(pats[i]))
        check(int(got[i]) == want, f"proteins count {got[i]} != {want}")
    log(f"proteins: {len(pats)} counts in {dt:.3f}s ({len(pats) / dt:.0f} "
        f"queries/s), unpacked rank kernel; {memory(dev)}")


def phase_four_chips(args, icfg, clock: CompileClock) -> None:
    """``build_index`` over a 4-device ``parts`` mesh vs a one-chip build
    of the same prepared text; dist_count/dist_locate vs one-chip answers."""
    import jax

    from repro.core.dist_suffix_array import DistSAConfig
    from repro.core.pipeline import (build_index, build_index_prepared,
                                     prepare_tokens)
    from repro.data.corpus import corpus

    devs = jax.devices()
    check(len(devs) >= 4, f"--chips 4 needs 4 devices, found {len(devs)}")
    devs = devs[:4]
    n = 1 << args.n_log2
    toks = corpus("dna", n, seed=SEED)
    cfg = DistSAConfig(engine=icfg.engine,
                       capacity_factor=icfg.capacity_factor,
                       qgram=icfg.qgram, qgram_words=icfg.qgram_words,
                       discard=icfg.discard, local_sort=icfg.local_sort)
    rng = np.random.default_rng(SEED)
    scan = TextScan(toks)
    pats = absent_patterns(rng, scan, ABSENT, icfg.query_len)
    pats += sample_patterns(rng, toks, icfg.query_batch - ABSENT, 8,
                            icfg.query_len)
    from repro.core.fm_index import PAD

    batch = np.full((len(pats), icfg.query_len), PAD, np.int32)
    for i, p in enumerate(pats):
        batch[i, : len(p)] = p
    k = icfg.locate_k

    # the reference first, on chip 0 alone: run beside the distributed
    # build, its programs wait behind that build's on chip 0 (a 2^26
    # reference took 519.7 s that way on a v5e 2x2)
    s, sigma = prepare_tokens(toks, 4 * icfg.sample_rate)
    with jax.default_device(devs[0]):
        t0, c0 = time.perf_counter(), clock.total()
        one = build_index_prepared(s, sigma, sample_rate=icfg.sample_rate,
                                   sa_config=cfg,
                                   sa_sample_rate=icfg.sa_sample_rate)
        one_bwt, one_row = np.asarray(one.bwt), int(one.row)
        log(f"4 chips: one-chip build of {len(s)} tokens "
            f"{time.perf_counter() - t0}s, of which compile "
            f"{clock.total() - c0}s")
        one_counts = np.asarray(one.count(batch))
        one_pos, one_lc = (np.asarray(a) for a in one.locate(batch, k))
        del one
    mesh = jax.make_mesh((4,), ("parts",), devices=devs)
    t0, c0 = time.perf_counter(), clock.total()
    dist = build_index(toks, mesh, sample_rate=icfg.sample_rate,
                       sa_config=cfg, sa_sample_rate=icfg.sa_sample_rate)
    jax.block_until_ready(dist.bwt)
    log(f"4 chips: distributed build {time.perf_counter() - t0}s, of which "
        f"compile {clock.total() - c0}s; engine {cfg.engine}")
    check(np.array_equal(np.asarray(dist.bwt), one_bwt)
          and int(dist.row) == one_row,
          "4-chip BWT != one-chip BWT of the same text")
    log("4 chips: BWT bit-identical to the one-chip build")
    counts = np.asarray(dist.count(batch))
    check(np.array_equal(counts, one_counts), "dist_count != one-chip count")
    pos, lc = (np.asarray(a) for a in dist.locate(batch, k))
    check(np.array_equal(lc, one_lc), "dist_locate counts differ")
    for i, p in enumerate(pats):
        got = np.sort(pos[i, : lc[i]])
        if counts[i] <= k:
            check(np.array_equal(got, np.sort(one_pos[i, : one_lc[i]])),
                  "dist_locate positions differ")
        win = toks[got[:, None] + np.arange(len(p))[None, :]]
        check(bool((win == p[None, :]).all()),
              "a dist_locate position does not match")
    for i in range(CHECKED):
        want = scan.positions(pats[i])
        check(counts[i] == len(want), "dist_count != numpy scan")
        if len(want) <= k:
            check(np.array_equal(np.sort(pos[i, : lc[i]]), want),
                  "dist_locate positions != numpy scan")
    log("4 chips: dist_count/dist_locate == one-chip answers and numpy")
    share = len(s) * 4 / 4  # one int32 BWT shard per chip, at least
    for d in devs:
        st = d.memory_stats() or {}
        used, peak = st.get("bytes_in_use", 0), st.get("peak_bytes_in_use", 0)
        log(f"4 chips: {d} bytes_in_use={used} peak_bytes_in_use={peak}")
        check(used >= share, f"{d} holds less than its BWT shard")


def phase_size_probe(dev, args, icfg) -> None:
    """Build DNA indexes of 2^k tokens, smallest first, until one does not
    fit; each reports the device's high-water mark after its build."""
    import gc

    import jax

    from repro.core.pipeline import build_index
    from repro.data.corpus import corpus

    for k in args.size_probe:
        toks = corpus("dna", 1 << k, seed=SEED)
        t0 = time.perf_counter()
        try:
            idx = build_index(toks, sample_rate=icfg.sample_rate,
                              sa_sample_rate=icfg.sa_sample_rate)
            jax.block_until_ready(idx.fm.fused)
        except Exception as e:  # noqa: BLE001 — the probe reports the limit
            log(f"size probe: 2^{k} failed after "
                f"{time.perf_counter() - t0}s: {type(e).__name__}: "
                f"{str(e)[:300]}")
            return
        log(f"size probe: 2^{k} built in {time.perf_counter() - t0}s "
            f"(incl. compile); {memory(dev)}")
        del idx
        gc.collect()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the distributed build and its one-chip "
                         "comparison")
    ap.add_argument("--n-log2", type=int, default=26,
                    help="DNA tokens of the main index, log2")
    ap.add_argument("--size-probe", type=int, nargs="+", default=None,
                    metavar="LOG2",
                    help="only build DNA indexes of these sizes and report "
                         "peak memory")
    args = ap.parse_args(argv)

    if not (SRC / "repro").is_dir():
        print(f"chip_smoke: repository sources not found under {SRC}",
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(SRC))
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {dev.platform})",
              file=sys.stderr)
        return 1
    from repro.configs.bwt_index import CONFIG as icfg
    from repro.launch import compile_cache

    log(f"device: {dev.device_kind} x{len(devices)}, jax {jax.__version__}, "
        f"compile cache {compile_cache.enable()}")
    clock = CompileClock()
    t_start = time.perf_counter()
    try:
        if args.size_probe:
            with jax.default_device(dev):
                phase_size_probe(dev, args, icfg)
        elif args.chips == 4:
            phase_four_chips(args, icfg, clock)
        else:
            with jax.default_device(dev):
                phase_small_build(dev)
                phase_main(dev, args, icfg, clock)
                phase_proteins(dev, icfg)
    except CheckFailed as e:
        print(f"chip_smoke: CHECK FAILED: {e}", file=sys.stderr)
        return 1
    log(f"total {time.perf_counter() - t_start}s, of which compile "
        f"{clock.total()}s; persistent cache {compile_cache.stats()}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
