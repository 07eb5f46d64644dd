"""FM-index query engine benchmark: packed-Pallas rank path vs jnp reference.

Compares the production query engine (bit-packed fused layout dispatched
through kernels/ops — Pallas popcount kernel on TPU, vectorised jnp
popcount fallback on CPU) against the unpacked jnp reference layout on
identical query batches, for both ``count`` (backward search) and
``locate`` (SA-sample LF-walk), plus a rank-kernel microbenchmark.  On real
TPU the fused kernel's single-row DMA per query is the win; off-TPU the
packed fallback still reads 8-16x fewer bytes per in-block count.

``--smoke`` runs a seconds-scale variant with parity assertions (CI).
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import alphabet as al
from repro.core.bwt import bwt_from_sa
from repro.core.fm_index import (
    PAD,
    build_fm_index,
    count,
    locate,
    locate_naive,
)
from repro.core.pipeline import prepare_tokens
from repro.core.suffix_array import suffix_array
from repro.data.corpus import corpus


def _bench(fn, *args, reps=5):
    jax.block_until_ready(fn(*args))
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ts.append(time.perf_counter() - t0)
    return min(ts)


def build_indexes(n, sample_rate=64, sa_sample_rate=32):
    """(packed index, unpacked reference index, text) over the same corpus —
    one SA/BWT build shared by both layouts."""
    toks = corpus("dna", n - 1)
    s, sigma = prepare_tokens(toks, sample_rate)
    s_dev = jnp.asarray(s)
    sa = suffix_array(s_dev, sigma)
    bwt_arr, row = bwt_from_sa(s_dev, sa)
    kw = dict(sa=sa, sa_sample_rate=sa_sample_rate)
    fm_packed = build_fm_index(bwt_arr, row, sigma, sample_rate, **kw)
    fm_ref = build_fm_index(bwt_arr, row, sigma, sample_rate, pack=False, **kw)
    assert fm_packed.bits, "dna corpus should bit-pack"
    return fm_packed, fm_ref, s, sa


def _query_batch(rng, s, B, pattern_len):
    pats = np.full((B, pattern_len), PAD, np.int32)
    lens = rng.integers(4, pattern_len + 1, B)
    for i, L in enumerate(lens):
        st = rng.integers(0, len(s) - L - 2)
        pats[i, :L] = s[st : st + L]  # mostly-hitting queries
    return jnp.asarray(pats)


def count_paths(n=1 << 16, batches=(64, 512), pattern_len=16, reps=5):
    """Packed vs reference ``count`` on identical batches; asserts parity."""
    fm_packed, fm_ref, s, _sa = build_indexes(n)
    rng = np.random.default_rng(0)
    rows = []
    for B in batches:
        pats = _query_batch(rng, s, B, pattern_len)
        got_p = np.asarray(count(fm_packed, pats))
        got_r = np.asarray(count(fm_ref, pats))
        assert np.array_equal(got_p, got_r), "packed/reference count mismatch"
        t_packed = _bench(lambda p: count(fm_packed, p), pats, reps=reps)
        t_ref = _bench(lambda p: count(fm_ref, p), pats, reps=reps)
        rows.append({
            "batch": B,
            "packed_us": t_packed * 1e6,
            "ref_us": t_ref * 1e6,
            "speedup": t_ref / t_packed,
            "qps_packed": B / t_packed,
        })
    return rows


def locate_path(n=1 << 14, B=32, pattern_len=12, k=64, reps=3):
    """Sampled-SA locate vs the full-SA oracle: exact-match assertion plus
    throughput of the production path."""
    fm_packed, _fm_ref, s, sa = build_indexes(n)
    rng = np.random.default_rng(1)
    pats = _query_batch(rng, s, B, pattern_len)
    pos, cnt = locate(fm_packed, pats, k)
    pos, cnt = np.asarray(pos), np.asarray(cnt)
    for i in range(B):
        want = np.asarray(locate_naive(fm_packed, sa, pats[i]))
        nocc = int((want < fm_packed.n).sum())
        assert cnt[i] == min(nocc, k)
        if nocc <= k:
            assert np.array_equal(pos[i, :nocc], want[:nocc]), i
    t = _bench(lambda p: locate(fm_packed, p, k), pats, reps=reps)
    return {"batch": B, "k": k, "us": t * 1e6, "qps": B / t, "match": True}


def kernel_microbench(nblocks=256, r=64, B=1024, reps=5, smoke=False):
    """rank_packed impls on one fused array: jnp fallback vs interpret-mode
    kernel (parity always; timing skipped for interpret in smoke mode)."""
    from repro.kernels import ops, ref
    from repro.kernels.rank_select import pack_words

    rng = np.random.default_rng(1)
    sigma, bits = 6, 4
    syms = rng.integers(0, sigma, nblocks * r).astype(np.int32)
    words = np.asarray(pack_words(jnp.asarray(syms), bits)).reshape(nblocks, -1)
    onehot = (syms.reshape(nblocks, r)[:, :, None] == np.arange(sigma)).sum(1)
    occ = np.concatenate(
        [np.zeros((1, sigma), np.int64), np.cumsum(onehot, 0)]
    )[:nblocks].astype(np.int32)
    fused = jnp.asarray(np.concatenate([occ, words], axis=1))
    bidx = jnp.asarray(rng.integers(0, nblocks, B).astype(np.int32))
    c = jnp.asarray(rng.integers(0, sigma, B).astype(np.int32))
    cut = jnp.asarray(rng.integers(0, r + 1, B).astype(np.int32))

    args = (fused, bidx, c, cut)
    kw = dict(bits=bits, sigma=sigma)
    want = np.asarray(ref.rank_packed_ref(*args, **kw))
    got_jnp = np.asarray(ops.rank_packed(*args, **kw, impl="jnp"))
    got_int = np.asarray(ops.rank_packed(*args, **kw, impl="interpret"))
    match = np.array_equal(want, got_jnp) and np.array_equal(want, got_int)
    t_jnp = _bench(lambda *a: ops.rank_packed(*a, **kw, impl="jnp"),
                   *args, reps=reps)
    t_int = (None if smoke else
             _bench(lambda *a: ops.rank_packed(*a, **kw, impl="interpret"),
                    *args, reps=max(1, reps // 2)))
    return {"jnp_us": t_jnp * 1e6,
            "interpret_us": None if t_int is None else t_int * 1e6,
            "match": bool(match)}


def main(argv=None):
    from repro.launch import compile_cache

    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="seconds-scale CI variant with parity assertions")
    args = ap.parse_args(argv)

    if args.smoke:
        # n stays at the full 1<<16: below ~64Ki symbols the whole unpacked
        # index is cache-resident and the packed layout has nothing to save
        count_kw = dict(n=1 << 16, batches=(64,), pattern_len=12, reps=3)
        locate_kw = dict(n=1 << 10, B=8, pattern_len=6, k=1 << 10, reps=1)
        kernel_kw = dict(nblocks=32, r=64, B=64, reps=2, smoke=True)
    else:
        count_kw, locate_kw, kernel_kw = {}, {}, {}

    print("fmbench,metric,value,derived")
    for r in count_paths(**count_kw):
        print(
            f"fmbench,count_b{r['batch']},{r['packed_us']:.0f},"
            f"ref_us={r['ref_us']:.0f};speedup={r['speedup']:.2f}x;"
            f"qps={r['qps_packed']:.0f}"
        )
    loc = locate_path(**locate_kw)
    print(
        f"fmbench,locate_b{loc['batch']}_k{loc['k']},{loc['us']:.0f},"
        f"qps={loc['qps']:.0f};match={loc['match']}"
    )
    k = kernel_microbench(**kernel_kw)
    extra = ("" if k["interpret_us"] is None
             else f";interpret_us={k['interpret_us']:.0f}")
    print(
        f"fmbench,rank_packed,{k['jnp_us']:.0f},"
        f"match={k['match']}{extra}"
    )
    print("fmbench OK")


if __name__ == "__main__":
    main()
