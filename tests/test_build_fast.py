"""Parity suite for the fused-key build engine: every knob combination of
the fast builder (fused pair keys / radix local sort / packed q-gram init /
active-suffix discarding) must reproduce the seed prefix-doubling oracle
bit-for-bit — SA, BWT, and downstream count()/locate().  Plus the pad-key
regression tests for the unsigned packed layout (ISSUE 2 satellites)."""

import numpy as np
import jax.numpy as jnp
import pytest
from jax import lax

from repro.core import alphabet as al
from repro.core import keypack
from repro.core.bwt import bwt_from_sa, bwt_naive
from repro.core.suffix_array import (
    OVERFLOW_RANK,
    build_isa_fast,
    isa_prefix_doubling,
    prefix_max,
    sa_from_isa,
    suffix_array_fast,
)

SIGMAS = [2, 4, 20, 64]
ENGINES = ["compare", "radix"]


def _corpus(sigma_hi: int, n: int, seed: int = 0) -> np.ndarray:
    """Sentinel-terminated text over [1, sigma_hi); repetitive for tiny
    alphabets so several doubling rounds actually execute."""
    rng = np.random.default_rng(seed + sigma_hi + n)
    if sigma_hi <= 2:
        toks = np.ones(n - 1, np.int32)            # unary: worst repetition
    else:
        toks = rng.integers(1, sigma_hi, n - 1).astype(np.int32)
    return al.append_sentinel(toks)


@pytest.mark.parametrize("n,width", [(1, 4), (7, 8), (8, 8), (9, 8),
                                     (1000, 16), (5000, 1024)])
def test_prefix_max_matches_running_max(n, width):
    """The blocked running max equals numpy's flat one, including the
    -1 'no head yet' sentinels the build passes in."""
    rng = np.random.default_rng(n)
    x = rng.integers(-1, 10 * n, n).astype(np.int32)
    got = np.asarray(prefix_max(jnp.asarray(x), width))
    assert np.array_equal(got, np.maximum.accumulate(x))


class TestKeypack:
    @pytest.mark.parametrize("n", [2, 3, 1000, 40000, 65535, 100000])
    def test_roundtrip_and_order(self, n):
        rng = np.random.default_rng(n)
        spec = keypack.pair_spec(n)
        r1 = rng.integers(0, n, 512).astype(np.int32)
        r2 = rng.integers(-1, n, 512).astype(np.int32)
        words = keypack.pack_pairs(jnp.asarray(r1), jnp.asarray(r2), spec)
        u1, u2 = keypack.unpack_pairs(words, spec)
        assert np.array_equal(np.asarray(u1), r1)
        assert np.array_equal(np.asarray(u2), r2)
        # sorting by the packed words == sorting by (r1, r2)
        perm = lax.sort(
            (*words, jnp.arange(512, dtype=jnp.int32)),
            num_keys=spec.words, is_stable=True,
        )[-1]
        want = np.lexsort((np.arange(512), r2, r1))
        assert np.array_equal(np.asarray(perm), want)

    def test_overflow_rank_sorts_first(self):
        """OVERFLOW_RANK (-1) must pack below every real rank2 (the
        shorter-suffix-sorts-first rule survives packing)."""
        for n in (100, 100000):
            spec = keypack.pair_spec(n)
            r1 = jnp.asarray([5, 5, 5], jnp.int32)
            r2 = jnp.asarray([0, OVERFLOW_RANK, n - 1], jnp.int32)
            words = keypack.pack_pairs(r1, r2, spec)
            perm = lax.sort(
                (*words, jnp.arange(3, dtype=jnp.int32)), num_keys=spec.words
            )[-1]
            assert list(np.asarray(perm)) == [1, 0, 2], n

    @pytest.mark.parametrize("n", [2, 1000, 65535, 100000])
    def test_pads_sort_after_real_keys_unsigned(self, n):
        """Regression for the INT_PAD signed-compare bug: fused keys use the
        full uint32 range, so the pad must win an UNSIGNED comparison.  At
        n=65535 the packed field is exactly 32 bits and real keys exceed
        2^31 — int32 ordering would put them before small keys and the old
        INT_PAD (2^31 - 1) would sort before them entirely."""
        spec = keypack.pair_spec(n)
        pads = spec.pad_words()
        r1 = jnp.asarray([0, n - 1], jnp.int32)
        r2 = jnp.asarray([OVERFLOW_RANK, n - 1], jnp.int32)
        words = keypack.pack_pairs(r1, r2, spec)
        for w, p in zip(words, pads):
            assert w.dtype == jnp.uint32
            assert int(jnp.max(w)) < p  # strict: pads sort last
        if n == 65535:
            assert sum(spec.key_bits) == 32
            assert int(jnp.max(words[0])) > 2**31  # breaks signed compare
            assert pads[0] > jnp.iinfo(jnp.int32).max  # INT_PAD would lose

    def test_qgram_saturated_key_unsigned(self):
        """A text of all max-chars saturates the q-gram field (all-ones
        uint32); unsigned order must still rank it above smaller keys."""
        q, fpw, bits = keypack.qgram_params(16, 1)  # 4-bit chars, 8/word
        assert fpw * bits == 32
        hi = jnp.full(40, 15, jnp.int32)   # packs to 0xFFFFFFFF
        lo = jnp.full(40, 1, jnp.int32)
        (vh,) = keypack.qgram_keys_local(hi, fpw, bits, 1)
        (vl,) = keypack.qgram_keys_local(lo, fpw, bits, 1)
        assert int(vh[0]) == 0xFFFFFFFF
        assert bool(jnp.all(vh[: 40 - fpw] > vl[: 40 - fpw]))


class TestFastBuildParity:
    @pytest.mark.parametrize("sigma_hi", SIGMAS)
    @pytest.mark.parametrize("engine", ENGINES)
    def test_knob_matrix(self, sigma_hi, engine):
        """Fused/radix/q-gram/discard builds == the seed oracle, on odd
        (non-power-of-two) lengths."""
        n = 777  # deliberately odd
        s = _corpus(sigma_hi, n)
        sigma = al.sigma_of(s)
        want = np.asarray(isa_prefix_doubling(jnp.asarray(s), sigma))
        for qgram, qw in ((False, 1), (True, 1), (True, 2)):
            for discard in (False, True):
                got, stats = build_isa_fast(
                    jnp.asarray(s), sigma, local_sort=engine,
                    qgram=qgram, qgram_words=qw, discard=discard,
                )
                key = (sigma_hi, engine, qgram, qw, discard)
                assert np.array_equal(np.asarray(got), want), key
                assert stats.rounds_skipped == (
                    keypack.qgram_rounds_skipped(stats.q) if qgram else 0
                )

    def test_bwt_parity_downstream(self):
        """SA -> BWT equality against the naive oracle for the default
        fast configuration."""
        for sigma_hi in (4, 20):
            s = _corpus(sigma_hi, 1001, seed=7)
            sigma = al.sigma_of(s)
            sa, _ = suffix_array_fast(jnp.asarray(s), sigma)
            bwt_arr, row = bwt_from_sa(jnp.asarray(s), sa)
            want_bwt, want_row = bwt_naive(s)
            assert np.array_equal(np.asarray(bwt_arr), want_bwt)
            assert int(row) == want_row

    def test_rounds_and_active_shrink(self):
        """Discarding must shrink the active set monotonically and the
        q-gram init must skip >= 3 doubling rounds on a DNA-like corpus."""
        from repro.data.corpus import corpus

        s = al.append_sentinel(corpus("dna", 4095))
        sigma = al.sigma_of(s)
        isa, stats = build_isa_fast(jnp.asarray(s), sigma)
        assert np.array_equal(
            np.asarray(isa),
            np.asarray(isa_prefix_doubling(jnp.asarray(s), sigma)),
        )
        assert stats.rounds_skipped >= 3
        fr = stats.active_frac
        assert all(a >= b for a, b in zip(fr, fr[1:]))

    def test_count_locate_downstream(self):
        """build_index(fast=True) must serve identical count()/locate()
        to build_index(fast=False) (the seed builder)."""
        from repro.core.fm_index import PAD, count_naive
        from repro.core.pipeline import build_index

        rng = np.random.default_rng(3)
        toks = rng.integers(1, 5, 701).astype(np.int32)
        fast = build_index(toks, sample_rate=8, sa_sample_rate=8)
        slow = build_index(toks, sample_rate=8, sa_sample_rate=8, fast=False)
        assert fast.build_stats is not None and slow.build_stats is None
        B, L = 12, 5
        pats = np.full((B, L), PAD, np.int32)
        lens = rng.integers(1, L + 1, B)
        for b in range(B):
            pats[b, : lens[b]] = rng.integers(1, 5, lens[b])
        got = np.asarray(fast.count(pats))
        assert np.array_equal(got, np.asarray(slow.count(pats)))
        s = al.append_sentinel(toks)
        want = np.array([count_naive(s, pats[b, : lens[b]]) for b in range(B)])
        assert np.array_equal(got, want)
        fp, fc = fast.locate(pats, k=8)
        sp, sc = slow.locate(pats, k=8)
        assert np.array_equal(np.asarray(fp), np.asarray(sp))
        assert np.array_equal(np.asarray(fc), np.asarray(sc))
