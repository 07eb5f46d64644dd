"""Query-engine tests: packed-rank parity, locate vs the full-SA oracle,
and PAD / out-of-alphabet edge cases, across alphabet sizes and layouts.

The packed rank path has three implementations (Pallas kernel, its
interpret mode, and the jnp popcount fallback) plus a naive unpack-and-scan
oracle in kernels/ref.py; they must agree bit-for-bit on random batches.
"""

import dataclasses

import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import alphabet as al
from repro.core.bwt import bwt
from repro.core.fm_index import (
    PAD,
    build_fm_index,
    count,
    count_naive,
    locate,
    locate_naive,
)
from repro.core.suffix_array import suffix_array
from repro.kernels import ops, ref
from repro.kernels.rank_select import (
    pack_words,
    packed_bits,
    place_rows,
    rows_placed,
)


def _fused_fixture(rng, bits, sigma, nblocks, r):
    """Random fused [checkpoint | packed words] array + raw symbols."""
    syms = rng.integers(0, sigma, nblocks * r).astype(np.int32)
    words = np.asarray(pack_words(jnp.asarray(syms), bits)).reshape(nblocks, -1)
    onehot = (syms.reshape(nblocks, r)[:, :, None] == np.arange(sigma)).sum(1)
    occ = np.concatenate(
        [np.zeros((1, sigma), np.int64), np.cumsum(onehot, 0)]
    )[:nblocks].astype(np.int32)
    return jnp.asarray(np.concatenate([occ, words], axis=1)), syms, occ


class TestPackedRankParity:
    @pytest.mark.parametrize("bits,sigma,r", [
        (2, 4, 16), (2, 3, 32), (4, 16, 64), (4, 5, 8), (4, 6, 64),
    ])
    def test_all_impls_match_truth(self, bits, sigma, r):
        rng = np.random.default_rng(bits * 100 + sigma + r)
        nblocks = 17
        fused, syms, occ = _fused_fixture(rng, bits, sigma, nblocks, r)
        B = 53  # deliberately not a multiple of queries_per_step
        bidx = jnp.asarray(rng.integers(0, nblocks, B).astype(np.int32))
        c = jnp.asarray(rng.integers(0, sigma, B).astype(np.int32))
        cut = jnp.asarray(rng.integers(0, r + 1, B).astype(np.int32))
        want = occ[np.asarray(bidx), np.asarray(c)] + np.array([
            (syms.reshape(nblocks, r)[b, :k] == ch).sum()
            for b, ch, k in zip(np.asarray(bidx), np.asarray(c),
                                np.asarray(cut))
        ])
        kw = dict(bits=bits, sigma=sigma)
        for impl in ("jnp", "interpret"):
            got = np.asarray(
                ops.rank_packed(fused, bidx, c, cut, impl=impl, **kw)
            )
            assert np.array_equal(got, want), impl
        got_ref = np.asarray(ref.rank_packed_ref(fused, bidx, c, cut, **kw))
        assert np.array_equal(got_ref, want)

    def test_packed_bits_selection(self):
        assert packed_bits(4, 16) == 2
        assert packed_bits(5, 64) == 4
        assert packed_bits(16, 64) == 4
        assert packed_bits(17, 64) == 0       # alphabet too large
        assert packed_bits(4, 4) == 0         # r not a multiple of fields/word
        assert packed_bits(5, 8) == 4

    def test_full_and_zero_cutoffs(self):
        rng = np.random.default_rng(0)
        fused, syms, occ = _fused_fixture(rng, 4, 7, 4, 8)
        bidx = jnp.asarray([0, 3], np.int32)
        c = jnp.asarray([2, 2], np.int32)
        for cutv in (0, 8):
            cut = jnp.full((2,), cutv, jnp.int32)
            got = np.asarray(ops.rank_packed(
                fused, bidx, c, cut, bits=4, sigma=7, impl="jnp"))
            want = occ[[0, 3], 2] + (
                syms.reshape(4, 8)[[0, 3], :cutv] == 2).sum(axis=1)
            assert np.array_equal(got, want), cutv


class TestPlacedRowTable:
    """The row table placed in the rank kernel's layout, int32[nb, 1, w],
    answers bit-identically to the logical int32[nb, w] rows."""

    @pytest.mark.parametrize("bits,sigma,r", [(2, 4, 16), (4, 6, 64)])
    @pytest.mark.parametrize("impl", ["jnp", "interpret"])
    def test_rank_packed_placed_matches_ref(self, bits, sigma, r, impl):
        rng = np.random.default_rng(bits + sigma + r)
        nblocks = 19
        fused, _, _ = _fused_fixture(rng, bits, sigma, nblocks, r)
        placed = place_rows(fused)
        assert placed.shape == (nblocks, 1, fused.shape[1])
        assert rows_placed(placed) and not rows_placed(fused)
        B = 37
        bidx = jnp.asarray(rng.integers(0, nblocks, B).astype(np.int32))
        c = jnp.asarray(rng.integers(0, sigma, B).astype(np.int32))
        cut = jnp.asarray(rng.integers(0, r + 1, B).astype(np.int32))
        kw = dict(bits=bits, sigma=sigma)
        want = np.asarray(ref.rank_packed_ref(fused, bidx, c, cut, **kw))
        got = np.asarray(ops.rank_packed(placed, bidx, c, cut, impl=impl,
                                         **kw))
        assert np.array_equal(got, want)

    def test_place_rows_is_idempotent(self):
        fused, _, _ = _fused_fixture(np.random.default_rng(1), 4, 7, 5, 8)
        placed = place_rows(fused)
        assert place_rows(placed) is placed
        assert np.array_equal(np.asarray(placed)[:, 0], np.asarray(fused))

    @pytest.mark.parametrize("sigma_hi,sample_rate", [(4, 32), (16, 16)])
    def test_count_and_locate_of_built_index(self, sigma_hi, sample_rate):
        rng = np.random.default_rng(40 + sigma_hi)
        fm, s, sa = _build(rng, sigma_hi, 500, sample_rate)
        assert fm.bits and rows_placed(fm.fused)
        assert fm.fused.shape == (fm.n_blocks, 1,
                                  fm.sigma + sample_rate * fm.bits // 32)
        flat = dataclasses.replace(
            fm, fused=jnp.asarray(np.asarray(fm.fused)[:, 0]))
        unpacked, _, _ = _build(np.random.default_rng(40 + sigma_hi),
                                sigma_hi, 500, sample_rate, pack=False)
        B, L = 16, 5
        pats = np.full((B, L), PAD, np.int32)
        lens = rng.integers(1, L + 1, B)
        for i, m in enumerate(lens):
            pats[i, :m] = rng.integers(1, max(2, sigma_hi), m)
        pats = jnp.asarray(pats)
        got = np.asarray(count(fm, pats))
        assert np.array_equal(got, np.asarray(count(flat, pats)))
        assert list(got) == [count_naive(s, np.asarray(pats)[i, :lens[i]])
                             for i in range(B)]
        pos, cnt = (np.asarray(x) for x in locate(fm, pats, 8))
        pos_u, cnt_u = (np.asarray(x) for x in locate(unpacked, pats, 8))
        assert np.array_equal(pos, pos_u) and np.array_equal(cnt, cnt_u)


def _build(rng, sigma_hi, n, sample_rate, srate=8, pack=None):
    toks = rng.integers(1, max(2, sigma_hi), n).astype(np.int32)
    s = al.append_sentinel(toks)
    sigma = al.sigma_of(s)
    b, row = bwt(jnp.asarray(s), sigma)
    sa = suffix_array(jnp.asarray(s), sigma)
    fm = build_fm_index(b, row, sigma, sample_rate, sa=sa,
                        sa_sample_rate=srate, pack=pack)
    return fm, s, sa


class TestCountParityAcrossLayouts:
    @pytest.mark.parametrize("sigma_hi,sample_rate", [
        (2, 16),   # sigma 2 -> 2-bit
        (4, 32),   # sigma 4 or 5 -> 2/4-bit
        (16, 16),  # sigma up to 16 -> 4-bit
        (30, 16),  # sigma > 16 -> unpacked fallback
    ])
    def test_packed_equals_unpacked_equals_naive(self, sigma_hi, sample_rate):
        rng = np.random.default_rng(sigma_hi + sample_rate)
        fm, s, _sa = _build(rng, sigma_hi, 400, sample_rate)
        fm_ref, _, _ = _build(
            np.random.default_rng(sigma_hi + sample_rate), sigma_hi, 400,
            sample_rate, pack=False,
        )
        B, L = 20, 6
        pats = np.full((B, L), PAD, np.int32)
        lens = rng.integers(1, L + 1, B)
        for i, m in enumerate(lens):
            pats[i, :m] = rng.integers(1, max(2, sigma_hi), m)
        got = np.asarray(count(fm, jnp.asarray(pats)))
        got_ref = np.asarray(count(fm_ref, jnp.asarray(pats)))
        want = [count_naive(s, pats[i, :lens[i]]) for i in range(B)]
        assert list(got) == want
        assert list(got_ref) == want


class TestLocate:
    @pytest.mark.parametrize("sigma_hi", [2, 4, 16])
    @pytest.mark.parametrize("srate", [4, 16])
    def test_matches_full_sa_oracle(self, sigma_hi, srate):
        rng = np.random.default_rng(sigma_hi * 10 + srate)
        n = 300
        fm, s, sa = _build(rng, sigma_hi, n, 16, srate=srate)
        B, L = 12, 5
        pats = np.full((B, L), PAD, np.int32)
        lens = rng.integers(1, L + 1, B)
        for i, m in enumerate(lens):
            pats[i, :m] = rng.integers(1, max(2, sigma_hi), m)
        k = fm.n  # k >= every count: full parity with the sorted oracle
        pos, cnt = locate(fm, jnp.asarray(pats), k)
        pos, cnt = np.asarray(pos), np.asarray(cnt)
        for i in range(B):
            want = np.asarray(locate_naive(fm, sa, jnp.asarray(pats[i])))
            nocc = int((want < fm.n).sum())
            assert cnt[i] == min(nocc, k)
            assert np.array_equal(pos[i, :nocc], want[:nocc]), i
            assert (pos[i, nocc:] == fm.n).all()

    def test_first_k_are_true_occurrences(self):
        """k < count: every returned position is a real occurrence."""
        rng = np.random.default_rng(3)
        fm, s, _sa = _build(rng, 3, 500, 16)
        pat = np.full((1, 4), PAD, np.int32)
        pat[0, :2] = [1, 2]
        k = 4
        pos, cnt = locate(fm, jnp.asarray(pat), k)
        pos, cnt = np.asarray(pos)[0], int(np.asarray(cnt)[0])
        assert count_naive(s, [1, 2]) >= cnt == k
        for p in pos:
            assert np.array_equal(s[p : p + 2], [1, 2])

    def test_requires_sa_samples(self):
        rng = np.random.default_rng(4)
        toks = rng.integers(1, 4, 64).astype(np.int32)
        s = al.append_sentinel(toks)
        sigma = al.sigma_of(s)
        b, row = bwt(jnp.asarray(s), sigma)
        fm = build_fm_index(b, row, sigma, 16)  # no sa=
        with pytest.raises(ValueError, match="locate"):
            locate(fm, jnp.zeros((1, 2), jnp.int32), 4)


class TestEdgeCases:
    def _fm(self, pack=None):
        rng = np.random.default_rng(9)
        return _build(rng, 4, 200, 16, pack=pack)

    @pytest.mark.parametrize("pack", [None, False])
    def test_all_pad_pattern_counts_everything(self, pack):
        fm, s, _ = self._fm(pack)
        pats = np.full((1, 5), PAD, np.int32)
        # an all-PAD pattern never narrows the interval: count == n
        assert int(count(fm, jnp.asarray(pats))[0]) == fm.n

    @pytest.mark.parametrize("pack", [None, False])
    def test_out_of_alphabet_empties_interval(self, pack):
        fm, s, _ = self._fm(pack)
        pats = np.full((3, 4), PAD, np.int32)
        pats[0, :2] = [1, 99]     # unknown symbol mid-pattern
        pats[1, :1] = [fm.sigma]  # first symbol outside [1, sigma)
        pats[2, :2] = [0, 1]      # sentinel is not queryable
        got = np.asarray(count(fm, jnp.asarray(pats)))
        assert list(got) == [0, 0, 0]

    @pytest.mark.parametrize("pack", [None, False])
    def test_pad_then_symbol_is_skipped(self, pack):
        """PADs on the right are no-ops, not separators."""
        fm, s, _ = self._fm(pack)
        pats = np.full((1, 6), PAD, np.int32)
        pats[0, :2] = [2, 3]
        want = count_naive(s, [2, 3])
        assert int(count(fm, jnp.asarray(pats))[0]) == want

    def test_locate_out_of_alphabet_returns_empty(self):
        fm, s, _ = self._fm()
        pats = np.full((1, 3), PAD, np.int32)
        pats[0, :2] = [1, 99]
        pos, cnt = locate(fm, jnp.asarray(pats), 8)
        assert int(np.asarray(cnt)[0]) == 0
        assert (np.asarray(pos)[0] == fm.n).all()
