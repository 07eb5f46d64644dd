"""Per-kernel shape/dtype sweeps: every Pallas kernel (interpret mode on
CPU) against its ref.py pure-jnp oracle."""

import numpy as np
import jax.numpy as jnp
import pytest

from repro.kernels import ops, ref


class TestCharHistogram:
    @pytest.mark.parametrize("n", [1024, 4096, 5000, 12345])
    @pytest.mark.parametrize("sigma", [6, 22, 257])
    def test_sweep(self, n, sigma):
        rng = np.random.default_rng(n + sigma)
        toks = rng.integers(0, sigma, n).astype(np.int32)
        got = ops.char_histogram(jnp.asarray(toks), sigma)
        want = ref.char_histogram_ref(jnp.asarray(toks), sigma)
        assert np.array_equal(np.asarray(got), np.asarray(want))

    def test_block_rows_variants(self):
        rng = np.random.default_rng(0)
        toks = rng.integers(0, 17, 8192).astype(np.int32)
        for br in (1, 4, 16):
            got = ops.char_histogram(jnp.asarray(toks), 17, block_rows=br)
            want = ref.char_histogram_ref(jnp.asarray(toks), 17)
            assert np.array_equal(np.asarray(got), np.asarray(want))


class TestRerankScan:
    @pytest.mark.parametrize("n", [512, 2048, 3000])
    @pytest.mark.parametrize("vals", [3, 50, 100000])
    def test_sweep(self, n, vals):
        rng = np.random.default_rng(n + vals)
        r1 = rng.integers(0, vals, n).astype(np.int32)
        r2 = rng.integers(-1, vals, n).astype(np.int32)
        order = np.lexsort((r2, r1))
        r1, r2 = r1[order], r2[order]
        got_r, got_g = ops.rerank_scan(jnp.asarray(r1), jnp.asarray(r2))
        want_r, want_g = ref.rerank_scan_ref(jnp.asarray(r1), jnp.asarray(r2))
        assert np.array_equal(np.asarray(got_r), np.asarray(want_r))
        assert int(got_g) == int(want_g)

    def test_all_equal_pairs(self):
        r1 = np.zeros(1024, np.int32)
        r2 = np.zeros(1024, np.int32)
        got_r, got_g = ops.rerank_scan(jnp.asarray(r1), jnp.asarray(r2))
        assert int(got_g) == 1
        assert np.array_equal(np.asarray(got_r), np.zeros(1024, np.int32))

    def test_all_distinct(self):
        r1 = np.arange(1024, dtype=np.int32)
        r2 = np.zeros(1024, np.int32)
        got_r, got_g = ops.rerank_scan(jnp.asarray(r1), jnp.asarray(r2))
        assert int(got_g) == 1024
        assert np.array_equal(np.asarray(got_r), r1)

    @pytest.mark.parametrize("block", [256, 512, 1024])
    def test_block_sizes(self, block):
        rng = np.random.default_rng(block)
        r1 = np.sort(rng.integers(0, 9, 4096)).astype(np.int32)
        r2 = rng.integers(0, 9, 4096).astype(np.int32)
        order = np.lexsort((r2, r1))
        r1, r2 = r1[order], r2[order]
        got_r, got_g = ops.rerank_scan(jnp.asarray(r1), jnp.asarray(r2),
                                       block=block)
        want_r, want_g = ref.rerank_scan_ref(jnp.asarray(r1), jnp.asarray(r2))
        assert np.array_equal(np.asarray(got_r), np.asarray(want_r))
        assert int(got_g) == int(want_g)


class TestRadixHist:
    @pytest.mark.parametrize("shift", [0, 8, 16, 24])
    @pytest.mark.parametrize("n,block", [(2048, 1024), (8192, 2048), (4096, 128)])
    def test_sweep(self, shift, n, block):
        rng = np.random.default_rng(shift + n)
        keys = rng.integers(-(2**31), 2**31 - 1, n, dtype=np.int64).astype(np.int32)
        got = ops.radix_hist(jnp.asarray(keys), shift, block=block)
        want = ref.radix_hist_ref(jnp.asarray(keys), shift, block)
        assert np.array_equal(np.asarray(got), np.asarray(want))

    def test_histogram_sums_to_block(self):
        rng = np.random.default_rng(5)
        keys = rng.integers(0, 1000, 4096).astype(np.int32)
        got = np.asarray(ops.radix_hist(jnp.asarray(keys), 0, block=1024))
        assert (got.sum(axis=1) == 1024).all()


class TestRadixSort:
    """The full hist->scan->scatter LSD pipeline vs XLA's stable sort, in
    both the pure-jnp fallback and kernel interpret mode."""

    @pytest.mark.parametrize("impl", ["jnp", "interpret"])
    @pytest.mark.parametrize("n,bits", [(2048, 29), (5000, 17), (1024, 32)])
    def test_single_word(self, impl, n, bits):
        rng = np.random.default_rng(n + bits)
        keys = rng.integers(0, 1 << min(bits, 48), n).astype(np.uint64)
        keys = (keys & ((1 << bits) - 1)).astype(np.uint32)
        pay = np.arange(n, dtype=np.int32)
        got = ops.radix_sort((jnp.asarray(keys), jnp.asarray(pay)),
                             num_keys=1, key_bits=(bits,), impl=impl)
        want = ref.radix_sort_ref((jnp.asarray(keys), jnp.asarray(pay)), 1)
        for g, w in zip(got, want):
            assert np.array_equal(np.asarray(g), np.asarray(w))

    @pytest.mark.parametrize("impl", ["jnp", "interpret"])
    def test_two_word_stability(self, impl):
        """Heavy ties across both words: stability must match lax.sort."""
        rng = np.random.default_rng(9)
        n = 3000
        hi = rng.integers(0, 7, n).astype(np.uint32)
        lo = rng.integers(0, 11, n).astype(np.uint32)
        pay = np.arange(n, dtype=np.int32)
        args = (jnp.asarray(hi), jnp.asarray(lo), jnp.asarray(pay))
        got = ops.radix_sort(args, num_keys=2, key_bits=(3, 4), impl=impl)
        want = ref.radix_sort_ref(args, 2)
        for g, w in zip(got, want):
            assert np.array_equal(np.asarray(g), np.asarray(w))

    @pytest.mark.parametrize("impl", ["jnp", "interpret"])
    def test_saturated_keys_with_padding(self, impl):
        """Real keys equal to the field pad + an n that forces block
        padding: pads must stay strictly after the saturated real keys."""
        n = 1500  # not a multiple of the kernel block
        keys = np.full(n, (1 << 12) - 1, np.uint32)  # all saturate the field
        pay = np.arange(n, dtype=np.int32)
        got = ops.radix_sort((jnp.asarray(keys), jnp.asarray(pay)),
                             num_keys=1, key_bits=(12,), impl=impl)
        assert np.array_equal(np.asarray(got[0]), keys)
        assert np.array_equal(np.asarray(got[1]), pay)  # stable: untouched


class TestRankSelect:
    @pytest.mark.parametrize("nblocks,r,B", [(8, 64, 16), (32, 128, 64), (4, 256, 7)])
    @pytest.mark.parametrize("sigma", [5, 257])
    def test_sweep(self, nblocks, r, B, sigma):
        rng = np.random.default_rng(nblocks * r + B + sigma)
        bwt = rng.integers(0, sigma, (nblocks, r)).astype(np.int32)
        bidx = rng.integers(0, nblocks, B).astype(np.int32)
        c = rng.integers(0, sigma, B).astype(np.int32)
        cut = rng.integers(0, r + 1, B).astype(np.int32)
        args = [jnp.asarray(x) for x in (bwt, bidx, c, cut)]
        got = ops.rank_unpacked(*args, impl="interpret")
        want = ref.rank_select_ref(*args)
        assert np.array_equal(np.asarray(got), np.asarray(want))

    def test_full_block_cutoff(self):
        bwt = np.full((2, 64), 3, np.int32)
        got = ops.rank_unpacked(
            jnp.asarray(bwt),
            jnp.asarray([0, 1], np.int32),
            jnp.asarray([3, 3], np.int32),
            jnp.asarray([64, 0], np.int32),
            impl="interpret",
        )
        assert list(np.asarray(got)) == [64, 0]


class TestKernelIntegration:
    def test_rerank_consistent_with_suffix_array_round(self):
        """The rerank kernel reproduces one prefix-doubling re-rank."""
        from repro.core.suffix_array import rerank_from_sorted

        rng = np.random.default_rng(9)
        r1 = np.sort(rng.integers(0, 20, 2048)).astype(np.int32)
        r2 = rng.integers(-1, 20, 2048).astype(np.int32)
        order = np.lexsort((r2, r1))
        r1, r2 = r1[order], r2[order]
        kr, kg = ops.rerank_scan(jnp.asarray(r1), jnp.asarray(r2))
        cr, call_distinct = rerank_from_sorted(jnp.asarray(r1), jnp.asarray(r2))
        assert np.array_equal(np.asarray(kr), np.asarray(cr))
        assert (int(kg) == 2048) == bool(call_distinct)

    def test_char_histogram_matches_initial_ranks(self):
        """Kernel histogram + exclusive cumsum == the paper's Occ table."""
        from repro.core.suffix_array import initial_ranks

        rng = np.random.default_rng(10)
        s = rng.integers(0, 6, 4096).astype(np.int32)
        hist = np.asarray(ops.char_histogram(jnp.asarray(s), 6))
        occ = np.cumsum(hist) - hist
        want = np.asarray(initial_ranks(jnp.asarray(s), 6))
        assert np.array_equal(occ[s], want)
