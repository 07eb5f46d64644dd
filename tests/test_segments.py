"""Segmented incremental append: exact parity with a monolithic index
(modulo documented boundary semantics), compaction, global coordinates,
catalog save/load, and serving through FMQueryServer."""

import numpy as np
import pytest

from repro.core.fm_index import PAD
from repro.core.pipeline import build_index
from repro.core.segments import SegmentedIndex
from repro.kernels.rank_select import rows_placed
from repro.serving.engine import FMQueryServer

SIGMA = 7  # tokens 1..6
CHUNKS = (300, 150, 75, 512)


def _corpus(rng, sizes=CHUNKS, sigma=SIGMA):
    chunks = [rng.integers(1, sigma, n).astype(np.int32) for n in sizes]
    full = np.concatenate(chunks)
    offsets = np.cumsum([0] + [len(c) for c in chunks])[:-1]
    return chunks, full, offsets


def _patterns(rng, full, B=24, L=5):
    pats = np.full((B, L), PAD, np.int32)
    lens = rng.integers(1, L + 1, B)
    for b in range(B):
        st = rng.integers(0, len(full) - lens[b])
        pats[b, : lens[b]] = full[st : st + lens[b]]
    return pats, lens


def _occurrences(full, pat):
    """(within-segment positions, #cross-boundary) numpy oracle."""
    m = len(pat)
    w = np.lib.stride_tricks.sliding_window_view(full, m)
    return np.nonzero((w == pat).all(axis=1))[0]


def _split_hits(hits, offsets, m):
    cross = [p for p in hits if any(p < o < p + m for o in offsets[1:])]
    within = [p for p in hits if p not in cross]
    return within, cross


@pytest.fixture(scope="module")
def built():
    rng = np.random.default_rng(5)
    chunks, full, offsets = _corpus(rng)
    seg = SegmentedIndex(SIGMA, sample_rate=16, sa_sample_rate=8)
    for c in chunks:
        seg.append(c)
    mono = build_index(full, sample_rate=16, sa_sample_rate=8)
    return rng, chunks, full, offsets, seg, mono


class TestAppendParity:
    def test_count_equals_monolithic_minus_boundary(self, built):
        """The exact boundary-semantics statement: segmented count ==
        monolithic count - occurrences spanning a segment boundary."""
        rng, _, full, offsets, seg, mono = built
        pats, lens = _patterns(rng, full)
        mono_cnt = np.asarray(mono.count(pats), np.int64)
        seg_cnt = seg.count(pats)
        for b in range(pats.shape[0]):
            hits = _occurrences(full, pats[b, : lens[b]])
            _, cross = _split_hits(hits, offsets, lens[b])
            assert seg_cnt[b] == mono_cnt[b] - len(cross), b

    def test_locate_global_positions(self, built):
        """Global positions == the monolithic position set restricted to
        within-segment occurrences."""
        rng, _, full, offsets, seg, _ = built
        pats, lens = _patterns(rng, full)
        k = 2 * len(full)  # no clipping: full position sets must match
        pos, cnt = seg.locate(pats, k)
        for b in range(pats.shape[0]):
            hits = _occurrences(full, pats[b, : lens[b]])
            within, _ = _split_hits(hits, offsets, lens[b])
            assert sorted(pos[b, : cnt[b]]) == sorted(within), b

    def test_offsets_and_catalog(self, built):
        _, chunks, _, offsets, seg, _ = built
        cat = seg.catalog()
        assert [c["offset"] for c in cat] == list(offsets)
        assert [c["n_tokens"] for c in cat] == [len(c) for c in chunks]
        assert seg.total_tokens == sum(len(c) for c in chunks)

    def test_declared_alphabet_enforced(self):
        seg = SegmentedIndex(4)
        with pytest.raises(ValueError, match="alphabet"):
            seg.append(np.array([1, 2, 7], np.int32))
        with pytest.raises(ValueError, match="empty"):
            seg.append(np.array([], np.int32))

    def test_token_absent_from_one_segment(self):
        """A query token present globally but absent from some segment must
        count 0 there (and not match that segment's padding)."""
        seg = SegmentedIndex(10, sample_rate=16, sa_sample_rate=8)
        seg.append(np.full(50, 2, np.int32))       # alphabet {2}
        seg.append(np.array([5] * 60, np.int32))   # alphabet {5}
        pats = np.full((2, 2), PAD, np.int32)
        pats[0, 0] = 5
        pats[1, :] = (2, 5)  # spans only a boundary -> 0 by semantics
        got = seg.count(pats)
        assert got[0] == 60 and got[1] == 0, got


class TestCompact:
    def test_compact_is_answer_invariant(self):
        """Compaction never changes an answer: documents keep their own
        sentinels inside the merged text, so counts AND (unclipped) locate
        sets are identical before and after — under both strategies."""
        rng = np.random.default_rng(9)
        chunks, full, offsets = _corpus(rng)
        for strategy in ("merge", "rebuild"):
            seg = SegmentedIndex(SIGMA, sample_rate=16, sa_sample_rate=8)
            for c in chunks:
                seg.append(c)
            pats, lens = _patterns(rng, full)
            k = 2 * len(full)
            before_c = seg.count(pats)
            before_p, before_k = seg.locate(pats, k)
            assert seg.compact(strategy=strategy) == 1
            assert len(seg.segments) == 1 and seg.segments[0].multi_doc
            assert np.array_equal(seg.count(pats), before_c), strategy
            pos, cnt = seg.locate(pats, k)
            assert np.array_equal(pos, before_p), strategy
            assert np.array_equal(cnt, before_k), strategy
            # and the answers are exactly the within-document hits
            for b in range(pats.shape[0]):
                hits = _occurrences(full, pats[b, : lens[b]])
                within, _ = _split_hits(hits, offsets, lens[b])
                assert sorted(pos[b, : cnt[b]]) == sorted(within), b

    def test_merge_equals_rebuild_bit_identical(self):
        """The BWT-merge strategy must produce the very same FMIndex the
        raw-token rebuild produces — every array, every aux field."""
        rng = np.random.default_rng(19)
        chunks, _, _ = _corpus(rng)
        segs = {}
        for strategy in ("merge", "rebuild"):
            seg = SegmentedIndex(SIGMA, sample_rate=16, sa_sample_rate=8)
            for c in chunks:
                seg.append(c)
            assert seg.compact(strategy=strategy) == 1
            segs[strategy] = seg.segments[0]
        a, b = segs["merge"], segs["rebuild"]
        assert a.docs == b.docs and a.offset == b.offset
        from repro.core.fm_index import fm_mismatch

        assert not (diff := fm_mismatch(a.index.fm, b.index.fm)), diff

    def test_compact_threshold_preserves_large_segments(self):
        rng = np.random.default_rng(10)
        seg = SegmentedIndex(SIGMA, sample_rate=16, sa_sample_rate=8)
        sizes = (40, 30, 600, 25, 20)
        for n in sizes:
            seg.append(rng.integers(1, SIGMA, n).astype(np.int32))
        pats, _ = _patterns(rng, np.concatenate([s.tokens for s in seg.segments]))
        before = seg.count(pats)
        # merge only segments under 100 tokens: [40+30], [600], [25+20]
        assert seg.compact(min_tokens=100) == 2
        assert [s.n_tokens for s in seg.segments] == [70, 600, 45]
        assert [s.offset for s in seg.segments] == [0, 70, 670]
        # document semantics: compaction is answer-invariant, exactly
        assert np.array_equal(seg.count(pats), before)

    def test_compact_noop_on_single_segment(self):
        rng = np.random.default_rng(11)
        seg = SegmentedIndex(SIGMA)
        seg.append(rng.integers(1, SIGMA, 100).astype(np.int32))
        assert seg.compact() == 0 and len(seg.segments) == 1

    def test_maybe_compact_policy(self):
        """Cost-based background trigger: fires when the cheapest merge
        estimate undercuts the rebuild estimate; a run still needs >= 2
        adjacent smalls."""
        rng = np.random.default_rng(23)
        seg = SegmentedIndex(SIGMA, sample_rate=16, sa_sample_rate=8,
                             segment_min_tokens=100,
                             # make merging estimate-cheap for tiny runs so
                             # the cost trigger (not the backstop) decides
                             compact_cost_merge_us=0.0,
                             compact_cost_walk_ns=1.0,
                             compact_cost_token_ns=1.0)
        seg.append(rng.integers(1, SIGMA, 400).astype(np.int32))
        seg.append(rng.integers(1, SIGMA, 30).astype(np.int32))
        assert seg.maybe_compact() == 0      # a run needs >= 2 smalls
        seg.append(rng.integers(1, SIGMA, 40).astype(np.int32))
        assert seg.maybe_compact() == 1      # merge estimate beats rebuild
        assert [len(s.docs) for s in seg.segments] == [1, 2]
        assert seg.maybe_compact() == 0      # nothing small is adjacent

    def test_maybe_compact_cost_deferral_and_backstop(self):
        """When no merge flavor pays for itself vs the rebuild, runs defer
        — until the compact_max_small backstop bounds per-query fan-out.
        compact_cost_merge_us=0 disables the immediate-fire clause (a run
        whose rebuild costs less than one merge dispatch compacts right
        away), isolating the deferral path: equal tiny segments make the
        sequential walk estimate dominate the vectorized sort estimate."""
        rng = np.random.default_rng(29)
        seg = SegmentedIndex(SIGMA, sample_rate=16, sa_sample_rate=8,
                             segment_min_tokens=100, compact_max_small=4,
                             compact_cost_merge_us=0.0)
        for _ in range(3):
            seg.append(rng.integers(1, SIGMA, 30).astype(np.int32))
            assert seg.maybe_compact() == 0  # cost model defers
        seg.append(rng.integers(1, SIGMA, 30).astype(np.int32))
        assert seg.maybe_compact() == 1      # 4 smalls: backstop fires
        assert len(seg.segments) == 1 and len(seg.segments[0].docs) == 4


class TestLifecycle:
    def test_save_load_roundtrip(self, built, tmp_path):
        rng, chunks, full, _, seg, _ = built
        pats, _ = _patterns(rng, full)
        seg.save(str(tmp_path))
        loaded = SegmentedIndex.load(str(tmp_path))
        assert loaded.sigma == seg.sigma
        assert loaded.catalog() == seg.catalog()
        assert np.array_equal(seg.count(pats), loaded.count(pats))
        p0, c0 = seg.locate(pats, 64)
        p1, c1 = loaded.locate(pats, 64)
        assert np.array_equal(p0, p1) and np.array_equal(c0, c1)
        # the catalog keeps growing after restore
        loaded.append(rng.integers(1, SIGMA, 64).astype(np.int32))
        assert loaded.total_tokens == seg.total_tokens + 64

    def test_catalog_persists_build_knobs(self, tmp_path):
        """Knobs round-trip through catalog.json so post-restore compactions
        build segments exactly like the saved ones; kwargs still override."""
        rng = np.random.default_rng(12)
        seg = SegmentedIndex(SIGMA, sample_rate=16, sa_sample_rate=8,
                             pack=False, compress_sa=False,
                             segment_min_tokens=128)
        seg.append(rng.integers(1, SIGMA, 60).astype(np.int32))
        seg.append(rng.integers(1, SIGMA, 70).astype(np.int32))
        seg.save(str(tmp_path))
        loaded = SegmentedIndex.load(str(tmp_path))
        assert (loaded.pack, loaded.compress_sa) == (False, False)
        assert loaded.segment_min_tokens == 128
        assert loaded.sa_config == seg.sa_config
        # both segments are under the persisted threshold -> default compact
        # merges them, rebuilt with the persisted knobs
        assert loaded.compact() == 1
        assert loaded.segments[0].index.fm.bits == 0       # pack=False kept
        assert loaded.segments[0].index.fm.sa_val_bits == 0
        # explicit override wins over the catalog
        loaded2 = SegmentedIndex.load(str(tmp_path), sample_rate=32)
        assert loaded2.sample_rate == 32

    def test_from_config(self):
        from repro.configs.bwt_index import reduced

        cfg = reduced()
        seg = SegmentedIndex.from_config(SIGMA, cfg)
        assert seg.sample_rate == cfg.sample_rate
        assert seg.sa_sample_rate == cfg.sa_sample_rate
        assert seg.segment_min_tokens == cfg.segment_min_tokens
        assert seg.sa_config.engine == cfg.engine
        rng = np.random.default_rng(13)
        seg.append(rng.integers(1, SIGMA, 200).astype(np.int32))
        assert seg.count(np.array([[1]], np.int32))[0] > 0

    def test_save_is_incremental_and_gcs_orphans(self, tmp_path):
        """Re-saving skips persisted immutable segments; compact() orphans
        are removed so the directory tracks the live catalog."""
        import os

        rng = np.random.default_rng(14)
        seg = SegmentedIndex(SIGMA, sample_rate=16, sa_sample_rate=8)
        seg.append(rng.integers(1, SIGMA, 60).astype(np.int32))
        seg.append(rng.integers(1, SIGMA, 70).astype(np.int32))
        seg.save(str(tmp_path))
        first = {d: os.path.getmtime(tmp_path / d / "tokens.npz")
                 for d in ("seg_000000", "seg_000001")}
        seg.save(str(tmp_path))  # no-op for existing segments
        for d, t in first.items():
            assert os.path.getmtime(tmp_path / d / "tokens.npz") == t, d
        assert seg.compact() == 1
        seg.save(str(tmp_path))
        dirs = sorted(d for d in os.listdir(tmp_path) if d.startswith("seg_"))
        assert dirs == ["seg_000002"]  # old segment dirs GC'd
        loaded = SegmentedIndex.load(str(tmp_path))
        assert loaded.catalog() == seg.catalog()

    def test_load_rejects_foreign_dir(self, tmp_path):
        (tmp_path / "catalog.json").write_text('{"format": "other"}')
        with pytest.raises(ValueError, match="catalog"):
            SegmentedIndex.load(str(tmp_path))

    def test_served_through_query_server(self, built):
        """FMQueryServer speaks SequenceIndex's interface; a SegmentedIndex
        drops in unchanged."""
        rng, _, full, offsets, seg, _ = built
        server = FMQueryServer(seg, length_buckets=(4, 8), max_batch=16)
        queries = [full[o : o + 3] for o in (0, 10, 400, 700)]
        got = server.count(queries)
        for q, g in zip(queries, got):
            hits = _occurrences(full, q)
            within, _ = _split_hits(hits, offsets, len(q))
            assert g == len(within)
        pos = server.locate([full[:4]], k=8)[0]
        assert 0 in pos


class TestMergeEdgeCases:
    """BWT-merge corner coverage: empty/one-symbol operands, SA-sample
    bit-width growth across a merge, and in-place stacked append after a
    merge (no recompilation)."""

    def _build_prepared(self, tokens, sigma_declared, r=8, srate=4):
        from repro.core.pipeline import build_index_prepared, prepare_tokens

        s, sig = prepare_tokens(np.asarray(tokens, np.int32), r,
                                sigma_declared)
        return build_index_prepared(s, sig, sample_rate=r,
                                    sa_sample_rate=srate), s, sig

    def _assert_merge_equals_rebuild(self, docs, sigma_declared, r=8,
                                     srate=4):
        from repro.core.bwt_merge import merge_fm_indexes
        from repro.core.pipeline import build_index_prepared, prepare_tokens

        preps = [prepare_tokens(np.asarray(d, np.int32), r,
                                sigma_declared)[0] for d in docs]
        sig = sigma_declared + 1
        acc = self._build_prepared(docs[-1], sigma_declared, r, srate)[0].fm
        for d in reversed(docs[:-1]):
            left = self._build_prepared(d, sigma_declared, r, srate)[0].fm
            acc = merge_fm_indexes(left, acc)
        want = build_index_prepared(
            np.concatenate(preps), sig, sample_rate=r, sa_sample_rate=srate,
        ).fm
        from repro.core.fm_index import fm_mismatch

        assert not (diff := fm_mismatch(acc, want)), diff
        return acc

    def test_empty_right_document(self):
        """An empty document (sentinel + pads only) merges exactly — as the
        right operand AND as the left."""
        rng = np.random.default_rng(31)
        body = rng.integers(1, 5, 20).astype(np.int32)
        self._assert_merge_equals_rebuild([body, []], 5)
        self._assert_merge_equals_rebuild([[], body], 5)
        self._assert_merge_equals_rebuild([[], []], 5)

    def test_single_symbol_segments(self):
        """Length-1 (and unary) segments: maximal padding, periodic merged
        text — the adversarial case for the interleave walk."""
        seg = SegmentedIndex(3, sample_rate=8, sa_sample_rate=4)
        for _ in range(3):
            seg.append(np.array([1], np.int32))
        pats = np.array([[1, PAD], [1, 1], [2, PAD]], np.int32)
        assert list(seg.count(pats)) == [3, 0, 0]
        before_p, before_c = seg.locate(pats, 8)
        assert seg.compact(strategy="merge") == 1
        assert list(seg.count(pats)) == [3, 0, 0]
        pos, cnt = seg.locate(pats, 8)
        assert np.array_equal(pos, before_p) and np.array_equal(cnt, before_c)
        self._assert_merge_equals_rebuild([[1], [1], [1]], 3)

    def test_sa_val_bits_grows_across_merge(self):
        """Merging can push the packed SA-value quotient past a power of
        two: the merged stream re-packs at the wider width, identical to
        what a rebuild computes."""
        rng = np.random.default_rng(33)
        seg = SegmentedIndex(5, sample_rate=8, sa_sample_rate=4)
        for _ in range(2):
            seg.append(rng.integers(1, 5, 27).astype(np.int32))
        per_seg_bits = {s.index.fm.sa_val_bits for s in seg.segments}
        assert per_seg_bits == {3}  # 32 positions / stride 4 -> q_max 7
        assert seg.compact(strategy="merge") == 1
        merged = seg.segments[0].index.fm
        assert merged.sa_val_bits == 4  # 64 positions -> q_max 15
        rng2 = np.random.default_rng(33)
        seg2 = SegmentedIndex(5, sample_rate=8, sa_sample_rate=4)
        for _ in range(2):
            seg2.append(rng2.integers(1, 5, 27).astype(np.int32))
        assert seg2.compact(strategy="rebuild") == 1
        assert seg2.segments[0].index.fm.sa_val_bits == 4
        assert np.array_equal(np.asarray(merged.sa_vals),
                              np.asarray(seg2.segments[0].index.fm.sa_vals))

    def test_merge_then_stacked_append_no_recompile(self):
        """After a merge compaction patched into the stacked catalog, an
        append into spare pow2 capacity must reuse the already-compiled
        stacked query program: n_seg is a pytree LEAF, and both the
        replace and the append preserve every static shape."""
        from repro.core.fm_index import StackedFMIndex, count_stacked

        rng = np.random.default_rng(37)
        seg = SegmentedIndex(SIGMA, sample_rate=16, sa_sample_rate=8,
                             parallel=True)
        seg.append(rng.integers(1, SIGMA, 700).astype(np.int32))
        for n in (40, 50, 30):
            seg.append(rng.integers(1, SIGMA, n).astype(np.int32))
        pats, _ = _patterns(rng, seg.segments[0].tokens, B=8, L=4)
        want = seg.count(pats)
        assert isinstance(seg._stacked_cache, StackedFMIndex)
        compiles_before = count_stacked._cache_size()
        st_before = seg._stacked_cache

        assert seg.compact(min_tokens=100, strategy="merge") == 1
        assert isinstance(seg._stacked_cache, StackedFMIndex), \
            "merge within the block bucket must patch the cache in place"
        assert np.array_equal(seg.count(pats), want)

        seg.append(rng.integers(1, SIGMA, 35).astype(np.int32))
        assert seg._stacked_cache is not None
        assert int(seg._stacked_cache.n_seg) == 3
        assert seg._stacked_cache.seg_pad == st_before.seg_pad
        assert seg._stacked_cache.blocks_pad == st_before.blocks_pad
        got = seg.count(pats)
        assert count_stacked._cache_size() == compiles_before, \
            "stacked append/replace recompiled the query program"
        # sequential path agrees with the patched stacked catalog
        seg.parallel = False
        seq = seg.count(pats)
        seg.parallel = True
        assert np.array_equal(got, seq)

    def test_stacked_row_table_stays_placed(self):
        """Stacking, the in-place append and the compaction splice each
        leave the stacked row table in the rank kernel's layout, and it is
        the one table the catalog's queries read."""
        rng = np.random.default_rng(41)
        seg = SegmentedIndex(SIGMA, sample_rate=16, sa_sample_rate=8,
                             parallel=True)
        seg.append(rng.integers(1, SIGMA, 700).astype(np.int32))
        for n in (40, 50, 30):
            seg.append(rng.integers(1, SIGMA, n).astype(np.int32))

        def table():
            (t,) = seg.packed_row_tables()
            assert t is seg._stacked_cache.fused
            return t

        assert rows_placed(table())
        assert seg.compact(min_tokens=100, strategy="merge") == 1
        assert rows_placed(table())
        seg.append(rng.integers(1, SIGMA, 35).astype(np.int32))
        assert rows_placed(table())
        assert all(rows_placed(s.index.fm.fused) for s in seg.segments)


class TestKWayAndPlanner:
    """K-way interleave merge + cost-based planner: merged-of-merged
    operands on BOTH sides (the PR 5 'multi-doc only as the right operand'
    restriction is gone), bit-identity against the rebuild oracle across
    alphabets, direct merge_kway conformance, and rebuild-fallback
    telemetry for context-order-unsafe runs."""

    def _grow(self, seed, sigma, sizes, strategy, r=8, srate=4):
        rng = np.random.default_rng(seed)
        seg = SegmentedIndex(sigma, sample_rate=r, sa_sample_rate=srate,
                             compact_strategy=strategy)
        for n in sizes:
            seg.append(rng.integers(1, sigma, n).astype(np.int32))
        return seg

    @pytest.mark.parametrize("sigma", [2, 4, 16, 17])
    def test_merged_of_merged_both_sides(self, sigma):
        """Two already-merged multi-doc segments compact into one,
        bit-identical to the rebuild, under every strategy."""
        from repro.core.fm_index import fm_mismatch

        sizes = (57, 33, 41, 29)
        final = {}
        for strategy in ("kway", "pairwise", "merge", "rebuild"):
            seg = self._grow(41 + sigma, sigma, sizes, strategy)
            # pre-merge adjacent pairs -> two multi-doc segments
            a = seg._merge_run(seg.segments[:2], "rebuild")
            b = seg._merge_run(seg.segments[2:], "rebuild")
            seg.segments = [a, b]
            seg._stacked_cache = None
            assert all(s.multi_doc for s in seg.segments)
            assert seg.compact(strategy=strategy) == 1
            final[strategy] = seg
        want = final["rebuild"].segments[0].index.fm
        for strategy in ("kway", "pairwise", "merge"):
            got = final[strategy].segments[0].index.fm
            assert not (d := fm_mismatch(got, want)), (strategy, d)
            # answer-invariance on top of bit-identity
            assert final[strategy].segments[0].docs == \
                final["rebuild"].segments[0].docs

    def test_kway_runs_without_fallback_on_typical_text(self):
        """Random multi-doc corpora are context-order safe in practice
        (document pads sort above every real token): the forced k-way
        strategy must actually run the k-way walk, not fall back."""
        seg = self._grow(57, 16, (57, 33, 41, 29), "kway")
        assert seg.compact(strategy="kway") == 1
        assert seg.compact_fallbacks == 0
        assert seg.compact_strategy_counts == {"kway": 1}
        plan = seg.compact_last_plan
        assert plan["strategy"] == "kway" and plan["reason"] is None
        assert plan["actual_walk_steps"] == plan["est_walk_steps"] > 0

    @pytest.mark.parametrize("sigma", [2, 4, 16, 17])
    def test_direct_merge_kway_matches_build(self, sigma):
        """merge_kway on k=4 prepared docs == build_index_prepared on
        their concatenation — every array, every aux field."""
        from repro.core.bwt_merge import context_order_safe, merge_kway
        from repro.core.fm_index import fm_mismatch
        from repro.core.pipeline import build_index_prepared, prepare_tokens

        r, srate = 8, 4
        rng = np.random.default_rng(67 + sigma)
        docs = [rng.integers(1, sigma, n).astype(np.int32)
                for n in (45, 30, 22, 11)]
        preps, sigs, fms = [], [], []
        for d in docs:
            s, sig = prepare_tokens(d, r, sigma)
            preps.append(s)
            sigs.append(sig)
            fms.append(build_index_prepared(
                s, sig, sample_rate=r, sa_sample_rate=srate).fm)
        for i in range(len(preps) - 1):  # precondition of the k-way walk
            assert context_order_safe(preps[i], np.concatenate(preps[i+1:]))
        got = merge_kway(fms)
        want = build_index_prepared(
            np.concatenate(preps), max(sigs), sample_rate=r,
            sa_sample_rate=srate).fm
        assert not (d := fm_mismatch(got, want)), d

    def test_unsafe_run_falls_back_with_telemetry(self):
        """A run no candidate order can rescue — two *identical* merged
        multi-doc segments whose texts end in a bare sentinel (the
        self-similar tied tail is context-order unsafe in either
        direction, and there is no single-doc segment to lead with) —
        must NOT merge silently wrong: the planner detects it, warns,
        counts the fallback, and the rebuild stays bit-identical to the
        oracle."""
        import warnings as _w

        from repro.core.fm_index import fm_mismatch

        r, srate, sigma = 8, 4, 4
        d1 = np.full(7, 3, np.int32)  # 7 + sentinel = block: no pads
        d2 = np.full(7, 1, np.int32)  # merged [d1,d2] text ends with 0

        def grow(strategy):
            seg = SegmentedIndex(sigma, sample_rate=r, sa_sample_rate=srate,
                                 compact_strategy=strategy)
            for d in (d1, d2, d1, d2):
                seg.append(d)
            for lo in (2, 0):  # pre-merge (d1,d2) pairs -> two multis
                m = seg._merge_run(seg.segments[lo : lo + 2], "rebuild")
                seg.segments = (seg.segments[:lo] + [m]
                                + seg.segments[lo + 2 :])
            seg._stacked_cache = None
            seg.compact_strategy_counts = {}  # drop the setup merges' counts
            return seg

        oracle = grow("rebuild")
        assert oracle.compact() == 1
        for strategy in ("kway", "pairwise", "merge"):
            seg = grow(strategy)
            with pytest.warns(RuntimeWarning, match="fell back"):
                assert seg.compact(strategy=strategy) == 1
            assert seg.compact_fallbacks == 1
            assert "context-order" in seg.compact_last_fallback_reason
            assert seg.compact_strategy_counts == {"rebuild": 1}
            assert not fm_mismatch(seg.segments[0].index.fm,
                                   oracle.segments[0].index.fm)
        _w.simplefilter("default")

    def test_fallback_telemetry_survives_save_load(self, tmp_path):
        """compact_fallbacks / last reason persist through the catalog."""
        seg = SegmentedIndex(4, sample_rate=8, sa_sample_rate=4,
                             compact_strategy="kway")
        for d in (np.full(7, 3, np.int32), np.full(7, 1, np.int32)) * 2:
            seg.append(d)
        for lo in (2, 0):  # two identical multis: unrescuably unsafe
            m = seg._merge_run(seg.segments[lo : lo + 2], "rebuild")
            seg.segments = seg.segments[:lo] + [m] + seg.segments[lo + 2 :]
        seg._stacked_cache = None
        with pytest.warns(RuntimeWarning):
            seg.compact()
        seg.save(str(tmp_path))
        loaded = SegmentedIndex.load(str(tmp_path))
        assert loaded.compact_fallbacks == seg.compact_fallbacks == 1
        assert loaded.compact_last_fallback_reason == \
            seg.compact_last_fallback_reason
