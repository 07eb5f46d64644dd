"""The plain reference against brute force at small sizes."""

import numpy as np
import pytest

import reference
from generators import texts


def _brute(text, p):
    m = len(p)
    return np.array([i for i in range(len(text) - m + 1)
                     if np.array_equal(text[i : i + m], p)], np.int64)


def test_exact_matcher_equals_brute_force():
    rng = np.random.default_rng(3)
    text = rng.integers(1, 5, 3000).astype(np.int32)
    text[500:700] = np.tile([1, 2], 100)          # a tandem repeat
    pats = [text[s : s + m].copy() for s, m in
            [(510, 19), (10, 30), (2000, 12), (505, 40)]]
    pats.append(np.array([4] * 25, np.int32))     # absent
    ref = reference.ExactMatcher(text, pats)
    for i, p in enumerate(pats):
        assert np.array_equal(ref.positions(i, p), _brute(text, p))


def test_unsorted_rows_of_a_suffix_array():
    s = np.array([2, 1, 2, 1, 0, 5, 5], np.int32)
    sa = np.array(sorted(range(len(s)), key=lambda i: list(s[i:])))
    assert reference.unsorted_rows(s, sa).size == 0
    swapped = sa.copy()
    swapped[[1, 2]] = swapped[[2, 1]]
    assert reference.unsorted_rows(s, swapped).tolist() == [2]
    repeated = sa.copy()
    repeated[3] = repeated[2]
    assert reference.unsorted_rows(s, repeated).tolist() == [3]


@pytest.fixture(scope="module")
def small_index():
    import json

    from repro.configs.bwt_index import CONFIG
    from repro.core.segments import SegmentedIndex

    import harness

    cfg = json.loads((harness.BENCH / "configs" / "pc_proteins_2p24.json")
                     .read_text())
    toks = texts.generate(cfg, 9, 1 << 12)
    cat = SegmentedIndex.from_config(cfg["sigma"],
                                     CONFIG.replace(sa_sample_rate=32))
    idx = cat.append(toks).index
    fm = idx.fm
    got = {"sa": np.asarray(idx.sa), "bwt": np.asarray(idx.bwt),
           "row": int(idx.row), "c_array": np.asarray(fm.c_array),
           "occ_samples": np.asarray(fm.occ_samples),
           "sa_marks": np.asarray(fm.sa_marks),
           "sa_vals": np.asarray(fm.sa_vals), "sample_rate": fm.sample_rate,
           "sa_sample_rate": fm.sa_sample_rate,
           "sa_val_bits": fm.sa_val_bits}
    return reference.prepared_text(toks, 64, cfg["sigma"]), got


def test_a_built_index_has_no_bad_rows(small_index):
    s, got = small_index
    assert set(reference.index_bad_rows(s, got).values()) == {0}


def test_each_part_of_the_index_is_checked(small_index):
    s, got = small_index
    for key, bad in [("bwt", "bwt"), ("occ_samples", "occ_samples"),
                     ("sa_vals", "sa_vals"), ("c_array", "c_array")]:
        broken = dict(got)
        arr = np.array(got[key])
        arr.reshape(-1)[len(arr.reshape(-1)) // 2] += 1
        broken[key] = arr
        assert reference.index_bad_rows(s, broken)[bad] > 0, key
    broken = dict(got, row=got["row"] + 1)
    assert reference.index_bad_rows(s, broken)["row"] == 1
