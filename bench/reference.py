"""The plain reference: exact answers from numpy, sharing no code with the
index under test.

``ExactMatcher`` answers count/locate for a set of patterns over a text by
q-gram candidate lookup and a symbol-by-symbol check (the starting point
was the bring-up smoke test's ``TextScan`` scan).  ``index_bad_rows``
checks a built index against the definition of a suffix array.
"""

from __future__ import annotations

import numpy as np

CHUNK = 1 << 20  # candidate rows checked per gather


def _qgram_codes(text: np.ndarray, q: int, bits: int) -> np.ndarray:
    """int64 code of the q symbols starting at each position (positions
    past ``len(text) - q`` read zeros, which no pattern symbol equals)."""
    t = np.concatenate([text.astype(np.int64), np.zeros(q, np.int64)])
    n = len(text)
    code = np.zeros(n, np.int64)
    for j in range(q):
        code = (code << bits) | t[j : j + n]
    return code


class ExactMatcher:
    """Occurrences of each of a fixed set of patterns in ``text``."""

    def __init__(self, text: np.ndarray, patterns: list[np.ndarray]):
        self.text = np.ascontiguousarray(text, np.int32)
        self.bits = max(1, int(self.text.max()).bit_length())
        self.q = max(1, min(min(len(p) for p in patterns), 24 // self.bits))
        codes = _qgram_codes(self.text, self.q, self.bits)
        want = np.zeros(1 << (self.bits * self.q), bool)
        self._pcodes = [self._code(p) for p in patterns]
        want[self._pcodes] = True
        cand = np.flatnonzero(want[codes])
        ccodes = codes[cand]
        order = np.argsort(ccodes, kind="stable")
        self._cand, self._ccodes = cand[order], ccodes[order]

    def _code(self, p: np.ndarray) -> int:
        c = 0
        for s in p[: self.q]:
            c = (c << self.bits) | int(s)
        return c

    def positions(self, i: int, p: np.ndarray) -> np.ndarray:
        """Sorted start positions of pattern ``i`` (= ``p``) in the text."""
        lo, hi = np.searchsorted(self._ccodes, self._pcodes[i], "left"), \
            np.searchsorted(self._ccodes, self._pcodes[i], "right")
        cand = self._cand[lo:hi]
        cand = cand[cand <= len(self.text) - len(p)]
        off = np.arange(len(p))
        out = []
        for a in range(0, len(cand), CHUNK):
            c = cand[a : a + CHUNK]
            ok = (self.text[c[:, None] + off[None, :]] == p[None, :]).all(1)
            out.append(c[ok])
        return np.concatenate(out) if out else cand[:0]


def prepared_text(tokens: np.ndarray, multiple: int, pad_symbol: int
                  ) -> np.ndarray:
    """The string an index covers: the tokens, the sentinel 0, then
    ``pad_symbol`` up to a multiple of ``multiple``."""
    s = np.concatenate([np.asarray(tokens, np.int32), [0]]).astype(np.int32)
    pad = (-len(s)) % multiple
    return np.concatenate([s, np.full(pad, pad_symbol, np.int32)])


def unsorted_rows(s: np.ndarray, sa: np.ndarray) -> np.ndarray:
    """Rows i >= 1 whose suffix does not sort strictly after row i-1's
    (a suffix that is a prefix of another sorts first)."""
    n = len(s)
    a, b = sa[:-1].astype(np.int64), sa[1:].astype(np.int64)
    same = a == b               # one suffix twice: never in order
    idx = np.flatnonzero(~same)
    bad = [np.flatnonzero(same)]
    j = 0
    while idx.size:
        pa, pb = a[idx] + j, b[idx] + j
        ca = np.where(pa < n, s[np.minimum(pa, n - 1)], -1)
        cb = np.where(pb < n, s[np.minimum(pb, n - 1)], -1)
        bad.append(idx[(ca > cb) | ((ca == cb) & (ca < 0))])
        idx = idx[(ca == cb) & (ca >= 0)]
        j += 1
    return np.sort(np.concatenate(bad)) + 1


def _unpack_stream(words: np.ndarray, count: int, bits: int) -> np.ndarray:
    """Values 0..count-1 of an LSB-first bitstream of ``bits``-bit fields."""
    w = np.concatenate([words.view(np.uint32).astype(np.uint64),
                        np.zeros(2, np.uint64)])
    pos = np.arange(count, dtype=np.int64) * bits
    lo = w[pos >> 5] | (w[(pos >> 5) + 1] << np.uint64(32))
    return ((lo >> (pos & 31).astype(np.uint64))
            & np.uint64((1 << bits) - 1)).astype(np.int64)


def index_bad_rows(s: np.ndarray, got: dict) -> dict:
    """Rows of a built index that break the definition, by part.

    ``s``: the prepared string.  ``got``: host copies of the index's
    ``sa``, ``bwt``, ``row``, ``c_array``, ``occ_samples`` (per block of
    ``sample_rate`` rows), ``sa_marks``/``sa_vals`` and the ints
    ``sample_rate``, ``sa_sample_rate``, ``sa_val_bits``."""
    n = len(s)
    sa = np.asarray(got["sa"], np.int64)
    out = {}
    seen = np.bincount(np.clip(sa, 0, n - 1), minlength=n)
    out["sa_not_permutation"] = int(np.count_nonzero(seen != 1)
                                    + np.count_nonzero((sa < 0) | (sa >= n)))
    out["sa_unsorted"] = int(len(unsorted_rows(s, sa)))
    sa = np.clip(sa, 0, n - 1)
    bwt = s[(sa - 1) % n]
    out["bwt"] = int(np.count_nonzero(np.asarray(got["bwt"])[:n] != bwt))
    out["row"] = int(int(got["row"]) != int(np.argmin(sa)))
    sigma = int(np.asarray(got["c_array"]).shape[0])
    hist = np.bincount(s, minlength=sigma)[:sigma]
    out["c_array"] = int(np.count_nonzero(
        np.asarray(got["c_array"]) != np.cumsum(hist) - hist))
    r = int(got["sample_rate"])
    nb = n // r
    per = np.bincount((np.arange(n) // r) * sigma + bwt,
                      minlength=nb * sigma).reshape(nb, sigma)
    occ = np.concatenate([np.zeros((1, sigma), np.int64),
                          np.cumsum(per, axis=0)])
    got_occ = np.asarray(got["occ_samples"])
    out["occ_samples"] = int(np.count_nonzero(
        (got_occ[: nb + 1] != occ).any(axis=1)))
    stride = int(got["sa_sample_rate"])
    marked = sa % stride == 0
    words = np.asarray(got["sa_marks"]).view(np.uint32)
    mbits = np.unpackbits(words.view(np.uint8), bitorder="little")[:n]
    out["sa_marks"] = int(np.count_nonzero(mbits.astype(bool) != marked))
    rows = np.flatnonzero(marked)
    vb = int(got["sa_val_bits"])
    vals = np.asarray(got["sa_vals"])
    vals = (_unpack_stream(vals, len(rows), vb) * stride if vb
            else vals[: len(rows)].astype(np.int64))
    out["sa_vals"] = int(np.count_nonzero(vals != sa[rows]))
    return out
