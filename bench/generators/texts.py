"""Seeded text generators for the benchmark's configurations.

Each generator takes its parameters from the configuration file and two
seeds.  The *layout* seed (fixed in the configuration) places every record
boundary, repeat copy, tandem tract and substitution; the run's ``--seed``
draws the symbols.  So every seed gives the index the same amount of
repeated text, at the same places and of the same lengths, and the build
does the same rounds over the same active-set sizes; only the content
differs.

Token ids follow the index's convention: 0 is the sentinel (never
generated), real symbols are >= 1, and the record separator is the
largest id of the alphabet.
"""

from __future__ import annotations

import numpy as np


def _rng(seed: int, salt: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence([int(seed), salt]))


def _record_ends(layout: np.random.Generator, n: int, lo: int, hi: int,
                 *, lognormal=None) -> np.ndarray:
    """Separator positions: record lengths uniform in [lo, hi] (or
    log-normal ``(mu, sigma)`` clipped to [lo, hi]) laid end to end."""
    est = n // max(1, lo) + 2
    if lognormal is None:
        lens = layout.integers(lo, hi + 1, est)
    else:
        mu, sd = lognormal
        lens = np.clip(np.rint(layout.lognormal(mu, sd, est)), lo, hi)
    ends = np.cumsum(lens.astype(np.int64) + 1) - 1   # +1: the separator
    return ends[ends < n]


def _ranges(starts: np.ndarray, lens: np.ndarray) -> np.ndarray:
    """Concatenation of ``arange(s, s + l)`` for every (s, l)."""
    total = int(lens.sum())
    rel = np.arange(total) - np.repeat(np.cumsum(lens) - lens, lens)
    return np.repeat(starts, lens) + rel


def dna_repeats(n: int, seed: int, p: dict) -> np.ndarray:
    """A reference as ``bwa index`` lays it out: a forward strand of ACGT
    (ids 1-4) ended by id 5, then its reverse complement ended by id 5.
    The strand carries an Alu-like interspersed family and simple tandem
    repeats.

    ``p``: ``family_len``; ``family_share`` (of the strand);
    ``family_divergence`` [lo, hi] per copy; ``tandem_share``;
    ``tandem_unit`` [lo, hi]; ``tandem_len`` [lo, hi]; ``layout_seed``.
    """
    half = n // 2
    fwd = _dna_strand(half - 1, seed, p)
    rc = (5 - fwd)[::-1]                     # A<->T (1, 4), C<->G (2, 3)
    return np.concatenate([fwd, [5], rc, [5] * (n - 2 * half + 1)]
                          ).astype(np.int32)


def _dna_strand(n: int, seed: int, p: dict) -> np.ndarray:
    layout, content = _rng(p["layout_seed"], 0xD7A), _rng(seed, 0xD7A)
    text = content.integers(1, 5, n, dtype=np.int32)

    # tandem tracts: a short unit repeated over the whole tract
    t_mean = sum(p["tandem_len"]) / 2
    n_tr = int(round(p["tandem_share"] * n / t_mean))
    t_len = layout.integers(p["tandem_len"][0], p["tandem_len"][1] + 1, n_tr)
    t_unit = layout.integers(p["tandem_unit"][0], p["tandem_unit"][1] + 1,
                             n_tr)
    t_start = layout.integers(0, n - int(t_len.max()), n_tr)
    umax = int(t_unit.max())
    units = content.integers(1, 5, (n_tr, umax), dtype=np.int32)
    pos = _ranges(t_start, t_len)
    tract = np.repeat(np.arange(n_tr), t_len)
    phase = (pos - np.repeat(t_start, t_len)) % np.repeat(t_unit, t_len)
    text[pos] = units[tract, phase]

    # the interspersed family: copies of one consensus, each diverged
    L = int(p["family_len"])
    n_cp = int(round(p["family_share"] * n / L))
    c_start = layout.integers(0, n - L, n_cp)
    div = layout.uniform(*p["family_divergence"], n_cp)
    mutated = layout.random((n_cp, L)) < div[:, None]
    consensus = content.integers(1, 5, L, dtype=np.int32)
    shift = content.integers(1, 4, (n_cp, L), dtype=np.int32)
    copies = np.where(mutated, (consensus[None, :] - 1 + shift) % 4 + 1,
                      consensus[None, :])
    text[_ranges(c_start, np.full(n_cp, L))] = copies.reshape(-1)

    return text


def proteins_redundant(n: int, seed: int, p: dict) -> np.ndarray:
    """Residue records (ids 1-20, frequencies ``residue_freq``) separated
    by id 21; a share of the records are near-copies of earlier ones.

    ``p``: ``record_len`` [lo, hi] and ``record_lognormal`` [mu, sigma];
    ``copy_share`` (of the records); ``copy_substitution`` [lo, hi] per
    copy; ``residue_freq`` (20 weights); ``layout_seed``.
    """
    layout, content = _rng(p["layout_seed"], 0x9B0), _rng(seed, 0x9B0)
    freq = np.asarray(p["residue_freq"], np.float64)
    cdf = np.cumsum(freq / freq.sum())
    text = (np.searchsorted(cdf, content.random(n), side="right")
            .clip(0, 19) + 1).astype(np.int32)

    ends = _record_ends(layout, n, *p["record_len"],
                        lognormal=p["record_lognormal"])
    starts = np.concatenate([[0], ends + 1])[: len(ends)]
    lens = ends - starts
    n_rec = len(lens)
    # each copy takes an earlier original record whose length fits its slot
    is_copy = layout.random(n_rec) < p["copy_share"]
    is_copy[0] = False
    originals = np.flatnonzero(~is_copy)
    src = originals[(layout.random(n_rec)
                     * np.searchsorted(originals, np.arange(n_rec))
                     ).astype(np.int64)]
    cp = np.flatnonzero(is_copy)
    src = src[cp]
    cp_len = np.minimum(lens[cp], lens[src])
    dst_pos = _ranges(starts[cp], cp_len)
    src_pos = _ranges(starts[src], cp_len)
    rate = np.repeat(layout.uniform(*p["copy_substitution"], len(cp)), cp_len)
    sub = layout.random(len(dst_pos)) < rate
    vals = text[src_pos]
    shift = content.integers(1, 20, len(dst_pos), dtype=np.int32)
    text[dst_pos] = np.where(sub, (vals - 1 + shift) % 20 + 1, vals)
    text[ends] = 21
    return text


GENERATORS = {"dna_repeats": dna_repeats,
              "proteins_redundant": proteins_redundant}


def generate(cfg: dict, seed: int, n: int | None = None) -> np.ndarray:
    """The configuration's text for ``seed`` (``n`` overrides its size)."""
    return GENERATORS[cfg["generator"]](int(n or cfg["n"]), seed,
                                        cfg["generator_params"])
