"""FM-index over a BWT: C array, Occ checkpoints, backward search, locate.

This is the "full-text index that enables fast querying" the paper builds
toward (§1): exact pattern matching in O(m) rank queries per pattern,
independent of the indexed-text length, plus occurrence localisation via a
sampled suffix array.

Layout (all dense arrays, shard- and jit-friendly):

* ``bwt``          int32[n_blocks * r]  last column, PAD beyond position n
* ``C``            int32[sigma]  # chars strictly smaller (exclusive cumsum)
* ``occ_samples``  int32[n_blocks + 1, sigma]  checkpointed exclusive Occ
* ``fused``        int32[n_blocks, 1, sigma + r/fpw]  (small alphabets only)
  per-block [Occ checkpoint | bit-packed words] — the interleaved succinct
  layout the Pallas rank kernel consumes, stored where it is made in the
  kernel's own layout (``rank_select.place_rows``), so no query program
  relays it out
* ``sa_marks/sa_mark_ranks/sa_vals``  SA sample for locate(): rows whose SA
  value is a multiple of ``sa_sample_rate`` are marked in a bitvector (with
  per-word popcount checkpoints) and their values stored in row order; any
  occurrence is recovered by LF-walking <= sa_sample_rate - 1 steps to a
  marked row.  The stored values are optionally *compressed*: every marked
  value is a multiple of the stride s, so ``val // s`` fits in
  ``ceil(log2(n / s))`` bits and is bit-packed into a contiguous int32
  bitstream (``sa_val_bits`` > 0 selects the packed decode).  At small
  strides this shrinks the dominant locate structure ~2-3x (e.g. 32 -> 12
  bits per value for n = 2^16, s = 4).

rank(c, p) = occ_samples[p // r, c] + count of c in bwt[(p//r)*r : p].
``sample_rate`` trades memory for per-query scan length r — the classic
FM-index trade-off the paper cites ([4] Ferragina-Manzini).  The in-block
count is the hot spot; all query paths dispatch through ``kernels/ops``
(packed popcount Pallas kernel on TPU, vectorised jnp fallback elsewhere).

``build_sa_samples`` opens the profiler span ``fm.build.sa_samples``
(``rows`` marked), with an ``fm.fetch`` child around the copy of the suffix
array to the host.  The query programs carry named scopes
(``backward_search``, ``locate_walk``, ``sample_lookup``) that label their
operations in a device trace.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.profiler import TraceAnnotation

from ..kernels import ops
from ..kernels.rank_select import pack_words, packed_bits, place_rows

PAD = -1  # query padding token


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class FMIndex:
    bwt: jax.Array            # int32[n_blocks * r], PAD beyond position n
    row: jax.Array            # scalar int32: row of the original string
    c_array: jax.Array        # int32[sigma]
    occ_samples: jax.Array    # int32[n_blocks + 1, sigma]
    fused: jax.Array | None   # int32[n_blocks, 1, sigma + W] packed layout,
                              # in the rank kernel's layout (place_rows)
    sa_marks: jax.Array | None       # int32[ceil(n/32)] bitvector
    sa_mark_ranks: jax.Array | None  # int32[ceil(n/32)] excl. popcount cumsum
    sa_vals: jax.Array | None        # int32[#marked] SA values, row order
                                     # (or packed words when sa_val_bits > 0)
    sample_rate: int          # static (pytree aux data)
    sigma: int                # static (pytree aux data)
    length: int               # static: true text length n
    bits: int                 # static: packed field width (0 = unpacked)
    sa_sample_rate: int       # static: SA sampling stride (0 = no locate)
    sa_val_bits: int = 0      # static: bits per packed SA value (0 = raw)

    @property
    def n(self) -> int:
        return self.length

    @property
    def n_blocks(self) -> int:
        return self.occ_samples.shape[0] - 1

    def tree_flatten(self):
        return (
            (self.bwt, self.row, self.c_array, self.occ_samples, self.fused,
             self.sa_marks, self.sa_mark_ranks, self.sa_vals),
            (self.sample_rate, self.sigma, self.length, self.bits,
             self.sa_sample_rate, self.sa_val_bits),
        )

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, *aux)


def pack_sa_values(q: np.ndarray, bits: int) -> np.ndarray:
    """Bit-pack int values ``q`` (each < 2^bits, bits < 32) LSB-first into a
    contiguous int32 bitstream; value i occupies bits [i*bits, (i+1)*bits).

    One trailing guard word is appended so the two-word decode in
    ``unpack_sa_value`` never reads out of bounds.  Host-side numpy.
    """
    q = np.asarray(q, np.uint64)
    n = q.size
    bitpos = np.arange(n, dtype=np.int64) * bits
    w = bitpos >> 5
    off = (bitpos & 31).astype(np.uint64)
    nwords = int(-(-(n * bits) // 32)) + 1  # ceil + guard word
    words = np.zeros(nwords, np.uint64)
    lo = q << off                       # spans <= 2 consecutive 32-bit words
    np.bitwise_or.at(words, w, lo & np.uint64(0xFFFFFFFF))
    np.bitwise_or.at(words, w + 1, lo >> np.uint64(32))
    return words.astype(np.uint32).view(np.int32)


def unpack_sa_value(words: jax.Array, idx: jax.Array, bits: int) -> jax.Array:
    """Decode packed value ``idx`` from a ``pack_sa_values`` bitstream.

    ``words`` int32[nwords], ``idx`` int32[B] (any shape), ``bits`` static.
    Two gathers + shifts per value; out-of-range idx (garbage lanes of the
    locate walk) clamp in bounds and decode garbage, exactly like the raw
    ``vals[clip(idx)]`` path.
    """
    W = lax.bitcast_convert_type(words, jnp.uint32)
    # idx * bits can overflow int32 at corpus scale; split the product
    base = (idx // 32) * bits
    rem = (idx % 32) * bits
    w = jnp.clip(base + rem // 32, 0, words.shape[0] - 2)
    off = (rem % 32).astype(jnp.uint32)
    lo = W[w] >> off
    hi = jnp.where(
        off > 0,
        W[w + 1] << ((jnp.uint32(32) - off) & jnp.uint32(31)),
        jnp.uint32(0),
    )
    mask = jnp.uint32((1 << bits) - 1)
    return ((lo | hi) & mask).astype(jnp.int32)


def sample_arrays_from_rows(rows, vals, n: int, sa_sample_rate: int, *,
                            compress: bool | None = None):
    """(marks, mark_ranks, vals, val_bits) from an explicit marked-row set.

    ``rows``: sorted row indices whose SA value is a multiple of the
    stride; ``vals``: those values in the same (row) order; ``n``: index
    length.  The single constructor of the on-index SA-sample arrays,
    shared by ``build_sa_samples`` (rows derived from a full SA) and the
    BWT-merge path (rows spliced from two merged indexes) — so both
    produce bit-identical arrays, including the ``compress`` decision,
    for the same marked set.
    """
    idx = np.asarray(rows, np.int64)
    vals = np.asarray(vals, np.int32)
    nwords = -(-n // 32)
    words = np.zeros(nwords, np.uint32)
    np.bitwise_or.at(
        words, idx // 32, np.uint32(1) << (idx % 32).astype(np.uint32)
    )
    pc = np.unpackbits(words.view(np.uint8)).reshape(nwords, 32).sum(axis=1)
    ranks = (np.cumsum(pc) - pc).astype(np.int32)
    q = vals // sa_sample_rate             # exact: marked vals are multiples
    val_bits = max(1, int(q.max()).bit_length()) if q.size else 0
    if compress is None:
        compress = 0 < val_bits < 32
    if compress and not 0 < val_bits < 32:
        raise ValueError(f"cannot compress SA sample (val_bits={val_bits})")
    if not compress:
        val_bits = 0
    return (
        jnp.asarray(words.view(np.int32)),
        jnp.asarray(ranks),
        jnp.asarray(pack_sa_values(q, val_bits) if compress else vals),
        val_bits,
    )


def build_sa_samples(sa, sa_sample_rate: int, *, compress: bool | None = None):
    """(marks, mark_ranks, vals, val_bits) for locate(): host-side, exact.

    Rows i with SA[i] % s == 0 are marked; their SA values are stored in row
    order.  Value lookup for marked row i is vals[mark_ranks[i//32] +
    popcount(marks[i//32] & low_bits(i%32))] — O(1), fully vectorisable.

    ``compress`` bit-packs the stored values: every sampled value is a
    multiple of s, so ``val // s`` fits ``ceil(log2(n/s))`` bits.  None
    (default) packs whenever that width beats raw int32; the returned
    ``val_bits`` (0 = raw) selects the decode in ``sample_lookup``.
    """
    with TraceAnnotation("fm.build.sa_samples") as span:
        with TraceAnnotation("fm.fetch"):
            sa_np = np.asarray(sa)
        marked = (sa_np % sa_sample_rate) == 0
        rows = np.nonzero(marked)[0]
        span.set_metadata(rows=len(rows))
        # SA holds 0, so the marked set is never empty
        return sample_arrays_from_rows(
            rows, sa_np[marked].astype(np.int32),
            sa_np.shape[0], sa_sample_rate, compress=compress,
        )


def decode_sa_values(fm) -> np.ndarray:
    """Raw int32 SA-sample values of an index in row order (host-side),
    undoing the optional bit-packing.  The sampled values are exactly
    {0, s, 2s, ...} below the text length, so the count is implied."""
    nvals = -(-fm.length // fm.sa_sample_rate)
    if fm.sa_val_bits:
        return np.asarray(unpack_sa_value(
            fm.sa_vals, jnp.arange(nvals, dtype=jnp.int32), fm.sa_val_bits,
        )) * fm.sa_sample_rate
    return np.asarray(fm.sa_vals)[:nvals]


def sample_marked_rows(fm) -> np.ndarray:
    """Sorted row indices carrying an SA sample (host-side): the set bits
    of the ``sa_marks`` bitvector below the text length."""
    words = np.asarray(fm.sa_marks).view(np.uint32)
    bits = np.unpackbits(words.view(np.uint8), bitorder="little")
    return np.nonzero(bits[: fm.length])[0]


FM_ARRAY_FIELDS = ("bwt", "row", "c_array", "occ_samples", "fused",
                   "sa_marks", "sa_mark_ranks", "sa_vals")
FM_AUX_FIELDS = ("sample_rate", "sigma", "length", "bits",
                 "sa_sample_rate", "sa_val_bits")


def fm_mismatch(a: FMIndex, b: FMIndex) -> list:
    """Field names on which two FM-indexes differ (empty = bit-identical).

    The single bit-identity oracle behind every merge-vs-rebuild parity
    assertion (fuzz suite, dist driver, compaction benchmark) — one field
    list, so a new ``FMIndex`` field cannot silently fall out of parity
    coverage."""
    out = [name for name in FM_AUX_FIELDS
           if getattr(a, name) != getattr(b, name)]
    for name in FM_ARRAY_FIELDS:
        x, y = getattr(a, name), getattr(b, name)
        if (x is None) != (y is None):
            out.append(name)
        elif x is not None and not np.array_equal(np.asarray(x),
                                                  np.asarray(y)):
            out.append(name)
    return out


def build_fm_index(
    bwt_arr: jax.Array, row: jax.Array, sigma: int, sample_rate: int = 64,
    *, sa: jax.Array | None = None, sa_sample_rate: int = 32,
    pack: bool | None = None, compress_sa: bool | None = None,
    sa_samples: tuple | None = None,
) -> FMIndex:
    """Build the query index from a BWT.

    ``bwt_arr`` int32[n] (tokens in [0, sigma)), ``row`` scalar int32 (the
    BWT row of the original string), ``sample_rate`` the Occ checkpoint
    spacing r.  ``pack=None`` bit-packs whenever the alphabet fits (sigma <=
    16 and r divisible by the fields-per-word); ``pack=False`` forces the
    unpacked layout (benchmark baseline).  Passing the suffix array ``sa``
    enables ``locate`` via SA sampling; ``compress_sa`` as in
    ``build_sa_samples``.  ``sa_samples`` = (marks, mark_ranks, vals,
    val_bits) injects prebuilt sample arrays instead (checkpoint restore,
    where the full SA no longer exists).
    """
    n = bwt_arr.shape[0]
    counts = jnp.bincount(bwt_arr, length=sigma)
    c_array = (jnp.cumsum(counts) - counts).astype(jnp.int32)

    n_blocks = -(-n // sample_rate)  # ceil
    pad = n_blocks * sample_rate - n
    padded = jnp.pad(bwt_arr, (0, pad), constant_values=PAD)
    onehot = (padded[:, None] == jnp.arange(sigma)[None, :]).astype(jnp.int32)
    block_counts = onehot.reshape(n_blocks, sample_rate, sigma).sum(axis=1)
    occ_samples = jnp.concatenate(
        [jnp.zeros((1, sigma), jnp.int32), jnp.cumsum(block_counts, axis=0)]
    )  # exclusive checkpoints: occ_samples[k] counts bwt[: k*r]

    bits = 0 if pack is False else packed_bits(sigma, sample_rate)
    if pack and not bits:
        raise ValueError(
            f"cannot pack sigma={sigma} at sample_rate={sample_rate}"
        )
    fused = None
    if bits:
        words = pack_words(padded, bits).reshape(n_blocks, -1)
        fused = place_rows(jnp.concatenate([occ_samples[:-1], words], axis=1))

    if sa_samples is not None:
        sa_marks, sa_mark_ranks, sa_vals, sa_val_bits = sa_samples
    elif sa is not None:
        sa_marks, sa_mark_ranks, sa_vals, sa_val_bits = build_sa_samples(
            sa, sa_sample_rate, compress=compress_sa
        )
    else:
        sa_marks = sa_mark_ranks = sa_vals = None
        sa_sample_rate = sa_val_bits = 0

    # the padded copy keeps every in-block dynamic_slice in bounds
    return FMIndex(padded, jnp.asarray(row, jnp.int32), c_array, occ_samples,
                   fused, sa_marks, sa_mark_ranks, sa_vals,
                   sample_rate, sigma, n, bits, sa_sample_rate, sa_val_bits)


def occ_batch(index: FMIndex, c: jax.Array, p: jax.Array) -> jax.Array:
    """# occurrences of c_i in ``bwt[:p_i]`` (exclusive rank), batched.

    Dispatches through kernels/ops: packed popcount rank when the index is
    bit-packed, batched unpacked gather otherwise.  p == n_blocks*r is
    folded into the last block (cutoff r) so checkpoints beyond the fused
    rows are never needed.
    """
    r = index.sample_rate
    blk = jnp.minimum(p // r, index.n_blocks - 1)
    cut = p - blk * r
    if index.bits:
        return ops.rank_packed(index.fused, blk, c, cut,
                               bits=index.bits, sigma=index.sigma)
    base = index.occ_samples[blk, c]
    blocks = index.bwt.reshape(index.n_blocks, r)
    return base + ops.rank_unpacked(blocks, blk, c, cut)


def occ(index: FMIndex, c: jax.Array, p: jax.Array) -> jax.Array:
    """Scalar Occ(c, p): int32 scalars in, int32 scalar out — convenience
    wrapper over the batched path (same kernel dispatch)."""
    return occ_batch(index, c[None] if c.ndim == 0 else c,
                     p[None] if p.ndim == 0 else p)[0]


def _interval_step(c, sp, ep, sigma: int, rank):
    """One backward-search transition, shared by the monolithic and the
    stacked (segment-parallel) paths — any divergence here would break
    their bit-identity.  ``rank(c_safe, p)`` maps a symbol/position pair to
    ``C[c] + Occ(c, p)``; all arrays are elementwise-broadcastable.

    PAD steps are no-ops; an already-empty interval stays empty; an
    out-of-alphabet symbol (unknown to the index) empties it."""
    in_alphabet = (c >= 1) & (c < sigma)
    valid = in_alphabet & (ep > sp)
    c_safe = jnp.where(in_alphabet, c, 0)
    nsp = rank(c_safe, sp)
    nep = rank(c_safe, ep)
    return (
        jnp.where(valid, nsp, sp),
        jnp.where(valid, nep, jnp.where((c != PAD) & ~in_alphabet, sp, ep)),
    )


@jax.named_scope("backward_search")
def backward_search_batch(
    index: FMIndex, patterns: jax.Array
) -> tuple[jax.Array, jax.Array]:
    """(sp, ep) suffix-array intervals for int32[B, m] PAD-padded patterns.

    Count of exact occurrences is ``ep - sp``.  One scan step per pattern
    position; each step issues a single batched rank call per interval end,
    so the whole batch shares kernel launches.
    """
    B = patterns.shape[0]

    def rank(c, p):
        return index.c_array[c] + occ_batch(index, c, p)

    def step(state, c):
        return _interval_step(c, *state, index.sigma, rank), None

    # process right-to-left; PADs sit on the right so they come first and
    # are skipped by ``valid``
    init = (jnp.zeros(B, jnp.int32), jnp.full((B,), index.n, jnp.int32))
    (sp, ep), _ = lax.scan(step, init, patterns.T[::-1])
    return sp, ep


def backward_search(index: FMIndex, pattern: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Single-pattern (sp, ep) — batched path with B=1."""
    sp, ep = backward_search_batch(index, pattern[None, :])
    return sp[0], ep[0]


@jax.jit
def count(index: FMIndex, patterns: jax.Array) -> jax.Array:
    """Batched exact-match counts: patterns int32[B, m] PAD-padded (PAD =
    -1 on the right) -> counts int32[B].  One rank-kernel dispatch per
    pattern position and interval end (see ``occ_batch``), jit-cached per
    (B, m) shape."""
    sp, ep = backward_search_batch(index, patterns)
    return jnp.maximum(ep - sp, 0)


@jax.named_scope("sample_lookup")
def sample_lookup(marks, mark_ranks, vals, rows, *, val_bits: int = 0,
                  val_scale: int = 1, idx_offset=0):
    """(marked, value) of the SA sample at each row (value garbage when
    unmarked).  Raw-array form shared with the distributed index and the
    stacked segment-parallel path.

    ``rows`` int32[B]; ``val_bits`` > 0 decodes the bit-packed value stream
    (value = packed quotient * ``val_scale``, the sampling stride); 0 reads
    raw int32 values.  ``idx_offset`` shifts the value-stream index (the
    stacked path concatenates per-segment value arrays and passes each
    lane's segment base).
    """
    w = rows // 32
    b = (rows % 32).astype(jnp.uint32)
    word = lax.bitcast_convert_type(marks[w], jnp.uint32)
    marked = ((word >> b) & jnp.uint32(1)).astype(bool)
    below = lax.population_count(
        word & ((jnp.uint32(1) << b) - jnp.uint32(1))
    )
    idx = mark_ranks[w] + below.astype(jnp.int32) + idx_offset
    if val_bits:
        val = unpack_sa_value(vals, idx, val_bits) * val_scale
    else:
        val = vals[jnp.clip(idx, 0, vals.shape[0] - 1)]
    return marked, val


def _sample_lookup(index: FMIndex, rows: jax.Array):
    return sample_lookup(index.sa_marks, index.sa_mark_ranks, index.sa_vals,
                         rows, val_bits=index.sa_val_bits,
                         val_scale=index.sa_sample_rate)


def packed_symbol(fused, blk, j, *, sigma: int, bits: int) -> jax.Array:
    """Decode symbol ``j`` of fused row ``blk`` from the packed words —
    the one packed-layout decode, shared by the monolithic and stacked
    paths."""
    fpw = 32 // bits
    word = fused[blk, 0, sigma + j // fpw]
    w = lax.bitcast_convert_type(word, jnp.uint32)
    sh = ((j % fpw) * bits).astype(jnp.uint32)
    return ((w >> sh) & jnp.uint32((1 << bits) - 1)).astype(jnp.int32)


def bwt_symbol(index: FMIndex, rows: jax.Array) -> jax.Array:
    """bwt[rows] batched: rows int32[B] -> symbols int32[B] — extracted
    from packed words when bit-packed, so the locate walk touches only the
    compact layout."""
    if not index.bits:
        return index.bwt[rows]
    r = index.sample_rate
    return packed_symbol(index.fused, rows // r, rows % r,
                         sigma=index.sigma, bits=index.bits)


@jax.named_scope("locate_walk")
def _locate_walk(n_steps: int, rows, valid, lookup, lf_next):
    """The locate LF-walk, shared by the monolithic and stacked paths —
    any divergence here would break their bit-identity.  Each lane walks
    ``rows`` toward its nearest SA-sampled row: ``lookup(rows)`` ->
    (marked, sampled value), ``lf_next(rows)`` -> LF-mapped rows.  Returns
    flat positions (garbage where ``~valid``)."""

    def body(_, st):
        rows, pos, steps, done = st
        marked, val = lookup(rows)
        pos = jnp.where(marked & ~done, val + steps, pos)
        done = done | marked
        rows = jnp.where(done, rows, lf_next(rows))
        steps = steps + jnp.where(done, 0, 1)
        return rows, pos, steps, done

    zeros = jnp.zeros(rows.shape[0], jnp.int32)
    _, pos, _, _ = lax.fori_loop(
        0, n_steps, body, (rows, zeros, zeros, ~valid)
    )
    return pos


@functools.partial(jax.jit, static_argnames=("k",))
def locate(
    index: FMIndex, patterns: jax.Array, k: int
) -> tuple[jax.Array, jax.Array]:
    """First-k occurrence positions per pattern via the SA sample.

    patterns int32[B, m] PAD-padded.  Returns (positions int32[B, k] sorted
    ascending with ``n`` filling unused slots, counts int32[B] clipped to k).
    Each of the B*k candidate rows LF-walks (<= sa_sample_rate - 1 steps,
    every step one batched rank call) to its nearest marked row; position =
    sampled value + steps walked.
    """
    if index.sa_sample_rate == 0:
        raise ValueError("index built without sa= — locate unavailable")
    sp, ep = backward_search_batch(index, patterns)
    B = sp.shape[0]
    rows = (sp[:, None] + jnp.arange(k, dtype=jnp.int32)[None, :])
    valid = (rows < ep[:, None]).reshape(-1)
    rows = jnp.where(valid, rows.reshape(-1), 0)

    def lf_next(rows):
        c = bwt_symbol(index, rows)
        return index.c_array[c] + occ_batch(index, c, rows)

    pos = _locate_walk(index.sa_sample_rate, rows, valid,
                       lambda rows: _sample_lookup(index, rows), lf_next)
    out = jnp.where(valid, pos, index.n).reshape(B, k)
    return jnp.sort(out, axis=1), jnp.minimum(jnp.maximum(ep - sp, 0), k)


# -- segment-parallel stacked queries ----------------------------------------
#
# A SegmentedIndex answers a query by asking every live segment.  Done
# naively that is one jit dispatch per segment per backward-search step; the
# stacked layout below pads every segment's fused rows to one bucket shape
# (power-of-two block count) and concatenates them row-wise, so the whole
# catalog answers through a SINGLE kernels/ops rank call per step — the
# per-query work is identical element-wise to the sequential path, so the
# results are bit-identical (asserted in tests/test_segments.py).


def _next_pow2(x: int) -> int:
    p = 1
    while p < x:
        p *= 2
    return p


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class StackedFMIndex:
    """S per-segment FM-indexes padded to one bucket shape.

    ``fused``/``blocks`` rows of all segments concatenate along axis 0
    (segment s owns rows [s*blocks_pad, s*blocks_pad + n_blocks[s])), so a
    flat query vector carrying a segment id per lane addresses the whole
    catalog in one gather.  Bucket shapes (``seg_pad`` segments x
    ``blocks_pad`` blocks, both powers of two) keep the jit cache stable as
    segments append and compact.  Pad segments have length 0 (their search
    interval starts empty) and pad blocks are never addressed (block ids
    clamp to the true per-segment ``n_blocks``).  SA-sample values are
    stored raw (packed streams are decoded at stack time) so one decode
    path serves every segment.
    """

    fused: jax.Array | None    # int32[S*NB, 1, sigma + W]  (packed layout,
                               # placed as FMIndex.fused)
    blocks: jax.Array | None   # int32[S*NB, r]             (unpacked layout)
    occ: jax.Array | None      # int32[S, NB, sigma]        (unpacked layout)
    c_array: jax.Array         # int32[S, sigma]
    n_blocks: jax.Array        # int32[S] true per-segment block counts
    lengths: jax.Array         # int32[S] true per-segment text lengths
    sa_marks: jax.Array | None       # int32[S*MW] (segment-major)
    sa_mark_ranks: jax.Array | None  # int32[S*MW] per-segment cumsums
    sa_vals: jax.Array | None        # int32[S*MV] raw (decoded) SA values
    n_seg: jax.Array    # int32 scalar: real segment count (<= seg_pad) —
                        # a LEAF, not static aux: appending a segment into
                        # spare bucket capacity must not recompile
    seg_pad: int        # static: padded segment count S
    blocks_pad: int     # static: padded per-segment block count NB
    sample_rate: int    # static
    sigma: int          # static
    bits: int           # static
    sa_sample_rate: int  # static (0 = no locate)

    def tree_flatten(self):
        return (
            (self.fused, self.blocks, self.occ, self.c_array, self.n_blocks,
             self.lengths, self.sa_marks, self.sa_mark_ranks, self.sa_vals,
             self.n_seg),
            (self.seg_pad, self.blocks_pad, self.sample_rate,
             self.sigma, self.bits, self.sa_sample_rate),
        )

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, *aux)


def stack_fm_indexes(
    fms: list[FMIndex], *, seg_pad: int | None = None,
    blocks_pad: int | None = None,
) -> StackedFMIndex:
    """Assemble single-device FM-indexes into one stacked bucket layout.

    All indexes must agree on (sigma, sample_rate, bits, sa_sample_rate) —
    segments built through one ``SegmentedIndex`` do by construction (the
    declared alphabet reserves the pad slot, see ``pipeline.prepare_tokens``).
    Raises ``ValueError`` on a mixed catalog (e.g. segments restored from a
    pre-uniform-alphabet checkpoint); callers fall back to the sequential
    path.  ``seg_pad``/``blocks_pad`` override the power-of-two bucket
    defaults (must be >= the real sizes).
    """
    if not fms:
        raise ValueError("cannot stack an empty catalog")
    f0 = fms[0]
    sig = (f0.sigma, f0.sample_rate, f0.bits, f0.sa_sample_rate)
    for fm in fms:
        if not isinstance(fm, FMIndex):
            raise ValueError(f"cannot stack {type(fm).__name__}")
        if (fm.sigma, fm.sample_rate, fm.bits, fm.sa_sample_rate) != sig:
            raise ValueError(
                "mixed segment layouts: "
                f"{(fm.sigma, fm.sample_rate, fm.bits, fm.sa_sample_rate)} "
                f"!= {sig}"
            )
    sigma, r, bits, srate = sig
    S = seg_pad or _next_pow2(len(fms))
    NB = blocks_pad or _next_pow2(max(fm.n_blocks for fm in fms))
    if S < len(fms) or NB < max(fm.n_blocks for fm in fms):
        raise ValueError("bucket shape smaller than the catalog")

    fused = blocks = occ = None
    if bits:
        fused_np = np.zeros((S * NB,) + f0.fused.shape[1:], np.int32)
        for i, fm in enumerate(fms):
            fused_np[i * NB : i * NB + fm.n_blocks] = np.asarray(fm.fused)
        fused = place_rows(jnp.asarray(fused_np))
    else:
        blocks_np = np.full((S * NB, r), PAD, np.int32)
        occ_np = np.zeros((S, NB, sigma), np.int32)
        for i, fm in enumerate(fms):
            nb = fm.n_blocks
            blocks_np[i * NB : i * NB + nb] = (
                np.asarray(fm.bwt).reshape(nb, r)
            )
            occ_np[i, :nb] = np.asarray(fm.occ_samples)[:-1]
        blocks, occ = jnp.asarray(blocks_np), jnp.asarray(occ_np)

    c_np = np.zeros((S, sigma), np.int32)
    nb_np = np.ones(S, np.int32)       # pad segments clamp blk to 0
    len_np = np.zeros(S, np.int32)     # pad segments start with ep == 0
    for i, fm in enumerate(fms):
        c_np[i] = np.asarray(fm.c_array)
        nb_np[i] = fm.n_blocks
        len_np[i] = fm.length

    sa_marks = sa_mark_ranks = sa_vals = None
    if srate:
        MW = -(-(NB * r) // 32)
        MV = -(-(NB * r) // srate)
        marks_np = np.zeros((S, MW), np.int32)
        ranks_np = np.zeros((S, MW), np.int32)
        vals_np = np.zeros((S, MV), np.int32)
        for i, fm in enumerate(fms):
            m = np.asarray(fm.sa_marks)
            marks_np[i, : m.shape[0]] = m
            ranks_np[i, : m.shape[0]] = np.asarray(fm.sa_mark_ranks)
            raw = decode_sa_values(fm)
            vals_np[i, : raw.shape[0]] = raw
        sa_marks, sa_mark_ranks, sa_vals = (
            jnp.asarray(marks_np.reshape(-1)),
            jnp.asarray(ranks_np.reshape(-1)),
            jnp.asarray(vals_np.reshape(-1)),
        )

    return StackedFMIndex(
        fused, blocks, occ, jnp.asarray(c_np), jnp.asarray(nb_np),
        jnp.asarray(len_np), sa_marks, sa_mark_ranks, sa_vals,
        jnp.asarray(len(fms), jnp.int32), S, NB, r, sigma, bits, srate,
    )


def stack_rank_arrays(fms: list[FMIndex], *, seg_pad: int | None = None,
                      blocks_pad: int | None = None):
    """Bucket-stack the rank-addressable arrays of same-layout indexes:
    ``(fused, blocks, occ, c_mat, nb_vec, blocks_pad)`` with segment i
    owning block rows [i*blocks_pad, i*blocks_pad + n_blocks_i).

    The rank-only core of ``stack_fm_indexes`` (no SA-sample stacking),
    built for the k-way merge walk: one batched ``ops.rank_walkers``
    dispatch addresses every walker's segment through a single array, and
    the pow2 bucket (``seg_pad`` segments x ``blocks_pad`` blocks) keeps
    steady-state compactions re-hitting one compiled walk per shape.
    ``occ`` is flattened to int32[S*NB, sigma] so packed and unpacked
    layouts share the flat ``seg * blocks_pad + blk`` addressing."""
    if not fms:
        raise ValueError("cannot stack an empty run")
    f0 = fms[0]
    sig = (f0.sigma, f0.sample_rate, f0.bits)
    for fm in fms:
        if (fm.sigma, fm.sample_rate, fm.bits) != sig:
            raise ValueError(
                f"mixed layouts {(fm.sigma, fm.sample_rate, fm.bits)} "
                f"!= {sig}"
            )
    sigma, r, bits = sig
    S = seg_pad or _next_pow2(len(fms))
    NB = blocks_pad or _next_pow2(max(fm.n_blocks for fm in fms))
    if S < len(fms) or NB < max(fm.n_blocks for fm in fms):
        raise ValueError("bucket shape smaller than the run")
    fused = blocks = occ = None
    if bits:
        fused_np = np.zeros((S * NB,) + f0.fused.shape[1:], np.int32)
        for i, fm in enumerate(fms):
            fused_np[i * NB : i * NB + fm.n_blocks] = np.asarray(fm.fused)
        fused = place_rows(jnp.asarray(fused_np))
    else:
        blocks_np = np.full((S * NB, r), PAD, np.int32)
        occ_np = np.zeros((S * NB, sigma), np.int32)
        for i, fm in enumerate(fms):
            nb = fm.n_blocks
            blocks_np[i * NB : i * NB + nb] = (
                np.asarray(fm.bwt).reshape(nb, r)
            )
            occ_np[i * NB : i * NB + nb] = np.asarray(fm.occ_samples)[:-1]
        blocks, occ = jnp.asarray(blocks_np), jnp.asarray(occ_np)
    c_np = np.zeros((S, sigma), np.int32)
    for i, fm in enumerate(fms):
        c_np[i] = np.asarray(fm.c_array)
    return fused, blocks, occ, jnp.asarray(c_np), jnp.asarray(
        np.array([fm.n_blocks for fm in fms] + [1] * (S - len(fms)),
                 np.int32)
    ), NB


def _stack_check(st: StackedFMIndex, fm: FMIndex) -> None:
    """Raise unless ``fm`` fits the stacked bucket layout (same static
    signature, block count within the bucket)."""
    if not isinstance(fm, FMIndex):
        raise ValueError(f"cannot stack {type(fm).__name__}")
    sig = (st.sigma, st.sample_rate, st.bits, st.sa_sample_rate)
    if (fm.sigma, fm.sample_rate, fm.bits, fm.sa_sample_rate) != sig:
        raise ValueError(
            "segment layout does not match the stacked catalog: "
            f"{(fm.sigma, fm.sample_rate, fm.bits, fm.sa_sample_rate)} "
            f"!= {sig}"
        )
    if fm.n_blocks > st.blocks_pad:
        raise ValueError(
            f"segment blocks {fm.n_blocks} exceed bucket {st.blocks_pad}"
        )


def _seg_rows(st: StackedFMIndex, fm: FMIndex):
    """One segment's per-leaf row payloads, padded to the bucket shapes —
    the update unit shared by ``stacked_append`` and ``stacked_replace``."""
    NB, r, sigma = st.blocks_pad, st.sample_rate, st.sigma
    out = {}
    if st.bits:
        rows = jnp.zeros((NB,) + st.fused.shape[1:], jnp.int32)
        out["fused"] = rows.at[: fm.n_blocks].set(fm.fused)
    else:
        rows = jnp.full((NB, r), PAD, jnp.int32)
        out["blocks"] = rows.at[: fm.n_blocks].set(
            fm.bwt.reshape(fm.n_blocks, r)
        )
        occ = jnp.zeros((NB, sigma), jnp.int32)
        out["occ"] = occ.at[: fm.n_blocks].set(fm.occ_samples[:-1])
    out["c_array"] = fm.c_array
    out["n_blocks"] = jnp.asarray(fm.n_blocks, jnp.int32)
    out["lengths"] = jnp.asarray(fm.length, jnp.int32)
    if st.sa_sample_rate:
        MW = st.sa_marks.shape[0] // st.seg_pad
        MV = st.sa_vals.shape[0] // st.seg_pad
        m = np.asarray(fm.sa_marks)
        marks = np.zeros(MW, np.int32)
        ranks = np.zeros(MW, np.int32)
        vals = np.zeros(MV, np.int32)
        marks[: m.shape[0]] = m
        ranks[: m.shape[0]] = np.asarray(fm.sa_mark_ranks)
        raw = decode_sa_values(fm)
        vals[: raw.shape[0]] = raw
        out["sa_marks"] = jnp.asarray(marks)
        out["sa_mark_ranks"] = jnp.asarray(ranks)
        out["sa_vals"] = jnp.asarray(vals)
    return out


def stacked_append(st: StackedFMIndex, fm: FMIndex) -> StackedFMIndex:
    """Append one segment into spare bucket capacity, in place.

    Writes the new segment's rows into slot ``n_seg`` of every leaf and
    bumps ``n_seg`` — all static shapes and aux data are unchanged, so the
    query jit programs compiled for the old catalog serve the new one
    without recompiling (``n_seg`` is a pytree leaf).  Raises ``ValueError``
    when the bucket is full or the segment does not fit; callers re-stack.
    """
    _stack_check(st, fm)
    i = int(st.n_seg)
    if i >= st.seg_pad:
        raise ValueError(f"stacked catalog full ({i} == seg_pad)")
    NB = st.blocks_pad
    rows = _seg_rows(st, fm)
    rep = {"n_seg": jnp.asarray(i + 1, jnp.int32)}
    for name in ("fused", "blocks"):
        if rows.get(name) is not None and getattr(st, name) is not None:
            rep[name] = getattr(st, name).at[i * NB : (i + 1) * NB].set(
                rows[name]
            )
    if st.bits:
        rep["fused"] = place_rows(rep["fused"])
    else:
        rep["occ"] = st.occ.at[i].set(rows["occ"])
    rep["c_array"] = st.c_array.at[i].set(rows["c_array"])
    rep["n_blocks"] = st.n_blocks.at[i].set(rows["n_blocks"])
    rep["lengths"] = st.lengths.at[i].set(rows["lengths"])
    if st.sa_sample_rate:
        MW = st.sa_marks.shape[0] // st.seg_pad
        MV = st.sa_vals.shape[0] // st.seg_pad
        rep["sa_marks"] = st.sa_marks.at[i * MW : (i + 1) * MW].set(
            rows["sa_marks"]
        )
        rep["sa_mark_ranks"] = st.sa_mark_ranks.at[
            i * MW : (i + 1) * MW
        ].set(rows["sa_mark_ranks"])
        rep["sa_vals"] = st.sa_vals.at[i * MV : (i + 1) * MV].set(
            rows["sa_vals"]
        )
    return dataclasses.replace(st, **rep)


def stacked_replace_run(st: StackedFMIndex, start: int, count: int,
                        fm: FMIndex) -> StackedFMIndex:
    """Replace segments [start, start+count) with one merged segment.

    The incremental stacked-catalog update after a merge compaction:
    later segments shift left on-device (concatenation of existing leaf
    slices — no host re-assembly of the whole catalog), bucket shapes stay
    fixed, so steady-state compaction re-hits the same query jit programs.
    Raises ``ValueError`` when the merged segment does not fit the bucket.
    """
    _stack_check(st, fm)
    n = int(st.n_seg)
    if not (0 <= start and count >= 1 and start + count <= n):
        raise ValueError(f"bad run [{start}, {start + count}) of {n}")
    rows = _seg_rows(st, fm)
    n_new = n - count + 1
    S = st.seg_pad

    def splice(arr, unit, new_rows, fill):
        head = arr[: (start + 1) * unit].at[
            start * unit : (start + 1) * unit
        ].set(new_rows)
        tail = arr[(start + count) * unit : n * unit]
        npad = S * unit - head.shape[0] - tail.shape[0]
        pad = jnp.broadcast_to(
            fill, (npad,) + arr.shape[1:]
        ).astype(arr.dtype)
        return jnp.concatenate([head, tail, pad])

    rep = {"n_seg": jnp.asarray(n_new, jnp.int32)}
    NB = st.blocks_pad
    if st.bits:
        rep["fused"] = place_rows(splice(st.fused, NB, rows["fused"], 0))
    else:
        rep["blocks"] = splice(st.blocks, NB, rows["blocks"], PAD)
        rep["occ"] = splice(st.occ, 1, rows["occ"][None], 0)
    rep["c_array"] = splice(st.c_array, 1, rows["c_array"][None], 0)
    # pad segments clamp blk to 0 and start with ep == 0 (stack invariant)
    rep["n_blocks"] = splice(st.n_blocks, 1, rows["n_blocks"][None], 1)
    rep["lengths"] = splice(st.lengths, 1, rows["lengths"][None], 0)
    if st.sa_sample_rate:
        MW = st.sa_marks.shape[0] // st.seg_pad
        MV = st.sa_vals.shape[0] // st.seg_pad
        rep["sa_marks"] = splice(st.sa_marks, MW, rows["sa_marks"], 0)
        rep["sa_mark_ranks"] = splice(
            st.sa_mark_ranks, MW, rows["sa_mark_ranks"], 0
        )
        rep["sa_vals"] = splice(st.sa_vals, MV, rows["sa_vals"], 0)
    return dataclasses.replace(st, **rep)


def _stacked_occ_batch(st: StackedFMIndex, seg, c, p):
    """Occ(c_i, p_i) inside segment seg_i — flat int32[Q] lanes, one
    kernels/ops dispatch for the whole catalog (the fan-out hot path)."""
    r = st.sample_rate
    blk = jnp.minimum(p // r, st.n_blocks[seg] - 1)
    cut = p - blk * r
    row = seg * st.blocks_pad + blk
    if st.bits:
        return ops.rank_packed(st.fused, row, c, cut,
                               bits=st.bits, sigma=st.sigma)
    base = st.occ[seg, blk, c]
    return base + ops.rank_unpacked(st.blocks, row, c, cut)


@jax.named_scope("backward_search")
def _stacked_backward_search(st: StackedFMIndex, patterns: jax.Array):
    """(sp, ep) int32[S, B]: every pattern against every segment, two rank
    dispatches per scan step (``_interval_step`` — the exact transition of
    ``backward_search_batch`` — over lanes flattened to segments x batch).
    """
    S, B = st.seg_pad, patterns.shape[0]
    seg = jnp.repeat(jnp.arange(S, dtype=jnp.int32), B)

    def rank(c, p):
        cf, pf = c.reshape(-1), p.reshape(-1)
        return (st.c_array[seg, cf]
                + _stacked_occ_batch(st, seg, cf, pf)).reshape(S, B)

    def step(state, c):
        cB = jnp.broadcast_to(c[None, :], (S, B))
        return _interval_step(cB, *state, st.sigma, rank), None

    init = (jnp.zeros((S, B), jnp.int32),
            jnp.broadcast_to(st.lengths[:, None], (S, B)))
    (sp, ep), _ = lax.scan(step, init, patterns.T[::-1])
    return sp, ep


@jax.jit
def count_stacked(st: StackedFMIndex, patterns: jax.Array) -> jax.Array:
    """Per-segment exact-match counts, int32[S, B] for int32[B, m]
    PAD-padded patterns; row s is bit-identical to ``count`` on segment s
    alone (pad-segment rows are all zero)."""
    sp, ep = _stacked_backward_search(st, patterns)
    return jnp.maximum(ep - sp, 0)


@functools.partial(jax.jit, static_argnames=("k",))
def locate_stacked(
    st: StackedFMIndex, patterns: jax.Array, k: int
) -> tuple[jax.Array, jax.Array]:
    """Per-segment first-k locate: (positions int32[S, B, k] segment-local,
    sorted, filled with the segment length; counts int32[S, B] clipped to
    k).  Row s is bit-identical to ``locate`` on segment s alone; the
    caller offsets to global coordinates and merges."""
    if st.sa_sample_rate == 0:
        raise ValueError("catalog stacked without SA samples — no locate")
    sp, ep = _stacked_backward_search(st, patterns)
    S, B = sp.shape
    seg = jnp.repeat(jnp.arange(S, dtype=jnp.int32), B * k)
    rows = sp[:, :, None] + jnp.arange(k, dtype=jnp.int32)[None, None, :]
    valid = (rows < ep[:, :, None]).reshape(-1)
    rows = jnp.where(valid, rows.reshape(-1), 0)

    # per-segment SA-sample strides in the flat (segment-major) arrays:
    # pseudo-row seg*MW*32 + row lands on segment seg's mark words, and
    # idx_offset shifts into its slice of the value stream
    MW = st.sa_marks.shape[0] // st.seg_pad
    MV = st.sa_vals.shape[0] // st.seg_pad

    def lookup(rows):
        return sample_lookup(st.sa_marks, st.sa_mark_ranks, st.sa_vals,
                             seg * (MW * 32) + rows, idx_offset=seg * MV)

    def lf_next(rows):
        r = st.sample_rate
        blk = seg * st.blocks_pad + rows // r
        if st.bits:
            c = packed_symbol(st.fused, blk, rows % r,
                              sigma=st.sigma, bits=st.bits)
        else:
            c = st.blocks[blk, rows % r]
        return st.c_array[seg, c] + _stacked_occ_batch(st, seg, c, rows)

    pos = _locate_walk(st.sa_sample_rate, rows, valid, lookup, lf_next)
    fill = jnp.repeat(st.lengths, B * k)
    out = jnp.where(valid, pos, fill).reshape(S, B, k)
    return (jnp.sort(out, axis=2),
            jnp.minimum(jnp.maximum(ep - sp, 0), k))


def locate_naive(index: FMIndex, sa: jax.Array, pattern: jax.Array) -> jax.Array:
    """Occurrence positions via a full SA (test oracle for ``locate``)."""
    sp, ep = backward_search(index, pattern)
    return jnp.sort(jnp.where(
        (jnp.arange(index.n) >= sp) & (jnp.arange(index.n) < ep), sa, index.n
    ))


def count_naive(text, pattern) -> int:
    """Overlapping substring-count numpy oracle."""
    text, pattern = np.asarray(text), np.asarray(pattern)
    m = len(pattern)
    if m == 0 or m > len(text):
        return 0
    windows = np.lib.stride_tricks.sliding_window_view(text, m)
    return int((windows == pattern).all(axis=1).sum())
