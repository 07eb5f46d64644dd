"""Rebuild-free BWT merge of two adjacent index segments (Sirén-style).

``SegmentedIndex.compact`` used to throw away per-segment BWTs and rebuild
the merged segment from raw tokens — O(total tokens) of suffix sorting per
compaction.  This module merges two built FM-indexes directly, the way
Sirén's *BWT for terabases* (arXiv:1511.00898) grows terabase BWTs: the
merged suffix order is an **interleave** of the two segments' suffix
orders, and the interleave bitvector is computed by LF-stepping the right
segment's symbols through the left segment's FM-index — one fused
``kernels/ops`` rank call per step (Pallas popcount kernel on TPU, jnp
fallback elsewhere), never touching raw tokens or running a sort.

Let ``TA``/``TB`` be the two segments' *prepared* texts (each a
concatenation of sentinel-terminated, pad-filled documents — see
``pipeline.prepare_tokens``) and ``U = TA · TB`` the merged text.  Because
every document carries its own sentinel and pad run:

* suffixes of ``U`` starting inside ``TB`` are literally the standalone
  suffixes of ``TB`` (it sits at the end), and
* suffixes starting inside ``TA`` keep their standalone relative order —
  **provided TA is context-order safe against TB**
  (``context_order_safe``).  A single prepared document always is:
  comparisons between two of its suffixes resolve at its unique sentinel
  or inside its trailing pad run, before the continuation into ``TB``
  can matter.  A multi-document TA can contain one suffix that is a
  proper prefix of another — e.g. two identical documents — whose order
  legitimately depends on what follows; the exact token-level check
  admits such a text whenever the actual continuation preserves the
  order, lifting the former "multi-document texts only on the RIGHT"
  restriction (``segments._plan_run`` checks it per operand, falling
  back to a rebuild — now counted and warned — when it fails).

So ``SA(U)`` interleaves ``SA(TA)`` and ``SA(TB)``, and ``BWT(U)`` is the
corresponding interleave of the two BWTs with exactly two cells exchanged
(the wrap-around characters at each side's row of suffix 0).  The
interleave is produced by one backward walk over ``TB``, tracked entirely
inside the two indexes:

    I(j) = #{TA suffixes (continued into TB) < TB[j:]}
         = C_A[c] + Occ_A(c, I(j+1))
           + [c = lastA] * ([rowB < r(j+1)] - [rowA < I(j+1)])
    r(j) = C_B[c] + Occ_B(c, r(j+1)) + [c = lastB] * [r(j+1) <= rowB]

with ``c = BWT_B[r(j+1)] = TB[j]``, ``lastX = BWT_X[rowX]`` the last
character of each text and ``r(j) = ISA_B[j]``.  The first correction
accounts for TA's final suffix continuing into ``TB`` instead of ending;
the second repairs the cyclic wrap entry that ``bwt_from_sa`` stores at
``rowB`` (exact for any multi-document right operand).  The walk anchors
at ``I(nB-1) = C_A[lastB]``, ``r(nB-1) = C_B[lastB]`` — the shortest
suffix of ``TB`` sorts before every longer suffix sharing its first
character.

The merged SA sample is spliced from the per-segment samples: left rows
keep their values, right rows shift by ``len(TA)`` (requiring the stride
to divide ``len(TA)`` — checked by ``merge_eligible``), and the merged
stream re-packs at the merged bit width through the same
``fm_index.sample_arrays_from_rows`` constructor the rebuild path uses.
The result is bit-identical to rebuilding over ``U`` from raw tokens
(asserted per-trajectory by ``tests/test_lifecycle_fuzz.py``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..kernels import ops
from ..kernels.rank_select import place_rows
from ..testing.faultinject import fault_point
from .fm_index import (
    FMIndex,
    _next_pow2,
    build_fm_index,
    decode_sa_values,
    packed_symbol,
    sample_arrays_from_rows,
    sample_marked_rows,
    stack_rank_arrays,
)


def merge_eligible(left: FMIndex, right: FMIndex) -> str | None:
    """Why the pair cannot BWT-merge, or None when it can.

    The left operand's text must additionally be *context-order safe*
    against the right's (``context_order_safe``; single prepared
    documents always are — callers know the document structure and
    tokens, this function checks only what the indexes expose).  The
    rebuild path remains the fallback (and the bit-identity oracle) for
    every ineligible pair.
    """
    for side, fm in (("left", left), ("right", right)):
        if not isinstance(fm, FMIndex):
            return f"{side} segment is not a single-device FMIndex"
    sig_l = (left.sigma, left.sample_rate, left.bits, left.sa_sample_rate)
    sig_r = (right.sigma, right.sample_rate, right.bits, right.sa_sample_rate)
    if sig_l != sig_r:
        return f"mixed layouts {sig_l} != {sig_r}"
    for side, fm in (("left", left), ("right", right)):
        if fm.length % fm.sample_rate:
            return f"{side} length {fm.length} not a block multiple"
    if left.sa_sample_rate:
        if left.sa_marks is None or right.sa_marks is None:
            return "missing SA sample arrays"
        if left.length % left.sa_sample_rate:
            return (
                f"SA stride {left.sa_sample_rate} does not divide left "
                f"length {left.length}"
            )
    return None


def _bucket_rows(arr, rows: int, fill):
    """Pad a row-major array to ``rows`` rows so the walk's jit program is
    reused across merges within the same power-of-two bucket."""
    if arr.shape[0] == rows:
        return arr
    pad = jnp.broadcast_to(
        fill, (rows - arr.shape[0],) + arr.shape[1:]
    ).astype(arr.dtype)
    return jnp.concatenate([arr, pad])


def _side_arrays(fm: FMIndex, nb_bucket: int):
    """(fused, blocks, occ) of one side, padded to the block bucket.  Pad
    rows are never addressed (block ids clamp to the true count)."""
    if fm.bits:
        return place_rows(_bucket_rows(fm.fused, nb_bucket, 0)), None, None
    r = fm.sample_rate
    blocks = _bucket_rows(fm.bwt.reshape(fm.n_blocks, r), nb_bucket, 0)
    occ = _bucket_rows(fm.occ_samples[:-1], nb_bucket, 0)
    return None, blocks, occ


def _occ_side(fused, blocks, occ, nb_real, c, p, *, r: int, bits: int,
              sigma: int):
    """Occ(c_i, p_i) on one side — the fused kernels/ops rank dispatch
    (p == nb_real * r folds into the last block, as in ``occ_batch``)."""
    blk = jnp.minimum(p // r, nb_real - 1)
    cut = p - blk * r
    return ops.rank_walkers(fused, blocks, occ, blk, c, cut,
                            bits=bits, sigma=sigma)


@functools.partial(jax.jit, static_argnames=("sigma", "bits", "r"))
def _merge_walk(fusedA, blocksA, occA, cA, nbA, rowA, lastA,
                fusedB, blocksB, occB, cB, nbB, rowB, lastB, nB,
                *, sigma: int, bits: int, r: int):
    """Interleave counts ``ins[row]`` = #{left suffixes < right suffix of
    that row}, for every row of the right index.

    Array shapes are bucket-padded and the true sizes (``nbA``/``nbB``
    block counts, ``nB`` text length) are traced scalars, so steady-state
    compaction re-hits one compiled program per bucket shape.  The right
    side's symbol and LF maps are precomputed in two batched dispatches;
    the walk proper then issues ONE fused rank call (on the left index)
    per step.
    """
    n_bucket = blocksB.shape[0] * r if bits == 0 else fusedB.shape[0] * r
    rows = jnp.arange(n_bucket, dtype=jnp.int32)
    # right side, batched: symbol of every row, then the (wrap-corrected)
    # LF map.  Pad rows decode garbage that the walk never visits.
    if bits:
        c_all = packed_symbol(fusedB, rows // r, rows % r,
                              sigma=sigma, bits=bits)
    else:
        c_all = blocksB[rows // r, rows % r]
    c_all = jnp.clip(c_all, 0, sigma - 1)
    lf_all = (
        cB[c_all]
        + _occ_side(fusedB, blocksB, occB, nbB, c_all, rows,
                    r=r, bits=bits, sigma=sigma)
        + ((c_all == lastB) & (rows <= rowB)).astype(jnp.int32)
    )

    ins0 = jnp.zeros(n_bucket, jnp.int32)
    # anchor: the length-1 suffix TB[nB-1:] sorts before every longer
    # suffix sharing its first character lastB
    I0, r0 = cA[lastB], cB[lastB]
    ins0 = ins0.at[r0].set(I0)

    def body(_, state):
        I, rr, ins = state
        c = c_all[rr]
        corr = jnp.where(
            c == lastA,
            (rowB < rr).astype(jnp.int32) - (rowA < I).astype(jnp.int32),
            0,
        )
        occ = _occ_side(fusedA, blocksA, occA, nbA, c[None], I[None],
                        r=r, bits=bits, sigma=sigma)[0]
        I_new = cA[c] + occ + corr
        r_new = lf_all[rr]
        return I_new, r_new, ins.at[r_new].set(I_new)

    with jax.named_scope("merge_walk"):
        _, _, ins = lax.fori_loop(0, nB - 1, body, (I0, r0, ins0))
    return ins


def merge_fm_indexes(
    left: FMIndex, right: FMIndex, *, compress_sa: bool | None = None,
    pack: bool | None = None,
) -> FMIndex:
    """BWT of ``T_left · T_right`` from the two built indexes — no sort.

    PRECONDITION (not checkable from the indexes alone): ``left``'s text
    is *context-order safe* against ``right``'s
    (``context_order_safe`` — a single prepared document always is);
    ``right`` may be any document concatenation.  ``merge_eligible`` must
    have returned None.  ``compress_sa``/``pack`` as in
    ``build_fm_index`` — pass the same knobs the rebuild path would use
    so both construct the identical layout.
    """
    reason = merge_eligible(left, right)
    if reason:
        raise ValueError(f"cannot merge: {reason}")
    nA, nB = left.length, right.length
    r, sigma, bits = left.sample_rate, left.sigma, left.bits
    nbA_b = _next_pow2(left.n_blocks)
    nbB_b = _next_pow2(right.n_blocks)
    fA, bA, oA = _side_arrays(left, nbA_b)
    fB, bB, oB = _side_arrays(right, nbB_b)
    ins = np.asarray(_merge_walk(
        fA, bA, oA, left.c_array, jnp.asarray(left.n_blocks, jnp.int32),
        left.row, left.bwt[left.row],
        fB, bB, oB, right.c_array, jnp.asarray(right.n_blocks, jnp.int32),
        right.row, right.bwt[right.row], jnp.asarray(nB, jnp.int32),
        sigma=sigma, bits=bits, r=r,
    ))[:nB].astype(np.int64)
    # a crash here leaves the operands untouched and no merged index —
    # callers (segments.compact, the frontend's growth retry) must recover
    # by retrying or keeping the pre-merge generation serving
    fault_point("merge.mid")

    # splice: right rows land at ins[k] + k, left rows fill the gaps in
    # order; then exchange the two wrap cells (each side's row of suffix 0
    # must hold the OTHER side's last character in the merged text)
    rowA, rowB = int(left.row), int(right.row)
    bwtA = np.asarray(left.bwt)[:nA]
    bwtB = np.asarray(right.bwt)[:nB]
    pos_b = ins + np.arange(nB)
    is_b = np.zeros(nA + nB, bool)
    is_b[pos_b] = True
    pos_a = np.nonzero(~is_b)[0]
    merged = np.empty(nA + nB, np.int32)
    merged[pos_a] = bwtA
    merged[pos_b] = bwtB
    merged[pos_a[rowA]] = bwtB[rowB]
    merged[pos_b[rowB]] = bwtA[rowA]

    sa_samples = None
    srate = left.sa_sample_rate
    if srate:
        rows_m = np.concatenate([
            pos_a[sample_marked_rows(left)],
            pos_b[sample_marked_rows(right)],
        ])
        vals_m = np.concatenate([
            decode_sa_values(left),
            decode_sa_values(right) + nA,
        ]).astype(np.int32)
        order = np.argsort(rows_m, kind="stable")
        sa_samples = sample_arrays_from_rows(
            rows_m[order], vals_m[order], nA + nB, srate,
            compress=compress_sa,
        )

    return build_fm_index(
        jnp.asarray(merged), jnp.asarray(pos_a[rowA], jnp.int32), sigma, r,
        pack=bool(bits) if pack is None else pack,
        sa_samples=sa_samples, sa_sample_rate=srate,
    )


# -- k-way merge --------------------------------------------------------------
#
# ``merge_kway`` generalizes the pairwise walk to a whole compaction run:
# ONE right-to-left walk over U = T_1 ··· T_k maintains k interleave
# states I_j — #{T_j suffixes (continued into the rest of U) < the current
# U-suffix} — updated per step as
#
#     I_j <- C_j[c] + Occ_j(c, I_j) + [c = last_j] * (NEXT_j - [row_j < I_j])
#
# with NEXT_j = [row_{j+1} < I_{j+1}] for j < k and NEXT_k = 1: segment
# j's final suffix continues into segment j+1's first suffix (the last
# segment's continues into nothing, which sorts before everything — the
# pairwise anchor).  At k = 2 this is exactly the pairwise recurrence
# pair.  The current suffix's merged position is simply sum_j I_j, and the
# walk's state at a segment boundary IS the next segment's entry state, so
# the k-1 walked texts chain through one loop: n - n_1 sequential steps
# total (the first text is never walked), each issuing ONE batched rank
# dispatch over a pow2-bucket-stacked array covering every walker.  The
# pairwise fold pays the same walk steps but rebuilds and re-splices every
# intermediate accumulator — Theta(n * k / 2) splice + occ-sample work vs
# the k-way walk's single Theta(n) splice.


def context_order_safe(text, continuation, *, budget: int = 1 << 24) -> bool:
    """True when ``text``'s standalone suffix order survives having
    ``continuation`` appended after it (exact, token-level).

    Standalone, a suffix that is a proper prefix of another sorts FIRST
    (shorter-first: ``suffix_array.OVERFLOW_RANK``).  In context the
    shorter suffix continues into the following text ``G`` while the
    longer continues inside ``text`` — the pair flips iff ``G`` compares
    greater.  Every tied pair shares its comparison outcome with the
    length-1 tie at the same internal position, so safety reduces to: for
    every p < n-1 with ``text[p] == text[-1]``, require
    ``G <= text[p+1:] + G``.  A single prepared document is always safe
    (its sentinel is unique and its pads sort above every real token,
    including the continuation's first); a multi-document text is unsafe
    only when a document tail recurs with an adverse continuation.
    Returns False, conservatively, when the scan exceeds ``budget``
    token comparisons — callers fall back to the rebuild path.
    """
    T = np.asarray(text, np.int64)
    G = np.asarray(continuation, np.int64)
    n, g = len(T), len(G)
    if n == 0 or g == 0:
        return True
    S = np.concatenate([T[1:], G])  # S[p:] = text[p+1:] + G
    cand = np.nonzero(T[:-1] == T[-1])[0]
    work, i = cand.size, 0
    while cand.size and i < g:
        if work > budget:
            return False
        s = S[cand + i]
        if np.any(s < G[i]):
            return False        # the longer suffix's side is smaller: flip
        cand = cand[s == G[i]]  # still tied: compare one token deeper
        work += cand.size
        i += 1
    # survivors tie through all of G: the shorter suffix ends first and
    # sorts first, matching the standalone order
    return True


def kway_eligible(fms: list[FMIndex]) -> str | None:
    """Why this ordered run of indexes cannot k-way merge, or None.

    Layout conditions only: context-order safety of every operand but the
    last (``context_order_safe`` — callers know the document structure
    and token content) is the caller's responsibility, exactly as the
    pairwise left-operand precondition is for ``merge_fm_indexes``.
    """
    if len(fms) < 2:
        return "k-way merge needs at least 2 segments"
    for i, fm in enumerate(fms):
        if not isinstance(fm, FMIndex):
            return f"segment {i} is not a single-device FMIndex"
    f0 = fms[0]
    sig0 = (f0.sigma, f0.sample_rate, f0.bits, f0.sa_sample_rate)
    for i, fm in enumerate(fms):
        sig = (fm.sigma, fm.sample_rate, fm.bits, fm.sa_sample_rate)
        if sig != sig0:
            return f"mixed layouts {sig} != {sig0}"
        if fm.length % fm.sample_rate:
            return f"segment {i} length {fm.length} not a block multiple"
        if f0.sa_sample_rate:
            if fm.sa_marks is None:
                return "missing SA sample arrays"
            if i < len(fms) - 1 and fm.length % f0.sa_sample_rate:
                return (
                    f"SA stride {f0.sa_sample_rate} does not divide "
                    f"segment {i} length {fm.length}"
                )
    return None


def kway_walk_steps(lengths) -> int:
    """Sequential rank steps of a k-way merge over prepared ``lengths``:
    everything but the first text is walked, minus the anchor state.  The
    pairwise fold (largest text leftmost) pays the same count — its extra
    cost is the per-fold intermediate splice/rebuild, not the walk."""
    lengths = list(lengths)
    return max(0, sum(lengths[1:]) - 1)


@functools.partial(jax.jit, static_argnames=("sigma", "bits", "r", "k_pad"))
def _kway_walk(fusedS, blocksS, occS, c_mat, nb_vec, row_vec, last_vec,
               n_vec, k_actual, *, sigma: int, bits: int, r: int,
               k_pad: int):
    """Interleave counts for every walked row of every walked segment:
    ``ins[s, row]`` = #{suffixes of OTHER segments < segment s's suffix of
    that row}, for s in [1, k).  Merged position = ins[s, row] + row.

    One fused ``ops.rank_walkers`` dispatch per step ranks ALL walkers
    against their segments through the ``stack_rank_arrays`` bucket;
    shapes are pow2-bucketed (``k_pad`` lanes x padded blocks) and true
    sizes are traced, so steady-state compaction re-hits one compiled
    walk per bucket shape.  Walks segments k-1 .. 1 right-to-left; the
    state crossing a segment boundary is exactly the next segment's
    anchor, so the whole run is one ``fori_loop``.
    """
    nb_pad = (fusedS if bits else blocksS).shape[0] // k_pad
    n_bucket = nb_pad * r
    lanes = jnp.arange(k_pad, dtype=jnp.int32)
    active = lanes < k_actual
    anchor = lanes == k_actual - 1

    def symbol_at(seg, rank):
        blk = seg * nb_pad + rank // r
        if bits:
            return packed_symbol(fusedS, blk, rank % r,
                                 sigma=sigma, bits=bits)
        return blocksS[blk, rank % r]

    def record(ins, seg, I_vec):
        return ins.at[seg, I_vec[seg]].set(I_vec.sum() - I_vec[seg])

    # anchor: U's length-1 suffix (the last text's final character) sorts
    # before every longer suffix sharing its first character — in EVERY
    # segment's order at once
    seg0 = k_actual - 1
    I0 = jnp.where(active, c_mat[lanes, last_vec[seg0]], 0)
    ins0 = record(jnp.zeros((k_pad, n_bucket), jnp.int32), seg0, I0)
    pos0 = n_vec[seg0] - 1

    def body(_, state):
        I_vec, seg, pos, ins = state
        boundary = pos == 0
        # the symbol to prepend: within a segment, its own BWT at the
        # self rank; at a boundary, the PREVIOUS segment's last character
        c = jnp.where(
            boundary, last_vec[seg - 1],
            jnp.clip(symbol_at(seg, I_vec[seg]), 0, sigma - 1),
        )
        # per-walker wrap corrections, all from PRE-update states: drop
        # the bogus cyclic entry stored at row_j, add segment j's final
        # suffix iff its continuation (segment j+1's first suffix; for
        # the anchor lane, nothing) precedes the current suffix
        cmp = (row_vec < I_vec).astype(jnp.int32)
        nxt = jnp.where(anchor, 1, jnp.roll(cmp, -1))
        corr = jnp.where(last_vec == c, nxt - cmp, 0)
        blk = jnp.minimum(I_vec // r, nb_vec - 1)
        occ = ops.rank_walkers(
            fusedS, blocksS, occS, lanes * nb_pad + blk,
            jnp.full((k_pad,), c, jnp.int32), I_vec - blk * r,
            bits=bits, sigma=sigma,
        )
        I_new = jnp.where(active, c_mat[lanes, c] + occ + corr, 0)
        seg_new = jnp.where(boundary, seg - 1, seg)
        pos_new = jnp.where(boundary, n_vec[seg - 1] - 1, pos - 1)
        return I_new, seg_new, pos_new, record(ins, seg_new, I_new)

    n_walk = jnp.where(active & (lanes >= 1), n_vec, 0).sum()
    with jax.named_scope("kway_walk"):
        _, _, _, ins = lax.fori_loop(
            0, n_walk - 1, body, (I0, seg0, pos0, ins0)
        )
    return ins


def merge_kway(
    fms: list[FMIndex], *, compress_sa: bool | None = None,
    pack: bool | None = None,
) -> FMIndex:
    """BWT of ``T_1 ··· T_k`` spliced from the k built indexes — one
    rank-directed interleave walk, no sort, no intermediate accumulators.

    PRECONDITION (not checkable from the indexes alone): every operand but
    the last is *context-order safe* against the concatenation following
    it (``context_order_safe``; single prepared documents always are — the
    generalization that lifts the pairwise "multi-document texts only on
    the RIGHT" restriction).  ``kway_eligible`` must have returned None.
    The first operand is never walked (``segments._plan_run`` puts the
    largest there); all others LF-step right-to-left in one chained pass.
    Bit-identical to ``build_index_prepared`` on the same concatenation,
    and to the pairwise fold at k = 2.
    """
    reason = kway_eligible(fms)
    if reason:
        raise ValueError(f"cannot merge: {reason}")
    k = len(fms)
    f0 = fms[0]
    r, sigma, bits = f0.sample_rate, f0.sigma, f0.bits
    srate = f0.sa_sample_rate
    k_pad = _next_pow2(k)
    fused, blocks, occ, c_mat, nb_vec, _ = stack_rank_arrays(
        fms, seg_pad=k_pad
    )
    lens = [fm.length for fm in fms]
    pad = [0] * (k_pad - k)
    rows = [int(fm.row) for fm in fms]
    lasts = [int(np.asarray(fm.bwt)[rows[i]]) for i, fm in enumerate(fms)]
    ins = np.asarray(_kway_walk(
        fused, blocks, occ, c_mat, nb_vec,
        jnp.asarray(np.array(rows + pad, np.int32)),
        jnp.asarray(np.array(lasts + pad, np.int32)),
        jnp.asarray(np.array(lens + pad, np.int32)),
        jnp.asarray(k, jnp.int32),
        sigma=sigma, bits=bits, r=r, k_pad=k_pad,
    )).astype(np.int64)
    # a crash here leaves the operands untouched and no merged index —
    # same recovery contract as the pairwise ``merge.mid`` point
    fault_point("merge.kway")
    fault_point("merge.mid")

    # one-pass splice: walked rows land at ins[s, row] + row, the first
    # segment's rows fill the complement in order; then the chained wrap
    # exchange — each segment's suffix-0 cell holds the char preceding it
    # in U, i.e. the PREVIOUS segment's last char (U's own last char, the
    # cyclic wrap, for segment 0)
    N = sum(lens)
    offs = np.concatenate([[0], np.cumsum(lens)]).astype(np.int64)
    merged = np.empty(N, np.int32)
    is_walked = np.zeros(N, bool)
    pos = [None] * k
    for s in range(1, k):
        ps = ins[s, : lens[s]] + np.arange(lens[s])
        pos[s] = ps
        is_walked[ps] = True
        merged[ps] = np.asarray(fms[s].bwt)[: lens[s]]
    pos[0] = np.nonzero(~is_walked)[0]
    merged[pos[0]] = np.asarray(f0.bwt)[: lens[0]]
    for s in range(k):
        merged[pos[s][rows[s]]] = lasts[(s - 1) % k]

    sa_samples = None
    if srate:
        rows_m = np.concatenate([
            pos[s][sample_marked_rows(fms[s])] for s in range(k)
        ])
        vals_m = np.concatenate([
            decode_sa_values(fms[s]) + offs[s] for s in range(k)
        ]).astype(np.int32)
        order = np.argsort(rows_m, kind="stable")
        sa_samples = sample_arrays_from_rows(
            rows_m[order], vals_m[order], N, srate, compress=compress_sa,
        )

    return build_fm_index(
        jnp.asarray(merged), jnp.asarray(pos[0][rows[0]], jnp.int32),
        sigma, r, pack=bool(bits) if pack is None else pack,
        sa_samples=sa_samples, sa_sample_rate=srate,
    )
