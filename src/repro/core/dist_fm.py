"""Distributed FM-index: sharded (bit-packed) BWT + rank queries via psum.

Scale story (DESIGN.md §2): for genome/corpus-scale indexes the BWT does not
fit one device, so it stays sharded over the mesh ``parts`` axis.  A rank
query Occ(c, p) decomposes over position ranges:

    Occ(c, p) = Σ_d  count of c in  (device d's range ∩ [0, p))

Each device answers from its local checkpoints (+ one in-block count), and a
single ``psum`` combines the partials — O(B) bytes of collective traffic per
backward-search step for a batch of B queries, independent of n.

The local rank path is the same engine as the single-device index: when the
alphabet packs (sigma <= 16) each shard stores the fused
[checkpoint | packed words] layout and dispatches through
``kernels/ops.rank_packed`` (Pallas popcount kernel on TPU, jnp fallback
elsewhere); larger alphabets fall back to ``ops.rank_unpacked``.

``dist_count`` (batched pattern counting) is the inference path lowered in
the multi-pod dry-run for the ``bwt_index`` config; ``dist_locate`` resolves
occurrence positions by LF-walking to a replicated SA sample, one psum-rank
per walk step.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from jax import shard_map
from ..kernels import ops
from ..kernels.rank_select import pack_words, packed_bits
from .fm_index import PAD, build_sa_samples, sample_lookup

AXIS = "parts"


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class DistFMIndex:
    """Global arrays carry NamedShardings; static metadata rides as aux."""

    bwt: jax.Array          # int32[n]            sharded over parts
    occ_samples: jax.Array  # int32[nblocks, sigma] sharded (exclusive, per-shard)
    c_array: jax.Array      # int32[sigma]        replicated
    row: jax.Array          # int32 scalar        replicated
    fused: jax.Array | None        # int32[nblocks, sigma+W] sharded (packed);
                                   # 2-D, so the rank kernel views and relays
                                   # out each shard itself (rank_select.py)
    sa_marks: jax.Array | None     # int32[ceil(n/32)]  replicated
    sa_mark_ranks: jax.Array | None
    sa_vals: jax.Array | None      # raw int32, or packed when sa_val_bits > 0
    sample_rate: int
    sigma: int
    length: int
    parts: int
    bits: int               # packed field width (0 = unpacked layout)
    sa_sample_rate: int     # 0 = locate unavailable
    sa_val_bits: int = 0    # bits per packed SA value (0 = raw int32)

    def tree_flatten(self):
        return ((self.bwt, self.occ_samples, self.c_array, self.row,
                 self.fused, self.sa_marks, self.sa_mark_ranks, self.sa_vals),
                (self.sample_rate, self.sigma, self.length, self.parts,
                 self.bits, self.sa_sample_rate, self.sa_val_bits))

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, *aux)


def _build_local(bwt_local: jax.Array, *, sigma: int, sample_rate: int,
                 bits: int):
    """Per-shard exclusive Occ checkpoints (+ fused packed rows) + C array."""
    m = bwt_local.shape[0]
    r = sample_rate
    nblocks = m // r
    onehot = (bwt_local[:, None] == jnp.arange(sigma)[None, :]).astype(jnp.int32)
    block_counts = onehot.reshape(nblocks, r, sigma).sum(axis=1)
    cum = jnp.cumsum(block_counts, axis=0)
    occ_local = jnp.concatenate([jnp.zeros((1, sigma), jnp.int32), cum[:-1]])
    totals = cum[-1]
    counts = lax.psum(totals, AXIS)
    c_array = jnp.cumsum(counts) - counts
    if bits:
        words = pack_words(bwt_local, bits).reshape(nblocks, -1)
        fused = jnp.concatenate([occ_local, words], axis=1)
    else:
        fused = jnp.zeros((1, 1), jnp.int32)  # placeholder, unused
    return occ_local, fused, c_array.astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("sigma", "sample_rate", "bits",
                                             "mesh"))
def _build_jit(bwt, sigma, sample_rate, bits, mesh):
    fn = functools.partial(_build_local, sigma=sigma, sample_rate=sample_rate,
                           bits=bits)
    return shard_map(
        fn, mesh=mesh, in_specs=P(AXIS),
        out_specs=(P(AXIS), P(AXIS) if bits else P(), P()),
    )(bwt)


def build_dist_fm_index(
    bwt, row, mesh: Mesh, *, sigma: int, sample_rate: int = 64,
    sa=None, sa_sample_rate: int = 32, pack: bool | None = None,
    compress_sa: bool | None = None, sa_samples: tuple | None = None,
) -> DistFMIndex:
    """Shard a BWT over the mesh ``parts`` axis and build per-shard Occ
    checkpoints (+ fused packed rows when the alphabet fits).

    ``bwt`` int32[n] with n divisible by parts * sample_rate; ``sa`` /
    ``sa_sample_rate`` / ``compress_sa`` enable a replicated SA sample for
    ``dist_locate`` (as in ``fm_index.build_fm_index``); ``sa_samples``
    injects prebuilt (marks, ranks, vals, val_bits) on checkpoint restore.
    """
    n = bwt.shape[0]
    parts = mesh.shape[AXIS]
    if (n % parts) or ((n // parts) % sample_rate):
        raise ValueError(
            f"n={n} must be divisible by parts*sample_rate={parts}*{sample_rate}"
        )
    bits = 0 if pack is False else packed_bits(sigma, sample_rate)
    if pack and not bits:
        raise ValueError(
            f"cannot pack sigma={sigma} at sample_rate={sample_rate}"
        )
    bwt = jax.device_put(bwt, NamedSharding(mesh, P(AXIS)))
    occ_samples, fused, c_array = _build_jit(bwt, sigma, sample_rate, bits,
                                             mesh)
    if sa_samples is not None:
        sa_marks, sa_mark_ranks, sa_vals, sa_val_bits = sa_samples
    elif sa is not None:
        sa_marks, sa_mark_ranks, sa_vals, sa_val_bits = build_sa_samples(
            sa, sa_sample_rate, compress=compress_sa
        )
    else:
        sa_marks = sa_mark_ranks = sa_vals = None
        sa_sample_rate = sa_val_bits = 0
    return DistFMIndex(
        bwt, occ_samples, c_array, jnp.asarray(row, jnp.int32),
        fused if bits else None, sa_marks, sa_mark_ranks, sa_vals,
        sample_rate, sigma, n, parts, bits, sa_sample_rate, sa_val_bits,
    )


def _occ_partial(bwt_local, occ_local, fused_local, c, p, *, m, r, bits,
                 sigma):
    """count of character c in (my range ∩ [0, p)) — vectorised over queries,
    dispatched through kernels/ops on the local shard's layout.

    bwt_local int32[m]; c, p int32[B].  p_loc == m folds into the last block
    (cutoff r), so base + in-block covers exactly [0, m) with no tail case.
    """
    me = lax.axis_index(AXIS)
    p_loc = jnp.clip(p - me * m, 0, m)          # clip into my range
    block = jnp.minimum(p_loc // r, m // r - 1)
    cut = p_loc - block * r
    if bits:
        return ops.rank_packed(fused_local, block, c, cut,
                               bits=bits, sigma=sigma)
    base = occ_local[block, c]                   # (B,)
    inblock = ops.rank_unpacked(bwt_local.reshape(m // r, r), block, c, cut)
    return (base + inblock).astype(jnp.int32)


def _search_local(bwt_local, occ_local, fused_local, c_array, patterns,
                  *, m, r, n, bits, sigma):
    """shard_map body: batched backward search over replicated patterns."""

    def step(state, c):
        sp, ep = state
        in_alphabet = (c >= 1) & (c < sigma)
        valid = in_alphabet & (ep > sp)
        c_safe = jnp.where(in_alphabet, c, 0)
        occ_kw = dict(m=m, r=r, bits=bits, sigma=sigma)
        occ_sp = lax.psum(
            _occ_partial(bwt_local, occ_local, fused_local, c_safe, sp,
                         **occ_kw), AXIS)
        occ_ep = lax.psum(
            _occ_partial(bwt_local, occ_local, fused_local, c_safe, ep,
                         **occ_kw), AXIS)
        nsp = c_array[c_safe] + occ_sp
        nep = c_array[c_safe] + occ_ep
        sp = jnp.where(valid, nsp, sp)
        # out-of-alphabet symbols (not PAD) empty the interval permanently
        ep = jnp.where(valid, nep, jnp.where((c != PAD) & ~in_alphabet, sp, ep))
        return (sp, ep), None

    B = patterns.shape[0]
    init = (jnp.zeros(B, jnp.int32), jnp.full((B,), n, jnp.int32))
    # scan right-to-left over pattern positions (PADs on the right come first)
    (sp, ep), _ = lax.scan(step, init, patterns.T[::-1])
    return sp, ep


def _fused_operand(index: DistFMIndex):
    """The fused operand to ship into shard_map — a replicated dummy when
    the index is unpacked (the jits spec it P(AXIS) iff index.bits)."""
    return index.fused if index.bits else jnp.zeros((1, 1), jnp.int32)


@functools.partial(jax.jit, static_argnames=("index_static", "mesh"))
def _count_jit(index_arrays, patterns, index_static, mesh):
    sample_rate, sigma, n, parts, bits = index_static
    bwt, occ_samples, c_array, fused = index_arrays
    m = n // parts
    fn = functools.partial(
        _search_local, m=m, r=sample_rate, n=n, bits=bits, sigma=sigma
    )
    sp, ep = shard_map(
        fn, mesh=mesh,
        in_specs=(P(AXIS), P(AXIS), P(AXIS) if bits else P(), P(), P()),
        out_specs=(P(), P()),
        check_vma=not ops.kernels_on_chip(),
    )(bwt, occ_samples, fused, c_array, patterns)
    return jnp.maximum(ep - sp, 0)


def dist_count(index: DistFMIndex, patterns, mesh: Mesh) -> jax.Array:
    """Batched exact-match counts over the sharded index.

    ``patterns``: int32[B, L], PAD-padded on the right, replicated.
    """
    arrays = (index.bwt, index.occ_samples, index.c_array,
              _fused_operand(index))
    static = (index.sample_rate, index.sigma, index.length, index.parts,
              index.bits)
    return _count_jit(arrays, jnp.asarray(patterns), static, mesh)


def _locate_local(bwt_local, occ_local, fused_local, c_array,
                  marks, mark_ranks, vals, patterns,
                  *, m, r, n, bits, sigma, s, k, val_bits):
    """shard_map body: backward search + LF-walk to the replicated SA sample.

    Every walk step costs one psum'd rank batch plus one psum'd BWT-symbol
    gather; positions/marks are replicated so all shards agree lane-by-lane.
    """
    sp, ep = _search_local(bwt_local, occ_local, fused_local, c_array,
                           patterns, m=m, r=r, n=n, bits=bits, sigma=sigma)
    B = sp.shape[0]
    me = lax.axis_index(AXIS)
    rows = sp[:, None] + jnp.arange(k, dtype=jnp.int32)[None, :]
    valid = (rows < ep[:, None]).reshape(-1)
    rows = jnp.where(valid, rows.reshape(-1), 0)
    occ_kw = dict(m=m, r=r, bits=bits, sigma=sigma)

    def bwt_at(rows):
        loc = rows - me * m
        inside = (loc >= 0) & (loc < m)
        sym = jnp.where(inside, bwt_local[jnp.clip(loc, 0, m - 1)], 0)
        return lax.psum(sym, AXIS)

    def body(_, st):
        rows, pos, steps, done = st
        marked, val = sample_lookup(marks, mark_ranks, vals, rows,
                                    val_bits=val_bits, val_scale=s)
        pos = jnp.where(marked & ~done, val + steps, pos)
        done = done | marked
        c = bwt_at(rows)
        nxt = c_array[c] + lax.psum(
            _occ_partial(bwt_local, occ_local, fused_local, c, rows, **occ_kw),
            AXIS)
        rows = jnp.where(done, rows, nxt)
        steps = steps + jnp.where(done, 0, 1)
        return rows, pos, steps, done

    zeros = jnp.zeros(B * k, jnp.int32)
    _, pos, _, _ = lax.fori_loop(0, s, body, (rows, zeros, zeros, ~valid))
    out = jnp.where(valid, pos, n).reshape(B, k)
    return jnp.sort(out, axis=1), jnp.minimum(jnp.maximum(ep - sp, 0), k)


@functools.partial(jax.jit, static_argnames=("index_static", "k", "mesh"))
def _locate_jit(index_arrays, patterns, index_static, k, mesh):
    sample_rate, sigma, n, parts, bits, s, val_bits = index_static
    bwt, occ_samples, c_array, fused, marks, mark_ranks, vals = index_arrays
    m = n // parts
    fn = functools.partial(
        _locate_local, m=m, r=sample_rate, n=n, bits=bits, sigma=sigma,
        s=s, k=k, val_bits=val_bits,
    )
    return shard_map(
        fn, mesh=mesh,
        in_specs=(P(AXIS), P(AXIS), P(AXIS) if bits else P(), P(),
                  P(), P(), P(), P()),
        out_specs=(P(), P()),
        check_vma=not ops.kernels_on_chip(),
    )(bwt, occ_samples, fused, c_array, marks, mark_ranks, vals, patterns)


def dist_locate(index: DistFMIndex, patterns, k: int, mesh: Mesh):
    """First-k occurrence positions per pattern over the sharded index.

    Returns (positions int32[B, k] sorted ascending, n-filled; counts
    int32[B] clipped to k) — same contract as ``fm_index.locate``.
    """
    if index.sa_sample_rate == 0:
        raise ValueError("index built without sa= — locate unavailable")
    arrays = (index.bwt, index.occ_samples, index.c_array,
              _fused_operand(index),
              index.sa_marks, index.sa_mark_ranks, index.sa_vals)
    static = (index.sample_rate, index.sigma, index.length, index.parts,
              index.bits, index.sa_sample_rate, index.sa_val_bits)
    return _locate_jit(arrays, jnp.asarray(patterns), static, k, mesh)
