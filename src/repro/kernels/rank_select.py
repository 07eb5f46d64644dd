"""Pallas TPU kernels for FM-index rank queries over a bit-packed BWT.

Layout (the "succinct" direction of Sirén's terabase-scale BWT work): the
BWT is planed into 2-bit (sigma <= 4) or 4-bit (sigma <= 16) fields packed
LSB-first into int32 words, and each checkpoint block is stored as one
*fused* row

    fused[b] = [ Occ checkpoint (sigma int32) | packed words (r/fpw int32) ]

so a single row fetch (one cache line / one DMA) yields both the rank base
and the block payload — the interleaved-checkpoint struct of classic
cache-aware FM indexes.

Row-table contract: the kernel reads the table as ``int32[n_blocks, 1,
sigma + W]``, one ``(1, sigma + W)`` block per grid step.  ``place_rows``
stores a table that way once, where it is made: that shape, row-major, in
``(1, 128)`` tiles (``ROW_LAYOUT``), so each table row is one lane row of
HBM and the kernel's DMA reads the table as it lies.  A 2-D ``int32[nb,
w]`` table (a ``DistFMIndex`` shard, the unpacked ``bwt`` blocks) still
works: the kernel views it as ``(nb, 1, w)`` itself, and XLA then relays
the whole table out to that layout inside every calling program.

``rank_packed_pallas`` is the fused kernel: the fused array stays in HBM
and each inner grid step DMAs the one row its scalar-prefetched block index
selects, then counts matches popcount-style over the packed words instead
of scanning symbols.  ``rank_packed_jnp`` is the same math as a pure-jnp
fallback for hosts without a TPU (selected at dispatch time in ``ops.py``).

``rank_select_pallas`` runs the same row gather over *unpacked* int32
blocks, the layout of alphabets too large to pack (sigma > 16).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.layout import Format, Layout
from jax.experimental.pallas import tpu as pltpu

# LSB of every 2-bit / 4-bit field — replicating a symbol across fields is
# one multiply; a field equals the symbol iff its XOR-difference is zero.
_REP = {2: 0x55555555, 4: 0x11111111}


# the rank kernel's row-table layout: row-major, one padded lane row per
# table row
ROW_LAYOUT = Layout(major_to_minor=(0, 1, 2), tiling=((1, 128),))


def _rows_3d(rows: jax.Array) -> jax.Array:
    """int32[nb, w] -> the kernel's (nb, 1, w) view; 3-D tables as they are."""
    if rows.ndim == 2:
        return rows.reshape(rows.shape[0], 1, rows.shape[1])
    return rows


def rows_placed(rows: jax.Array) -> bool:
    """Whether a row table is stored as the rank kernel reads it: 3-D, in
    ``ROW_LAYOUT`` (read from the array's format, on the host)."""
    if rows.ndim != 3:
        return False
    layout = rows.format.layout
    return (tuple(layout.major_to_minor) == ROW_LAYOUT.major_to_minor
            and tuple(map(tuple, layout.tiling)) == ROW_LAYOUT.tiling)


def place_rows(rows: jax.Array) -> jax.Array:
    """A row table int32[nb, w] or int32[nb, 1, w], stored as the rank
    kernel reads it: int32[nb, 1, w] in ``ROW_LAYOUT`` on the device it is
    on.  The one placement of packed row tables; eager only."""
    rows = _rows_3d(rows)
    if rows_placed(rows):
        return rows
    return jax.device_put(rows, Format(ROW_LAYOUT, rows.sharding))


def packed_bits(sigma: int, sample_rate: int) -> int:
    """Field width for (sigma, block length r): 2, 4, or 0 (unpackable)."""
    for bits in (2, 4):
        if sigma <= (1 << bits) and sample_rate % (32 // bits) == 0:
            return bits
    return 0


def pack_words(symbols: jax.Array, bits: int) -> jax.Array:
    """int32[k*fpw] symbols in [0, 2^bits) -> int32[k] packed words.

    Negative entries (PAD tails) pack as 0; rank queries never reach them
    because in-block cutoffs are bounded by the true text length.
    """
    fpw = 32 // bits
    v = jnp.maximum(symbols, 0).astype(jnp.uint32).reshape(-1, fpw)
    shifts = jnp.arange(fpw, dtype=jnp.uint32) * jnp.uint32(bits)
    words = jnp.sum(v << shifts[None, :], axis=1, dtype=jnp.uint32)
    return lax.bitcast_convert_type(words, jnp.int32)


def _eq_fields(x: jax.Array, bits: int) -> jax.Array:
    """Per-field zero test on XOR-ed packed words: LSB of each field is 1
    iff the whole field is 0 (i.e. the symbols matched)."""
    rep = jnp.uint32(_REP[bits])
    t = x | (x >> 1)
    if bits == 4:
        t = t | (t >> 2)
    return (t & rep) ^ rep


def _cutoff_mask(word_iota, cutoff, bits: int):
    """uint32 select mask keeping only the first ``cutoff`` fields of a
    block laid out over consecutive words (cutoff in [0, r])."""
    fpw = 32 // bits
    full = cutoff // fpw
    rem = (cutoff - full * fpw).astype(jnp.uint32)
    partial = (jnp.uint32(1) << (jnp.uint32(bits) * rem)) - jnp.uint32(1)
    return jnp.where(
        word_iota < full,
        jnp.uint32(0xFFFFFFFF),
        jnp.where(word_iota == full, partial, jnp.uint32(0)),
    )


def rank_packed_jnp(fused, block_idx, c, cutoff, *, bits: int, sigma: int):
    """Pure-jnp popcount rank over the fused layout (CPU fallback).

    fused int32[nb, 1, sigma + W] (or int32[nb, sigma + W]);
    block_idx/c/cutoff int32[B].  Returns int32[B]: Occ checkpoint + count
    of c in the first ``cutoff`` symbols of the selected block.
    """
    rows = fused[block_idx]
    if rows.ndim == 3:
        rows = rows[:, 0]                                    # (B, sigma+W)
    base = jnp.take_along_axis(rows, c[:, None], axis=1)[:, 0]
    w = lax.bitcast_convert_type(rows[:, sigma:], jnp.uint32)  # (B, W)
    rep = jnp.uint32(_REP[bits])
    eq = _eq_fields(w ^ (c.astype(jnp.uint32) * rep)[:, None], bits)
    wi = jnp.arange(w.shape[1], dtype=jnp.int32)[None, :]
    sel = _cutoff_mask(wi, cutoff[:, None], bits)
    cnt = jnp.sum(lax.population_count(eq & sel), axis=1)
    return (base + cnt.astype(jnp.int32)).astype(jnp.int32)


def _packed_row_count(row, c, cut, *, bits: int, sigma: int):
    """Occ checkpoint + in-block popcount of one fused row (1, sigma + W):
    the base is a one-hot masked sum over the sigma checkpoint lanes (no
    dynamic lane slice), the count an XOR + popcount over the word lanes."""
    lane = lax.broadcasted_iota(jnp.int32, row.shape, 1)
    base = jnp.sum(jnp.where(lane == c, row, 0))
    w = lax.bitcast_convert_type(row, jnp.uint32)
    eq = _eq_fields(w ^ c.astype(jnp.uint32) * jnp.uint32(_REP[bits]), bits)
    wi = lane - sigma
    sel = jnp.where(wi >= 0, _cutoff_mask(wi, cut, bits), jnp.uint32(0))
    return base + jnp.sum(lax.population_count(eq & sel).astype(jnp.int32))


def _unpacked_row_count(row, c, cut):
    """Count of symbol c among the first ``cut`` entries of one int32 row."""
    pos = lax.broadcasted_iota(jnp.int32, row.shape, 1)
    return jnp.sum(((row == c) & (pos < cut)).astype(jnp.int32))


def _gather_kernel(bidx_ref, c_ref, cut_ref, row_ref, out_ref, *,
                   row_count, queries_per_step: int):
    i, j = pl.program_id(0), pl.program_id(1)
    g = i * queries_per_step + j
    val = row_count(row_ref[...], c_ref[g], cut_ref[g])

    @pl.when(j == 0)
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    lane = lax.broadcasted_iota(jnp.int32, out_ref.shape, 1)
    out_ref[...] = jnp.where(lane == j, val, out_ref[...])


def _row_gather_call(row_count, rows, block_idx, c, cutoff, *, name: str,
                     queries_per_step: int, interpret: bool):
    """One rank query per inner grid step over an HBM-resident row table;
    ``name`` is the kernel's name in a device trace.

    ``rows`` int32[nb, 1, w] (as ``place_rows`` stores it) or int32[nb, w]
    stays in HBM; the scalar-prefetched ``block_idx`` drives the input
    index map, so each step DMAs only the selected row, a (1, w) block of
    the 3-D (nb, 1, w) array (its last two dims equal the array's, which
    the TPU tiling rule admits for any w).  A 2-D table is viewed as that
    3-D array here, which costs a relayout of the whole table per call.
    Each outer step writes one lane-aligned (1, Q) output row.  B must be
    a multiple of ``queries_per_step`` (ops.py pads).
    """
    B = block_idx.shape[0]
    Q = queries_per_step
    assert B % Q == 0, (B, Q)
    rows = _rows_3d(rows)
    wid = rows.shape[2]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B // Q, Q),
        in_specs=[pl.BlockSpec((None, 1, wid),
                               lambda i, j, b, c, t: (b[i * Q + j], 0, 0))],
        out_specs=pl.BlockSpec((None, 1, Q), lambda i, j, b, c, t: (i, 0, 0)),
    )
    out = pl.pallas_call(
        functools.partial(_gather_kernel, row_count=row_count,
                          queries_per_step=Q),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B // Q, 1, Q), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name=name,
    )(block_idx, c, cutoff, rows)
    return out.reshape(B)


def rank_packed_pallas(fused, block_idx, c, cutoff, *, bits: int, sigma: int,
                       queries_per_step: int = 128, interpret: bool = False):
    """Fused rank kernel over the packed layout: Occ checkpoint base +
    XOR/popcount in-block count, one fused row fetched per query.

    The fused array, int32[nb, 1, sigma + W] as ``place_rows`` stores it
    (a 2-D table is viewed so), stays in HBM (a genome-scale index does
    not fit VMEM); B must be a multiple of ``queries_per_step`` (ops.py pads).
    """
    row_count = functools.partial(_packed_row_count, bits=bits, sigma=sigma)
    return _row_gather_call(row_count, fused, block_idx, c, cutoff,
                            name="rank_gather_packed",
                            queries_per_step=queries_per_step,
                            interpret=interpret)


# ---------------------------------------------------------------------------
# unpacked path (sigma > 16): the same row gather over int32 symbol blocks
# ---------------------------------------------------------------------------


def rank_select_pallas(bwt_blocks, block_idx, c, cutoff, *,
                       queries_per_step: int = 128, interpret=False):
    """In-block counts for FM rank queries over unpacked int32 blocks.

    bwt_blocks int32[nblocks, r]; block_idx/c/cutoff int32[B] with B a
    multiple of ``queries_per_step``.  Returns int32[B]: count of c among
    the first ``cutoff`` entries of the selected block.
    """
    return _row_gather_call(_unpacked_row_count, bwt_blocks, block_idx, c,
                            cutoff, name="rank_gather_unpacked",
                            queries_per_step=queries_per_step,
                            interpret=interpret)
