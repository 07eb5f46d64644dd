"""LSD radix sort over packed uint32 key words — the local-sort engine of
the distributed BWT build (DESIGN.md §4).

Completes the orphaned ``radix_hist`` counting kernel into the full
hist -> exclusive-scan -> scatter pipeline, one 8-bit digit per pass:

  1. ``radix_hist_pallas``      per-block 256-bin digit histograms (VMEM)
  2. digit-major exclusive scan (tiny: nblocks x 256, plain jnp)
  3. ``radix_pos_pallas``       per-element destination = global bin base +
                                stable intra-block rank (one-hot rows times
                                a triangular matrix on the MXU, no gathers)
  4. apply                      one XLA scatter of all operands, stacked

The scatter itself stays in XLA on purpose: Mosaic's block model cannot
express an arbitrary HBM scatter (an output block must be addressed by the
grid index map), while steps 1-3 — the compute-heavy part — stay in VMEM.

Keys are **field-limited**: only ``key_bits[w]`` low bits of word ``w`` are
significant (see ``core.keypack``), so a k-bit key costs ``ceil(k/8)``
passes instead of 4, and multi-word (64-bit logical) keys sort
least-significant word first.  Every pass is stable, hence so is the whole
sort — pad slots appended after real data stay behind equal real keys.

``radix_sort_jnp`` is the collective-free pure-jnp fallback used off-TPU
(same counting sort; the per-pass transient is an (n, 2^radix_bits) int32
cumsum, so auto mode narrows the digit with n to hold it near 64 MiB —
floored at 1-bit digits, where transients grow past the target for
n > 2^23); dispatch lives in ``kernels.ops.radix_sort``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .radix_hist import LANES, block_layout, digit_onehot, radix_hist_pallas


def _pos_kernel(shift_ref, keys_ref, base_ref, out_ref, *, rows: int,
                group: int):
    shift = shift_ref[0]
    li = lax.broadcasted_iota(jnp.int32, (LANES, LANES), 0)
    lj = lax.broadcasted_iota(jnp.int32, (LANES, LANES), 1)
    upper = (li < lj).astype(jnp.bfloat16)         # upper[l', l] = l' < l
    base_t = base_ref[...].T                        # (256, group) bin bases
    col = lax.broadcasted_iota(jnp.int32, base_t.shape, 1)

    def one_block(j, c):
        r0 = pl.multiple_of(j * rows, rows)
        tile = keys_ref[pl.ds(r0, rows), :]
        # running per-bin destination: global bin base + keys of that bin
        # already placed by earlier rows of this block
        nxt = jnp.sum(jnp.where(col == j, base_t, 0), axis=1, keepdims=True)
        out = []
        for r in range(rows):
            onehot = digit_onehot(tile[r:r + 1, :], shift)   # (256, 128)
            # stable intra-row rank: same-bin keys on earlier lanes, via a
            # strictly-triangular matmul on the MXU (counts <= 127, exact)
            before = jnp.dot(onehot.astype(jnp.bfloat16), upper,
                             preferred_element_type=jnp.float32)
            dest = before.astype(jnp.int32) + nxt
            out.append(jnp.sum(jnp.where(onehot, dest, 0), axis=0,
                               keepdims=True))
            nxt = nxt + jnp.sum(onehot.astype(jnp.int32), axis=1,
                                keepdims=True)
        out_ref[pl.ds(r0, rows), :] = jnp.concatenate(out, axis=0)
        return c

    lax.fori_loop(0, group, one_block, 0)


def radix_pos_pallas(keys, base, shift, *, block: int = 1024,
                     interpret: bool = False):
    """Destination position of every element for one 8-bit digit pass.

    keys uint32[n] (n % block == 0), base int32[n//block, 256] = global
    start of (block, digit) runs in digit-major order; ``shift`` may be
    traced.
    """
    n = keys.shape[0]
    nblocks, rows, group = block_layout(n, block)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nblocks // group,),
        in_specs=[
            pl.BlockSpec((group * rows, LANES), lambda i, s: (i, 0)),
            pl.BlockSpec((group, 256), lambda i, s: (i, 0)),
        ],
        out_specs=pl.BlockSpec((group * rows, LANES), lambda i, s: (i, 0)),
    )
    out = pl.pallas_call(
        functools.partial(_pos_kernel, rows=rows, group=group),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n // LANES, LANES), jnp.int32),
        interpret=interpret,
    )(jnp.asarray(shift, jnp.int32).reshape(1), keys.reshape(-1, LANES), base)
    return out.reshape(n)


def _digit_major_bases(hist):
    """(nblocks, 256) per-block histograms -> (nblocks, 256) global bin
    bases: exclusive scan in (digit, block) order, i.e. all smaller digits
    plus this digit in earlier blocks."""
    before = jnp.cumsum(hist, axis=0) - hist
    totals = jnp.sum(hist, axis=0)
    return (before + (jnp.cumsum(totals) - totals)[None, :]).astype(jnp.int32)


def radix_sort_pallas(operands, num_keys: int, key_bits, *,
                      block: int = 1024, interpret: bool = False):
    """Stable LSD radix sort of uint32 key words + payload operands.

    ``operands[:num_keys]`` are uint32 key words, most-significant first
    (the ``lax.sort`` convention); ``key_bits[w]`` bounds the significant
    bits of word w.  n must be a multiple of ``GROUP * block`` (ops pads).
    The passes over one key word run as a ``fori_loop`` with the digit
    shift traced, so the program holds one pass per key word; all operands
    move in one scatter of their stacked 32-bit columns.
    """
    dtypes = [a.dtype for a in operands]
    cols = jnp.stack([lax.bitcast_convert_type(a, jnp.int32)
                      for a in operands], axis=1)

    def one_pass(w, p, cols):
        word = cols[:, w]
        shift = p * 8
        hist = radix_hist_pallas(word, shift, block=block,
                                 interpret=interpret)
        pos = radix_pos_pallas(word, _digit_major_bases(hist), shift,
                               block=block, interpret=interpret)
        return jnp.zeros_like(cols).at[pos].set(cols, unique_indices=True)

    for w in range(num_keys - 1, -1, -1):
        passes = -(-key_bits[w] // 8)
        cols = lax.fori_loop(0, passes, functools.partial(one_pass, w), cols)
    return tuple(lax.bitcast_convert_type(cols[:, i], dt)
                 for i, dt in enumerate(dtypes))


def radix_sort_jnp(operands, num_keys: int, key_bits, *,
                   radix_bits: int | None = None):
    """Pure-jnp stable LSD counting sort (the off-TPU fallback).

    The per-pass transient is an (n, 2^radix_bits) int32 cumsum; auto mode
    narrows the digit as n grows to keep it near 64 MiB (floor: 1-bit
    digits, so the bound is exceeded for n > 2^23 — more, cheaper passes
    beat an OOM).
    """
    n = operands[0].shape[0]
    if radix_bits is None:
        # n * 2^bits * 4 B <= ~2^26  =>  bits <= 24 - log2(n)
        radix_bits = max(1, min(8, 24 - max(1, n - 1).bit_length()))
    arrs = list(operands)
    for w in range(num_keys - 1, -1, -1):
        for shift in range(0, key_bits[w], radix_bits):
            nb = min(radix_bits, key_bits[w] - shift)
            nbins = 1 << nb
            word = arrs[w].astype(jnp.uint32)
            d = ((word >> shift) & (nbins - 1)).astype(jnp.int32)
            onehot = d[:, None] == jnp.arange(nbins, dtype=jnp.int32)[None, :]
            incl = jnp.cumsum(onehot.astype(jnp.int32), axis=0)
            totals = incl[-1]
            starts = jnp.cumsum(totals) - totals
            intra = jnp.take_along_axis(incl, d[:, None], axis=1)[:, 0] - 1
            pos = starts[d] + intra
            arrs = [jnp.zeros_like(a).at[pos].set(a) for a in arrs]
    return tuple(arrs)
