"""Pallas TPU kernel: per-block 8-bit digit histograms.

The counting pass of an LSD radix sort — the local-sort hot loop inside both
distributed sort engines (DESIGN.md §4).  Keys are viewed as rows of 128
lanes; a digit block is ``block // 128`` such rows.  Each grid step reads up
to ``GROUP`` digit blocks and writes their 256-bin histogram rows, so both
the key tile and the (GROUP, 256) output tile obey the TPU (8, 128) rule.
Inside the kernel the digits stay 2-D: a row's one-hot lies bins-on-sublanes
(256, 128), the rows of a block sum into it, and one (8, 128) x (256, 128)^T
matmul on the MXU turns the lane sums into a lane-dense (1, 256) row.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
GROUP = 8  # digit blocks per grid step (a full sublane tile of histograms)


def digit_onehot(keys_row, shift):
    """(1, 128) keys -> (256, 128) bool: bins on sublanes, keys on lanes."""
    d = (keys_row.astype(jnp.uint32) >> shift.astype(jnp.uint32)) & 0xFF
    return lax.broadcasted_iota(jnp.uint32, (256, LANES), 0) == d


def block_layout(n: int, block: int) -> tuple[int, int, int]:
    """(nblocks, rows per block, blocks per grid step) for n keys."""
    if block % LANES:
        raise ValueError(f"block={block} must be a multiple of {LANES}")
    if n % block:
        raise ValueError(f"n={n} must be a multiple of block={block}")
    nblocks = n // block
    if nblocks % GROUP:
        raise ValueError(f"n={n} must hold a multiple of {GROUP} blocks")
    return nblocks, block // LANES, GROUP


def _kernel(shift_ref, keys_ref, out_ref, *, rows: int, group: int):
    shift = shift_ref[0]
    ones = jnp.ones((8, LANES), jnp.bfloat16)

    def one_block(j, carry):
        tile = keys_ref[pl.ds(pl.multiple_of(j * rows, rows), rows), :]
        acc = jnp.zeros((256, LANES), jnp.int32)
        for r in range(rows):
            acc = acc + digit_onehot(tile[r:r + 1, :], shift).astype(jnp.int32)
        # every row of ones @ acc^T holds the per-bin totals (<= block, exact)
        cnt = lax.dot_general(ones, acc.astype(jnp.bfloat16),
                              (((1,), (1,)), ((), ())),
                              preferred_element_type=jnp.float32)
        out_ref[pl.ds(j, 1), :] = cnt[0:1, :].astype(jnp.int32)
        return carry

    lax.fori_loop(0, group, one_block, 0)


def radix_hist_pallas(
    keys, shift, *, block: int = 1024, interpret: bool = False
):
    """keys int32/uint32[n] (n % (block * GROUP) == 0) -> int32[n//block, 256]
    histograms of ``(keys >> shift) & 0xFF``; ``shift`` may be traced."""
    n = keys.shape[0]
    nblocks, rows, group = block_layout(n, block)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nblocks // group,),
        in_specs=[pl.BlockSpec((group * rows, LANES), lambda i, s: (i, 0))],
        out_specs=pl.BlockSpec((group, 256), lambda i, s: (i, 0)),
    )
    return pl.pallas_call(
        functools.partial(_kernel, rows=rows, group=group),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((nblocks, 256), jnp.int32),
        interpret=interpret,
        name="radix_hist",
    )(jnp.asarray(shift, jnp.int32).reshape(1), keys.reshape(-1, LANES))
