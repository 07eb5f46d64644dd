"""HBM bytes one call of each kernel must move, from its shapes alone.

The least time a call can take is its bytes over the chip's HBM bandwidth
(``peaks.json``); a kernel's roofline share is that time over its measured
device time.  These kernels do integer compares, popcounts and one-hot
counts, no floating-point work worth a compute bound, so bandwidth bounds
each of them.

In a TPU trace a Pallas kernel is an HLO ``custom-call`` with
``custom_call_target="tpu_custom_call"`` whose text carries its output and
operand shapes; ``kernel_call`` recognises each kernel of the index by
those shapes:

- rank gather (``kernels/rank_select.py``, packed and unpacked): operands
  int32[B] block, symbol and cutoff (scalar-prefetched) and the row table
  int32[nb, 1, w] left in HBM; output int32[B/Q, 1, Q].  Per query one
  w-word row is read, three scalars are read and one answer is written;
  the rest of the table is not touched.
- radix histogram (``kernels/radix_hist.py``): operands the int32[1]
  shift and the keys int32[n/128, 128]; output int32[n/block, 256].  All
  keys are read, one 256-bin row per block is written.
- radix position (``kernels/radix_sort.py`` ``radix_pos_pallas``):
  operands the shift, the keys and the int32[n/block, 256] bin bases;
  output int32[n/128, 128] destinations.
"""

from __future__ import annotations

WORD = 4
BINS = 256
LANES = 128


def rank_gather_bytes(queries: int, row_words: int) -> int:
    return queries * WORD * (row_words + 3 + 1)


def radix_hist_bytes(n: int, blocks: int) -> int:
    return WORD * (1 + n + blocks * BINS)


def radix_pos_bytes(n: int, blocks: int) -> int:
    return WORD * (1 + 2 * n + blocks * BINS)


def _size(dims) -> int:
    out = 1
    for d in dims:
        out *= d
    return out


def kernel_call(outs, args):
    """("rank_gather" | "radix_hist" | "radix_pos", bytes) for one kernel
    call's (output shapes, operand shapes), or None for another kernel.
    Shapes are (dtype, dims) pairs as ``trace/reduce.call_shapes`` gives
    them."""
    dims = [d for _, d in args]
    out = [d for _, d in outs]
    if len(out) != 1:
        return None
    out = out[0]
    if (len(dims) == 4 and all(len(d) == 1 for d in dims[:3])
            and len({d[0] for d in dims[:3]}) == 1 and len(dims[3]) == 3
            and dims[3][1] == 1 and len(out) == 3
            and _size(out) == dims[0][0]):
        return "rank_gather", rank_gather_bytes(dims[0][0], dims[3][2])
    if (len(dims) == 2 and dims[0] == (1,) and len(dims[1]) == 2
            and dims[1][1] == LANES and len(out) == 2 and out[1] == BINS):
        return "radix_hist", radix_hist_bytes(_size(dims[1]), out[0])
    if (len(dims) == 3 and dims[0] == (1,) and len(dims[1]) == 2
            and dims[1][1] == LANES and len(dims[2]) == 2
            and dims[2][1] == BINS and out == dims[1]):
        return "radix_pos", radix_pos_bytes(_size(dims[1]), dims[2][0])
    return None
