"""Pallas TPU kernels for the indexing hot spots (DESIGN.md §2).

Each kernel: <name>.py (pl.pallas_call + BlockSpec), a jit wrapper in
ops.py, and a pure-jnp oracle in ref.py; tests sweep shapes/dtypes and
assert exact agreement in interpret mode.
"""

from .ops import (  # noqa: F401
    char_histogram,
    radix_hist,
    radix_sort,
    rank_packed,
    rank_unpacked,
    rerank_scan,
)
