"""Jit'd public wrappers for the Pallas kernels.

Each wrapper auto-selects interpret mode off-TPU (this container is
CPU-only; TPU is the compile target), pads inputs to kernel granularity,
and exposes the same signature as the ``ref.py`` oracle.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import ref
from .char_histogram import char_histogram_pallas
from .radix_hist import GROUP, radix_hist_pallas
from .radix_sort import radix_sort_jnp, radix_sort_pallas
from .rank_select import rank_packed_jnp, rank_packed_pallas, rank_select_pallas
from .rerank_scan import rerank_scan_pallas


def _interpret_default() -> bool:
    return jax.default_backend() != "tpu"


def kernels_on_chip() -> bool:
    """True where the backend defaults route the rank and local-sort hot
    paths to the Pallas kernels.  Their outputs carry no varying-manual-axes
    type, so a ``shard_map`` body that calls them runs with ``check_vma``
    off; everywhere else the check stays on."""
    return jax.default_backend() == "tpu"


def _impl_default() -> str:
    """Build-time backend selection for the rank and local-sort hot paths:
    the real Pallas kernels on TPU, the pure-jnp fallbacks elsewhere
    ("interpret" is opt-in for kernel parity tests — far too slow to serve
    or build from)."""
    return "pallas" if kernels_on_chip() else "jnp"


@functools.partial(jax.jit, static_argnames=("sigma", "block_rows", "interpret"))
def char_histogram(tokens, sigma: int, *, block_rows: int = 8,
                   interpret: bool | None = None):
    """Histogram of token values: int32[n] -> int32[sigma].

    Pallas kernel on TPU; ``interpret=None`` auto-selects interpret mode
    off-TPU.  Inputs pad to ``block_rows * 128`` lanes with the value
    ``sigma``, which lands out of range and is dropped by construction
    (padded lanes count into a scratch bin)."""
    interpret = _interpret_default() if interpret is None else interpret
    unit = block_rows * 128
    n = tokens.shape[0]
    pad = (-n) % unit
    if pad:
        # pad value sigma falls outside [0, sigma) -> contributes nothing
        tokens = jnp.pad(tokens, (0, pad), constant_values=sigma)
    return char_histogram_pallas(
        tokens, sigma, block_rows=block_rows, interpret=interpret
    )


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def rerank_scan(r1, r2, *, block: int = 512, interpret: bool | None = None):
    """(ranks int32[n], num_groups int32 scalar) for sorted key pairs
    ``r1``/``r2`` int32[n]: rank = index of each pair's first occurrence.

    Pallas scan kernel (interpret mode auto-selected off-TPU); inputs pad to
    ``block`` with a strictly larger tail pair so padding forms its own
    trailing group, subtracted from ``num_groups`` before returning."""
    interpret = _interpret_default() if interpret is None else interpret
    n = r1.shape[0]
    pad = (-n) % block
    if pad:
        big = jnp.iinfo(jnp.int32).max
        r1 = jnp.pad(r1, (0, pad), constant_values=big)
        r2 = jnp.pad(r2, (0, pad), constant_values=big)
    ranks, ngroups = rerank_scan_pallas(r1, r2, block=block, interpret=interpret)
    if pad:
        ranks = ranks[:n]
        ngroups = ngroups - 1  # the padding group
    return ranks, ngroups[0]


COMPARE = "compare"
RADIX = "radix"


def resolve_sort_engine(engine: str) -> str:
    """"auto" -> the backend default: the radix engine on TPU, lax.sort
    off-TPU (the jnp counting-sort fallback loses ~3x to XLA's native sort
    on CPU)."""
    if engine == "auto":
        return RADIX if jax.default_backend() == "tpu" else COMPARE
    if engine not in (COMPARE, RADIX):
        raise ValueError(f"unknown local_sort engine {engine!r}")
    return engine


def local_sort(operands, num_keys: int, *, engine: str = COMPARE,
               key_bits=None):
    """Stable local sort of key operands + payloads by the chosen engine
    (the single dispatch used by both the single-device builder and the
    distributed sort engines).

    ``operands``: tuple of equal-length 1-D arrays, the first ``num_keys``
    of which are uint32/int32 sort keys (most-significant first).  Engine
    ``"compare"`` = ``lax.sort``; ``"radix"`` = the LSD radix pipeline
    below.  Both engines are stable, so they are interchangeable
    bit-for-bit."""
    operands = tuple(operands)
    if engine == RADIX:
        if key_bits is None:
            key_bits = (32,) * num_keys
        return radix_sort(operands, num_keys=num_keys,
                          key_bits=tuple(key_bits))
    return jax.lax.sort(operands, num_keys=num_keys, is_stable=True)


@functools.partial(
    jax.jit, static_argnames=("num_keys", "key_bits", "block", "impl")
)
def radix_sort(operands, *, num_keys: int, key_bits, block: int = 1024,
               impl: str | None = None):
    """Stable LSD radix sort of uint32 key words (MSW first) + payloads.

    ``key_bits[w]`` bounds the significant bits of key word ``w`` — pads
    (and every caller's pad slots, see ``core.keypack``) must be field-
    limited, because digits above ``key_bits`` are never examined.
    ``impl``: None -> backend default ("pallas" on TPU, "jnp" elsewhere);
    "interpret" runs the kernels in interpret mode for parity testing.
    """
    impl = _impl_default() if impl is None else impl
    operands = tuple(operands)
    key_bits = tuple(key_bits)
    if impl == "jnp":
        return radix_sort_jnp(operands, num_keys, key_bits)
    n = operands[0].shape[0]
    pad = (-n) % (block * GROUP)
    if pad:
        # pads go AFTER real data; per-pass stability keeps them there even
        # when a real key saturates its field (ties resolve to input order)
        operands = tuple(
            jnp.concatenate([
                a,
                jnp.full((pad,),
                         (1 << key_bits[i]) - 1 if i < num_keys else 0,
                         a.dtype),
            ])
            for i, a in enumerate(operands)
        )
    out = radix_sort_pallas(operands, num_keys, key_bits, block=block,
                            interpret=(impl == "interpret"))
    if pad:
        out = tuple(a[:n] for a in out)
    return out


@functools.partial(jax.jit, static_argnames=("shift", "block", "interpret"))
def radix_hist(keys, shift: int, *, block: int = 1024,
               interpret: bool | None = None):
    """Per-block 8-bit digit histograms: uint32[n] -> int32[n/block, 256]
    of counts of ``(keys >> shift) & 0xFF``.  ``block`` must divide n
    (callers tile); interpret mode auto-selected off-TPU.  Whole zero
    blocks pad the keys to the kernel's ``GROUP`` blocks per grid step;
    their rows are trimmed."""
    interpret = _interpret_default() if interpret is None else interpret
    nblocks = keys.shape[0] // block
    pad = (-nblocks) % GROUP * block
    if pad:
        keys = jnp.concatenate([keys, jnp.zeros((pad,), keys.dtype)])
    out = radix_hist_pallas(keys, shift, block=block, interpret=interpret)
    return out[:nblocks]


# queries per row-gather kernel call: the call's block/symbol/cutoff
# vectors are scalar-prefetched into SMEM (1 MiB on a v5e), 12 B per query
GATHER_CHUNK = 1 << 15


def _gather_queries(kernel, block_idx, c, cutoff, queries_per_step: int,
                    **kw):
    """Pad int32[B] queries to the kernel's per-step multiple and trim the
    answers.  ``queries_per_step`` clamps to the next power of two >= B, so
    scalar walks (the BWT-merge interleave walk issues one- and two-query
    dispatches per step) don't pay for a full lane row of grid steps.
    Batches above ``GATHER_CHUNK`` run as a ``lax.map`` over chunks."""
    B = block_idx.shape[0]
    Q = min(queries_per_step, 1 << max(0, B - 1).bit_length())
    unit = GATHER_CHUNK if B > GATHER_CHUNK else Q
    pad = (-B) % unit
    if pad:
        z = jnp.zeros(pad, jnp.int32)
        block_idx, c, cutoff = (
            jnp.concatenate([a, z]) for a in (block_idx, c, cutoff)
        )

    def call(q):
        return kernel(block_idx=q[0], c=q[1], cutoff=q[2],
                      queries_per_step=Q, **kw)

    if B <= GATHER_CHUNK:
        return call((block_idx, c, cutoff))[:B]
    chunks = tuple(a.reshape(-1, GATHER_CHUNK) for a in (block_idx, c, cutoff))
    return jax.lax.map(call, chunks).reshape(-1)[:B]


@functools.partial(
    jax.jit, static_argnames=("bits", "sigma", "queries_per_step", "impl")
)
def rank_packed(fused, block_idx, c, cutoff, *, bits: int, sigma: int,
                queries_per_step: int = 128, impl: str | None = None):
    """Full rank queries (checkpoint base + in-block popcount) over the
    fused packed layout: Occ(c_i, block_idx_i * r + cutoff_i) for each query.

    ``fused`` int32[n_blocks, sigma + r*bits/32] rows of
    [Occ checkpoint | packed words]; ``block_idx``/``c``/``cutoff``
    int32[B] -> int32[B].  ``bits`` in {2, 4} is the packed field width.
    ``impl``: None -> backend default ("pallas" on TPU, "jnp" popcount
    fallback elsewhere); "interpret" runs the kernel in interpret mode for
    parity testing.  ``queries_per_step`` (one lane row of answers per
    outer grid step) clamps as in ``_gather_queries``.
    """
    impl = _impl_default() if impl is None else impl
    if impl == "jnp":
        return rank_packed_jnp(fused, block_idx, c, cutoff,
                               bits=bits, sigma=sigma)
    return _gather_queries(
        functools.partial(rank_packed_pallas, fused, bits=bits, sigma=sigma),
        block_idx, c, cutoff, queries_per_step,
        interpret=(impl == "interpret"),
    )


def rank_walkers(fused, blocks, occ, block_idx, c, cutoff, *, bits: int,
                 sigma: int):
    """Full Occ(c_i, block_idx_i * r + cutoff_i) on either block layout in
    ONE batched dispatch — the per-step rank call of the BWT-merge
    interleave walks (pairwise and k-way).

    Packed layouts (``bits`` > 0) pass ``fused`` (checkpoint base folds
    into the kernel); unpacked layouts pass ``blocks`` plus flat per-block
    Occ checkpoints ``occ`` int32[n_blocks, sigma].  ``block_idx`` may
    address a stacked multi-segment array (``fm_index.stack_rank_arrays``)
    with the lane base already folded in by the caller, so one dispatch
    ranks every walker of a k-way merge step against its own segment.
    """
    if bits:
        return rank_packed(fused, block_idx, c, cutoff,
                           bits=bits, sigma=sigma)
    return occ[block_idx, c] + rank_unpacked(blocks, block_idx, c, cutoff)


@functools.partial(jax.jit, static_argnames=("impl",))
def rank_unpacked(bwt_blocks, block_idx, c, cutoff, *, impl: str | None = None):
    """Batched in-block rank counts over unpacked int32 blocks (the sigma>16
    layout): ``bwt_blocks`` int32[n_blocks, r]; per query i the count of
    symbol ``c[i]`` in the first ``cutoff[i]`` positions of block
    ``block_idx[i]`` — int32[B] queries -> int32[B] counts, NOT including
    the checkpoint base.  Dispatch: scalar-prefetch row-gather Pallas kernel
    on TPU, vectorised jnp gather elsewhere; "interpret" for parity
    testing."""
    impl = _impl_default() if impl is None else impl
    if impl == "jnp":
        return ref.rank_select_ref(bwt_blocks, block_idx, c, cutoff)
    return _gather_queries(
        functools.partial(rank_select_pallas, bwt_blocks),
        block_idx, c, cutoff, 128, interpret=(impl == "interpret"),
    )
