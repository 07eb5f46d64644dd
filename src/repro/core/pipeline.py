"""End-to-end indexing pipeline: tokens -> (SA, BWT, FM-index).

Public API used by examples, benchmarks, and the data-pipeline dedup stage.
Dispatches between the single-device reference path and the distributed
shard_map path (any mesh with a ``parts`` axis).

Padding note: SPMD needs n divisible by parts*sample_rate.  We append the
unique smallest sentinel first (required by the BWT), then pad with a
dedicated token HIGHER than every real token.  Pad suffixes consist only of
pad tokens, so they can never match a query over the real alphabet, and real
char ranks are unaffected — counting semantics are exact (asserted by tests).

Profiler spans (host code; inert unless a trace is being recorded):
``fm.build`` around ``build_index`` (``n`` raw tokens, ``sigma``),
``fm.build.prepare`` around ``prepare_tokens``, ``fm.build.bwt`` around the
BWT step and ``fm.build.fm`` around the FM step; the suffix sort and the SA
sampling open their own (``suffix_array.build_isa_fast``,
``fm_index.build_sa_samples``).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from . import alphabet as al
from .bwt import bwt_from_sa
from .dist_fm import DistFMIndex, build_dist_fm_index, dist_count, dist_locate
from .dist_suffix_array import (
    DistSAConfig,
    _bwt_jit,
    build_isa_sharded,
    isa_overflowed,
)
from .fm_index import (
    FMIndex,
    build_fm_index,
    count as fm_count,
    locate as fm_locate,
)
from .suffix_array import BuildStats, suffix_array, suffix_array_fast


@dataclasses.dataclass
class SequenceIndex:
    """A built full-text index plus query methods."""

    fm: FMIndex | DistFMIndex
    sa: jax.Array | None
    bwt: jax.Array
    row: jax.Array
    sigma: int
    length: int          # padded length
    text_length: int     # true length incl. sentinel
    mesh: Mesh | None = None
    build_stats: BuildStats | None = None  # fast-build trajectory (1-device)

    def count(self, patterns) -> jax.Array:
        """Exact-match counts for int32[B, L] PAD-padded patterns."""
        patterns = jnp.asarray(patterns, jnp.int32)
        if self.mesh is None:
            return fm_count(self.fm, patterns)
        return dist_count(self.fm, patterns, self.mesh)

    def locate(self, patterns, k: int) -> tuple[jax.Array, jax.Array]:
        """First-k occurrence positions per pattern via the SA sample built
        during indexing.  Returns (positions int32[B, k] sorted, filled with
        the padded length for unused slots; counts int32[B] clipped to k)."""
        patterns = jnp.asarray(patterns, jnp.int32)
        if self.mesh is None:
            return fm_locate(self.fm, patterns, k)
        return dist_locate(self.fm, patterns, k, self.mesh)

    def packed_row_tables(self) -> tuple:
        """The packed row tables that ``count`` and ``locate`` read: the
        index's ``fused`` rows (none for the unpacked layout)."""
        return () if self.fm.fused is None else (self.fm.fused,)


def prepare_tokens(
    tokens: np.ndarray, multiple: int, sigma: int | None = None,
    reserve_pad: bool | None = None,
) -> tuple[np.ndarray, int]:
    """Sentinel-terminate and pad to a multiple; returns (padded, sigma).

    ``sigma`` forces a minimum alphabet size (tokens in [1, sigma)): indexes
    built over different texts then share one alphabet, so the pad token
    (placed at the shared sigma) sorts above every real token of *any* of
    them — required by the segmented index, where a query may carry tokens
    absent from this particular segment.

    ``reserve_pad`` keeps the pad slot in the alphabet even when no padding
    tokens are appended.  Default (None) reserves it exactly for
    declared-``sigma`` builds, so every such index lands on the *same*
    effective sigma (and therefore the same fused-row layout) regardless of
    its length — the invariant the stacked segment-parallel query path
    relies on (``fm_index.stack_fm_indexes``).  Note this costs one
    alphabet slot: a declared sigma=16 build lands on 17 and falls out of
    the 4-bit packed layout; pass ``reserve_pad=False`` to opt out when the
    index will never be stacked.
    """
    with TraceAnnotation("fm.build.prepare"):
        s = al.append_sentinel(np.asarray(tokens, dtype=np.int32))
        data_sigma = al.sigma_of(s)
        declared = sigma is not None
        if declared and sigma < data_sigma:
            raise ValueError(f"tokens exceed declared alphabet {sigma}")
        if reserve_pad is None:
            reserve_pad = declared
        sigma = max(data_sigma, sigma or 0)
        pad = (-len(s)) % multiple
        if pad:
            s = np.concatenate([s, np.full(pad, sigma, np.int32)])
        if pad or reserve_pad:
            sigma += 1
        return s, sigma


def build_index_prepared(
    s: np.ndarray,
    sigma: int,
    *,
    sample_rate: int = 64,
    sa_config: DistSAConfig = DistSAConfig(),
    sa_sample_rate: int = 32,
    pack: bool | None = None,
    fast: bool = True,
    compress_sa: bool | None = None,
    text_length: int | None = None,
) -> SequenceIndex:
    """Single-device build over an already-prepared text.

    ``s`` is a ``prepare_tokens``-style token array — or a concatenation of
    several such prepared documents, each carrying its own sentinel and pad
    run (the rebuild strategy of ``SegmentedIndex.compact`` and the oracle
    for ``core.bwt_merge``).  The prefix-doubling builders need no unique
    terminal sentinel: suffixes of a multi-document text are still pairwise
    distinct (different lengths resolve through the overflow rank), and
    queries over the real alphabet can never match a sentinel or pad, so
    counting semantics stay exact per document.
    """
    s_dev = jnp.asarray(s, jnp.int32)
    if fast:
        sa, stats = suffix_array_fast(
            s_dev, sigma, local_sort=sa_config.local_sort,
            qgram=sa_config.qgram, qgram_words=sa_config.qgram_words,
            discard=sa_config.discard,
        )
    else:
        sa, stats = suffix_array(s_dev, sigma), None
    with TraceAnnotation("fm.build.bwt"):
        bwt_arr, row = bwt_from_sa(s_dev, sa)
    sa_kw = dict(sa_sample_rate=sa_sample_rate) if sa_sample_rate else {}
    with TraceAnnotation("fm.build.fm"):
        fm = build_fm_index(bwt_arr, row, sigma, sample_rate, pack=pack,
                            compress_sa=compress_sa,
                            sa=sa if sa_sample_rate else None, **sa_kw)
    n = int(s_dev.shape[0])
    return SequenceIndex(fm, sa, bwt_arr, row, sigma, n,
                         n if text_length is None else text_length,
                         build_stats=stats)


def build_index(
    tokens: np.ndarray,
    mesh: Mesh | None = None,
    *,
    sample_rate: int = 64,
    sa_config: DistSAConfig = DistSAConfig(),
    max_retries: int = 3,
    sa_sample_rate: int = 32,
    pack: bool | None = None,
    fast: bool = True,
    sigma: int | None = None,
    compress_sa: bool | None = None,
    reserve_pad: bool | None = None,
) -> SequenceIndex:
    """Build a (distributed) BWT/FM index over raw tokens (no sentinel).

    The suffix array produced as a build byproduct is sampled every
    ``sa_sample_rate``-th text position into the index, enabling
    ``SequenceIndex.locate`` (set 0 to skip).  ``pack`` as in
    ``build_fm_index`` (None = bit-pack when the alphabet fits);
    ``compress_sa`` as in ``build_sa_samples`` (None = bit-pack the SA
    sample whenever it shrinks it); ``sigma`` declares a minimum alphabet
    (see ``prepare_tokens`` — the segmented index passes its global one;
    ``reserve_pad`` as there, None = reserve the pad slot for declared
    alphabets so same-``sigma`` builds share one layout).

    ``sa_config`` also carries the build-engine knobs (qgram / discard /
    local_sort) for both the distributed and the single-device path; the
    single-device path uses the fused-key fast builder unless ``fast=False``
    (the seed ``lax.while_loop`` reference — same output bit-for-bit).

    With a mesh, retries samplesort capacity overflows with doubled factor —
    the explicit analogue of Spark skew recovery (DESIGN.md §4).
    """
    tokens = np.asarray(tokens, dtype=np.int32)
    with TraceAnnotation("fm.build", n=len(tokens)) as span:
        text_length = len(tokens) + 1
        sa_kw = dict(sa_sample_rate=sa_sample_rate) if sa_sample_rate else {}

        if mesh is None:
            s, sigma = prepare_tokens(tokens, sample_rate, sigma, reserve_pad)
            span.set_metadata(sigma=sigma)
            return build_index_prepared(
                s, sigma, sample_rate=sample_rate, sa_config=sa_config,
                sa_sample_rate=sa_sample_rate, pack=pack, fast=fast,
                compress_sa=compress_sa, text_length=text_length,
            )

        parts = mesh.shape[sa_config.axis]
        s, sigma = prepare_tokens(tokens, parts * sample_rate, sigma,
                                  reserve_pad)
        span.set_metadata(sigma=sigma)
        # shard the text straight from the host: no device holds all of it
        s_dev = jax.device_put(
            s, NamedSharding(mesh, PartitionSpec(sa_config.axis)))
        cfg = sa_config
        for attempt in range(max_retries):
            isa = build_isa_sharded(s_dev, mesh, cfg, sigma=sigma)
            if not isa_overflowed(isa):
                break
            cfg = cfg._replace(capacity_factor=cfg.capacity_factor * 2)
        else:
            raise RuntimeError(
                f"samplesort capacity overflow after {max_retries} retries "
                f"(factor {cfg.capacity_factor})"
            )
        sa, bwt_arr, row = _bwt_jit(s_dev, isa, cfg, parts, mesh)
        fm = build_dist_fm_index(bwt_arr, row, mesh, sigma=sigma,
                                 sample_rate=sample_rate, pack=pack,
                                 compress_sa=compress_sa,
                                 sa=sa if sa_sample_rate else None, **sa_kw)
        return SequenceIndex(fm, sa, bwt_arr, row, sigma, len(s), text_length,
                             mesh=mesh)
