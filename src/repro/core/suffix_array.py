"""Single-device suffix-array construction by prefix doubling.

``isa_prefix_doubling`` is the reference implementation of the paper's
algorithm (§2.2) and the bit-for-bit oracle for every faster path:

    Init      rank[i] = Occ(S(i))          (count of strictly-smaller chars)
    Pair      pair rank[i] with rank[i+h]  (overflow pairs with a value that
                                            compares below every real rank)
    Re-rank   sort pairs, new rank = position of the head of the equal-group
    Iterate   h <- 2h, until all ranks distinct (<= ceil(log2 n) rounds)

``build_isa_fast`` / ``suffix_array_fast`` are the production build engine
(same output, asserted bit-for-bit by tests/test_build_fast.py), with three
hot-loop optimisations the reference deliberately omits:

* **Fused pair keys** — each (rank, rank[i+h]) pair packs into one uint32
  word (two for n > 65535) via ``core.keypack``, so the sort moves 2
  operands instead of 3 and the radix engine knows the significant key bits.
* **Packed q-gram init** — initial ranks come from the first
  q = words * floor(32 / ceil(log2 sigma)) characters packed into 1-2
  uint32 words (two words by default: 20 chars for the sigma=6 DNA
  corpora), so the loop starts at h=q and skips the first ceil(log2 q)
  doubling rounds (measured on the 64 Ki corpora: 5 of 16 rounds skipped
  for DNA and ZERO rounds left to run — the init resolves every suffix;
  english still runs 2 rounds over a 44%-then-3.5% active set).
* **Active-suffix discarding** — a suffix whose rank is unique never
  changes rank again; each round partition-compacts the still-ambiguous
  suffixes into a geometrically shrinking capacity bucket (host-driven, one
  compile per power-of-two capacity) and sorts only those.  Re-ranking uses
  the grouped form ``new_rank = r1 + (pair_head_pos - r1_head_pos)``, which
  reduces to the paper's head-position rank when everything is active.

Local sorts dispatch through ``kernels.ops.radix_sort`` (Pallas LSD radix
on TPU, jnp counting sort fallback) or ``lax.sort``, selected by the
``local_sort`` knob ("auto" picks radix on TPU, compare elsewhere — the
jnp counting sort loses to XLA's native sort on CPU by ~3x).

Measured end-to-end (benchmarks/table2_bwt.py, one CPU core, 64 Ki
corpora): 2.3-2.6x vs the seed single-jit builder and 2.7-3.8x vs the
Menon et al. competitor, identical BWT output everywhere.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax import lax

from . import keypack
from ..kernels import ops as kernel_ops

OVERFLOW_RANK = -1  # shorter suffix sorts first; real ranks are >= 0


def initial_ranks(s: jax.Array, sigma: int) -> jax.Array:
    """Paper's Init step: rank[i] = Occ(S(i)) via histogram + exclusive
    cumulative sum (the map/reduce + local scan of §2.2)."""
    counts = jnp.bincount(s, length=sigma)
    occ = jnp.cumsum(counts) - counts  # exclusive prefix sum == Occ(c)
    return occ[s].astype(jnp.int32)


def rerank_from_sorted(
    r1_sorted: jax.Array, r2_sorted: jax.Array
) -> tuple[jax.Array, jax.Array]:
    """Paper's Re-rank step, applied to lexicographically sorted pairs.

    new_rank[i] = i                 if pair[i] != pair[i-1]
                = new_rank[i-1]     otherwise
    which equals a prefix-max over ``i * [pair changed at i]``.

    Returns ``(new_ranks, all_distinct)``; ``all_distinct`` is true when every
    sorted pair differs from its predecessor (termination condition).
    """
    n = r1_sorted.shape[0]
    neq = (r1_sorted[1:] != r1_sorted[:-1]) | (r2_sorted[1:] != r2_sorted[:-1])
    flags = jnp.concatenate([jnp.ones((1,), dtype=bool), neq])
    heads = jnp.where(flags, jnp.arange(n, dtype=jnp.int32), 0)
    return lax.associative_scan(jnp.maximum, heads), jnp.all(flags)


def prefix_max(x: jax.Array, width: int = 1024) -> jax.Array:
    """Inclusive running max of an int32[n] array.

    A flat ``lax.associative_scan`` over tens of millions of elements
    takes minutes to compile for the TPU; this form — a running max inside
    ``width``-wide rows, then the running max of the row maxima (same
    recursion) carried into each row — compiles in seconds and returns the
    same values."""
    n = x.shape[0]
    if n <= width:
        return lax.cummax(x)
    lo = jnp.iinfo(x.dtype).min
    rows = lax.cummax(
        jnp.pad(x, (0, (-n) % width), constant_values=lo).reshape(-1, width),
        axis=1,
    )
    carried = prefix_max(rows[:, -1], width)
    before = jnp.concatenate([jnp.full((1,), lo, x.dtype), carried[:-1]])
    return jnp.maximum(rows, before[:, None]).reshape(-1)[:n]


def shifted_ranks(rank: jax.Array, h: jax.Array) -> jax.Array:
    """rank2[i] = rank[i+h] for i+h < n else OVERFLOW_RANK (paper's Shifting
    and Pairing, expressed as a roll + mask instead of a keyed join)."""
    n = rank.shape[0]
    idx = jnp.arange(n, dtype=jnp.int32)
    rolled = jnp.roll(rank, -h)
    return jnp.where(idx + h < n, rolled, OVERFLOW_RANK).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("sigma",))
def isa_prefix_doubling(s: jax.Array, sigma: int) -> jax.Array:
    """Compute the inverse suffix array (ISA: suffix index -> rank) of ``s``.

    ``s`` must terminate with the unique smallest sentinel (token 0); see
    ``alphabet.append_sentinel``.
    """
    n = s.shape[0]
    idx = jnp.arange(n, dtype=jnp.int32)
    rank0 = initial_ranks(s, sigma)

    def cond(state):
        _, h, done = state
        return (h < n) & ~done

    def body(state):
        rank, h, _ = state
        r2 = shifted_ranks(rank, h)
        r1s, r2s, perm = lax.sort((rank, r2, idx), num_keys=2)
        new_sorted, done = rerank_from_sorted(r1s, r2s)
        new_rank = jnp.zeros_like(rank).at[perm].set(new_sorted)
        return new_rank, h * 2, done

    # the sentinel makes n == 1 trivially done; otherwise at least one round
    rank, _, _ = lax.while_loop(
        cond, body, (rank0, jnp.int32(1), jnp.asarray(n == 1))
    )
    return rank


def sa_from_isa(isa: jax.Array) -> jax.Array:
    """SA[rank] = i  (inversion of a permutation)."""
    n = isa.shape[0]
    idx = jnp.arange(n, dtype=jnp.int32)
    return jnp.zeros_like(isa).at[isa].set(idx)


@functools.partial(jax.jit, static_argnames=("sigma",))
def suffix_array(s: jax.Array, sigma: int) -> jax.Array:
    """Suffix array of a sentinel-terminated token string."""
    return sa_from_isa(isa_prefix_doubling(s, sigma))


# ---------------------------------------------------------------------------
# fast build engine: fused keys + packed q-gram init + discarding
# ---------------------------------------------------------------------------

# engine dispatch lives in kernels.ops (single implementation, shared with
# the distributed sort engines in dist_sort.py)
from ..kernels.ops import (  # noqa: E402  (re-export)
    COMPARE,
    RADIX,
    resolve_sort_engine as resolve_local_sort,
)


@dataclasses.dataclass
class BuildStats:
    """Machine-readable build trajectory (feeds BENCH_build.json)."""

    n: int
    sigma: int
    q: int                       # packed chars in the init key (1 = Occ init)
    h0: int                      # first pairing distance (q, or 1)
    rounds_executed: int = 0
    rounds_skipped: int = 0      # h=1.. doubling rounds the q-gram init skips
    active_frac: list = dataclasses.field(default_factory=list)
    local_sort: str = COMPARE
    discard: bool = True

    def as_dict(self):
        return dataclasses.asdict(self)


@functools.partial(jax.jit, static_argnames=("fpw", "bits", "words", "engine"))
def _qgram_init(s, fpw: int, bits: int, words: int, engine: str):
    """Initial (rank, active) from the packed q-gram key of every suffix:
    one q-gram key sort + grouped re-rank instead of ceil(log2 q) doubling
    rounds.  rank = head position of the key-equal group (the same
    invariant the Occ init establishes); active = group size > 1."""
    n = s.shape[0]
    keys = keypack.qgram_keys_local(s, fpw, bits, words)
    kb = (min(32, fpw * bits),) * words
    idx = jnp.arange(n, dtype=jnp.int32)
    sorted_ops = kernel_ops.local_sort(
        (*keys, idx), words, engine=engine, key_bits=kb
    )
    ks, perm = sorted_ops[:words], sorted_ops[words]
    neq = jnp.zeros(n - 1, bool)
    for k in ks:
        neq = neq | (k[1:] != k[:-1])
    head = jnp.concatenate([jnp.ones(1, bool), neq])
    ranks_sorted = prefix_max(jnp.where(head, idx, 0))
    succ_head = jnp.concatenate([head[1:], jnp.ones(1, bool)])
    active_sorted = ~(head & succ_head)
    rank = jnp.zeros(n, jnp.int32).at[perm].set(ranks_sorted)
    active = jnp.zeros(n, bool).at[perm].set(active_sorted)
    return rank, active


@functools.partial(jax.jit, static_argnames=("sigma",))
def _occ_init(s, sigma: int):
    """Seed Occ init + active flags (char occurs more than once)."""
    counts = jnp.bincount(s, length=sigma)
    occ = jnp.cumsum(counts) - counts
    return occ[s].astype(jnp.int32), counts[s] > 1


@functools.lru_cache(maxsize=None)
def _fast_round(n: int, cap: int, engine: str):
    """One fused-key doubling round over the compacted active set.

    Static in (n, cap, engine) — the host loop shrinks cap geometrically,
    so at most log2(n) variants compile; h and n_active are traced.
    Grouped re-rank: every rank is the global head position of its equal
    group (invariant from both inits and preserved below), a size->=2 group
    is entirely active, and its active members are contiguous in the sorted
    active sequence — so
        new_rank = r1 + (pair_subrun_head_pos - r1_run_head_pos)
    equals the head position the full re-rank would assign.
    """
    spec = keypack.pair_spec(n)
    pads = spec.pad_words()
    kb = spec.key_bits
    W = spec.words

    @jax.jit
    def step(rank, active_idx, n_active, h):
        slot = jnp.arange(cap, dtype=jnp.int32)
        valid = slot < n_active
        ai = jnp.where(valid, active_idx, 0)
        r1 = rank[ai]
        tgt = ai + h
        r2 = jnp.where(tgt < n, rank[jnp.minimum(tgt, n - 1)], OVERFLOW_RANK)
        words = keypack.pack_pairs(r1, r2, spec)
        words = tuple(
            jnp.where(valid, w, jnp.uint32(p)) for w, p in zip(words, pads)
        )
        sorted_ops = kernel_ops.local_sort(
            (*words, ai), W, engine=engine, key_bits=kb
        )
        r1s, r2s = keypack.unpack_pairs(sorted_ops[:W], spec)
        ais = sorted_ops[W]

        valid_s = slot < n_active   # pads sort strictly last (keypack proof)
        neq1 = jnp.concatenate([jnp.ones(1, bool), r1s[1:] != r1s[:-1]])
        neq2 = jnp.concatenate([jnp.ones(1, bool), r2s[1:] != r2s[:-1]])
        r1_head = valid_s & neq1
        pair_head = valid_s & (neq1 | neq2)
        r1_pos = prefix_max(jnp.where(r1_head, slot, -1))
        pair_pos = prefix_max(jnp.where(pair_head, slot, -1))
        new_rank = r1s + (pair_pos - r1_pos)

        succ_head = (
            jnp.concatenate([pair_head[1:], jnp.zeros(1, bool)])
            | (slot + 1 >= n_active)
        )
        still = valid_s & ~(pair_head & succ_head)

        scatter_idx = jnp.where(valid_s, ais, n)
        rank = rank.at[scatter_idx].set(new_rank, mode="drop")
        (keep_pos,) = jnp.nonzero(still, size=cap, fill_value=cap)
        new_active = jnp.where(
            keep_pos < cap, ais[jnp.minimum(keep_pos, cap - 1)], n
        )
        return rank, new_active, jnp.sum(still.astype(jnp.int32))

    return step


def _cap_bucket(n_active: int, n: int, min_cap: int = 128) -> int:
    """Next power-of-two capacity (floored) for the compacted active set."""
    return min(n, max(min_cap, 1 << max(0, n_active - 1).bit_length()))


def build_isa_fast(
    s,
    sigma: int,
    *,
    local_sort: str = "auto",
    qgram: bool = True,
    qgram_words: int = 2,
    discard: bool = True,
):
    """ISA of a sentinel-terminated token string via the fused-key engine.

    Host-driven round loop (reads back the active count each round to pick
    the next capacity bucket); bit-for-bit identical to
    ``isa_prefix_doubling``.  Returns ``(isa, BuildStats)``.
    """
    s = jnp.asarray(s, jnp.int32)
    n = s.shape[0]
    engine = resolve_local_sort(local_sort)
    if qgram and n > 1:
        q, fpw, bits = keypack.qgram_params(sigma, qgram_words)
        rank, active = _qgram_init(s, fpw, bits, qgram_words, engine)
        h = q
        skipped = keypack.qgram_rounds_skipped(q)
    else:
        q, h, skipped = 1, 1, 0
        rank, active = _occ_init(s, sigma)
    stats = BuildStats(n=n, sigma=sigma, q=q, h0=h, rounds_skipped=skipped,
                       local_sort=engine, discard=discard)
    if n <= 1:
        return rank, stats

    if discard:
        (active_pos,) = jnp.nonzero(active, size=n, fill_value=n)
        n_active = int(jnp.sum(active))
        cap = _cap_bucket(n_active, n)
        active_buf = active_pos[:cap].astype(jnp.int32)
    else:
        n_active = n if bool(jnp.any(active)) else 0
        cap = n
        active_buf = jnp.arange(n, dtype=jnp.int32)

    while n_active > 0:
        assert h < 2 * n, "prefix doubling failed to converge (bad sentinel?)"
        stats.active_frac.append(n_active / n)
        step = _fast_round(n, cap, engine)
        rank, new_buf, n_active_dev = step(
            rank, active_buf, jnp.int32(n_active), jnp.int32(h)
        )
        stats.rounds_executed += 1
        h *= 2
        remaining = int(n_active_dev)
        if discard:
            n_active = remaining
            new_cap = _cap_bucket(n_active, n)
            active_buf = new_buf[:new_cap] if new_cap < cap else new_buf
            cap = min(cap, new_cap)
        else:
            n_active = n if remaining else 0
    return rank, stats


def suffix_array_fast(s, sigma: int, **kwargs):
    """(SA, BuildStats) via the fused-key build engine."""
    isa, stats = build_isa_fast(s, sigma, **kwargs)
    return sa_from_isa(isa), stats


def suffix_array_naive(s) -> "np.ndarray":  # noqa: F821 - numpy oracle
    """O(n^2 log n) numpy oracle for tests."""
    import numpy as np

    s = np.asarray(s)
    n = len(s)
    suffixes = sorted(range(n), key=lambda i: s[i:].tolist())
    return np.array(suffixes, dtype=np.int32)
