"""Distributed prefix-doubling suffix array + BWT (the paper's contribution).

The Spark pipeline of §2.2 mapped onto a TPU mesh axis (DESIGN.md §2), with
the PR-2 build-engine optimisations (fused keys / q-gram init / discarding):

    Init       packed q-gram ranking: the first q = words * floor(32/ceil
               (log2 sigma)) characters of every suffix packed into 1-2
               uint32 words (q ppermute shifts), one distributed sort, and
               a grouped re-rank — replaces the seed's single-char Occ init
               AND the first ceil(log2 q) doubling rounds (3-5 rounds on
               the paper's corpora; single-device builds measure 2.3-2.6x
               end-to-end vs the seed on CPU).  The seed histogram init
               (`dist_initial_ranks`) remains behind ``qgram=False``.
    Shift      ``shift_sharded`` (two static ppermutes instead of a keyed join)
    Pair+Sort  each (rank, rank[i+h]) pair packs into one fused uint32 key
               word (two for n > 65535; ``core.keypack``), so the engines
               move one or two uint32 keys + an int32 index instead of three
               int32 operands — engine 'bitonic' (deterministic) or
               'samplesort' (the paper's range shuffle); local sorts
               dispatch to lax.sort or the Pallas LSD radix engine
               (``local_sort`` knob).
    Re-rank    grouped form: new_rank = rank + (pair-run head - rank-run
               head), boundary halos + local prefix-max + distributed
               exclusive max.  Identical to the paper's head-position rank
               when every suffix is active, and correct under discarding.
    Discard    a suffix whose rank is unique never re-sorts: its key becomes
               a pad, samplesort's capacity-bounded all_to_all skips pad
               slots entirely (shuffle volume tracks the active fraction;
               the bitonic engine keeps fixed buffers and gains nothing),
               and re-ranking touches only the shrinking active set.
    Scatter    route new ranks + active flags back to index order
               (sort-by-permutation or capacity-bounded all_to_all)
    Iterate    h <- q, 2q, 4q, ... in a ``lax.while_loop`` that stops on
               the no-actives-left flag; the traced h picks the shift's
               static ppermute perms with a ``lax.switch``.

Everything here runs INSIDE ``shard_map``; ``build_isa_sharded`` /
``build_bwt_sharded`` are the jit-able host-level entry points.  The
doubling state (rank, done) is exposed so the driver can checkpoint the
loop at any round boundary (fault tolerance — DESIGN.md §7).
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from jax import shard_map

from . import keypack
from ..kernels import ops as kernel_ops
from .dist_sort import (
    ShardInfo,
    bitonic_sort_sharded,
    exclusive_max_sharded,
    exclusive_scan_sharded,
    samplesort_sharded,
    scatter_to_index_bitonic,
    scatter_to_index_samplesort,
    shift_sharded,
)
from .suffix_array import OVERFLOW_RANK, prefix_max, resolve_local_sort

BITONIC = "bitonic"
SAMPLESORT = "samplesort"


class DistSAConfig(NamedTuple):
    axis: str = "parts"
    engine: str = BITONIC
    capacity_factor: float = 2.0   # samplesort bucket slack (Spark skew knob)
    rounds: int | None = None      # default ceil(log2 (n / h0))
    qgram: bool = True             # packed q-gram init (False: seed Occ init)
    qgram_words: int = 2           # uint32 words per init key (64-bit logical)
    discard: bool = True           # drop unique-rank suffixes from the loop
    local_sort: str = "auto"       # "compare" | "radix" | "auto" (radix on TPU)


def _gidx(info: ShardInfo) -> jax.Array:
    return lax.axis_index(info.axis) * info.part_size + jnp.arange(
        info.part_size, dtype=jnp.int32
    )


def dist_initial_ranks(info: ShardInfo, s_local: jax.Array, sigma: int):
    """Seed Init: global char histogram (map/reduce == psum of local
    bincounts), exclusive cumsum = Occ, local lookup.  Also returns the
    active flags (char occurs more than once) for the discarding loop."""
    counts = lax.psum(jnp.bincount(s_local, length=sigma), info.axis)
    occ = jnp.cumsum(counts) - counts
    return occ[s_local].astype(jnp.int32), counts[s_local] > 1


def dist_rerank(
    info: ShardInfo,
    cols,
    n_valid: jax.Array,
    *,
    grouped: bool = False,
    want_active: bool = False,
):
    """Paper's Re-Ranking on the globally sorted (active) sequence.

    ``cols`` is a tuple of same-dtype sorted column arrays whose valid
    slots form a prefix of each local shard (engines guarantee this);
    global position of local valid slot p = (# valid on earlier devices)
    + p.  Group heads are found with a one-element halo from the previous
    non-empty device.

    * ``grouped=False``: rank = global head position of the equal-group —
      the paper's re-rank, used for the init sort.
    * ``grouped=True`` (``cols = (rank, rank2)``): rank = cols[0] +
      (pair-run head pos - rank-run head pos).  Because every rank is the
      head position of its rank-group (invariant of both inits, preserved
      here) and any group of size >= 2 is entirely active and contiguous in
      the sorted active sequence, this equals the head position the full
      re-rank would assign — while only ever looking at active suffixes.
    * ``want_active``: additionally return "my pair-group has size >= 2"
      flags (needs a successor halo: the first valid pair of the next
      non-empty device).

    Returns ``(ranks, active)``; ``active`` is None unless requested.
    """
    cols = tuple(cols)
    slots = cols[0].shape[0]
    pos = jnp.arange(slots, dtype=jnp.int32)
    valid = pos < n_valid
    offset = exclusive_scan_sharded(info, n_valid)
    gpos = offset + pos

    # previous device's last valid tuple (halo for the boundary comparison)
    has_any = n_valid > 0
    last = jnp.maximum(n_valid - 1, 0)
    lastk = jnp.stack([c[last] for c in cols])
    g_last = lax.all_gather(lastk, info.axis)          # (P, K)
    g_has = lax.all_gather(has_any, info.axis)         # (P,)
    me = lax.axis_index(info.axis)
    jidx = jnp.arange(info.parts)
    prev_mask = (jidx < me) & g_has
    prev_exists = jnp.any(prev_mask)
    prev_j = jnp.argmax(jnp.where(prev_mask, jidx, -1))
    prev_k = g_last[prev_j]                            # (K,)

    prevs = [
        jnp.concatenate([prev_k[i][None], c[:-1]]) for i, c in enumerate(cols)
    ]
    neq0 = cols[0] != prevs[0]
    neq_pair = neq0
    for c, pv in zip(cols[1:], prevs[1:]):
        neq_pair = neq_pair | (c != pv)
    # first global element has no predecessor -> always a group head
    neq0 = neq0.at[0].set(jnp.where(prev_exists, neq0[0], True))
    neq_pair = neq_pair.at[0].set(jnp.where(prev_exists, neq_pair[0], True))

    def head_pos(heads):
        local = prefix_max(jnp.where(heads, gpos, -1))
        carry = exclusive_max_sharded(info, local[-1], identity=-1)
        return jnp.maximum(local, carry)

    pair_head = valid & neq_pair
    pair_pos = head_pos(pair_head)
    if grouped:
        col0_pos = head_pos(valid & neq0)
        ranks = (cols[0].astype(jnp.int32) + (pair_pos - col0_pos)).astype(
            jnp.int32
        )
    else:
        ranks = pair_pos.astype(jnp.int32)
    if not want_active:
        return ranks, None

    # successor halo: first valid tuple of the next non-empty device
    firstk = jnp.stack([c[0] for c in cols])
    g_first = lax.all_gather(firstk, info.axis)        # (P, K)
    next_mask = (jidx > me) & g_has
    next_j = jnp.argmax(next_mask)                     # first True (or 0)
    next_k = g_first[next_j]

    total = lax.psum(n_valid, info.axis)
    in_shard = pos + 1 < n_valid
    neq_succ = jnp.zeros(slots, bool)
    for i, c in enumerate(cols):
        succ = jnp.where(in_shard, jnp.roll(c, -1), next_k[i])
        neq_succ = neq_succ | (c != succ)
    is_glast = gpos == total - 1                       # no successor at all
    active = valid & ~(pair_head & (neq_succ | is_glast))
    return ranks, active


def dist_qgram_init(info: ShardInfo, cfg: DistSAConfig, eng: str,
                    s_local: jax.Array, sigma: int):
    """Packed q-gram init: rank every suffix by its first q characters in
    one distributed sort.  Returns (rank, active, q, overflow)."""
    q, fpw, bits = keypack.qgram_params(sigma, cfg.qgram_words)
    m = info.part_size
    if q - 1 <= m:
        # all q windows are local given a (q-1)-char halo from the next
        # device: ONE small ppermute instead of q-1 full-shard shifts
        if q > 1:
            perm = [(i, (i - 1) % info.parts) for i in range(info.parts)]
            halo = lax.ppermute(s_local[: q - 1], info.axis, perm)
            # past the global end the window reuses the sentinel value 0
            halo = jnp.where(
                lax.axis_index(info.axis) == info.parts - 1, 0, halo
            )
            ext = jnp.concatenate([s_local, halo])
        else:
            ext = s_local
        chars = [ext[j: j + m] for j in range(q)]
    else:
        # tiny shards (m < q - 1): fall back to iterated distributed shifts
        chars = [s_local]
        for _ in range(q - 1):
            chars.append(shift_sharded(info, chars[-1], 1, 0))
    words = []
    for w in range(cfg.qgram_words):
        v = jnp.zeros_like(s_local, dtype=jnp.uint32)
        for j in range(w * fpw, (w + 1) * fpw):
            v = (v << bits) | chars[j].astype(jnp.uint32)
        words.append(v)
    gidx = _gidx(info)
    nw = cfg.qgram_words
    kb = (min(32, fpw * bits),) * nw

    if cfg.engine == BITONIC:
        sorted_ops = bitonic_sort_sharded(
            info, (*words, gidx), num_keys=nw, local_sort=eng, key_bits=kb
        )
        ranks_s, active_s = dist_rerank(
            info, sorted_ops[:nw], jnp.int32(info.part_size),
            grouped=False, want_active=True,
        )
        rank, act = scatter_to_index_bitonic(
            info, sorted_ops[nw], (ranks_s, active_s.astype(jnp.int32)),
            local_sort=eng,
        )
        return rank, act.astype(bool), q, jnp.asarray(False)

    pads = (keypack.qgram_pad(fpw, bits),) * nw
    res = samplesort_sharded(
        info, (*words, gidx), num_keys=nw,
        capacity_factor=cfg.capacity_factor, key_pads=pads,
        local_sort=eng, key_bits=kb,
    )
    ranks_s, active_s = dist_rerank(
        info, res.operands[:nw], res.n_valid, grouped=False, want_active=True
    )
    pos = jnp.arange(res.operands[0].shape[0], dtype=jnp.int32)
    (rank, act), ovf = scatter_to_index_samplesort(
        info, res.operands[nw], (ranks_s, active_s.astype(jnp.int32)),
        valid=pos < res.n_valid, capacity_factor=cfg.capacity_factor,
    )
    bad = res.overflow | ovf
    rank = jnp.where(bad, jnp.int32(-2), rank)
    return rank, act.astype(bool), q, bad


def _doubling_round(info: ShardInfo, cfg: DistSAConfig, eng: str,
                    spec: keypack.PairSpec, h, rank, gidx, active):
    """One fused-key prefix-doubling round over the active suffixes;
    returns (new_rank, new_active, done)."""
    r2 = shift_sharded(info, rank, h, OVERFLOW_RANK)
    words = keypack.pack_pairs(rank, r2, spec)
    pads = spec.pad_words()
    kb = spec.key_bits
    W = spec.words
    if cfg.discard:
        # unique-rank suffixes become pad slots: they sort last and (with
        # samplesort) never enter the all_to_all
        words = tuple(
            jnp.where(active, w, jnp.uint32(p)) for w, p in zip(words, pads)
        )

    if cfg.engine == BITONIC:
        sorted_ops = bitonic_sort_sharded(
            info, (*words, gidx), num_keys=W, local_sort=eng, key_bits=kb
        )
        r1s, r2s = keypack.unpack_pairs(sorted_ops[:W], spec)
        idxs = sorted_ops[W]
        if cfg.discard:
            # pads sort after every real pair key, so the global active
            # prefix maps to per-device valid prefixes
            n_act = lax.psum(jnp.sum(active.astype(jnp.int32)), info.axis)
            me = lax.axis_index(info.axis)
            n_valid = jnp.clip(
                n_act - me * info.part_size, 0, info.part_size
            ).astype(jnp.int32)
        else:
            n_valid = jnp.int32(info.part_size)
        ranks_s, active_s = dist_rerank(
            info, (r1s, r2s), n_valid, grouped=True, want_active=True
        )
        pos = jnp.arange(r1s.shape[0], dtype=jnp.int32)
        valid_s = pos < n_valid
        vr = jnp.where(valid_s, ranks_s, 0)
        va = jnp.where(valid_s, 1 + active_s.astype(jnp.int32), 0)
        nr, na = scatter_to_index_bitonic(info, idxs, (vr, va), local_sort=eng)
        bad = jnp.asarray(False)
    else:
        n_valid_in = (
            jnp.sum(active.astype(jnp.int32)) if cfg.discard else None
        )
        res = samplesort_sharded(
            info, (*words, gidx), num_keys=W,
            capacity_factor=cfg.capacity_factor, key_pads=pads,
            n_valid_in=n_valid_in, local_sort=eng, key_bits=kb,
        )
        r1s, r2s = keypack.unpack_pairs(res.operands[:W], spec)
        idxs = res.operands[W]
        ranks_s, active_s = dist_rerank(
            info, (r1s, r2s), res.n_valid, grouped=True, want_active=True
        )
        pos = jnp.arange(r1s.shape[0], dtype=jnp.int32)
        valid_s = pos < res.n_valid
        vr = jnp.where(valid_s, ranks_s, 0)
        va = jnp.where(valid_s, 1 + active_s.astype(jnp.int32), 0)
        (nr, na), ovf = scatter_to_index_samplesort(
            info, idxs, (vr, va), valid=valid_s,
            capacity_factor=cfg.capacity_factor,
        )
        bad = res.overflow | ovf

    # va encodes per-index outcome: 0 untouched (stays final), 1 became
    # unique, 2 still ambiguous
    new_rank = jnp.where(na > 0, nr, rank)
    new_active = jnp.where(na > 0, na == 2, active)
    # overflow poisons the result with a recognizable sentinel; the host
    # driver checks ``isa_overflowed`` and retries with a larger factor
    new_rank = jnp.where(bad, jnp.int32(-2), new_rank)
    remaining = lax.psum(jnp.sum(new_active.astype(jnp.int32)), info.axis)
    return new_rank, new_active, (remaining == 0) | bad


def num_rounds(n: int, h0: int = 1) -> int:
    """Doubling rounds to cover length n starting from pairing distance
    h0: smallest r with h0 * 2^r >= n."""
    if n <= max(1, h0):
        return 0
    return max(1, math.ceil(math.log2(n / h0)))


def dist_isa_local(
    info: ShardInfo, cfg: DistSAConfig, s_local: jax.Array, sigma: int
) -> jax.Array:
    """shard_map body: local shard of S -> local shard of the ISA."""
    if cfg.qgram and info.n > 1:
        eng = resolve_local_sort(cfg.local_sort)
        rank, active, h0, bad = dist_qgram_init(info, cfg, eng, s_local, sigma)
    else:
        eng = resolve_local_sort(cfg.local_sort)
        rank, active = dist_initial_ranks(info, s_local, sigma)
        h0, bad = 1, jnp.asarray(False)
    gidx = _gidx(info)
    spec = keypack.pair_spec(info.n)
    remaining = lax.psum(jnp.sum(active.astype(jnp.int32)), info.axis)
    done = jnp.asarray(info.n <= 1) | (remaining == 0) | bad
    rounds = cfg.rounds if cfg.rounds is not None else num_rounds(info.n, h0)

    def more(st):
        r, _, _, done = st
        return (r < rounds) & ~done

    def round_(st):
        r, rank, active, _ = st
        h = jnp.left_shift(jnp.int32(h0), r)
        return (r + 1, *_doubling_round(info, cfg, eng, spec, h, rank, gidx,
                                        active))

    # one compiled round, iterated: the program stays the size of a round
    # whatever ceil(log2 n) is
    _, rank, _, _ = lax.while_loop(
        more, round_, (jnp.int32(0), rank, active, done))
    return rank


def dist_bwt_local(
    info: ShardInfo, cfg: DistSAConfig, s_local: jax.Array, isa_local: jax.Array
):
    """shard_map body: (S, ISA) -> (SA, BWT, row) local shards.

    The paper's "join": bwt[i] = S[(SA[i]-1) mod n].  Routing steps (all
    permutations, so the bitonic engine is always exact here):
      1. SA[isa[i]] = i           (scatter by rank)
      2. fetch c[i] = S[SA[i]-1]  (scatter query to owner, answer in place)
      3. scatter answers back by output position
    """
    gidx = _gidx(info)
    n = info.n
    # 1. SA in index order
    (sa_local,) = scatter_to_index_bitonic(info, isa_local, (gidx,))
    # 2. j = (SA-1) mod n; route (j, out_pos) to the owner of j
    j = jnp.mod(sa_local - 1, n)
    j_sorted, outpos = bitonic_sort_sharded(info, (j, gidx), num_keys=1)
    # j is a permutation -> after sorting, local j's are exactly my range
    chars = s_local[j_sorted - lax.axis_index(info.axis) * info.part_size]
    # 3. route chars to their output position
    (bwt_local,) = scatter_to_index_bitonic(info, outpos, (chars,))
    row = lax.psum(jnp.sum(jnp.where(sa_local == 0, gidx, 0)), info.axis)
    return sa_local, bwt_local, row.astype(jnp.int32)


# ---------------------------------------------------------------------------
# host-level entry points (jit + shard_map over a 1-D mesh axis)
# ---------------------------------------------------------------------------

def isa_overflowed(isa) -> bool:
    """True when a samplesort round overflowed its capacity bound."""
    return bool(jnp.any(isa == -2))


@functools.partial(
    jax.jit, static_argnames=("sigma", "cfg", "mesh_axis_size", "mesh")
)
def _isa_jit(s, sigma, cfg, mesh_axis_size, mesh):
    info = ShardInfo(cfg.axis, mesh_axis_size, s.shape[0] // mesh_axis_size)
    fn = functools.partial(dist_isa_local, info, cfg, sigma=sigma)
    return shard_map(
        fn, mesh=mesh, in_specs=P(cfg.axis), out_specs=P(cfg.axis),
        check_vma=not kernel_ops.kernels_on_chip(),
    )(s)


def build_isa_sharded(s, mesh: Mesh, cfg: DistSAConfig = DistSAConfig(), *, sigma: int):
    """Distributed ISA of a sentinel-terminated token string.

    ``len(s)`` must be divisible by the mesh axis size (pad upstream with
    trailing sentinels is NOT valid — the sentinel must be unique; instead
    the data pipeline pads with distinct high tokens, see data/corpus.py).
    """
    axis_size = mesh.shape[cfg.axis]
    if s.shape[0] % axis_size:
        raise ValueError(f"n={s.shape[0]} not divisible by axis size {axis_size}")
    s = jax.device_put(s, NamedSharding(mesh, P(cfg.axis)))
    return _isa_jit(s, sigma, cfg, axis_size, mesh)


@functools.partial(jax.jit, static_argnames=("cfg", "mesh_axis_size", "mesh"))
def _bwt_jit(s, isa, cfg, mesh_axis_size, mesh):
    info = ShardInfo(cfg.axis, mesh_axis_size, s.shape[0] // mesh_axis_size)
    fn = functools.partial(dist_bwt_local, info, cfg)
    return shard_map(
        fn,
        mesh=mesh,
        in_specs=(P(cfg.axis), P(cfg.axis)),
        out_specs=(P(cfg.axis), P(cfg.axis), P()),
        check_vma=not kernel_ops.kernels_on_chip(),
    )(s, isa)


def build_bwt_sharded(s, mesh: Mesh, cfg: DistSAConfig = DistSAConfig(), *, sigma: int):
    """Distributed (SA, BWT, row) of a sentinel-terminated token string."""
    isa = build_isa_sharded(s, mesh, cfg, sigma=sigma)
    axis_size = mesh.shape[cfg.axis]
    s = jax.device_put(s, NamedSharding(mesh, P(cfg.axis)))
    return _bwt_jit(s, isa, cfg, axis_size, mesh)
