"""Distributed sorting / scanning primitives for TPU meshes.

These functions are called INSIDE ``shard_map`` over a 1-D device axis
(``info.axis``) and operate on the local shard view.  They implement the
Spark-shuffle equivalents from DESIGN.md §4:

* ``bitonic_sort_sharded`` — Batcher bitonic merge-exchange across devices
  (deterministic buffer sizes, ``log²P`` ppermute rounds; the beyond-paper
  engine — SPMD-native, no capacity assumptions).
* ``samplesort_sharded`` — the paper-faithful range-partitioned sample sort:
  regular splitter sampling + capacity-bounded ``all_to_all`` shuffle.
  Overflow is reported, not hidden (Spark would spill; ICI cannot).
* ``exclusive_scan_sharded`` / ``exclusive_max_sharded`` — distributed
  exclusive scans of per-shard aggregates (the "offset of the previous
  partitions" of the paper's Re-Ranking step).
* ``shift_sharded`` — the distributed roll that implements the paper's
  "Shifting" map (rank[i + h]) with two neighbour ppermutes.

All collective permutations use static perms (ppermute requirement); the
prefix-doubling driver's traced pairing distance ``h`` selects among the
``P`` static shard offsets (``shift_sharded``).

Fused-key layout (PR 2): the doubling driver packs each (rank, rank[i+h])
pair into 1-2 **uint32 key words** (``core.keypack``), so both engines sort
one or two unsigned key operands plus an int32 index payload instead of
three int32 operands.  Consequences handled here:

* pads are per-dtype (``pad_value``) instead of the signed ``INT_PAD``, and
  a key word may legitimately saturate its field (packed q-gram keys), so
  the samplesort recombine step breaks pad/real ties on a validity key;
* local sorts dispatch through ``local_sort``/``key_bits`` to either
  ``lax.sort`` or the Pallas LSD radix engine (``kernels.ops.radix_sort``);
* ``samplesort_sharded`` takes ``n_valid_in`` so the discarding driver can
  mark already-unique suffixes as pad slots — they are excluded from
  sampling and never enter the all_to_all, shrinking shuffle traffic with
  the active fraction.
"""

from __future__ import annotations

from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp
from jax import lax

# COMPARE/RADIX and the local-sort dispatch (lax.sort vs the LSD radix
# pipeline over key_bits significant bits) live in kernels.ops — one
# implementation shared with the single-device builder
from ..kernels import ops as kernel_ops
from ..kernels.ops import COMPARE, RADIX  # noqa: F401  (re-export)


def pad_value(dtype) -> int:
    """Largest value of ``dtype`` — the pad key for unsigned/signed sorts.
    (The seed's signed int32 ``INT_PAD`` is this for int32; uint32 key
    words need 0xFFFFFFFF, which int32 comparison would order *first*.)"""
    return int(jnp.iinfo(jnp.dtype(dtype)).max)


class ShardInfo(NamedTuple):
    """Static description of the sharded 1-D array layout."""

    axis: str        # mesh axis name the array is sharded over
    parts: int       # number of shards P (must be a power of two for bitonic)
    part_size: int   # local elements m; global n = P * m

    @property
    def n(self) -> int:
        return self.parts * self.part_size


def _me(info: ShardInfo) -> jax.Array:
    return lax.axis_index(info.axis)


# ---------------------------------------------------------------------------
# distributed exclusive scans (per-shard aggregates)
# ---------------------------------------------------------------------------

def exclusive_scan_sharded(info: ShardInfo, local_agg: jax.Array) -> jax.Array:
    """Sum of ``local_agg`` over all devices with smaller axis index.

    ``local_agg`` may be scalar or have trailing dims (e.g. per-character
    count vectors for the distributed Occ table).
    """
    gathered = lax.all_gather(local_agg, info.axis)  # (P, ...)
    mask = jnp.arange(info.parts) < _me(info)
    mask = mask.reshape((info.parts,) + (1,) * (gathered.ndim - 1))
    return jnp.sum(jnp.where(mask, gathered, 0), axis=0)


def exclusive_max_sharded(
    info: ShardInfo, local_agg: jax.Array, identity: int = -1
) -> jax.Array:
    """Max of ``local_agg`` over devices with smaller axis index."""
    gathered = lax.all_gather(local_agg, info.axis)
    mask = jnp.arange(info.parts) < _me(info)
    mask = mask.reshape((info.parts,) + (1,) * (gathered.ndim - 1))
    return jnp.max(jnp.where(mask, gathered, identity), axis=0)


# ---------------------------------------------------------------------------
# distributed shift (the paper's "Shifting and Pairing" map)
# ---------------------------------------------------------------------------

def shift_sharded(
    info: ShardInfo, x: jax.Array, h, fill: int
) -> jax.Array:
    """out[g] = x[g + h] for global g, ``fill`` past the end.

    The data for any destination shard lives on at most two source shards
    (DESIGN.md §2 table, "distributed roll").  A static ``h`` (Python int)
    fixes their ppermute perms.  A traced ``h`` (the prefix-doubling loop's
    pairing distance) picks the pair of whole-shard offsets with a
    ``lax.switch`` over the ``P`` possible ones and rolls by ``h mod m``
    with a dynamic slice.
    """
    P, m = info.parts, info.part_size

    def ahead(j: int) -> jax.Array:
        """The shard of device (me + j): sender i sends to (i - j)."""
        if j % P == 0:
            return x
        return lax.ppermute(x, info.axis, [(i, (i - j) % P) for i in range(P)])

    if isinstance(h, int):
        q, rs = divmod(h, m)
        if q >= P:  # the whole shard is past the end
            return jnp.full_like(x, fill)
        a = ahead(q)
        out = a if rs == 0 else jnp.concatenate([a[rs:], ahead(q + 1)[:rs]])
    else:
        h = jnp.asarray(h, jnp.int32)
        # q >= P leaves every slot past the end; any branch then serves
        pair = lax.switch(
            jnp.minimum(h // m, P - 1),
            [lambda j=j: jnp.concatenate([ahead(j), ahead(j + 1)])
             for j in range(P)],
        )
        out = lax.dynamic_slice(pair, (h % m,), (m,))

    gidx = _me(info) * m + jnp.arange(m, dtype=jnp.int32)
    return jnp.where(gidx + h < info.n, out, fill)


# ---------------------------------------------------------------------------
# engine 1: bitonic merge-exchange
# ---------------------------------------------------------------------------

def _merge_split(
    info: ShardInfo,
    operands: tuple[jax.Array, ...],
    num_keys: int,
    j: int,
    keep_low: jax.Array,
    is_lower: jax.Array,
    engine: str,
    key_bits,
):
    """Exchange full shards with partner ``me ^ j``; keep low or high half of
    the merged 2m block.  Multiple key operands give the lexicographic order
    (avoids int64 key packing, which TPUs dislike — fused uint32 words from
    ``core.keypack`` arrive here as separate operands).

    Both partners must sort the SAME sequence: both local engines are
    stable, so with tied keys the payload order depends on concatenation
    order.  Canonical order = lower device's shard first on both sides,
    which makes the kept halves exactly complementary."""
    m = info.part_size
    perm = [(i, i ^ j) for i in range(info.parts)]
    received = tuple(lax.ppermute(x, info.axis, perm) for x in operands)
    merged = kernel_ops.local_sort(
        tuple(
            jnp.concatenate(
                [jnp.where(is_lower, a, b), jnp.where(is_lower, b, a)]
            )
            for a, b in zip(operands, received)
        ),
        num_keys, engine=engine, key_bits=key_bits,
    )
    start = jnp.where(keep_low, 0, m)
    return tuple(lax.dynamic_slice_in_dim(x, start, m) for x in merged)


def bitonic_sort_sharded(
    info: ShardInfo,
    operands: Sequence[jax.Array],
    num_keys: int = 1,
    *,
    local_sort: str = COMPARE,
    key_bits=None,
) -> tuple[jax.Array, ...]:
    """Globally sort sharded arrays lexicographically by the first
    ``num_keys`` operands; remaining operands are payloads carried along.

    Returns shards of the globally sorted sequence (device d holds global
    positions [d*m, (d+1)*m)) — deterministic sizes, no capacity bounds.
    """
    P = info.parts
    if P & (P - 1):
        raise ValueError(f"bitonic engine needs power-of-two parts, got {P}")
    operands = kernel_ops.local_sort(operands, num_keys, engine=local_sort,
                                     key_bits=key_bits)
    me = _me(info)
    k = 2
    while k <= P:
        j = k // 2
        while j >= 1:
            partner = me ^ j
            ascending = (me & k) == 0
            is_lower = me < partner
            keep_low = is_lower == ascending
            operands = _merge_split(
                info, operands, num_keys, j, keep_low, is_lower,
                local_sort, key_bits,
            )
            j //= 2
        k *= 2
    return operands


def scatter_to_index_bitonic(
    info: ShardInfo, gidx: jax.Array, values: tuple[jax.Array, ...],
    *, local_sort: str = COMPARE,
) -> tuple[jax.Array, ...]:
    """Route (gidx, values) so device d ends up with values for global
    indices [d*m, (d+1)*m) in order.  ``gidx`` must be a permutation of
    0..n-1, hence sorting by it is a deterministic all-to-all."""
    kb = (max(1, info.n - 1).bit_length(),)
    sorted_ops = bitonic_sort_sharded(
        info, (gidx, *values), num_keys=1, local_sort=local_sort, key_bits=kb
    )
    return sorted_ops[1:]


# ---------------------------------------------------------------------------
# engine 2: sample sort (paper-faithful range shuffle)
# ---------------------------------------------------------------------------

def _lex_less(a: tuple, b: tuple):
    """Elementwise lexicographic a < b over parallel key arrays."""
    lt = jnp.zeros(jnp.broadcast_shapes(a[0].shape, b[0].shape), dtype=bool)
    eq = jnp.ones_like(lt)
    for x, y in zip(a, b):
        lt = lt | (eq & (x < y))
        eq = eq & (x == y)
    return lt


def _lex_searchsorted(sorted_keys: tuple, queries: tuple) -> jax.Array:
    """searchsorted(side='left') for multi-key arrays: position of the first
    sorted element not less than the query.  Binary search, vmapped over
    queries."""
    m = sorted_keys[0].shape[0]
    steps = max(1, (m - 1).bit_length())

    def one(q):
        # derive the carry from varying data so shard_map's varying-manual-
        # axes check accepts the fori_loop (constants would be unvarying)
        zero = (q[0] * 0).astype(jnp.int32)
        lo = zero
        hi = zero + m

        def body(_, state):
            lo, hi = state
            mid = (lo + hi) // 2
            key_mid = tuple(k[jnp.minimum(mid, m - 1)] for k in sorted_keys)
            # freeze once converged: extra fori iterations after lo == hi
            # must not move the bounds (they once pushed lo past m, which
            # made the capacity clip send one element twice — caught by the
            # non-power-of-two device-count test)
            active = lo < hi
            go_right = _lex_less(key_mid, q)
            new_lo = jnp.where(active & go_right, mid + 1, lo)
            new_hi = jnp.where(active & ~go_right, mid, hi)
            return new_lo, new_hi

        lo, hi = lax.fori_loop(0, steps + 1, body, (lo, hi))
        return lo

    return jax.vmap(one)(queries)


class SampleSortResult(NamedTuple):
    operands: tuple[jax.Array, ...]  # local slots, valid entries sorted first
    n_valid: jax.Array               # scalar: valid slots on this device
    overflow: jax.Array              # scalar bool: capacity exceeded anywhere


def samplesort_sharded(
    info: ShardInfo,
    operands: Sequence[jax.Array],
    num_keys: int = 1,
    capacity_factor: float = 2.0,
    *,
    key_pads: Sequence[int] | None = None,
    n_valid_in: jax.Array | None = None,
    local_sort: str = COMPARE,
    key_bits=None,
) -> SampleSortResult:
    """Paper's range-partitioned sort: sample splitters, range-shuffle via
    capacity-bounded all_to_all, sort locally.

    The global order is: all valid elements of device 0, then device 1, ...
    (within a device, valid slots are sorted and padded slots follow).
    Capacity per (src, dst) bucket is ``ceil(capacity_factor * m / P)``;
    overflow sets the flag (driver retries with larger factor — the explicit
    version of Spark's skew handling).

    ``key_pads`` is the per-key pad value (defaults to the dtype max; fused
    uint32 key words pass their field-limited pad from ``core.keypack``).  A
    real key may equal the pad (saturated q-gram fields), so the recombine
    sort breaks ties on a validity key — valid slots always sort first.

    ``n_valid_in`` (per-device count; requires the caller to have set the
    trailing/inactive slots to ``key_pads``) restricts splitter sampling to
    valid slots and **excludes pad slots from the shuffle entirely** — with
    active-suffix discarding the all_to_all volume shrinks with the active
    fraction instead of staying O(m).
    """
    P, m = info.parts, info.part_size
    operands = tuple(operands)
    if key_pads is None:
        key_pads = tuple(pad_value(k.dtype) for k in operands[:num_keys])

    # 1. local sort (stable engines; caller's pad slots go last)
    ops = kernel_ops.local_sort(operands, num_keys, engine=local_sort,
                                key_bits=key_bits)
    keys_s = ops[:num_keys]
    m_valid = jnp.int32(m) if n_valid_in is None else n_valid_in.astype(jnp.int32)

    # 2. regular sampling over the valid prefix: P-1 local samples ->
    # all_gather -> global splitters.  (A device with few/no valid slots
    # contributes pad samples; that only skews splitters, and any resulting
    # imbalance is caught by the capacity overflow flag.)
    sample_pos = ((jnp.arange(1, P, dtype=jnp.int32)) * m_valid) // P
    local_samples = tuple(k[sample_pos] for k in keys_s)
    gathered = tuple(
        lax.all_gather(s, info.axis).reshape(-1) for s in local_samples
    )  # (P*(P-1),)
    gsorted = lax.sort(gathered, num_keys=num_keys)
    # P-1 splitters at regular positions
    spl_pos = (jnp.arange(1, P, dtype=jnp.int32) * (P * (P - 1))) // P
    splitters = tuple(g[spl_pos] for g in gsorted)

    # 3. bucket boundaries in the local sorted run (binary search per
    # splitter); pad slots sit past m_valid and are never sent
    bounds = jnp.minimum(_lex_searchsorted(keys_s, splitters), m_valid)
    starts = jnp.concatenate([jnp.zeros(1, jnp.int32), bounds])
    ends = jnp.concatenate([bounds, m_valid[None]])
    counts = ends - starts                                  # (P,) per-dst

    cap = max(1, int(-(-capacity_factor * m // P)))
    overflow = jnp.any(counts > cap)

    # 4. build padded send buffers (P, cap) and shuffle
    slot = jnp.arange(cap, dtype=jnp.int32)
    take = starts[:, None] + slot[None, :]                  # (P, cap)
    valid_send = slot[None, :] < jnp.minimum(counts, cap)[:, None]
    take = jnp.clip(take, 0, m - 1)

    def exchange(buf):  # buf: (P, cap, ...) send blocks, block d -> device d
        return lax.all_to_all(
            buf.reshape(P * cap, *buf.shape[2:]), info.axis,
            split_axis=0, concat_axis=0, tiled=True,
        ).reshape(P, cap, *buf.shape[2:])

    def shuffle(x, pad):  # x: (m, ...) local sorted operand
        return exchange(jnp.where(valid_send, x[take], jnp.asarray(pad, x.dtype)))

    recv = tuple(
        shuffle(x, key_pads[i] if i < num_keys else 0)
        for i, x in enumerate(ops)
    )
    recv_valid = exchange(valid_send.astype(jnp.int32)).astype(bool)

    # 5. local sort of received slots; pads go to the end.  Validity is a
    # tie-break key after the real keys: a real key equal to its pad value
    # still sorts before the pad slots.
    flat = tuple(r.reshape(P * cap, *r.shape[2:]) for r in recv)
    vmask = recv_valid.reshape(P * cap)
    # force invalid slots to the pad on ALL keys so they sort last together
    flat = tuple(
        jnp.where(vmask, x, jnp.asarray(key_pads[i], x.dtype))
        if i < num_keys else x
        for i, x in enumerate(flat)
    )
    inv = (~vmask).astype(jnp.int32)
    tb_bits = None if key_bits is None else (*tuple(key_bits), 1)
    final = kernel_ops.local_sort(
        (*flat[:num_keys], inv, *flat[num_keys:]),
        num_keys + 1, engine=local_sort, key_bits=tb_bits,
    )
    final = (*final[:num_keys], *final[num_keys + 1:])
    n_valid = jnp.sum(vmask.astype(jnp.int32))
    return SampleSortResult(final, n_valid, lax.pmax(overflow, info.axis))


def scatter_to_index_samplesort(
    info: ShardInfo,
    gidx: jax.Array,
    values: tuple[jax.Array, ...],
    valid: jax.Array,
    capacity_factor: float = 2.0,
) -> tuple[tuple[jax.Array, ...], jax.Array]:
    """Route (gidx, *values) to the owner shard of each global index via a
    capacity-bounded all_to_all (owner = gidx // m).  Returns index-ordered
    local arrays + overflow flag.  Invalid slots (padding) are dropped."""
    P, m = info.parts, info.part_size
    slots = gidx.shape[0]
    dest = jnp.where(valid, gidx // m, P)  # P == "nowhere"

    # stable bucket slot: position among same-destination elements
    order = jnp.argsort(dest, stable=True)
    dest_s = dest[order]
    first = _lex_searchsorted((dest_s,), (dest_s,))
    slot_s = jnp.arange(slots, dtype=jnp.int32) - first
    cap = max(1, int(-(-capacity_factor * m // P)))
    overflow = jnp.any((dest_s < P) & (slot_s >= cap))

    def build(x):
        xs = x[order]
        buf = jnp.full((P, cap), -1, dtype=x.dtype)
        ok = (dest_s < P) & (slot_s < cap)
        row = jnp.where(ok, dest_s, P)  # row P is out of bounds -> dropped
        return buf.at[row, jnp.clip(slot_s, 0, cap - 1)].set(xs, mode="drop")

    def shuffle(buf):
        return lax.all_to_all(
            buf.reshape(P * cap), info.axis, split_axis=0, concat_axis=0,
            tiled=True,
        ).reshape(P, cap)

    gidx_r = shuffle(build(gidx)).reshape(-1)
    vals_r = tuple(shuffle(build(v)).reshape(-1) for v in values)
    ok = gidx_r >= 0
    local = jnp.where(ok, gidx_r % m, m)  # m is out of bounds -> dropped
    outs = tuple(
        jnp.zeros((m,), dtype=v.dtype).at[local].set(v, mode="drop")
        for v in vals_r
    )
    return outs, lax.pmax(overflow, info.axis)
