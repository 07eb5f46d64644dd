"""Multi-device correctness driver, run in a SUBPROCESS with forced host
devices (so the main pytest process keeps the default single device).

Usage: python tests/dist_driver.py <scenario> [devices]
Exits 0 on success; prints failures and exits 1 otherwise.
"""

import os
import sys

DEVICES = int(sys.argv[2]) if len(sys.argv) > 2 else 8
# forced host devices live on the CPU backend; never reach for a chip that
# the parent process may hold
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (
    f"--xla_force_host_platform_device_count={DEVICES} "
    + os.environ.get("XLA_FLAGS", "")
)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from jax import shard_map  # noqa: E402
from repro.core import alphabet as al  # noqa: E402
from repro.core.dist_sort import (  # noqa: E402
    ShardInfo,
    bitonic_sort_sharded,
    exclusive_scan_sharded,
    samplesort_sharded,
    scatter_to_index_bitonic,
    scatter_to_index_samplesort,
    shift_sharded,
)
from repro.core.dist_suffix_array import (  # noqa: E402
    BITONIC,
    SAMPLESORT,
    DistSAConfig,
    build_bwt_sharded,
    build_isa_sharded,
    isa_overflowed,
)
from repro.core.suffix_array import suffix_array_naive  # noqa: E402
from repro.core.bwt import bwt_naive  # noqa: E402

from jax.sharding import PartitionSpec as P  # noqa: E402

AXIS = "parts"


def make_mesh():
    return jax.make_mesh((DEVICES,), (AXIS,))


def shard_call(mesh, fn, *arrays, out_specs=P(AXIS)):
    return jax.jit(
        shard_map(
            fn, mesh=mesh, in_specs=tuple(P(AXIS) for _ in arrays),
            out_specs=out_specs,
        )
    )(*arrays)


def scenario_bitonic_sort():
    mesh = make_mesh()
    rng = np.random.default_rng(0)
    for trial in range(5):
        n = DEVICES * int(rng.integers(4, 40))
        info = ShardInfo(AXIS, DEVICES, n // DEVICES)
        k1 = rng.integers(0, 10, n).astype(np.int32)
        k2 = rng.integers(-1, 10, n).astype(np.int32)
        pay = np.arange(n, dtype=np.int32)

        def f(a, b, c):
            return bitonic_sort_sharded(info, (a, b, c), num_keys=2)

        r1, r2, rp = shard_call(
            mesh, f, jnp.asarray(k1), jnp.asarray(k2), jnp.asarray(pay),
            out_specs=(P(AXIS),) * 3,
        )
        order = np.lexsort((pay, k2, k1))
        assert np.array_equal(np.asarray(r1), k1[order]), "keys1 mismatch"
        assert np.array_equal(np.asarray(r2), k2[order]), "keys2 mismatch"
        # payload: equal keys may permute payloads; verify (k1,k2,pay) multiset
        got = sorted(zip(np.asarray(r1), np.asarray(r2), np.asarray(rp)))
        want = sorted(zip(k1, k2, pay))
        assert got == want, "payload multiset mismatch"
    print("bitonic sort ok")


def scenario_shift():
    mesh = make_mesh()
    rng = np.random.default_rng(1)
    n = DEVICES * 16
    info = ShardInfo(AXIS, DEVICES, n // DEVICES)
    x = rng.integers(0, 100, n).astype(np.int32)
    for h in [1, 2, 3, 15, 16, 17, 64, n - 1, n + 5]:
        want = np.full(n, -1, np.int32)
        want[: max(0, n - h)] = x[h:]
        # a static distance, and the same distance traced
        for dist in (h, jnp.int32(h)):
            def f(a):
                return shift_sharded(info, a, dist, -1)

            out = np.asarray(shard_call(mesh, f, jnp.asarray(x)))
            assert np.array_equal(out, want), f"shift h={dist!r}"
    print("shift ok")


def scenario_scan():
    mesh = make_mesh()
    rng = np.random.default_rng(2)
    info = ShardInfo(AXIS, DEVICES, 1)
    v = rng.integers(0, 50, DEVICES).astype(np.int32)

    def f(a):
        return exclusive_scan_sharded(info, a[0])[None]

    out = np.asarray(shard_call(mesh, f, jnp.asarray(v)))
    want = np.cumsum(v) - v
    assert np.array_equal(out, want), (out, want)
    print("scan ok")


def scenario_samplesort():
    mesh = make_mesh()
    rng = np.random.default_rng(3)
    for trial in range(5):
        n = DEVICES * int(rng.integers(8, 40))
        info = ShardInfo(AXIS, DEVICES, n // DEVICES)
        k1 = rng.integers(0, 8, n).astype(np.int32)  # heavy ties
        k2 = rng.integers(-1, 8, n).astype(np.int32)
        pay = np.arange(n, dtype=np.int32)

        def f(a, b, c):
            res = samplesort_sharded(info, (a, b, c), num_keys=2,
                                     capacity_factor=4.0)
            return res.operands + (res.n_valid[None], res.overflow[None])

        *ops, nv, ov = shard_call(
            mesh, f, jnp.asarray(k1), jnp.asarray(k2), jnp.asarray(pay),
            out_specs=(P(AXIS),) * 3 + (P(AXIS), P(AXIS)),
        )
        assert not np.any(np.asarray(ov)), "unexpected overflow"
        nv = np.asarray(nv)
        slots = np.asarray(ops[0]).shape[0] // DEVICES
        got = []
        for d in range(DEVICES):
            lo, hi = d * slots, d * slots + nv[d]
            got += list(zip(*(np.asarray(o)[lo:hi] for o in ops)))
        assert len(got) == n, f"lost elements {len(got)} != {n}"
        want_order = np.lexsort((pay, k2, k1))
        want_keys = list(zip(k1[want_order], k2[want_order]))
        got_keys = [(a, b) for a, b, _ in got]
        assert got_keys == want_keys, "samplesort key order mismatch"
        assert sorted(p for _, _, p in got) == list(range(n)), "payload lost"
    print("samplesort ok")


def scenario_scatter():
    mesh = make_mesh()
    rng = np.random.default_rng(4)
    n = DEVICES * 32
    info = ShardInfo(AXIS, DEVICES, n // DEVICES)
    perm = rng.permutation(n).astype(np.int32)
    vals = rng.integers(0, 1000, n).astype(np.int32)

    def f_b(i, v):
        return scatter_to_index_bitonic(info, i, (v,))[0]

    out = np.asarray(shard_call(mesh, f_b, jnp.asarray(perm), jnp.asarray(vals)))
    want = np.zeros(n, np.int32)
    want[perm] = vals
    assert np.array_equal(out, want), "bitonic scatter"

    def f_s(i, v):
        (o,), ov = scatter_to_index_samplesort(
            info, i, (v,), valid=jnp.ones_like(i, dtype=bool),
            capacity_factor=4.0,
        )
        return o, ov[None]

    out, ov = shard_call(mesh, f_s, jnp.asarray(perm), jnp.asarray(vals),
                         out_specs=(P(AXIS), P(AXIS)))
    assert not np.any(np.asarray(ov)), "scatter overflow"
    assert np.array_equal(np.asarray(out), want), "samplesort scatter"
    print("scatter ok")


def _check_sa(engine, seed, n_mult):
    mesh = make_mesh()
    rng = np.random.default_rng(seed)
    n = DEVICES * n_mult
    toks = rng.integers(1, 5, n - 1).astype(np.int32)
    s = al.append_sentinel(toks)
    sigma = al.sigma_of(s)
    cfg = DistSAConfig(axis=AXIS, engine=engine, capacity_factor=4.0)
    sa, bwt_arr, row = build_bwt_sharded(jnp.asarray(s), mesh, cfg, sigma=sigma)
    sa = np.asarray(sa)
    want_sa = suffix_array_naive(s)
    assert np.array_equal(sa, want_sa), f"{engine} SA mismatch n={n}"
    want_bwt, want_row = bwt_naive(s)
    assert np.array_equal(np.asarray(bwt_arr), want_bwt), f"{engine} BWT"
    assert int(row) == want_row, f"{engine} row"


def scenario_sa_bitonic():
    for seed, mult in [(0, 2), (1, 8), (2, 17), (3, 64)]:
        _check_sa(BITONIC, seed, mult)
    print("distributed SA/BWT (bitonic) ok")


def scenario_sa_fused():
    """Fused-key / q-gram / discard / radix knobs vs the naive oracle: each
    case must produce the identical SA + BWT.  The exhaustive knob matrix
    runs single-device in tests/test_build_fast.py; this covers the
    distributed-specific paths (both engines, active-aware shuffle, skew
    overflow retry, radix local sort inside shard_map)."""
    mesh = make_mesh()
    rng = np.random.default_rng(13)
    n = DEVICES * 24
    # (sigma_hi, engine, qgram, qgram_words, discard, local_sort)
    cases = [
        (2, BITONIC, True, 2, True, "compare"),
        (2, SAMPLESORT, True, 2, True, "compare"),   # max skew: all keys ==
        (4, BITONIC, True, 2, True, "radix"),
        (4, SAMPLESORT, True, 2, True, "radix"),
        (4, SAMPLESORT, True, 1, False, "compare"),
        (20, BITONIC, False, 1, True, "compare"),
        (20, SAMPLESORT, True, 2, True, "compare"),
        (64, BITONIC, True, 1, False, "radix"),
        (64, SAMPLESORT, False, 1, True, "compare"),
        (64, SAMPLESORT, True, 2, False, "compare"),
    ]
    corpora = {}
    for sigma_hi, engine, qgram, qw, discard, ls in cases:
        if sigma_hi not in corpora:
            toks = rng.integers(1, max(2, sigma_hi), n - 1).astype(np.int32)
            if sigma_hi == 2:
                toks[:] = 1  # unary: maximally repetitive AND skewed
            s = al.append_sentinel(toks)
            corpora[sigma_hi] = (
                s, suffix_array_naive(s), *bwt_naive(s)
            )
        s, want_sa, want_bwt, want_row = corpora[sigma_hi]
        sigma = al.sigma_of(s)
        cfg = DistSAConfig(
            axis=AXIS, engine=engine, capacity_factor=4.0, qgram=qgram,
            qgram_words=qw, discard=discard, local_sort=ls,
        )
        key = (sigma, engine, qgram, qw, discard, ls)
        # unary text: every key equal, range partitioning can't split ->
        # samplesort overflows by design; retry with doubled factor
        # exactly like pipeline.build_index
        for _ in range(4):
            isa = build_isa_sharded(jnp.asarray(s), mesh, cfg, sigma=sigma)
            if not isa_overflowed(isa):
                break
            cfg = cfg._replace(capacity_factor=cfg.capacity_factor * 2)
        else:
            raise AssertionError(f"overflow persists {key}")
        sa, bwt_arr, row = build_bwt_sharded(
            jnp.asarray(s), mesh, cfg, sigma=sigma
        )
        assert np.array_equal(np.asarray(sa), want_sa), key
        assert np.array_equal(np.asarray(bwt_arr), want_bwt), key
        assert int(row) == want_row, key
    print("fused/qgram/discard parity ok")


def scenario_sa_samplesort():
    for seed, mult in [(0, 8), (1, 17), (2, 64)]:
        _check_sa(SAMPLESORT, seed, mult)
    print("distributed SA/BWT (samplesort) ok")


def scenario_dist_fm():
    from repro.core.dist_fm import build_dist_fm_index, dist_count
    from repro.core.fm_index import PAD, count_naive

    mesh = make_mesh()
    rng = np.random.default_rng(7)
    r = 4
    n = DEVICES * 8 * r
    toks = rng.integers(1, 5, n - 1).astype(np.int32)
    s = al.append_sentinel(toks)
    sigma = al.sigma_of(s)
    cfg = DistSAConfig(axis=AXIS, engine=BITONIC)
    _sa, bwt_arr, row = build_bwt_sharded(jnp.asarray(s), mesh, cfg, sigma=sigma)
    idx = build_dist_fm_index(bwt_arr, row, mesh, sigma=sigma, sample_rate=r)
    L = 6
    B = 16
    pats = np.full((B, L), PAD, np.int32)
    lens = rng.integers(1, L + 1, B)
    for b in range(B):
        pats[b, : lens[b]] = rng.integers(1, 5, lens[b])
    got = np.asarray(dist_count(idx, jnp.asarray(pats), mesh))
    want = np.array([count_naive(s, pats[b, : lens[b]]) for b in range(B)])
    assert np.array_equal(got, want), (got, want)
    print("dist FM ok")


def scenario_dist_locate():
    """dist_count AND dist_locate agree with the single-device index built
    over the same corpus, for both the packed and unpacked local layouts."""
    from repro.core.dist_fm import build_dist_fm_index, dist_count, dist_locate
    from repro.core.fm_index import PAD, build_fm_index, count, locate
    from repro.core.suffix_array import suffix_array

    mesh = make_mesh()
    rng = np.random.default_rng(21)
    r = 8
    n = DEVICES * 8 * r
    for sigma_hi, srate in [(5, 8), (17, 4)]:  # packed (4-bit) / unpacked
        toks = rng.integers(1, sigma_hi, n - 1).astype(np.int32)
        s = al.append_sentinel(toks)
        sigma = al.sigma_of(s)
        cfg = DistSAConfig(axis=AXIS, engine=BITONIC)
        sa, bwt_arr, row = build_bwt_sharded(jnp.asarray(s), mesh, cfg,
                                             sigma=sigma)
        idx = build_dist_fm_index(bwt_arr, row, mesh, sigma=sigma,
                                  sample_rate=r, sa=sa, sa_sample_rate=srate)
        sa1 = suffix_array(jnp.asarray(s), sigma)
        fm = build_fm_index(jnp.asarray(np.asarray(bwt_arr)), row, sigma, r,
                            sa=sa1, sa_sample_rate=srate)
        expected_bits = 4 if sigma <= 16 else 0
        assert idx.bits == expected_bits == fm.bits, (idx.bits, fm.bits)
        B, L = 12, 6
        pats = np.full((B, L), PAD, np.int32)
        lens = rng.integers(1, L + 1, B)
        for b in range(B):
            pats[b, : lens[b]] = rng.integers(1, sigma_hi, lens[b])
        got = np.asarray(dist_count(idx, jnp.asarray(pats), mesh))
        want = np.asarray(count(fm, jnp.asarray(pats)))
        assert np.array_equal(got, want), (sigma, got, want)
        k = 32
        dpos, dcnt = dist_locate(idx, jnp.asarray(pats), k, mesh)
        spos, scnt = locate(fm, jnp.asarray(pats), k)
        assert np.array_equal(np.asarray(dcnt), np.asarray(scnt)), sigma
        assert np.array_equal(np.asarray(dpos), np.asarray(spos)), sigma
    print("dist locate ok")


def scenario_pipeline():
    from repro.core.pipeline import build_index
    from repro.core.fm_index import PAD, count_naive

    mesh = make_mesh()
    rng = np.random.default_rng(11)
    for engine in (BITONIC, SAMPLESORT):
        n = 777  # deliberately not divisible by anything
        toks = rng.integers(1, 6, n).astype(np.int32)
        idx = build_index(
            toks, mesh, sample_rate=8,
            sa_config=DistSAConfig(axis=AXIS, engine=engine, capacity_factor=3.0),
        )
        B, L = 8, 5
        pats = np.full((B, L), PAD, np.int32)
        lens = rng.integers(1, L + 1, B)
        for b in range(B):
            pats[b, : lens[b]] = rng.integers(1, 6, lens[b])
        got = np.asarray(idx.count(pats))
        s = al.append_sentinel(toks)
        want = np.array([count_naive(s, pats[b, : lens[b]]) for b in range(B)])
        assert np.array_equal(got, want), (engine, got, want)
    print("pipeline ok")


def scenario_elastic():
    """Elastic re-mesh (DESIGN.md §7): a checkpoint written from an
    8-shard mesh restores byte-identically onto a 4-shard mesh (the
    on-disk format is unsharded; shardings are reapplied on restore)."""
    import tempfile
    from jax.sharding import NamedSharding
    from repro.training.checkpoint import Checkpointer

    assert DEVICES >= 8
    rng = np.random.default_rng(0)
    state = {
        "w": rng.normal(size=(64, 32)).astype(np.float32),
        "m": rng.normal(size=(64, 32)).astype(np.float32),
    }

    mesh8 = jax.make_mesh((8,), (AXIS,), devices=jax.devices()[:8])
    sh8 = NamedSharding(mesh8, P(AXIS, None))
    tree8 = {k: jax.device_put(jnp.asarray(v), sh8) for k, v in state.items()}

    with tempfile.TemporaryDirectory() as d:
        ck = Checkpointer(d)
        ck.save(5, tree8, extra={"mesh": "8"})

        # "lose half the pod": restore onto a 4-device mesh
        mesh4 = jax.make_mesh((4,), (AXIS,), devices=jax.devices()[:4])
        sh4 = NamedSharding(mesh4, P(AXIS, None))
        tmpl = {k: jnp.zeros_like(jnp.asarray(v)) for k, v in state.items()}
        restored, meta = ck.restore(
            tmpl, shardings={k: sh4 for k in state}
        )
        assert meta["step"] == 5 and meta["mesh"] == "8"
        for k in state:
            assert restored[k].sharding.num_devices == 4
            assert np.array_equal(np.asarray(restored[k]), state[k]), k
    print("elastic re-mesh ok")


def scenario_index_io():
    """Index lifecycle across mesh shapes: a distributed index checkpointed
    from an 8-shard mesh restores and answers bit-identically on 8, 4, and
    1 device(s), and a single-device checkpoint restores onto a mesh."""
    import tempfile

    from repro.core.fm_index import PAD
    from repro.core.index_io import describe_index, restore_index, save_index
    from repro.core.pipeline import build_index

    assert DEVICES >= 8
    rng = np.random.default_rng(3)
    r = 8
    n = 8 * 8 * r  # padded length divides parts * r for parts in {8, 4, 1}
    toks = rng.integers(1, 5, n - 1).astype(np.int32)
    mesh8 = jax.make_mesh((8,), (AXIS,), devices=jax.devices()[:8])
    idx = build_index(toks, mesh8, sample_rate=r, sa_sample_rate=4)

    B, L, k = 12, 6, 64
    pats = np.full((B, L), PAD, np.int32)
    lens = rng.integers(1, L + 1, B)
    for b in range(B):
        st = rng.integers(0, n - 1 - lens[b])
        pats[b, : lens[b]] = toks[st : st + lens[b]]
    want_cnt = np.asarray(idx.count(pats))
    want_pos, want_k = (np.asarray(a) for a in idx.locate(pats, k))

    with tempfile.TemporaryDirectory() as d:
        save_index(d, idx)
        info = describe_index(d)
        assert info.kind == "dist_fm" and info.sa_val_bits > 0, info
        mesh4 = jax.make_mesh((4,), (AXIS,), devices=jax.devices()[:4])
        for mesh in (mesh8, mesh4, None):
            rest = restore_index(d, mesh)
            assert np.array_equal(np.asarray(rest.count(pats)), want_cnt), mesh
            pos, cnt = (np.asarray(a) for a in rest.locate(pats, k))
            assert np.array_equal(pos, want_pos), mesh
            assert np.array_equal(cnt, want_k), mesh

    # single-device checkpoint -> distributed restore
    idx1 = build_index(toks, None, sample_rate=r, sa_sample_rate=4)
    with tempfile.TemporaryDirectory() as d:
        save_index(d, idx1)
        # restoring onto a mesh needs the padded length to divide parts * r
        # (n = 512 here, so 4- and 8-shard meshes both qualify)
        for p in (4, 8):
            assert idx1.length % (p * r) == 0, (idx1.length, p, r)
        rest = restore_index(
            d, jax.make_mesh((4,), (AXIS,), devices=jax.devices()[:4])
        )
        assert np.array_equal(np.asarray(rest.count(pats)),
                              np.asarray(idx1.count(pats)))
        pos, cnt = (np.asarray(a) for a in rest.locate(pats, k))
        pos1, cnt1 = (np.asarray(a) for a in idx1.locate(pats, k))
        assert np.array_equal(pos, pos1) and np.array_equal(cnt, cnt1)
    print("index_io re-mesh ok")


def scenario_seg_merge():
    """K-way-vs-rebuild compaction parity with forced host devices present:
    segment builds stay single-device, and the k-way interleave walk must
    produce the identical index (and identical answers) no matter how many
    devices the backend exposes.  Also folds two already-merged
    (multi-document) segments — merged x merged compacts rebuild-free now
    that the left-operand restriction is lifted."""
    from repro.core.fm_index import PAD
    from repro.core.segments import SegmentedIndex

    assert len(jax.devices()) == DEVICES
    rng = np.random.default_rng(41)
    sigma = 5
    chunks = [rng.integers(1, sigma, n).astype(np.int32)
              for n in (3 * DEVICES, 20, 7 * DEVICES, 33)]
    seg_m = SegmentedIndex(sigma, sample_rate=8, sa_sample_rate=4)
    seg_r = SegmentedIndex(sigma, sample_rate=8, sa_sample_rate=4)
    for c in chunks:
        seg_m.append(c)
        seg_r.append(c)

    full = np.concatenate(chunks)
    B, L = 12, 5
    pats = np.full((B, L), PAD, np.int32)
    for b in range(B):
        m = int(rng.integers(1, L + 1))
        st = int(rng.integers(0, len(full) - m))
        pats[b, :m] = full[st : st + m]
    k = 2 * len(full)
    want_c = seg_m.count(pats)
    want_p, want_k = seg_m.locate(pats, k)

    # one k=4 interleave walk folds the whole catalog, no fallback
    assert seg_m.compact(strategy="kway") == 1
    assert seg_m.compact_fallbacks == 0, seg_m.compact_last_fallback_reason
    assert seg_m.compact_strategy_counts == {"kway": 1}
    assert seg_r.compact(strategy="rebuild") == 1
    from repro.core.fm_index import fm_mismatch

    diff = fm_mismatch(seg_m.segments[0].index.fm,
                       seg_r.segments[0].index.fm)
    assert not diff, diff
    assert np.array_equal(seg_m.count(pats), want_c), "answers changed"
    pos, cnt = seg_m.locate(pats, k)
    assert np.array_equal(pos, want_p) and np.array_equal(cnt, want_k)

    # grow two more documents and fold them into a SECOND multi-doc
    # segment (the thresholded compact leaves the big segment alone),
    # then fold merged x merged rebuild-free: the left-operand
    # restriction is lifted when the tokens are context-order safe.
    # The follower's leading document is all-ones (the minimal token),
    # which structurally wins every pad-boundary tie of the left multi —
    # unsafe corpora would fall back to the rebuild, counted, instead
    extra = [np.ones(34, np.int32),
             rng.integers(1, sigma, 21).astype(np.int32)]
    for s in (seg_m, seg_r):
        for c in extra:
            s.append(c)
    assert seg_m.compact(min_tokens=60, strategy="kway") == 1
    assert seg_r.compact(min_tokens=60, strategy="rebuild") == 1
    assert all(s.multi_doc for s in seg_m.segments)
    _, plan = seg_m._plan_run(seg_m.segments, "kway")
    assert plan["reason"] is None, plan["reason"]
    full = np.concatenate([full] + extra)
    c_before = seg_m.count(pats)
    assert seg_m.compact(strategy="kway") == 1  # merged x merged, no rebuild
    assert seg_m.compact_fallbacks == 0, seg_m.compact_last_fallback_reason
    assert seg_m.compact_strategy_counts == {"kway": 3}
    assert seg_r.compact(strategy="rebuild") == 1
    diff = fm_mismatch(seg_m.segments[0].index.fm,
                       seg_r.segments[0].index.fm)
    assert not diff, diff
    assert np.array_equal(seg_m.count(pats), c_before)
    print("seg_merge parity ok")


def scenario_crash_save():
    """Durability under multi-device builds: a segmented catalog built with
    forced host devices present crashes mid-save (injected ``io.write``
    fault), reloads to the last committed generation bit-identically, and
    a clean re-save then commits the new state — same answers, no orphans.
    Honors REPRO_FAULT_SCHEDULE when set (the CI lane passes io.write:3)."""
    import tempfile

    from repro.core.fm_index import PAD
    from repro.core.journal import GenerationJournal
    from repro.core.segments import SegmentedIndex
    from repro.testing import faultinject

    assert len(jax.devices()) == DEVICES
    rng = np.random.default_rng(53)
    sigma = 5
    seg = SegmentedIndex(sigma, sample_rate=8, sa_sample_rate=4)
    chunks = [rng.integers(1, sigma, n).astype(np.int32)
              for n in (4 * DEVICES, 21, 40)]
    for c in chunks[:2]:
        seg.append(c)
    full = np.concatenate(chunks[:2])
    B, L = 8, 5
    pats = np.full((B, L), PAD, np.int32)
    for b in range(B):
        m = int(rng.integers(1, L + 1))
        st = int(rng.integers(0, len(full) - m))
        pats[b, :m] = full[st : st + m]
    want_c = seg.count(pats)

    with tempfile.TemporaryDirectory() as d:
        seg.save(d)  # generation 0, committed clean
        seg.append(chunks[2])
        schedule = (faultinject.arm_from_env()
                    or faultinject.arm(
                        faultinject.FaultSchedule.parse("io.write:3")))
        try:
            seg.save(d)  # crashes mid-stage of generation 1
            raise AssertionError("fault schedule never fired")
        except faultinject.InjectedFault:
            pass
        finally:
            faultinject.arm(None)
        back = SegmentedIndex.load(d)
        man = GenerationJournal(d).committed()
        assert man["generation"] == 0, "torn save must not commit"
        assert not back.degraded, back.quarantined
        assert back.total_tokens == len(full)
        assert np.array_equal(back.count(pats), want_c), "answers changed"
        # recovery swept the staged debris: exactly the committed files
        on_disk = {os.path.relpath(os.path.join(r, f), d).replace(os.sep, "/")
                   for r, _, fs in os.walk(d) for f in fs}
        expected = set(man["files"]) | {
            "CURRENT", "catalog.json", f"gen_{man['generation']:08d}.json"
        }
        assert on_disk == expected, on_disk ^ expected
        # the retried save commits generation 1 with the appended text
        seg.save(d)
        again = SegmentedIndex.load(d)
        assert GenerationJournal(d).committed()["generation"] == 1
        assert again.total_tokens == len(np.concatenate(chunks))
        assert np.array_equal(again.count(pats), seg.count(pats))
    print("crash_save recovery ok")


SCENARIOS = {
    "pipeline": scenario_pipeline,
    "crash_save": scenario_crash_save,
    "seg_merge": scenario_seg_merge,
    "index_io": scenario_index_io,
    "elastic": scenario_elastic,
    "bitonic_sort": scenario_bitonic_sort,
    "shift": scenario_shift,
    "scan": scenario_scan,
    "samplesort": scenario_samplesort,
    "scatter": scenario_scatter,
    "sa_bitonic": scenario_sa_bitonic,
    "sa_fused": scenario_sa_fused,
    "sa_samplesort": scenario_sa_samplesort,
    "dist_fm": scenario_dist_fm,
    "dist_locate": scenario_dist_locate,
}

if __name__ == "__main__":
    name = sys.argv[1]
    if name == "all":
        for k, fn in SCENARIOS.items():
            fn()
    else:
        SCENARIOS[name]()
    print("OK", name)
