"""Index checkpoint/restore: bit-identical roundtrips, packing boundaries,
compressed SA samples, manifest versioning, and the re-mesh scenario."""

import os
import subprocess
import sys

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import alphabet as al
from repro.core.bwt import bwt_from_sa
from repro.core.fm_index import (
    PAD,
    build_fm_index,
    build_sa_samples,
    count,
    locate,
    pack_sa_values,
    unpack_sa_value,
)
from repro.core.index_io import (
    CorruptCheckpointError,
    IndexIOError,
    MissingCheckpointError,
    UnsupportedVersionError,
    describe_index,
    latest_index_step,
    restore_index,
    save_index,
)
from repro.core.pipeline import build_index
from repro.core.suffix_array import suffix_array
from repro.kernels.rank_select import rows_placed

DRIVER = os.path.join(os.path.dirname(__file__), "dist_driver.py")


def _random_patterns(rng, toks, B=8, L=6):
    pats = np.full((B, L), PAD, np.int32)
    lens = rng.integers(1, L + 1, B)
    for b in range(B):
        st = rng.integers(0, len(toks) - lens[b])
        pats[b, : lens[b]] = toks[st : st + lens[b]]
    return pats


def _assert_same_index(a, b, pats, k=64):
    """count/locate parity plus leaf-level bit identity."""
    assert np.array_equal(np.asarray(a.count(pats)), np.asarray(b.count(pats)))
    pa, ca = (np.asarray(x) for x in a.locate(pats, k))
    pb, cb = (np.asarray(x) for x in b.locate(pats, k))
    assert np.array_equal(pa, pb) and np.array_equal(ca, cb)
    la = jax.tree_util.tree_leaves(a.fm)
    lb = jax.tree_util.tree_leaves(b.fm)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        assert np.array_equal(np.asarray(x), np.asarray(y))


class TestRoundtrip:
    def test_bit_identical(self, tmp_path):
        rng = np.random.default_rng(0)
        toks = rng.integers(1, 5, 777).astype(np.int32)
        idx = build_index(toks, sample_rate=16, sa_sample_rate=8)
        save_index(str(tmp_path), idx)
        rest = restore_index(str(tmp_path))
        _assert_same_index(idx, rest, _random_patterns(rng, toks))
        assert rest.text_length == idx.text_length

    def test_fused_saved_2d_restores_placed(self, tmp_path):
        """The checkpoint holds the logical int32[n_blocks, sigma + W] rows
        (the format older checkpoints have); restore places them in the
        rank kernel's layout and answers bit-identically."""
        rng = np.random.default_rng(10)
        toks = rng.integers(1, 5, 900).astype(np.int32)
        idx = build_index(toks, sample_rate=16, sa_sample_rate=8)
        assert rows_placed(idx.fm.fused)
        save_index(str(tmp_path), idx)
        (npz,) = tmp_path.glob("step_*/arrays.npz")
        with np.load(npz) as arrays:
            saved = arrays["fused"]
        assert saved.shape == (idx.fm.n_blocks, idx.fm.fused.shape[2])
        assert np.array_equal(saved, np.asarray(idx.fm.fused)[:, 0])
        rest = restore_index(str(tmp_path))
        assert rows_placed(rest.fm.fused)
        _assert_same_index(idx, rest, _random_patterns(rng, toks))

    def test_no_sa_sample(self, tmp_path):
        """Empty SA sample (sa_sample_rate=0): roundtrips, locate raises."""
        rng = np.random.default_rng(1)
        toks = rng.integers(1, 5, 300).astype(np.int32)
        idx = build_index(toks, sample_rate=16, sa_sample_rate=0)
        save_index(str(tmp_path), idx)
        rest = restore_index(str(tmp_path))
        pats = _random_patterns(rng, toks)
        assert np.array_equal(np.asarray(idx.count(pats)),
                              np.asarray(rest.count(pats)))
        assert rest.fm.sa_vals is None and rest.fm.sa_sample_rate == 0
        with pytest.raises(ValueError, match="locate unavailable"):
            rest.locate(pats, 4)

    @pytest.mark.parametrize("sigma,want_bits", [
        (4, 2),    # 2-bit packing
        (16, 4),   # 4-bit packing, at the boundary
        (17, 0),   # one past the boundary: unpacked layout
    ])
    def test_packing_boundary(self, tmp_path, sigma, want_bits):
        """sigma = 16 (sentinel + 15 symbols) is the last packable alphabet;
        17 falls back to the unpacked layout — both roundtrip bit-identically."""
        rng = np.random.default_rng(2)
        r = 16
        toks = rng.integers(1, sigma, 16 * r - 1).astype(np.int32)
        toks[: sigma - 1] = np.arange(1, sigma)  # realise the full alphabet
        s = al.append_sentinel(toks)
        assert al.sigma_of(s) == sigma
        sd = jnp.asarray(s)
        sa = suffix_array(sd, sigma)
        bwt_arr, row = bwt_from_sa(sd, sa)
        fm = build_fm_index(bwt_arr, row, sigma, r, sa=sa, sa_sample_rate=4)
        assert fm.bits == want_bits
        save_index(str(tmp_path), fm)
        info = describe_index(str(tmp_path))
        assert info.bits == want_bits and info.kind == "fm"
        rest = restore_index(str(tmp_path))
        assert rest.fm.bits == want_bits
        pats = jnp.asarray(_random_patterns(rng, toks))
        assert np.array_equal(np.asarray(count(fm, pats)),
                              np.asarray(rest.count(pats)))
        pa, ca = (np.asarray(x) for x in locate(fm, pats, 32))
        pb, cb = (np.asarray(x) for x in rest.locate(pats, 32))
        assert np.array_equal(pa, pb) and np.array_equal(ca, cb)

    def test_uncompressed_sa_roundtrip(self, tmp_path):
        rng = np.random.default_rng(3)
        toks = rng.integers(1, 5, 500).astype(np.int32)
        idx = build_index(toks, sample_rate=16, sa_sample_rate=8,
                          compress_sa=False)
        assert idx.fm.sa_val_bits == 0
        save_index(str(tmp_path), idx)
        rest = restore_index(str(tmp_path))
        assert rest.fm.sa_val_bits == 0
        _assert_same_index(idx, rest, _random_patterns(rng, toks))

    def test_keep_k_steps(self, tmp_path):
        rng = np.random.default_rng(4)
        toks = rng.integers(1, 5, 200).astype(np.int32)
        idx = build_index(toks, sample_rate=16)
        for step in (1, 2, 3):
            save_index(str(tmp_path), idx, step=step, keep=2)
        assert latest_index_step(str(tmp_path)) == 3
        pats = _random_patterns(rng, toks)
        rest = restore_index(str(tmp_path), step=2)
        assert np.array_equal(np.asarray(idx.count(pats)),
                              np.asarray(rest.count(pats)))


class TestManifest:
    def test_version_guard(self, tmp_path):
        rng = np.random.default_rng(5)
        idx = build_index(rng.integers(1, 5, 200).astype(np.int32),
                          sample_rate=16)
        save_index(str(tmp_path), idx)
        meta_path = tmp_path / "step_00000000" / "meta.json"
        import json
        meta = json.loads(meta_path.read_text())
        meta["version"] = 999
        meta_path.write_text(json.dumps(meta))
        with pytest.raises(ValueError, match="newer"):
            restore_index(str(tmp_path))

    def test_not_an_index(self, tmp_path):
        from repro.training.checkpoint import Checkpointer

        Checkpointer(str(tmp_path)).save(0, {"x": jnp.zeros(4)})
        with pytest.raises(ValueError, match="not an index checkpoint"):
            restore_index(str(tmp_path))

    def test_describe_empty(self, tmp_path):
        with pytest.raises(FileNotFoundError):
            describe_index(str(tmp_path))

    def test_read_paths_do_not_create_directories(self, tmp_path):
        """Restoring/describing a mistyped path must not leave an empty
        directory tree behind (Checkpointer creates dirs lazily, on save)."""
        missing = tmp_path / "no" / "such" / "index"
        with pytest.raises(FileNotFoundError):
            restore_index(str(missing))
        assert latest_index_step(str(missing)) is None
        assert not missing.exists()


class TestTypedErrors:
    """Every restore failure mode raises a typed, actionable IndexIOError
    subclass that ALSO derives from the stdlib exception older callers
    caught (FileNotFoundError / ValueError)."""

    @pytest.fixture()
    def saved(self, tmp_path):
        rng = np.random.default_rng(9)
        toks = rng.integers(1, 5, 300).astype(np.int32)
        idx = build_index(toks, sample_rate=16, sa_sample_rate=8)
        save_index(str(tmp_path), idx)
        return tmp_path

    def test_empty_dir_is_missing(self, tmp_path):
        with pytest.raises(MissingCheckpointError) as ei:
            restore_index(str(tmp_path))
        assert isinstance(ei.value, FileNotFoundError)
        assert "save_index" in str(ei.value)  # actionable: how to make one

    def test_missing_manifest(self, saved):
        (saved / "step_00000000" / "meta.json").unlink()
        with pytest.raises(MissingCheckpointError):
            restore_index(str(saved))
        with pytest.raises(MissingCheckpointError, match="torn"):
            describe_index(str(saved))

    def test_version_from_the_future_is_typed(self, saved):
        import json
        meta_path = saved / "step_00000000" / "meta.json"
        meta = json.loads(meta_path.read_text())
        meta["version"] = 999
        meta_path.write_text(json.dumps(meta))
        with pytest.raises(UnsupportedVersionError, match="newer") as ei:
            restore_index(str(saved))
        assert isinstance(ei.value, (IndexIOError, ValueError))
        with pytest.raises(UnsupportedVersionError):
            describe_index(str(saved))

    def test_truncated_arrays_file(self, saved):
        """A torn arrays.npz (half the bytes) is corruption, not a crash
        with a zipfile traceback."""
        path = saved / "step_00000000" / "arrays.npz"
        blob = path.read_bytes()
        path.write_bytes(blob[: len(blob) // 2])
        with pytest.raises(CorruptCheckpointError, match="unreadable") as ei:
            restore_index(str(saved))
        assert isinstance(ei.value, ValueError)

    def test_missing_declared_array(self, saved):
        """arrays.npz missing a leaf the manifest declares -> corrupt, with
        the missing names listed."""
        path = saved / "step_00000000" / "arrays.npz"
        with np.load(str(path)) as z:
            flat = {k: z[k] for k in z.files if k != "row"}
        np.savez(str(path), **flat)
        with pytest.raises(CorruptCheckpointError, match="row"):
            restore_index(str(saved))

    def test_truncated_bwt_array(self, saved):
        """A bwt shorter than the manifest's length -> corrupt (truncated),
        caught before any index math runs."""
        path = saved / "step_00000000" / "arrays.npz"
        with np.load(str(path)) as z:
            flat = {k: z[k] for k in z.files}
        flat["bwt"] = flat["bwt"][: len(flat["bwt"]) // 2]
        np.savez(str(path), **flat)
        with pytest.raises(CorruptCheckpointError, match="truncated"):
            restore_index(str(saved))

    def test_unreadable_manifest_json(self, saved):
        (saved / "step_00000000" / "meta.json").write_text("{not json")
        with pytest.raises(CorruptCheckpointError):
            restore_index(str(saved))
        with pytest.raises(CorruptCheckpointError, match="unreadable"):
            describe_index(str(saved))

    def test_family_catch_all(self, saved):
        """One except clause covers the whole family."""
        (saved / "step_00000000" / "meta.json").unlink()
        with pytest.raises(IndexIOError):
            restore_index(str(saved))


class TestCompressedSAValues:
    def test_pack_unpack_exhaustive_widths(self):
        rng = np.random.default_rng(6)
        for bits in (1, 3, 7, 11, 12, 17, 23, 31):
            n = 257
            q = rng.integers(0, 1 << bits, n, dtype=np.int64)
            packed = jnp.asarray(pack_sa_values(q, bits))
            got = unpack_sa_value(packed, jnp.arange(n, dtype=jnp.int32), bits)
            assert np.array_equal(np.asarray(got), q), bits

    def test_build_sa_samples_parity(self):
        rng = np.random.default_rng(7)
        sa = jnp.asarray(rng.permutation(4096).astype(np.int32))
        mr, rr, vr, br = build_sa_samples(sa, 4, compress=False)
        mc, rc, vc, bc = build_sa_samples(sa, 4, compress=True)
        assert br == 0 and bc == 10  # 1024 sampled values -> 10 bits each
        assert vc.shape[0] < vr.shape[0] // 2  # genuinely smaller
        got = unpack_sa_value(vc, jnp.arange(vr.shape[0], dtype=jnp.int32), bc)
        assert np.array_equal(np.asarray(got) * 4, np.asarray(vr))

    def test_locate_parity_small_stride(self):
        """The compressed decode is exercised on every locate step."""
        rng = np.random.default_rng(8)
        toks = rng.integers(1, 5, 2000).astype(np.int32)
        raw = build_index(toks, sample_rate=16, sa_sample_rate=4,
                          compress_sa=False)
        cmp_ = build_index(toks, sample_rate=16, sa_sample_rate=4,
                           compress_sa=True)
        assert cmp_.fm.sa_val_bits > 0
        pats = _random_patterns(rng, toks, B=16)
        pr, cr = (np.asarray(x) for x in raw.locate(pats, 128))
        pc, cc = (np.asarray(x) for x in cmp_.locate(pats, 128))
        assert np.array_equal(pr, pc) and np.array_equal(cr, cc)


def test_restore_across_device_counts():
    """8-shard checkpoint serves from 8, 4, and 1 device(s) bit-identically
    (subprocess with forced host devices, like tests/test_distributed.py)."""
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, DRIVER, "index_io", "8"],
        capture_output=True, text=True, timeout=900, env=env,
    )
    assert proc.returncode == 0, (
        f"index_io failed:\nSTDOUT:{proc.stdout[-3000:]}\n"
        f"STDERR:{proc.stderr[-3000:]}"
    )
