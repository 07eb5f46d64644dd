"""Index lifecycle IO: versioned checkpoint/restore of built FM indexes.

The paper's index is a *persistent distributed artifact* — it must outlive
the process that built it and come back up on whatever hardware is
available.  This module serialises :class:`~repro.core.pipeline.SequenceIndex`
(wrapping either a single-device ``FMIndex`` or a sharded ``DistFMIndex``)
through the same atomic/keep-k :class:`~repro.training.checkpoint.Checkpointer`
machinery the training loop uses, with a versioned manifest so formats can
evolve.

On-disk layout (one ``Checkpointer`` step directory per saved index):

    ckpt_dir/step_00000000/
      arrays.npz      bwt (GLOBAL, host-gathered), row, SA-sample bitvector
                      + packed/raw values — plus, for single-device indexes,
                      the derived layout (c_array, occ_samples, fused rows)
      meta.json       manifest: format/version, kind, static aux (sigma,
                      sample_rate, bits, sa_sample_rate, sa_val_bits, ...)

Re-mesh rule: only *mesh-independent* state is authoritative on disk.  The
global BWT and the replicated SA sample restore bit-identically anywhere;
the per-shard Occ checkpoints and fused packed rows of a ``DistFMIndex``
depend on the number of shards, so restore recomputes them (one cheap
counting pass inside ``build_dist_fm_index``) for whatever mesh is passed —
a checkpoint written from 8 devices serves from 4, 13, or 1.  Query results
are exact integer math over the same BWT, hence bit-identical across mesh
shapes (asserted by ``tests/dist_driver.py index_io``).
"""

from __future__ import annotations

import dataclasses
import json
import os

import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from ..kernels.rank_select import place_rows
from ..training.checkpoint import Checkpointer
from .dist_fm import DistFMIndex, build_dist_fm_index
from .fm_index import FMIndex, build_fm_index
from .pipeline import SequenceIndex

FORMAT = "fm_index_ckpt"
VERSION = 1

# arrays every kind stores / arrays only the single-device layout stores
_COMMON = ("bwt", "row")
_SA_ARRAYS = ("sa_marks", "sa_mark_ranks", "sa_vals")
_FM_LAYOUT = ("c_array", "occ_samples", "fused")


class IndexIOError(Exception):
    """Base for typed index checkpoint errors.  Every subclass also
    derives from the stdlib exception a pre-typed caller would have seen
    (``FileNotFoundError`` / ``ValueError``), so existing handlers keep
    working while new callers can catch the whole family at once."""


class MissingCheckpointError(IndexIOError, FileNotFoundError):
    """No checkpoint where one was expected (empty dir, missing manifest
    or arrays file).  Actionable: point at a directory ``save_index``
    wrote, or rebuild and save the index."""


class CorruptCheckpointError(IndexIOError, ValueError):
    """The checkpoint exists but cannot be trusted: unreadable/truncated
    arrays, a manifest that is not an index manifest, or arrays
    inconsistent with the manifest.  Actionable: restore an earlier
    ``step`` (``save_index`` keeps ``keep`` of them) or rebuild."""


class UnsupportedVersionError(IndexIOError, ValueError):
    """Checkpoint written by a newer format revision.  Actionable:
    upgrade this build; the artifact itself is healthy."""


def _manifest(fm, text_length: int) -> dict:
    kind = "dist_fm" if isinstance(fm, DistFMIndex) else "fm"
    return {
        "format": FORMAT,
        "version": VERSION,
        "kind": kind,
        "sample_rate": fm.sample_rate,
        "sigma": fm.sigma,
        "length": fm.length,
        "bits": fm.bits,
        "sa_sample_rate": fm.sa_sample_rate,
        "sa_val_bits": fm.sa_val_bits,
        "text_length": text_length,
        "built_parts": getattr(fm, "parts", 1),  # informational only
    }


def save_index(directory: str, index, *, step: int = 0, keep: int = 3) -> int:
    """Checkpoint a built index; returns the step written.

    ``index`` is a ``SequenceIndex`` or a bare ``FMIndex``/``DistFMIndex``.
    Arrays are host-gathered before writing (the ``Checkpointer`` elastic
    rule), so a sharded index saves as one global BWT.  Atomic: a crash
    mid-save never corrupts the previous step; ``keep`` old steps are
    retained.
    """
    fm = index.fm if isinstance(index, SequenceIndex) else index
    text_length = (
        index.text_length if isinstance(index, SequenceIndex) else fm.length
    )
    tree = {"bwt": fm.bwt, "row": fm.row}
    if fm.sa_sample_rate:
        for name in _SA_ARRAYS:
            tree[name] = getattr(fm, name)
    if isinstance(fm, FMIndex):
        # the derived layout is cheap to store and makes single-device
        # restore a pure reconstruction (no recompute at all)
        tree["c_array"] = fm.c_array
        tree["occ_samples"] = fm.occ_samples
        if fm.fused is not None:
            # the logical rows, int32[n_blocks, sigma + W]; restore places
            # them in the rank kernel's layout
            tree["fused"] = np.asarray(fm.fused).reshape(fm.n_blocks, -1)
    manifest = _manifest(fm, text_length)
    manifest["arrays"] = sorted(tree)
    Checkpointer(directory, keep=keep).save(step, tree, extra=manifest)
    return step


def _check_manifest(meta: dict) -> None:
    if meta.get("format") != FORMAT:
        raise CorruptCheckpointError(
            f"not an index checkpoint (format={meta.get('format')!r})"
        )
    if meta.get("version", 0) > VERSION:
        raise UnsupportedVersionError(
            f"index checkpoint version {meta['version']} is newer than this "
            f"build supports ({VERSION}); upgrade the reader — the artifact "
            "itself is fine"
        )


def _load_raw(directory: str, step: int | None):
    """``Checkpointer.restore_raw`` with untyped filesystem/zip failures
    mapped to the typed error family, plus array-vs-manifest validation
    (missing leaves, truncated ``bwt``)."""
    import zipfile

    try:
        flat, meta = Checkpointer(directory).restore_raw(step)
    except FileNotFoundError as e:
        raise MissingCheckpointError(
            f"no readable index checkpoint under {directory!r}: {e}. "
            "Expected a step directory with meta.json + arrays.npz "
            "(written by save_index)."
        ) from e
    except (zipfile.BadZipFile, json.JSONDecodeError, OSError,
            KeyError) as e:
        raise CorruptCheckpointError(
            f"index checkpoint under {directory!r} is unreadable ({e}); "
            "restore an earlier step or rebuild the index"
        ) from e
    _check_manifest(meta)
    declared = meta.get("arrays")
    if declared:
        missing = sorted(set(declared) - set(flat))
        if missing:
            raise CorruptCheckpointError(
                f"index checkpoint under {directory!r} is missing arrays "
                f"{missing} declared by its manifest; restore an earlier "
                "step or rebuild the index"
            )
    if "bwt" in flat and flat["bwt"].shape[0] < meta.get("length", 0):
        raise CorruptCheckpointError(
            f"index checkpoint under {directory!r} has a truncated bwt "
            f"({flat['bwt'].shape[0]} < manifest length {meta['length']}); "
            "restore an earlier step or rebuild the index"
        )
    return flat, meta


def restore_index(
    directory: str, mesh: Mesh | None = None, *, step: int | None = None
) -> SequenceIndex:
    """Restore a checkpointed index, ready to serve.

    ``mesh=None`` restores to a single-device ``FMIndex``; with a mesh the
    BWT is re-sharded over its ``parts`` axis and the per-shard layout
    recomputed — independent of the mesh shape the checkpoint was written
    from.  Counting/locating on the restored index is bit-identical to the
    index that was saved.  Raises if the padded length does not divide the
    new ``parts * sample_rate`` (pick a compatible mesh, or restore
    single-device).
    """
    flat, meta = _load_raw(directory, step)
    sample_rate = meta["sample_rate"]
    sigma = meta["sigma"]
    srate = meta["sa_sample_rate"]
    bwt = jnp.asarray(flat["bwt"][: meta["length"]])
    row = jnp.asarray(flat["row"])
    sa_samples = None
    if srate:
        sa_samples = tuple(jnp.asarray(flat[k]) for k in _SA_ARRAYS) + (
            meta["sa_val_bits"],
        )

    if mesh is None:
        if meta["kind"] == "fm" and "occ_samples" in flat:
            # pure reconstruction from the stored layout
            fm = FMIndex(
                jnp.asarray(flat["bwt"]), row, jnp.asarray(flat["c_array"]),
                jnp.asarray(flat["occ_samples"]),
                place_rows(jnp.asarray(flat["fused"])) if "fused" in flat
                else None,
                *(sa_samples[:3] if sa_samples else (None, None, None)),
                sample_rate, sigma, meta["length"], meta["bits"],
                srate, meta["sa_val_bits"],
            )
        else:  # dist checkpoint onto one device: rebuild the local layout
            fm = build_fm_index(
                bwt, row, sigma, sample_rate, pack=bool(meta["bits"]),
                sa_samples=sa_samples, sa_sample_rate=srate,
            )
    else:
        fm = build_dist_fm_index(
            bwt, row, mesh, sigma=sigma, sample_rate=sample_rate,
            pack=bool(meta["bits"]),
            sa_samples=sa_samples, sa_sample_rate=srate,
        )
    return SequenceIndex(
        fm, None, fm.bwt, row, sigma, meta["length"], meta["text_length"],
        mesh=mesh,
    )


def latest_index_step(directory: str) -> int | None:
    """Newest saved step under ``directory`` (None when empty) — the serve
    launcher's restore-or-build decision."""
    return Checkpointer(directory).latest_step()


@dataclasses.dataclass(frozen=True)
class IndexInfo:
    """Human-readable summary of a checkpointed index (``describe_index``)."""

    kind: str
    step: int
    sigma: int
    length: int
    text_length: int
    sample_rate: int
    bits: int
    sa_sample_rate: int
    sa_val_bits: int


def describe_index(directory: str, step: int | None = None) -> IndexInfo:
    """Read just the manifest of a saved index (no array IO)."""
    if step is None:
        step = Checkpointer(directory).latest_step()
        if step is None:
            raise MissingCheckpointError(f"no checkpoints under {directory}")
    path = os.path.join(directory, f"step_{step:08d}", "meta.json")
    try:
        with open(path) as f:
            meta = json.load(f)
    except FileNotFoundError as e:
        raise MissingCheckpointError(
            f"checkpoint step {step} under {directory!r} has no manifest "
            f"({path} is missing) — the save was torn; restore an earlier "
            "step or re-save"
        ) from e
    except (OSError, json.JSONDecodeError) as e:
        raise CorruptCheckpointError(
            f"manifest {path!r} is unreadable ({e}); restore an earlier "
            "step or rebuild"
        ) from e
    _check_manifest(meta)
    return IndexInfo(
        meta["kind"], step, meta["sigma"], meta["length"],
        meta["text_length"], meta["sample_rate"], meta["bits"],
        meta["sa_sample_rate"], meta["sa_val_bits"],
    )
