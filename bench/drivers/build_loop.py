"""Build window: back-to-back index builds of texts made in set-up.

Each build is the ``core/pipeline.build_index`` call that
``SegmentedIndex.append`` makes, run through ``append`` on a fresh
catalog with the configuration's knobs, from host tokens to the index
arrays ready on the device.  Builds start while the window's seconds have
not run out; the window ends when the last one completes.  Each build's
index is released before the next starts, except the one the seed picks
for the check, which is copied to the host after the window and compared
with the definition of its suffix array.
"""

from __future__ import annotations

import dataclasses
import gc
import time

import numpy as np

import faults
import reference
from generators import texts

KEEP_AMONG = 3   # the checked build is one of the window's first three


@dataclasses.dataclass
class State:
    texts: list
    icfg: object = None
    kept: object = None          # (text index, SequenceIndex) under check
    kept_host: dict = None
    builds: list = None          # (start, end, tokens) per build
    window_out: dict = None


def _build(ctx, state: State, text: np.ndarray):
    import jax

    from repro.core.segments import SegmentedIndex

    cat = SegmentedIndex.from_config(int(ctx.config["sigma"]), state.icfg)
    idx = cat.append(text).index
    jax.block_until_ready((idx.fm, idx.sa, idx.bwt, idx.row))
    return idx


def setup(ctx) -> State:
    from harness import index_config

    n_texts = int(ctx.traffic["texts"])
    t0 = time.perf_counter()
    made = [texts.generate(ctx.config, seed, ctx.n)
            for seed in ctx.rng(0xB11D).integers(0, 2**62, n_texts)]
    state = State(texts=made, icfg=index_config(ctx.config))
    print(f"build_loop: {n_texts} texts of {ctx.n} tokens in "
          f"{time.perf_counter() - t0} s", flush=True)
    t0 = time.perf_counter()
    with faults.build_fault(ctx.fault):
        idx = _build(ctx, state, made[0])
    print(f"build_loop: warm build {time.perf_counter() - t0} s; "
          f"{idx.build_stats.as_dict() if idx.build_stats else None}",
          flush=True)
    del idx
    gc.collect()
    return state


def window(ctx, state: State) -> dict:
    keep = int(ctx.rng(0xC4EC).integers(0, KEEP_AMONG))
    builds = []
    held = None
    with faults.build_fault(ctx.fault):
        ctx.tracer.start()
        t_open = time.perf_counter()
        i = 0
        while time.perf_counter() - t_open < ctx.seconds:
            if held is not None and held[0] != keep:
                held = None           # release before the next build
            t = i % len(state.texts)
            tb = time.perf_counter()
            with ctx.tracer.span("bench.build", build=i):
                idx = _build(ctx, state, state.texts[t])
            te = time.perf_counter()
            builds.append((tb, te, len(state.texts[t])))
            if held is None or held[0] != keep:
                held = (i, t, idx)
            del idx
            i += 1
        ctx.tracer.stop()
    state.kept, state.builds = held, builds
    tokens = sum(b[2] for b in builds)
    span = builds[-1][1] - builds[0][0]
    # every window build runs the set-up's warm build's programs at its
    # size, so the allocator's peak is the window's own
    peak = int((ctx.device.memory_stats() or {}).get("peak_bytes_in_use", 0))
    out = {"build_tok_per_s": tokens / span, "builds": len(builds),
           "build_s": [b[1] - b[0] for b in builds], "kept_build": held[0],
           "memory_peak_bytes": peak}
    print(f"build_loop: window {out}", flush=True)
    if ctx.tracer.enabled:
        ctx.spans = [("bench.build", a, b) for a, b, _ in builds]
    state.window_out = out
    return out


def release(ctx, state: State) -> None:
    """Copy the checked build to the host and free every device array."""
    _, t, idx = state.kept
    fm = idx.fm
    state.kept_host = {
        "text": t, "sa": np.asarray(idx.sa), "bwt": np.asarray(idx.bwt),
        "row": int(idx.row), "c_array": np.asarray(fm.c_array),
        "occ_samples": np.asarray(fm.occ_samples),
        "sa_marks": np.asarray(fm.sa_marks), "sa_vals": np.asarray(fm.sa_vals),
        "sample_rate": fm.sample_rate, "sa_sample_rate": fm.sa_sample_rate,
        "sa_val_bits": fm.sa_val_bits, "sigma": idx.sigma,
    }
    state.kept = (state.kept[0], t, None)
    gc.collect()


def check(ctx, state: State):
    got = state.kept_host
    t0 = time.perf_counter()
    s = reference.prepared_text(state.texts[got["text"]],
                                int(ctx.config["index"]["sample_rate"]),
                                int(ctx.config["sigma"]))
    try:
        parts = reference.index_bad_rows(s, got)
    except (IndexError, ValueError) as e:   # malformed arrays: all wrong
        parts = {"malformed": len(s)}
        print(f"build_loop: index arrays malformed: {e!r}", flush=True)
    print(f"build_loop: build {state.kept[0]} checked against the reference "
          f"in {time.perf_counter() - t0} s: {parts}", flush=True)
    n_builds = len(state.builds)
    return {"bad_index_rows": (sum(parts.values()), 0)}, n_builds, 0
