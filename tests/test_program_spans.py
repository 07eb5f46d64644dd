"""The program's profiler spans, recorded in a real ``jax.profiler`` trace on
the CPU: the serving path (``AsyncQueryFrontend`` -> ``FMQueryServer`` ->
``SegmentedIndex``) and a build (``pipeline.build_index``) open every
``fm.`` span with its counters, nested where the work nests.  Also: the
query and init programs keep the module names that the benchmark's device
metrics look for."""

import collections

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.core import fm_index, suffix_array
from repro.core.pipeline import build_index
from repro.core.segments import SegmentedIndex
from repro.serving.engine import FMQueryServer
from repro.serving.frontend import AsyncQueryFrontend

SIGMA = 5  # dna-like: tokens 1..4

Span = collections.namedtuple("Span", "name start end args line")


def _trace(tmp_path, work):
    """Run ``work()`` under the profiler; return its ``fm.`` events, one
    line per thread, in order of start."""
    jax.profiler.start_trace(str(tmp_path))
    try:
        out = work()
    finally:
        jax.profiler.stop_trace()
    (path,) = tmp_path.glob("plugins/profile/*/*.xplane.pb")
    spans = []
    for plane in ProfileData.from_file(str(path)).planes:
        for line_no, line in enumerate(plane.lines):
            for ev in line.events:
                if ev.name.startswith("fm."):
                    spans.append(Span(ev.name, ev.start_ns,
                                      ev.start_ns + ev.duration_ns,
                                      dict(ev.stats), (plane.name, line_no)))
    return sorted(spans, key=lambda s: (s.start, -s.end)), out


def _inside(inner, outer):
    return (inner.line == outer.line and outer.start <= inner.start
            and inner.end <= outer.end)


def _repetitive(rng, n):
    """Random DNA-like text with a repeated block, so that the q-gram init
    leaves doubling rounds to run."""
    block = rng.integers(1, SIGMA, 300).astype(np.int32)
    rest = rng.integers(1, SIGMA, n - 4 * len(block)).astype(np.int32)
    return np.concatenate([block, rest[: len(rest) // 2], block, block,
                           rest[len(rest) // 2:], block])


@pytest.mark.parametrize("segments", [1, 2])
def test_serving_spans(tmp_path, segments):
    """One segment takes ``SegmentedIndex``'s sequential path, two its
    stacked path; both fetch results inside ``FMQueryServer.flush``."""
    rng = np.random.default_rng(11)
    text = rng.integers(1, SIGMA, 3000).astype(np.int32)
    cat = SegmentedIndex(SIGMA, sample_rate=16, sa_sample_rate=8)
    for part in np.array_split(text, segments):
        cat.append(part)
    server = FMQueryServer(cat, length_buckets=(8, 16), max_batch=16,
                           locate_k=4)
    pats = [text[i : i + m] for i, m in
            zip(rng.integers(0, len(text) - 16, 40), rng.integers(4, 15, 40))]
    kinds = ["count" if i % 3 else "locate" for i in range(len(pats))]

    def work():
        with AsyncQueryFrontend(server, max_wait_ms=1.0) as fe:
            futs = []
            for i, (p, kind) in enumerate(zip(pats, kinds)):
                futs.append(fe.submit(p, kind))
                if i % 8 == 7:
                    futs[-1].result()      # let the worker take a batch
            return [f.result() for f in futs]

    spans, results = _trace(tmp_path, work)
    assert len(results) == len(pats)
    names = collections.Counter(s.name for s in spans)
    for name in ("fm.admit", "fm.coalesce", "fm.batch", "fm.flush",
                 "fm.dispatch", "fm.fetch"):
        assert names[name] > 0, name

    admits = [s for s in spans if s.name == "fm.admit"]
    admitted = sorted(s.args["seq"] for s in admits)
    assert admitted == list(range(len(pats)))
    covered = [q for s in spans if s.name == "fm.batch"
               for q in range(s.args["seq_lo"], s.args["seq_hi"] + 1)]
    assert sorted(covered) == admitted          # each seq exactly once
    assert sum(s.args["n"] for s in spans if s.name == "fm.batch") == len(pats)

    flushes = [s for s in spans if s.name == "fm.flush"]
    (worker,) = {s.line for s in flushes}
    assert worker not in {s.line for s in admits}
    assert sum(s.args["requests"] for s in flushes) == len(pats)
    for name in ("fm.dispatch", "fm.fetch"):
        for s in spans:
            if s.name == name:
                assert any(_inside(s, f) for f in flushes), name
    dispatches = [s for s in spans if s.name == "fm.dispatch"]
    for d in dispatches:
        assert 1 <= d.args["used"] <= d.args["rows"] <= 16
        assert d.args["kind"] in ("count", "locate")
        assert d.args["length"] in (8, 16)
        assert d.args["placed"] == 1
        assert any(_inside(s, d) for s in spans if s.name == "fm.fetch")
    assert sum(d.args["used"] for d in dispatches) == len(pats)


@pytest.mark.parametrize("pack,placed", [(None, 1), (False, 0)])
def test_dispatch_placed(tmp_path, pack, placed):
    """``fm.dispatch`` carries ``placed``: 1 for a freshly built packed
    index, whose row table is stored in the rank kernel's layout; 0 for the
    unpacked layout, which has no packed row table."""
    rng = np.random.default_rng(12)
    text = rng.integers(1, SIGMA, 2000).astype(np.int32)
    index = build_index(text, sample_rate=16, sa_sample_rate=8, pack=pack)
    assert bool(index.fm.bits) == (pack is None)
    server = FMQueryServer(index, length_buckets=(8,), max_batch=8,
                           locate_k=4)
    pats = [text[i : i + 6] for i in range(0, 60, 5)]

    def work():
        server.count(pats)
        return server.locate(pats)

    spans, _ = _trace(tmp_path, work)
    dispatches = [s for s in spans if s.name == "fm.dispatch"]
    assert {d.args["kind"] for d in dispatches} == {"count", "locate"}
    assert [d.args["placed"] for d in dispatches] == [placed] * len(dispatches)


def test_build_spans(tmp_path):
    toks = _repetitive(np.random.default_rng(5), 4096)
    spans, index = _trace(tmp_path, lambda: build_index(
        toks, sample_rate=16, sa_sample_rate=8))
    (build,) = [s for s in spans if s.name == "fm.build"]
    assert build.args == {"n": len(toks), "sigma": index.sigma}
    for name in ("fm.build.prepare", "fm.build.init", "fm.build.bwt",
                 "fm.build.fm", "fm.build.sa_samples"):
        (stage,) = [s for s in spans if s.name == name]
        assert _inside(stage, build), name
    (fm,) = [s for s in spans if s.name == "fm.build.fm"]
    (samples,) = [s for s in spans if s.name == "fm.build.sa_samples"]
    (fetch,) = [s for s in spans if s.name == "fm.fetch"]
    assert _inside(samples, fm) and _inside(fetch, samples)
    assert samples.args["rows"] == -(-len(index.sa) // 8)

    rounds = [s for s in spans if s.name == "fm.build.round"]
    stats = index.build_stats
    assert stats.rounds_executed >= 1
    assert len(rounds) == stats.rounds_executed
    assert [r.args["round"] for r in rounds] == list(range(len(rounds)))
    for r, frac in zip(rounds, stats.active_frac):
        assert _inside(r, build)
        assert r.args["h"] == stats.h0 << r.args["round"]
        assert r.args["n_active"] == round(frac * stats.n)
        assert r.args["n_active"] <= r.args["cap"] <= stats.n


def test_traced_build_equals_untraced(tmp_path):
    """Recording the spans changes nothing the build computes."""
    toks = _repetitive(np.random.default_rng(6), 2048)
    _, traced = _trace(tmp_path, lambda: build_index(
        toks, sample_rate=16, sa_sample_rate=8))
    plain = build_index(toks, sample_rate=16, sa_sample_rate=8)
    assert fm_index.fm_mismatch(traced.fm, plain.fm) == []
    assert np.array_equal(np.asarray(traced.sa), np.asarray(plain.sa))
    assert traced.build_stats == plain.build_stats


def _module_name(lowered) -> str:
    text = lowered.as_text()
    return text.split("module @", 1)[1].split(" ", 1)[0]


def test_program_module_names():
    """The benchmark finds the query and init programs by these names."""
    toks = np.random.default_rng(3).integers(1, SIGMA, 1000).astype(np.int32)
    fm = build_index(toks, sample_rate=16, sa_sample_rate=8).fm
    pats = jnp.zeros((4, 8), jnp.int32)
    assert _module_name(fm_index.count.lower(fm, pats)) == "jit_count"
    assert _module_name(fm_index.locate.lower(fm, pats, 4)) == "jit_locate"
    s = jnp.asarray(np.append(toks, 0), jnp.int32)
    init = suffix_array._qgram_init.lower(s, 16, 2, 2, "compare")
    assert _module_name(init) == "jit__qgram_init"
