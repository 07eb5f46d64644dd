"""The readers of the program's own spans on hand-made planes, with answers
worked by hand; the spans' nesting; and the script's tables."""

import types

import pytest

import harness

PS = harness.load_module(harness.BENCH / "metrics" / "_program_spans.py",
                         "bench_metrics_program_spans").module()


def _host(*lines):
    return ("/host:CPU", [("python", list(events)) for events in lines])


def _ev(name, a, b, **args):
    return (name, a, b, args)


# the generator thread admits seq 0..3 and 5; seq 4 was admitted before
# the trace began
GENERATOR = [_ev("fm.admit", 0.00, 0.001, seq=0),
             _ev("fm.admit", 0.01, 0.011, seq=1),
             _ev("fm.admit", 0.02, 0.021, seq=2),
             _ev("fm.admit", 0.03, 0.031, seq=3),
             _ev("fm.admit", 0.50, 0.501, seq=5)]
WORKER = [
    _ev("fm.coalesce", 0.0, 0.05),
    _ev("fm.batch", 0.05, 0.25, n=2, seq_lo=0, seq_hi=1),
    _ev("fm.flush", 0.06, 0.24, requests=2, groups=2),
    _ev("fm.dispatch", 0.07, 0.17, kind="count", length=32, rows=2, used=2),
    _ev("fm.fetch", 0.10, 0.16),
    _ev("fm.dispatch", 0.17, 0.23, kind="locate", length=32, rows=4, used=3),
    _ev("fm.fetch", 0.20, 0.22),
    _ev("fm.coalesce", 0.25, 0.30),
    _ev("fm.batch", 0.30, 0.42, n=2, seq_lo=2, seq_hi=3),
    _ev("fm.flush", 0.31, 0.41, requests=2, groups=1),
    _ev("fm.dispatch", 0.32, 0.40, kind="count", length=64, rows=8, used=5),
    _ev("fm.fetch", 0.33, 0.38),
    _ev("fm.batch", 0.55, 0.60, n=2, seq_lo=4, seq_hi=5),
]
# a fetch outside any flush, on a thread of its own
OTHER = [_ev("fm.fetch", 0.0, 0.5)]

BUILDER = [
    _ev("fm.build", 0.0, 10.0, n=100, sigma=22),
    _ev("fm.build.init", 0.5, 1.0),
    _ev("fm.build.round", 1.0, 2.0, round=0, h=12, cap=64, n_active=40),
    _ev("fm.build.round", 2.0, 2.5, round=1, h=24, cap=32, n_active=20),
    _ev("fm.build.round", 2.5, 2.75, round=2, h=48, cap=16, n_active=9),
    _ev("fm.build.fm", 5.0, 9.0),
    _ev("fm.build.sa_samples", 6.0, 8.5, rows=4),
    _ev("fm.fetch", 6.0, 7.0),
    _ev("fm.build", 20.0, 25.0, n=100, sigma=22),
    _ev("fm.build.round", 21.0, 22.0, round=0, h=12, cap=64, n_active=40),
    _ev("fm.build.sa_samples", 23.0, 24.0, rows=4),
    _ev("fm.fetch", 23.0, 23.25),
]
DEVICE = ("/device:TPU:0", [("XLA Ops", [("fusion.1", 0.0, 1.0, None),
                                         ("fusion.2", 2.0, 6.0, None),
                                         ("fusion.3", 8.5, 10.0, None)])])


@pytest.fixture
def ctx_of(tmp_path, monkeypatch):
    """A run context whose trace holds the given planes."""

    def make(planes):
        trace = tmp_path / "trace"
        f = trace / "plugins" / "profile" / "run" / "host.xplane.pb"
        f.parent.mkdir(parents=True, exist_ok=True)
        f.write_bytes(b"")
        monkeypatch.setattr(PS.reduce, "load_planes", lambda path: planes)
        return types.SimpleNamespace(
            tracer=types.SimpleNamespace(directory=trace))

    return make


def _read(name, ctx):
    return harness.metric_reader(name).read(ctx)


def test_spans_nest_per_line():
    spans = PS.from_planes([_host(WORKER, OTHER)])
    by = {(s.name, s.start): s for s in spans}
    flush = by[("fm.flush", 0.06)]
    assert spans[flush.parent].name == "fm.batch"
    fetch = by[("fm.fetch", 0.10)]
    assert spans[fetch.parent].name == "fm.dispatch"
    assert PS.enclosing(spans, spans.index(fetch), "fm.flush") == \
        spans.index(flush)
    # the other thread's fetch has no parent, though it overlaps the flush
    assert by[("fm.fetch", 0.0)].parent == -1
    # children's time: the two dispatches of the first flush
    assert flush.child_s == pytest.approx(0.10 + 0.06)
    assert flush.self_s == pytest.approx(0.18 - 0.16)
    assert by[("fm.dispatch", 0.32)].args == {
        "kind": "count", "length": 64, "rows": 8, "used": 5}


def test_queue_wait(ctx_of):
    # batch 0..1 starts at 0.05: waits 0.05, 0.04; batch 2..3 at 0.30:
    # waits 0.28, 0.27; batch 4..5 is left out (seq 4 not in the trace).
    # p99 of [0.04, 0.05, 0.27, 0.28]: 0.27 + 0.97 * 0.01 = 0.2797 s
    ctx = ctx_of([_host(GENERATOR, WORKER)])
    assert _read("queue_wait_p99_ms.open", ctx) == pytest.approx(279.7)


def test_flush_host_time(ctx_of):
    # flush 1: 0.18 s less fetches 0.06 + 0.02 = 0.10 s; flush 2: 0.10 s
    # less 0.05 = 0.05 s; the other thread's fetch counts in neither
    ctx = ctx_of([_host(GENERATOR, WORKER, OTHER)])
    assert _read("flush_host_ms.open", ctx) == pytest.approx(75.0)


def test_batch_fill(ctx_of):
    # used 2 + 3 + 5 of rows 2 + 4 + 8
    ctx = ctx_of([_host(WORKER)])
    assert _read("batch_fill.open", ctx) == pytest.approx(100 * 10 / 14)


def test_rounds_per_build(ctx_of):
    # build 1: 1.0 + 0.5 + 0.25 s of rounds; build 2: 1.0 s
    ctx = ctx_of([DEVICE, _host(BUILDER)])
    assert _read("rounds_ms.build", ctx) == pytest.approx(1375.0)


def test_sa_sample_host_time(ctx_of):
    # build 1: 2.5 s less its 1.0 s fetch; build 2: 1.0 s less 0.25 s
    ctx = ctx_of([DEVICE, _host(BUILDER)])
    assert _read("sa_sample_host_ms.build", ctx) == pytest.approx(1125.0)


@pytest.mark.parametrize("name", ["queue_wait_p99_ms.open",
                                  "flush_host_ms.open", "batch_fill.open",
                                  "rounds_ms.build",
                                  "sa_sample_host_ms.build"])
def test_no_program_spans_no_value(ctx_of, tmp_path, name):
    """A trace of a program without these spans (the benchmark's own
    spans only), or no trace at all, reads as None."""
    ctx = ctx_of([DEVICE, _host([_ev("bench.build", 0.0, 10.0)])])
    assert _read(name, ctx) is None
    none = types.SimpleNamespace(
        tracer=types.SimpleNamespace(directory=tmp_path / "absent"))
    assert _read(name, none) is None


def test_idle_by_innermost_span():
    # device busy [0,1], [2,6], [8.5,10] in the extent [0, 25]: gap [1,2]
    # in a round, [6,8.5] (midpoint 7.25) in the SA sampling after its
    # fetch, [10,25] between the builds, outside every span
    planes = [DEVICE, _host(BUILDER)]
    idle = PS.idle_by_span(planes, PS.from_planes(planes))
    assert idle == {"fm.build.round": pytest.approx(1.0),
                    "fm.build.sa_samples": pytest.approx(2.5),
                    PS.OUTSIDE: pytest.approx(15.0)}
    assert PS.idle_by_span([_host(BUILDER)], []) is None


def test_idle_at_the_trace_edges():
    # the worker's spans are recorded whole over [0.25, 0.7] only (a span
    # open across the trace's start or stop is lost); the generator's
    # admissions stretch the extent to [0, 0.901].  Gaps [0, 0.2] and
    # [0.6, 0.901] lie outside that interval, [0.3, 0.5] in the batch
    device = ("/device:TPU:0", [("XLA Ops", [("fusion.1", 0.2, 0.3, None),
                                             ("fusion.2", 0.5, 0.6, None)])])
    host = _host([_ev("fm.admit", 0.0, 0.001, seq=0),
                  _ev("fm.admit", 0.9, 0.901, seq=9)],
                 [_ev("fm.batch", 0.25, 0.7, n=1, seq_lo=1, seq_hi=1)])
    spans = PS.from_planes([host])
    assert PS.recorded(spans) == (0.25, 0.7)
    idle = PS.idle_by_span([device, host], spans)
    assert idle == {PS.EDGE: pytest.approx(0.2 + 0.301),
                    "fm.batch": pytest.approx(0.2)}


def test_longest_admission_gap():
    # admissions at 0, 0.01, 0.02, 0.03, 0.50: the gap is [0.03, 0.50];
    # the worker's spans over it, by name
    a, b, cover = PS.admission_gap(PS.from_planes([_host(GENERATOR,
                                                         WORKER)]))
    assert (a, b) == (0.03, 0.50)
    assert cover == {"fm.coalesce": pytest.approx(0.02 + 0.05),
                     "fm.batch": pytest.approx(0.20 + 0.12),
                     "fm.flush": pytest.approx(0.18 + 0.10),
                     "fm.dispatch": pytest.approx(0.06 + 0.10 + 0.08),
                     "fm.fetch": pytest.approx(0.06 + 0.02 + 0.05)}


def test_device_time_by_named_scope():
    # a loop (not a leaf) holding two ops of the backward search; one op
    # of the walk; one op without an op_name
    rows = [("jit(count)/backward_search/while", 0.0, 3.0),
            ("jit(count)/backward_search/while/body/closed_call/"
             "jit(rank_packed)/reshape", 0.0, 1.0),
            ("jit(count)/backward_search/while/body/gather", 1.0, 3.0),
            ("jit(locate)/locate_walk/while/body/add", 4.0, 4.5),
            (None, 5.0, 6.0)]
    assert PS.scope_seconds([rows]) == {
        "jit(count)/backward_search/jit(rank_packed)": pytest.approx(1.0),
        "jit(count)/backward_search": pytest.approx(2.0),
        "jit(locate)/locate_walk": pytest.approx(0.5)}


def test_traced_serving_run_reads_the_program_spans(run_cell):
    """The serving cell at a tiny size on the CPU: its trace holds the
    program's spans, and their three readers report."""
    rc, line, _ = run_cell("dna-seed-open", trace=True)
    assert rc == 0 and line["correct"] is True
    m = line["metrics"]
    assert m["queue_wait_p99_ms.open"]["value"] > 0
    assert m["flush_host_ms.open"]["value"] > 0
    assert 0 < m["batch_fill.open"]["value"] <= 100


def test_traced_build_run_reads_the_program_spans(run_cell):
    rc, line, _ = run_cell("proteins-build", trace=True)
    assert rc == 0 and line["correct"] is True
    m = line["metrics"]
    assert m["rounds_ms.build"]["value"] > 0
    assert m["sa_sample_host_ms.build"]["value"] > 0
    assert "build_tok_per_s" not in m
