"""The trace reduction on hand-made events with answers worked by hand,
and on a small trace recorded on a TPU v5 lite chip."""

import pathlib

import pytest

TESTDATA = pathlib.Path(__file__).resolve().parents[1] / "testdata"


def _reduce():
    import harness

    return harness.load_module(harness.BENCH / "trace" / "reduce.py",
                               "bench_trace_reduce")


R = _reduce()

def test_union_length_merges_overlaps():
    # [0,2] u [1,3] u [5,6] u [6,7] = 3 + 2
    assert R.union_length([(0, 2), (1, 3), (5, 6), (6, 7)]) == 5
    assert R.union_length([]) == 0
    assert R.union_length([(4, 4)]) == 0

def test_gaps_are_the_complement_in_the_window():
    assert R.gaps([(1, 2), (1.5, 3), (5, 6)], 0, 8) == [(0, 1), (3, 5),
                                                         (6, 8)]
    assert R.gaps([(-1, 9)], 0, 8) == []

def test_gap_label_is_the_innermost_span():
    spans = [("bench.build", 0.0, 10.0, {}), ("bench.submit", 4.0, 6.0, {})]
    assert R.label_of(5.0, spans) == "bench.submit"
    assert R.label_of(1.0, spans) == "bench.build"
    assert R.label_of(11.0, spans) == R.NO_SPAN
    idle = R.idle_by_label([(4.5, 5.5), (1, 2), (10.5, 11)], spans)
    assert idle == {"bench.submit after trace start": 1.0,
                    "bench.build after trace start": 1.0,
                    f"{R.NO_SPAN} after trace start": 0.5}


def test_gap_label_names_the_program_before_it():
    spans = [("bench.build", 0.0, 10.0, {})]
    mods = [("jit_a(1)", 0.0, 1.0), ("jit_b(2)", 2.0, 3.0),
            ("jit_c(3)", 3.0, 4.5)]
    idle = R.idle_by_label([(1.0, 2.0), (4.5, 6.0), (3.0, 3.0)], spans,
                           mods)
    assert idle == {"bench.build after jit_a": 1.0,
                    "bench.build after jit_c": 1.5,
                    "bench.build after jit_b": 0.0}

FUSION = "%fusion.1 = s32[8]{0} fusion(s32[8]{0} %p), kind=kLoop"
KERNEL = ("%closed_call.2 = s32[1,1,8]{2,1,0} custom-call(s32[8]{0} %a, "
          "s32[8]{0} %b, s32[8]{0} %c, s32[64,1,15]{2,1,0} %t), "
          'custom_call_target="tpu_custom_call"')
LOOP = "%while.3 = (s32[], s32[8]{0}) while((s32[], s32[8]{0}) %t), body=%b"


def _planes():
    # one chip: a loop over [0,2] holding ops at [0,1] and [1,2]; an op at
    # [4,5]; a program over [0,2] and one over [4,5]; host spans; the
    # trace's extent [0, 6] is set by the outer host span
    dev = ("/device:TPU:0", [
        ("XLA Ops", [(LOOP, 0.0, 2.0, None), (FUSION, 0.0, 1.0, None),
                     (KERNEL, 1.0, 2.0, None), (KERNEL, 4.0, 5.0, None)]),
        ("XLA Modules", [("jit_count(1)", 0.0, 2.0, None),
                         ("jit_locate(2)", 4.0, 5.0, None)]),
    ])
    host = ("/host:CPU", [
        ("python", [("bench.flush", 0.0, 6.0, {}),
                    ("bench.submit", 2.5, 3.0, {}),
                    ("PjitFunction(count)", 0.0, 0.1, {})]),
    ])
    return [dev, host]


def test_call_shapes_of_an_hlo_instruction():
    outs, args = R.call_shapes(KERNEL)
    assert outs == [("s32", (1, 1, 8))]
    assert args == [("s32", (8,))] * 3 + [("s32", (64, 1, 15))]
    assert R.short_op(KERNEL) == "closed_call.2 tpu_custom_call s32[1,1,8]"
    assert R.short_module("jit_locate(2)") == "jit_locate"


def test_summary_of_hand_made_planes():
    s = R.from_planes(_planes(), host_window_s=6.0)
    assert s.chips == 1
    assert s.busy_s == pytest.approx(3.0)          # [0,2] + [4,5]
    assert s.idle_share == pytest.approx(0.5)      # 1 - 3/6
    assert (s.lo, s.hi) == (0.0, 6.0)
    # the loop holds the fusion and the first kernel call: not a leaf
    assert s.ops.leaves().tolist() == [False, True, True, True]
    by = s.op_seconds()
    assert by == {"jit_count/fusion.1 s32[8]": pytest.approx(1.0),
                  "jit_count/closed_call.2 tpu_custom_call s32[1,1,8]":
                  pytest.approx(1.0),
                  "jit_locate/closed_call.2 tpu_custom_call s32[1,1,8]":
                  pytest.approx(1.0)}
    # idle: [2,4] has midpoint 3, inside bench.submit [2.5,3], after
    # jit_count ended at 2; [5,6] inside bench.flush only, after jit_locate
    assert s.idle["bench.submit after jit_count"] == pytest.approx(2.0)
    assert s.idle["bench.flush after jit_locate"] == pytest.approx(1.0)
    assert [n for n, *_ in s.host_spans] == ["bench.flush", "bench.submit"]
    sel = s.modules.select(lambda n: n.startswith("jit_locate"))
    assert sel.tolist() == [False, True]
    b = s.breakdown()
    assert len(b["device_ops"]) == 3
    assert b["idle_gaps"][0] == ["bench.submit after jit_count", 2.0]


def test_no_device_plane_gives_no_summary():
    assert R.from_planes([_planes()[1]], host_window_s=6.0) is None

def test_two_chips_average_their_busy_time():
    p = _planes()
    second = ("/device:TPU:1", [("XLA Ops", [("fusion.2", 0.0, 6.0, None)])])
    s = R.from_planes(p + [second], host_window_s=6.0)
    assert s.chips == 2
    assert s.busy_s == pytest.approx((3.0 + 6.0) / 2)

def test_recorded_chip_trace():
    """One 2^16-token proteins build traced on a TPU v5 lite (a traced
    run's xplane, trimmed to the device's op and program lines around its
    third build and that build's ``bench.build`` host span)."""
    import types

    import harness

    s = R.from_planes(R.load_planes(TESTDATA / "proteins_build_2p16.xplane.pb"),
                      host_window_s=0.03692781)
    assert s.chips == 1
    assert (len(s.ops), len(s.modules)) == (934, 75)
    assert s.busy_s == pytest.approx(0.012762001, rel=1e-6)
    assert s.hi - s.lo == pytest.approx(0.04259546, rel=1e-6)
    assert s.busy_s < s.hi - s.lo
    # idle gaps are labelled by the program before them; every idle second
    # of the extent is labelled once
    assert sum(s.idle.values()) == pytest.approx(
        (s.hi - s.lo) - s.busy_s, rel=1e-9)
    label, secs = s.idle.most_common(1)[0]
    assert label == "bench.build after jit_convert_element_type"
    assert secs == pytest.approx(0.005285828, rel=1e-6)
    assert s.idle["bench.build after jit_step"] == pytest.approx(
        0.003047462, rel=1e-6)
    top = s.breakdown()["device_ops"][0][0]
    assert top.startswith("jit__qgram_init/fusion")
    ctx = types.SimpleNamespace(device_trace=s, counters={}, spans=[],
                                peaks=harness.peaks_for("TPU v5 lite"))
    init = harness.metric_reader("init_device_ms.build").read(ctx)
    assert init == pytest.approx(5.749782, rel=1e-6)
    roof = harness.metric_reader("radix_roofline.build").read(ctx)
    assert 0 < roof < 100 and roof == pytest.approx(1.3233968, rel=1e-6)
