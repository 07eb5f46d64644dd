"""Mean time of the doubling rounds per build: for each ``fm.build`` span
(``core/pipeline.build_index``), the summed durations of the
``fm.build.round`` spans inside it (``core/suffix_array.build_isa_fast``;
each round ends with the readback of its active count, so it holds the
round's device time)."""

import numpy as np

import harness

SOURCE = "program_span"


def read(ctx):
    ps = harness.load_module(harness.BENCH / "metrics" / "_program_spans.py",
                             "bench_metrics_program_spans").module()
    per_build = ps.per_outer(ps.spans_of(ctx) or [], "fm.build.round",
                             "fm.build", lambda s: s.duration)
    if not per_build:
        return None
    return float(np.mean(per_build) * 1e3)
