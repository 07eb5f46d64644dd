"""Mean host time of one flush (``serving/engine.py``
``FMQueryServer.flush``): each ``fm.flush`` span's duration less the
``fm.fetch`` spans inside it, the waits for and copies of device results.
What is left is grouping, padding, dispatch and the result objects."""

import numpy as np

import harness

SOURCE = "program_span"


def read(ctx):
    ps = harness.load_module(harness.BENCH / "metrics" / "_program_spans.py",
                             "bench_metrics_program_spans").module()
    spans = ps.spans_of(ctx) or []
    flushes = [s.duration for s in spans if s.name == "fm.flush"]
    if not flushes:
        return None
    fetch = ps.per_outer(spans, "fm.fetch", "fm.flush", lambda s: s.duration)
    return float(np.mean(np.subtract(flushes, fetch)) * 1e3)
