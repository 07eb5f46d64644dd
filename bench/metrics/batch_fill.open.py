"""Batch fill of the index calls of the flushes: 100 x (real requests) /
(padded rows) summed over the ``fm.dispatch`` spans of
``FMQueryServer.flush``, whose ``used`` and ``rows`` args count them.  Each
(kind, length bucket) group pads to a power of two up to ``max_batch``."""

import harness

SOURCE = "program_span"


def read(ctx):
    ps = harness.load_module(harness.BENCH / "metrics" / "_program_spans.py",
                             "bench_metrics_program_spans").module()
    calls = [s.args for s in ps.spans_of(ctx) or []
             if s.name == "fm.dispatch"]
    rows = sum(a["rows"] for a in calls)
    if not rows:
        return None
    return 100.0 * sum(a["used"] for a in calls) / rows
