"""p99 over requests of (``fm.batch`` start - ``fm.admit`` start): how long
a request waited in the frontend's queue (``serving/frontend.py``) before
the flush worker took it up.  Requests are joined on the admission number
``seq`` that ``fm.admit`` carries and that each ``fm.batch`` covers as
``seq_lo``..``seq_hi``.  Every batch that starts in the trace counts,
except one holding a request admitted before the trace began, whose wait
the trace cannot see."""

import numpy as np

import harness

SOURCE = "program_span"


def read(ctx):
    ps = harness.load_module(harness.BENCH / "metrics" / "_program_spans.py",
                             "bench_metrics_program_spans").module()
    spans = ps.spans_of(ctx) or []
    admitted = {s.args["seq"]: s.start for s in spans
                if s.name == "fm.admit" and "seq" in s.args}
    waits = []
    for b in spans:
        if b.name != "fm.batch":
            continue
        seqs = range(b.args["seq_lo"], b.args["seq_hi"] + 1)
        if all(q in admitted for q in seqs):
            waits += [b.start - admitted[q] for q in seqs]
    if not waits:
        return None
    return float(np.percentile(np.asarray(waits), 99) * 1e3)
