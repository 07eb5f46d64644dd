"""Mean host time of the SA sampling per build: for each ``fm.build`` span,
the self time of the ``fm.build.sa_samples`` span inside it
(``core/fm_index.build_sa_samples``) less its ``fm.fetch`` child, the copy
of the suffix array to the host.  What is left is the numpy work that the
chip waits for."""

import numpy as np

import harness

SOURCE = "program_span"


def read(ctx):
    ps = harness.load_module(harness.BENCH / "metrics" / "_program_spans.py",
                             "bench_metrics_program_spans").module()
    per_build = ps.per_outer(ps.spans_of(ctx) or [], "fm.build.sa_samples",
                             "fm.build", lambda s: s.self_s)
    if not per_build:
        return None
    return float(np.mean(per_build) * 1e3)
