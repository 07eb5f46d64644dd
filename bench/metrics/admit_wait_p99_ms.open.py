"""p99 of (flush start - due time) over the requests answered by flushes
that started in the traced interval: how long admission and coalescing
held a request before ``FMQueryServer.flush`` took it up.  Benchmark-side
span: the harness's wrapper stamps each flush's start."""

import numpy as np

SOURCE = "program_span"


def read(ctx):
    waits = ctx.counters.get("admit_wait_ms")
    if not waits:
        return None
    return float(np.percentile(np.asarray(waits), 99))
