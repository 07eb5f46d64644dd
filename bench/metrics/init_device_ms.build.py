"""Mean device time of the q-gram init program per build
(``core/suffix_array.py`` ``_qgram_init``: the packed q-gram key sort and
the grouped re-rank), from the trace's program (XLA module) events."""

import numpy as np

SOURCE = "device_trace"


def is_init(name: str) -> bool:
    return "_qgram_init" in name


def read(ctx):
    tr = ctx.device_trace
    if tr is None:
        return None
    sel = tr.modules.select(is_init)
    if not sel.any():
        return None
    return float(np.mean(tr.modules.end[sel] - tr.modules.start[sel]) * 1e3)
