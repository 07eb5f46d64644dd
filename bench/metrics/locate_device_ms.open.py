"""Mean device time of one dispatch of the locate program
(``core/fm_index.py`` ``locate``: backward search, then the LF walk of
``_locate_walk``), from the trace's program (XLA module) events."""

import numpy as np

SOURCE = "device_trace"


def is_locate(name: str) -> bool:
    return name.startswith("jit_locate") or name == "locate"


def read(ctx):
    tr = ctx.device_trace
    if tr is None:
        return None
    sel = tr.modules.select(is_locate)
    if not sel.any():
        return None
    return float(np.mean(tr.modules.end[sel] - tr.modules.start[sel]) * 1e3)
