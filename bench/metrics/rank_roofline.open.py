"""Roofline share of the rank gather kernel (``kernels/rank_select.py``,
the packed 4-bit layout in this cell) over the traced interval: the bytes
its calls must move (``kernels_bytes.rank_gather_bytes``, from each call's
shapes) over the HBM bandwidth of ``peaks.json``, divided by its device
time."""

import harness

SOURCE = "device_trace"
KINDS = ("rank_gather",)


def read(ctx):
    roof = harness.load_module(harness.BENCH / "metrics" / "_roofline.py",
                               "bench_metrics_roofline")
    return roof.share(ctx, KINDS)
