"""Roofline share of the radix sort kernels (``kernels/radix_hist.py``
histogram pass and the position pass of ``kernels/radix_sort.py``) over
the builds of the traced window: the bytes their calls must move
(``kernels_bytes``, from each call's shapes) over the HBM bandwidth of
``peaks.json``, divided by their device time.  The XLA scatter that
applies each pass is not a kernel of the index and is not counted."""

import harness

SOURCE = "device_trace"
KINDS = ("radix_hist", "radix_pos")


def read(ctx):
    roof = harness.load_module(harness.BENCH / "metrics" / "_roofline.py",
                               "bench_metrics_roofline")
    return roof.share(ctx, KINDS)
