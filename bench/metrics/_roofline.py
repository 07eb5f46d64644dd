"""Shared arithmetic of the ``*_roofline`` readers: the bytes the chosen
kernels' calls must move, over the chip's HBM bandwidth, divided by their
device time in the trace."""

import harness
import kernels_bytes as kb


def share(ctx, kinds) -> float | None:
    tr = ctx.device_trace
    if tr is None or not ctx.peaks:
        return None
    reduce = harness.load_module(harness.BENCH / "trace" / "reduce.py",
                                 "bench_trace_reduce")
    calls = {}
    for i, name in enumerate(tr.ops.names):
        if "tpu_custom_call" in name:
            hit = kb.kernel_call(*reduce.call_shapes(name))
            if hit is not None and hit[0] in kinds:
                calls[i] = hit[1]
    if not calls:
        return None
    seconds = need = 0.0
    for i, nbytes in calls.items():
        sel = tr.ops.name_id == i
        seconds += float((tr.ops.end[sel] - tr.ops.start[sel]).sum())
        need += nbytes * int(sel.sum())
    if seconds <= 0:
        return None
    return 100.0 * need / ctx.peaks["hbm_bytes_per_s"] / seconds
