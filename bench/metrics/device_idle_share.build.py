"""Share of the traced interval in which no operation ran on the chip:
1 - (union of device operation intervals) / (traced interval)."""

SOURCE = "device_trace"


def read(ctx):
    tr = ctx.device_trace
    if tr is None or tr.busy_s <= 0:
        return None
    return 100.0 * tr.idle_share
