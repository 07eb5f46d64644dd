"""Share of the traced interval the flush worker spent inside
``FMQueryServer.flush`` (grouping, padding, dispatch, waiting for the
device, host copies), from the benchmark's span around each flush,
clipped to the interval."""

SOURCE = "program_span"


def read(ctx):
    span = ctx.counters.get("traced_interval_s")
    if not span or "flush_seconds" not in ctx.counters:
        return None
    return 100.0 * ctx.counters["flush_seconds"] / span
