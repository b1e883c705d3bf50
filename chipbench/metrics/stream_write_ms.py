"""Milliseconds a tick that the request plane spends serialising and
writing SSE events, over the traced slice: the slice's difference of
``veles_serving_stream_write_seconds_sum`` (a span around each event's
write in restful_api ``_stream_reply``) x 1000 over its decode dispatches.
All handler threads together, so it may exceed the tick. A program without
the span gives nothing."""


def read(ctx):
    piece = ctx["report"].get("slice")
    counters = (piece or {}).get("counters") or {}
    steps = counters.get("veles_serving_decode_dispatches_total", 0)
    seconds = counters.get("veles_serving_stream_write_seconds_sum")
    if not steps or seconds is None:
        return None
    return 1000.0 * seconds / steps
