"""A request's waits before its first token is on the wire, from inside,
over the traced slice: one reader for the family ``request_ms.<wait>``.
The program observes each wait where it ends, once a request
(serving/scheduler.py ``Ticket``), into an unlabelled histogram:

- ``queue``: arrival to admission (``veles_serving_queue_wait_seconds``,
  at ``mark_admitted``);
- ``prefill``: admission to the host's read of the first token
  (``veles_serving_prefill_wait_seconds``): the step in flight that the
  prefill queues behind, the prefills ahead of it in the same tick, its
  own program;
- ``ttft``: arrival to that read (``veles_serving_ttft_seconds``), the
  two above together;
- ``first_write``: that read to the end of the handler thread's write of
  the first SSE event that carries a token
  (``veles_serving_first_write_seconds``, restful_api ``_stream_reply``).

Each is the slice's difference of the ``_sum`` x 1000 over that of the
``_count``: the mean of the waits that ENDED in the slice, in
milliseconds (the slice's ``counters`` keep no bucket, so no percentile).
A program without a series gives nothing for its wait; no sample in the
slice, nothing."""

SERIES = {"queue": "veles_serving_queue_wait_seconds",
          "prefill": "veles_serving_prefill_wait_seconds",
          "ttft": "veles_serving_ttft_seconds",
          "first_write": "veles_serving_first_write_seconds"}


def read(ctx):
    counters = (ctx["report"].get("slice") or {}).get("counters") or {}
    series = SERIES.get(ctx["metric"].partition(".")[2])
    if series is None:
        return None
    samples = counters.get(series + "_count", 0)
    seconds = counters.get(series + "_sum")
    if not samples or seconds is None:
        return None
    return 1000.0 * seconds / samples
