"""What the chip stood unfed for, by the sync that emptied it, over the
traced slice: one reader for the family ``unfed_ms.<cause>``. The serving
engine knows when the device has nothing queued: when a blocking read has
just returned on the newest program it dispatched (serving/engine.py
``_emptied``). From there until its next call of a compiled program has
returned it adds the seconds to an unlabelled histogram a cause,
``veles_serving_unfed_<cause>_seconds``: ``first_token`` after a prefill's
first token was read (the rest of the admission, further admissions'
preparation and the step's ``prepare`` and ``dispatch`` in series with an
idle chip), ``drain`` after a drained step's or a speculative or beam
round's tokens were read. The slice's difference of the ``_sum`` x 1000
over the slice's decode dispatches is the cause's milliseconds a tick.

The program renders a histogram from its first sample on, so a cause that
never came (``drain`` under a saturated plain pool) has no series: where
the slice holds the other cause's, that reads nought, not nothing. A
program without the account (or a slice without decode dispatches) gives
nothing. ``veles_serving_unfed_late_reads_total`` in the same ``counters``
says how many of the intervals began with a read that found its result
ready, and are lower bounds."""

CAUSES = ("first_token", "drain")
SERIES = "veles_serving_unfed_%s_seconds_sum"
DISPATCHES = "veles_serving_decode_dispatches_total"


def read(ctx):
    counters = (ctx["report"].get("slice") or {}).get("counters") or {}
    steps = counters.get(DISPATCHES, 0)
    cause = ctx["metric"].partition(".")[2]
    if not steps or cause not in CAUSES \
            or not any(SERIES % c in counters for c in CAUSES):
        return None
    return 1000.0 * counters.get(SERIES % cause, 0.0) / steps
