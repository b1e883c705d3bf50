"""The serving tick from inside, over the traced slice: one reader for the
family ``tick_ms.<phase>``. The program times each phase of a tick with a
span (serving/engine.py ``_tick``) that adds its duration to an unlabelled
histogram ``veles_serving_tick_<phase>_seconds``; the slice's difference of
its ``_sum`` x 1000 over the slice's decode dispatches is the phase's
milliseconds a tick. ``tick_ms.unaccounted`` is what no span covers: the
slice's seconds over its decode dispatches, less every
``veles_serving_tick_*_seconds_sum`` that the slice holds and the loop's
idle wait (``veles_serving_loop_wait_seconds_sum``), so a phase that a
later PR adds needs no edit here. A program without the spans (or a slice
without decode dispatches) gives nothing."""

TICK = "veles_serving_tick_"
SUM = "_seconds_sum"
LOOP_WAIT = "veles_serving_loop_wait_seconds_sum"
DISPATCHES = "veles_serving_decode_dispatches_total"


def read(ctx):
    piece = ctx["report"].get("slice")
    counters = (piece or {}).get("counters") or {}
    steps = counters.get(DISPATCHES, 0)
    phases = {k[len(TICK):-len(SUM)]: v for k, v in counters.items()
              if k.startswith(TICK) and k.endswith(SUM)}
    if not steps or not phases:
        return None
    phase = ctx["metric"].partition(".")[2]
    if phase == "unaccounted":
        seconds = (piece["window_s"] - sum(phases.values())
                   - counters.get(LOOP_WAIT, 0.0))
    elif phase in phases:
        seconds = phases[phase]
    else:
        return None
    return 1000.0 * seconds / steps
