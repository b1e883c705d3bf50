"""Device time of the decode program's events in the trace over their
count (the engine's one fixed-shape step, ``jit_step``)."""
from chipbench import reduce


def read(ctx):
    trace = ctx["report"].get("trace")
    if not trace:
        return None
    count, secs = reduce.matching(trace["modules"], ("jit_step",))
    return 1000.0 * secs / count if count else None
