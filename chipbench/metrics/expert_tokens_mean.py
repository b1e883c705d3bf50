"""Held assignments an expert a layer a step, from the program's counters:
the rise of ``veles_moe_assignments_held_total`` over the samples of the
``veles_moe_peak_load_tokens`` histogram (one a layer a step) and the
experts held. The program counts inside the step and publishes with each
epoch's metric drain, so the rise is of whole epochs: those drained in the
traced slice, or in the whole window where the slice saw no drain.

A check on the traffic, not a number to raise: 8,192 tokens x 10 / 512
experts is 160 at the seed's weights, and a reading far above it says
that training has pulled the routing onto the experts held (a share's
loss is the partial layer's), which makes the step longer, not better.
``BENCHMARK.json`` must give every metric a direction and has no
neutral one; PERF.md section 3 says the same. Also
home of what the other readers of these counters share. A program without
the counters gives nothing."""
from chipbench.modules import counts_of

HELD = "veles_moe_assignments_held_total"
PEAK = "veles_moe_peak_load_tokens"


def routing(ctx):
    """(held assignments, layer-steps they were counted over, sum of the
    fullest held expert's loads, experts held), or None."""
    r = ctx["report"]
    dims = counts_of(ctx["cfg"]).dims(ctx["cfg"])
    for counters in ((r.get("slice") or {}).get("counters"),
                     r.get("counters")):
        samples = (counters or {}).get(PEAK + "_count")
        if samples and counters.get(HELD) and dims.get("experts_held"):
            return (counters[HELD], samples, counters[PEAK + "_sum"],
                    dims["experts_held"])
    return None


def read(ctx):
    got = routing(ctx)
    if got is None:
        return None
    held, samples, _, experts = got
    return held / (samples * experts)
