"""The held experts' grouped products' share of their roofline in the
train step.

Least time for the routed experts of every layer over the slice's steps:
the larger of operations over peak FLOP/s (held assignments x the three
matrices of an expert, forward and backward) and bytes over peak bytes/s
(the held matrices read forward and backward and their gradient written,
and each assignment's rows), from the configuration's ``counts`` module,
over the device time of the operations under a scope whose last segment is
``experts``. The held assignments a layer a step are the program's own
count (``expert_tokens_mean``'s file says of which drains), times the
slice's steps and layers. Read by scope and counted by the algorithm. A
program without the counters or the scope gives nothing."""
from chipbench.metrics.delta_rule_roofline import scope_seconds
from chipbench.metrics.expert_tokens_mean import routing
from chipbench.modules import counts_of

SCOPE = "experts"


def read(ctx):
    r, cfg = ctx["report"], ctx["cfg"]
    counts, piece = counts_of(cfg), r.get("slice")
    got = routing(ctx)
    seconds = scope_seconds(r.get("trace"), SCOPE)
    if (ctx["peaks"] is None or not piece or not piece.get("steps")
            or not seconds or got is None
            or not hasattr(counts, "expert_flops")):
        return None
    held, samples = got[:2]
    layer_steps = piece["steps"] * counts.dims(cfg)["layers"]
    assignments = held / samples * layer_steps
    least = ctx["work"].roofline_seconds(
        counts.expert_flops(cfg, assignments),
        counts.expert_bytes(cfg, assignments, layer_steps), ctx["peaks"])[0]
    return 100.0 * least / seconds
