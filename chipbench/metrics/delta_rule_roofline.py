"""The gated delta rule's share of its roofline in the train step.

Least time for the recurrence, forward and backward, of every delta-rule
layer over the slice's tokens (the larger of its operations over peak
FLOP/s and of the bytes of q, k, v, g, beta, o and their gradients over
peak bytes/s; both from the configuration's ``counts`` module, which counts
the recurrence itself and so the least any form of it does) over the
device time of the operations under a scope whose last segment is
``delta_rule``. Read by scope and counted by the algorithm: the number is
of the same work whether XLA or a kernel under that scope does it. A
configuration whose counts know no delta rule, or a capture without that
scope, gives nothing."""
from chipbench.modules import counts_of

SCOPE = "delta_rule"


def scope_seconds(trace, name):
    """Device seconds of every program's operations under a scope whose
    last segment is ``name``; None where the capture names no scope or
    none such."""
    if not trace or not trace.get("scopes"):
        return None
    seconds = [s for rows in trace["scopes"].values()
               for scope, (_, s) in rows.items()
               if scope.rsplit("/", 1)[-1] == name]
    return sum(seconds) if seconds else None


def read(ctx):
    r, cfg = ctx["report"], ctx["cfg"]
    counts, piece = counts_of(cfg), r.get("slice")
    seconds = scope_seconds(r.get("trace"), SCOPE)
    if (ctx["peaks"] is None or not piece or not seconds
            or not hasattr(counts, "delta_rule_flops")):
        return None
    layers = counts.dims(cfg)["delta_layers"]
    least = ctx["work"].roofline_seconds(
        layers * counts.delta_rule_flops(cfg, piece["tokens"]),
        layers * counts.delta_rule_bytes(cfg, piece["tokens"]),
        ctx["peaks"])[0]
    return 100.0 * least / seconds
