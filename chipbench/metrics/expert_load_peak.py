"""The fullest held expert's load over the mean held expert's, a layer a
step on average: the ``veles_moe_peak_load_tokens`` histogram's sum over
its samples, over ``expert_tokens_mean`` (whose file says which drains the
counters are of). 1 is an even load."""
from chipbench.metrics.expert_tokens_mean import routing


def read(ctx):
    got = routing(ctx)
    if got is None:
        return None
    held, samples, peak_sum, experts = got
    return (peak_sum / samples) / (held / (samples * experts))
