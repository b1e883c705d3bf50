"""One general traffic generator; a mix is a data file of its parameters.

A workload file's ``traffic`` block gives the loop (``closed``: ``clients``
callers that each wait for their reply before they send the next), the
distributions of prompt and output lengths, and the share of requests that
sample their tokens. No code knows a cell by name. An open loop (arrivals
on a schedule) comes with the first cell that needs one.

Every seed gets the same set of sizes in another order: the sizes are the
quantiles of their distributions, paired by a shuffle that the mix fixes
(``pool_seed``), and ``--seed`` permutes the order of the requests and
draws the token ids. So runs with different seeds differ in what is asked
when, not in how much work there is.
"""

import random


def _quantile(dist, u):
    """The u-quantile (0 < u < 1) of a length distribution, unclipped."""
    if dist["dist"] == "uniform":
        return dist["min"] + u * (dist["max"] - dist["min"])
    raise ValueError("unknown distribution %r" % (dist["dist"],))


def lengths(dist, n):
    """``n`` whole lengths: the (i + 0.5) / n quantiles, clipped to
    [min, max]. The same for every seed."""
    return [int(min(dist["max"], max(dist["min"], round(
        _quantile(dist, (i + 0.5) / n))))) for i in range(n)]


def pool(traffic, n):
    """The mix's fixed set of ``n`` requests: (prompt_len, out_len,
    sampled). Prompt and output quantiles are paired by the mix's own
    shuffle; every ``1 / sampled_share``-th request samples its tokens."""
    rng = random.Random(traffic.get("pool_seed", 0))
    prompts = lengths(traffic["prompt_len"], n)
    outs = lengths(traffic["output_len"], n)
    rng.shuffle(prompts)
    rng.shuffle(outs)
    share = traffic.get("sampled_share", 0.0)
    sampled = [int((i + 1) * share) > int(i * share) for i in range(n)]
    rng.shuffle(sampled)
    return list(zip(prompts, outs, sampled))


def schedule(traffic, seed, vocab):
    """The requests of one run, in the order in which clients take them:
    [{"i", "prompt", "n_new", "sampled", "temperature", "seed"}],
    ``traffic["pool"]`` of them, repeated as needed."""
    if traffic["loop"] != "closed":
        raise ValueError("unknown loop %r" % (traffic["loop"],))
    rng = random.Random(int(seed))
    items = pool(traffic, traffic["pool"])
    rng.shuffle(items)
    out = []
    for i, (p_len, n_new, sampled) in enumerate(items):
        out.append({
            "i": i, "prompt": [rng.randrange(vocab) for _ in range(p_len)],
            "n_new": n_new, "sampled": sampled,
            "temperature": traffic.get("temperature", 0.8) if sampled
            else 0.0,
            "seed": rng.randrange(2 ** 31)})
    return out


def warmup_requests(traffic, buckets, vocab):
    """One short request for every prefill bucket that the mix's prompts
    can fall into, greedy and sampled: the shapes this cell uses and no
    others. Token ids are fixed: warm-up is not part of the seed's work."""
    rng = random.Random(0)
    lo, hi = traffic["prompt_len"]["min"], traffic["prompt_len"]["max"]
    out, prev = [], 0
    for b in sorted(buckets):
        if prev < hi and b >= lo:
            length = max(lo, min(b, hi))
            for sampled in ((False, True) if traffic.get("sampled_share")
                            else (False,)):
                out.append({"prompt": [rng.randrange(vocab)
                                       for _ in range(length)],
                            "n_new": 4, "sampled": sampled,
                            "temperature": 0.8 if sampled else 0.0,
                            "seed": 1})
        prev = b
    return out
