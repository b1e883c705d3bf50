"""The comparison that decides ``correct``: numbers, each with its limit.

Pure Python over host numbers, so the tests drive it without a device.
The limits themselves are data: each cell's file gives them under
``limits`` with the readings they were set from in ``PERF.md``.
"""

import statistics


def _flat(tree):
    return {"%s.%s" % (u, k): v for u, leaves in tree.items()
            for k, v in leaves.items()}


def norm_gap(program, reference, skip=()):
    """Worst leaf's gap between the program's norm and the reference's,
    measured against the reference's norm of that leaf or of the median
    leaf, whichever is larger. Returns (gap, leaf)."""
    prog, ref = _flat(program), _flat(reference)
    if set(prog) != set(ref):
        raise ValueError("leaves differ: %s" % sorted(set(prog) ^ set(ref)))
    median = statistics.median(ref.values())
    worst, where = 0.0, None
    for leaf, r in ref.items():
        if leaf in skip:
            continue
        gap = abs(prog[leaf] - r) / max(r, median, 1e-30)
        if not gap <= worst:        # a NaN is the worst there is
            worst, where = gap, leaf
    return worst, where


def still_leaves(reference_grad):
    """Leaves whose gradient is nought to rounding in the reference (under
    a thousandth of the median leaf's): Adam moves them by round-off
    alone, so their change is not compared. By rule, not by name."""
    ref = _flat(reference_grad)
    median = statistics.median(ref.values())
    return sorted(leaf for leaf, g in ref.items() if g < 1e-3 * median)


def compare_train(program, reference, limits):
    """``program`` and ``reference``: {"loss": [l1, l2, l3], "grad1":
    {unit: {leaf: norm}}, "delta": {unit: {leaf: norm}}}. Returns
    (correct, {name: {"value", "limit", ...}})."""
    loss_gaps = [abs(p - r) / abs(r)
                 for p, r in zip(program["loss"], reference["loss"])]
    loss_gap = (float("nan") if any(g != g for g in loss_gaps)
                else max(loss_gaps))
    grad_gap, grad_leaf = norm_gap(program["grad1"], reference["grad1"])
    skipped = still_leaves(reference["grad1"])
    delta_gap, delta_leaf = norm_gap(program["delta"], reference["delta"],
                                     skip=skipped)
    numbers = {
        "loss_gap": {"value": loss_gap, "limit": limits["loss_gap"]},
        "grad_norm_gap": {"value": grad_gap, "limit": limits["grad_norm_gap"],
                          "leaf": grad_leaf},
        "delta_norm_gap": {"value": delta_gap,
                           "limit": limits["delta_norm_gap"],
                           "leaf": delta_leaf, "still_leaves": len(skipped)},
    }
    return verdict(numbers), numbers


def verdict(numbers):
    """True when every number is within its limit; a NaN is not."""
    return all(n["value"] <= n["limit"] for n in numbers.values())


def compare_served(gaps, limits):
    """``gaps``: for every greedy served token of the sample, how far its
    reference logit lies below the reference's best."""
    numbers = {"served_logit_gap": {"value": max(gaps) if gaps else float(
        "nan"), "limit": limits["served_logit_gap"], "tokens": len(gaps)}}
    return verdict(numbers), numbers


def report_lines(numbers):
    return ["check %s = %r (limit %r)%s" % (
        name, n["value"], n["limit"],
        "".join(" %s=%s" % (k, v) for k, v in n.items()
                if k not in ("value", "limit")))
        for name, n in numbers.items()]
