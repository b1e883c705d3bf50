"""The held experts' products' share of their roofline in the served decode
step.

Least time for the routed experts of every layer over the slice's decode
steps: the larger of operations over peak FLOP/s (the held assignments of
the live rows x the three matrices of an expert, forward only) and bytes
over peak bytes/s (the matrices of the held experts that got a row, read
once a layer a step at 2 bytes, and each assignment's row read and its
result written), from the configuration's ``counts`` module, over the
device time of the operations of ``jit_step`` under a scope whose last
segment is ``experts``. Assignments and touched experts are the program's
own counts (``veles_moe_assignments_held_total``,
``veles_moe_experts_touched_total``: the step returns them with its tokens),
as their rise over the slice; prefill's routing is in neither the counts
nor the time. Read by scope and counted by the algorithm, so a kernel
under the same scope reads on the same scale and cannot pass 100 %. A
program without the counters or the scope gives nothing."""
from chipbench.modules import counts_of

SCOPE = "experts"
STEP_PROGRAM = "jit_step"
HELD = "veles_moe_assignments_held_total"
TOUCHED = "veles_moe_experts_touched_total"


def read(ctx):
    r, cfg = ctx["report"], ctx["cfg"]
    trace = r.get("trace") or {}
    counters = (r.get("slice") or {}).get("counters") or {}
    counts = counts_of(cfg)
    held, touched = counters.get(HELD), counters.get(TOUCHED)
    if (ctx["peaks"] is None or not trace.get("scopes") or not held
            or not touched or not hasattr(counts, "expert_bytes")):
        return None
    seconds = sum(s for program, rows in trace["scopes"].items()
                  if STEP_PROGRAM in program
                  for scope, (_, s) in rows.items()
                  if scope.rsplit("/", 1)[-1] == SCOPE)
    if not seconds:
        return None
    least = ctx["work"].roofline_seconds(
        counts.expert_flops(cfg, held),
        counts.expert_bytes(cfg, held, touched), ctx["peaks"])[0]
    return 100.0 * least / seconds
