"""The served delta rule's share of its roofline in the decode step.

Least time for the state updates of every delta-rule layer over the
slice's decode steps: the larger of operations over peak FLOP/s (seven an
element of a row's state) and bytes over peak bytes/s (each live row's
state, float32, read and written once a layer a step, and its q, k, v, g
and beta read and o written), from the configuration's ``counts`` module
(``delta_step_flops``, ``delta_step_bytes``), over the device time of the
operations of ``jit_step`` under a scope whose last segment is
``delta_rule`` (the update alone: the projections are under ``delta_in``
and ``delta_out``). The rows are the program's own count
(``veles_serving_state_rows_total``: live rows x delta-rule layers, which
the step returns with its tokens), as its rise over the slice; prefill's
chunked scan is in neither the count nor the time. Counted by the
algorithm, so it cannot pass 100 %. A program without the counter or the
scope gives nothing."""
from chipbench.modules import counts_of

SCOPE = "delta_rule"
STEP_PROGRAM = "jit_step"
ROWS = "veles_serving_state_rows_total"


def read(ctx):
    r, cfg = ctx["report"], ctx["cfg"]
    trace = r.get("trace") or {}
    counters = (r.get("slice") or {}).get("counters") or {}
    counts = counts_of(cfg)
    rows = counters.get(ROWS)
    if (ctx["peaks"] is None or not trace.get("scopes") or not rows
            or not hasattr(counts, "delta_step_bytes")):
        return None
    seconds = sum(s for program, scopes in trace["scopes"].items()
                  if STEP_PROGRAM in program
                  for scope, (_, s) in scopes.items()
                  if scope.rsplit("/", 1)[-1] == SCOPE)
    if not seconds:
        return None
    least = ctx["work"].roofline_seconds(
        counts.delta_step_flops(cfg, rows),
        counts.delta_step_bytes(cfg, rows), ctx["peaks"])[0]
    return 100.0 * least / seconds
