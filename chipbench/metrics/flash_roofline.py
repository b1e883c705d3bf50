"""The flash-attention kernels' share of their roofline in the train step.

Least time for the forward and backward calls (the larger of operations
over peak FLOP/s and bytes over peak bytes/s; operations from
chipbench/work.py, the recomputed QK^T not counted; bytes those of the
arrays each call reads and writes, as the trace names them) over the
kernels' device time in the trace. A flash call is a Pallas call
(``tpu_custom_call``) whose first operand is the cell's queries,
(minibatch x heads, T, head size); one with three operands (q, k, v) is a
forward call, one with more a backward call, charged the backward's
operations by its share of the three gradients. Any other Pallas call is
left out. A step that takes attention another way has no such call, and the
reader returns nothing."""


def read(ctx):
    trace = ctx["report"].get("trace")
    if not trace or ctx["peaks"] is None:
        return None
    work, wl = ctx["work"], ctx["wl"]
    s = work.dims(ctx["cfg"])
    queries = "[%d,%d,%d]" % (wl["minibatch"] * s["h"], wl["seq_len"],
                              s["hd"])
    per_position = ",%d,%d]" % (wl["seq_len"], s["hd"])
    least = seconds = 0.0
    for k in trace["kernels"].values():
        if not k["operands"] or not k["operands"][0].endswith(queries):
            continue
        backward = len(k["operands"]) > 3
        flops = work.flash_call_flops(
            wl["minibatch"] * s["h"], wl["seq_len"], s["hd"], causal=True,
            window=s["window"], backward=backward)
        if backward:
            # the backward products are shared among the calls of a layer
            # by the gradients each returns (dq, dk, dv), fused or not
            flops *= sum(1 for r in k["results"]
                         if r.endswith(per_position)) / 3.0
        nbytes = work.hlo_bytes(k["operands"] + k["results"])
        least += k["count"] * work.roofline_seconds(flops, nbytes,
                                                    ctx["peaks"])[0]
        seconds += k["seconds"]
    return 100.0 * least / seconds if seconds > 0 else None
