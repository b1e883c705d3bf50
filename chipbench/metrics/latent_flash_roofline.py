"""Latent attention's flash kernels' share of their roofline in the train
step.

Least time of the slice's attention work (the larger of its operations
over peak FLOP/s and its bytes over peak bytes/s) over the device time of
the Pallas calls whose name starts with ``veles_flash``, as
``flash_step_ms`` finds them. The work is the configuration's ``counts``
module's, for the slice's sequences in every layer: forward QK^T at the
key width and PV at the value width, backward dV and dP at the value
width and dQ and dK at the key width, over the causal context; the bytes
are q, k, v, o, dO, dQ, dK and dV at their published widths, each once.
Found by name and counted by the algorithm, so the reading is of the same
work whatever implements it: lanes padded past a width only lengthen the
time. A configuration whose counts know no such attention, or a capture
without the calls, gives nothing."""
from chipbench.modules import counts_of

KERNELS = "veles_flash"


def read(ctx):
    r, cfg, wl = ctx["report"], ctx["cfg"], ctx["wl"]
    trace, piece, counts = r.get("trace"), r.get("slice"), counts_of(cfg)
    if (ctx["peaks"] is None or not trace or not piece
            or not piece.get("steps") or not hasattr(counts, "flash_flops")):
        return None
    seconds = sum(k["seconds"] for name, k in trace["kernels"].items()
                  if name.startswith(KERNELS))
    if not seconds:
        return None
    sequences = piece["steps"] * wl["minibatch"]
    layers = counts.dims(cfg)["layers"]
    least = ctx["work"].roofline_seconds(
        layers * counts.flash_flops(cfg, wl["seq_len"], sequences),
        layers * counts.flash_bytes(cfg, wl["seq_len"], sequences),
        ctx["peaks"])[0]
    return 100.0 * least / seconds
