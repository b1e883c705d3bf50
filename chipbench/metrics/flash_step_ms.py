"""Device milliseconds a train step spends in the flash-attention kernels:
the seconds of the trace's Pallas calls whose name starts with
``veles_flash`` (ops/flash_attention.py names its three ``pallas_call``s
``veles_flash_fwd``, ``veles_flash_bwd_dkv``, ``veles_flash_bwd_dq``, and
the device operation takes that name) x 1000 over the slice's steps. Found
by name, where ``flash_roofline`` knows the same calls by their operands'
shape. A program whose kernels have no such name gives nothing."""


def read(ctx):
    r = ctx["report"]
    trace, piece = r.get("trace"), r.get("slice")
    if not trace or not piece or not piece.get("steps"):
        return None
    seconds = [k["seconds"] for name, k in trace["kernels"].items()
               if name.startswith("veles_flash")]
    if not seconds:
        return None
    return 1000.0 * sum(seconds) / piece["steps"]
