"""The whole step's share of the chip's peak over the traced slice: model
operations (forward and backward for training; for serving those of every
token that clients received in the slice, a request's first standing for
its prefill) over slice x chips x peak bf16 FLOP/s. Recomputation is not
counted. One reader for the family ``mfu.<kind of cell>``."""


def read(ctx):
    r, cfg, wl, work = ctx["report"], ctx["cfg"], ctx["wl"], ctx["work"]
    piece = r.get("slice")
    if ctx["peaks"] is None or not piece:
        return None
    if wl["kind"] == "train":
        flops = (work.train_flops_per_token(cfg, wl["seq_len"])
                 * piece["tokens"])
    else:
        flops = sum(work.token_flops(cfg, q["prompt_len"], i)
                    for q in r["requests"]
                    for i, stamp in enumerate(q["stamps"])
                    if piece["from_s"] <= stamp <= piece["to_s"])
    return 100.0 * flops / (piece["window_s"] * ctx["chips"]
                            * ctx["peaks"]["bf16_flops_per_s"])
