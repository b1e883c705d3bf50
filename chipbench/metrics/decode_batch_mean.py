"""Tokens a decode step emitted, on average, over the traced slice: the
tokens clients received in it, each request's first left out (the prefill
gives it), over the slice's difference of
veles_serving_decode_dispatches_total."""


def read(ctx):
    r = ctx["report"]
    piece = r.get("slice")
    steps = piece and piece["counters"].get(
        "veles_serving_decode_dispatches_total", 0)
    if not steps:
        return None
    decoded = sum(1 for q in r["requests"] for stamp in q["stamps"][1:]
                  if piece["from_s"] <= stamp <= piece["to_s"])
    return decoded / steps
