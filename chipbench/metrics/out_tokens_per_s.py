"""All output tokens that clients received inside the window over the
window's seconds (client's side, every request of the window)."""


def read(ctx):
    r = ctx["report"]
    return sum(q["tokens_in_window"] for q in r["requests"]) / r["window_s"]
