"""All tokens trained in the window over its seconds over the chips; the
window is closed by ``block_until_ready`` on the parameters."""


def read(ctx):
    r = ctx["report"]
    return r["tokens"] / r["window_s"] / ctx["chips"]
