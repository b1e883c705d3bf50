"""Device busy time in the traced slice over the steps in it."""


def read(ctx):
    r = ctx["report"]
    if "trace" not in r or not r["slice"]["steps"]:
        return None
    return 1000.0 * r["trace"]["busy_s"] / r["slice"]["steps"]
