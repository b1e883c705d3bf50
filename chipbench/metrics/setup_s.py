"""Process start (the parent's) to the first instant of the window."""


def read(ctx):
    return ctx["report"]["setup_s"]
