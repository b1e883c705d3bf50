"""1 - union of the device's operation intervals over the traced slice;
one reader for the family ``device_idle_pct.<kind of cell>``."""


def read(ctx):
    trace = ctx["report"].get("trace")
    if not trace:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
