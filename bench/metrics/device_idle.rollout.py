"""Share of the traced window in which no op runs on the device (one minus
the union of op intervals over the window), mean over the chips."""


def read(ctx):
    d = ctx.data
    if d.get("busy_s") is None or not d.get("window_s"):
        return None
    return 100.0 * (1.0 - d["busy_s"] / d["window_s"])
