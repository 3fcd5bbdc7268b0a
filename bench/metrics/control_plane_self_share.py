"""Share of the traced window spent in the serving control plane's own
host code: the self time of the program's ``serve_step`` spans, that is
their duration less that of the ``prefill_chunk`` and ``decode_horizon``
spans inside them."""

CHILDREN = ("prefill_chunk", "decode_horizon")


def read(ctx):
    ev = [e for e in ctx.data.get("spans", ()) if e.get("ph") == "X"]
    steps = [e for e in ev if e["name"] == "serve_step"]
    if not steps or not ctx.data.get("host_window_s"):
        return None
    kids = sorted((e["ts"], e["ts"] + e["dur"]) for e in ev
                  if e["name"] in CHILDREN)
    self_us = 0.0
    for s in steps:
        a, b = s["ts"], s["ts"] + s["dur"]
        inner = sum(min(kb, b) - max(ka, a) for ka, kb in kids
                    if kb > a and ka < b)
        self_us += s["dur"] - inner
    return 100.0 * self_us / 1e6 / ctx.data["host_window_s"]
