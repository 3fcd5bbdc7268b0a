"""Mean host duration of the program's ``decode_horizon`` spans (one
compiled decode launch and its drain) in the traced window."""


def read(ctx):
    spans = [e for e in ctx.data.get("spans", ())
             if e.get("ph") == "X" and e.get("name") == "decode_horizon"]
    if not spans:
        return None
    return sum(e["dur"] for e in spans) / len(spans) / 1e3
