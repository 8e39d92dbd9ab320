"""Device: share of the traced time in which the engine had work (a
harness ``step`` span was open) and no op ran on the device."""


def read(ctx):
    tr = ctx.get("trace")
    if not tr or not tr.get("engine_s"):
        return None
    return 100.0 * tr["engine_idle_s"] / tr["engine_s"]
