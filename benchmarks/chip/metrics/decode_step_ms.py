"""Runtime step: device time of the decode program per decode step, in
the traced part of the window (device trace)."""


def read(ctx):
    tc = ctx.get("traced_calls")
    if not tc or not tc["decode_steps"]:
        return None
    return 1e3 * tc["decode_s"] / tc["decode_steps"]
