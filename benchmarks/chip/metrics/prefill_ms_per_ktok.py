"""Runtime step: device time of the prefill programs per thousand real
prompt tokens, in the traced part of the window (device trace)."""


def read(ctx):
    tc = ctx.get("traced_calls")
    if not tc or not tc["prefill_rows"] or not sum(tc["prefill_real"]):
        return None
    return 1e3 * tc["prefill_s"] * 1e3 / sum(tc["prefill_real"])
