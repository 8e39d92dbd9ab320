"""Scheduler: share of the rows entering prefill that are bucket padding
(padded rows / all rows), over the window; host counter."""


def read(ctx):
    rows = sum(ctx["prefill_padded"])
    if not rows:
        return None
    return 100.0 * (rows - sum(ctx["prefill_real"])) / rows
