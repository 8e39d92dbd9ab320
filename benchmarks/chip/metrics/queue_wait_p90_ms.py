"""Scheduler: 90th percentile of the wait from a request's due time to
the start of its prefill, over the window; harness span around the
engine's prefill."""
import loadgen


def read(ctx):
    w = ctx["queue_wait_s"]
    return loadgen.percentile(w, 90) * 1e3 if w else None
