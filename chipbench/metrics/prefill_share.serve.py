"""prefill_share.serve: share of the traced window that the device spent in
the programs the host enqueued inside ``ModelStep.prefill_rows`` (the
benchmark's span around each call; each program is given to the span that
enqueued most of its executions)."""


def read(run):
    if run.trace is None:
        return None
    n, seconds = run.trace.span_device_time("prefill_rows")
    if not n:
        return None
    return 100.0 * seconds / run.trace.window_s
