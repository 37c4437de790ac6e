"""decode_step_ms.serve: device time of the programs the host enqueued
inside one batched decode step (``ModelStep.decode_logits`` to the return of
``ModelStep.sample``), over the decode steps in the traced window."""


def read(run):
    if run.trace is None:
        return None
    steps = run.trace.spans.get("decode_step", [])
    w0, w1 = run.trace.window_ns
    steps = [s for s in steps if w0 <= s[0] <= w1]
    n, seconds = run.trace.span_device_time("decode_step")
    if not n or not steps:
        return None
    return 1e3 * seconds / len(steps)
