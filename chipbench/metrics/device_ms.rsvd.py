"""device_ms.rsvd: device busy time in the traced part of the window over
the factorizations (``rsvd`` spans) begun in it."""


def read(run):
    if run.trace is None:
        return None
    w0, w1 = run.trace.window_ns
    calls = [s for s in run.trace.spans.get("rsvd", []) if w0 <= s[0] <= w1]
    if not calls:
        return None
    return 1e3 * run.trace.busy_s / len(calls)
