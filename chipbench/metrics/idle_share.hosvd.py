"""idle_share.hosvd: share of the traced window in which no operation ran on
the device (1 - union of device-op intervals / window)."""


def read(run):
    if run.trace is None:
        return None
    return 100.0 * run.trace.idle_share
