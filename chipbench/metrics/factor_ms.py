"""factor_ms: the window over the factorizations completed in it (host
clock; every call ends in block_until_ready)."""


def read(run):
    d = run.driver
    return 1e3 * d.window_s / d.calls
