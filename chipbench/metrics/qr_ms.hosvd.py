"""qr_ms.hosvd: device time of the operations under the scope
``hosvd.factor`` (line 3 of Algorithm 2, the thin QR of each mode's sketch)
per ``rp_hosvd`` call, from a trace of calls at the cell's arguments after
the window (``chipbench/scopes.py``).  Each operation counts its self
time."""

from chipbench import scopes


def read(run):
    return scopes.read_scope(run, "hosvd.factor")
