"""qr_ms.rsvd: device time of the operations under the scope ``rsvd.qr``
(line 2 of Algorithm 1, the thin QR of the sketch) per ``rsvd`` call, from
a trace of calls at the cell's arguments after the window
(``chipbench/scopes.py``).  Each operation counts its self time."""

from chipbench import scopes


def read(run):
    return scopes.read_scope(run, "rsvd.qr")
