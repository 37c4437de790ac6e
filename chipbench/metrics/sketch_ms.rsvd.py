"""sketch_ms.rsvd: device time of the operations under the scope
``rsvd.sketch`` (line 1 of Algorithm 1, the mixed-precision projection)
per ``rsvd`` call, from a trace of calls at the cell's arguments after the
window (``chipbench/scopes.py``).  Each operation counts its self time."""

from chipbench import scopes


def read(run):
    return scopes.read_scope(run, "rsvd.sketch")
