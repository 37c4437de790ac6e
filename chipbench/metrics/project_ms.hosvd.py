"""project_ms.hosvd: device time of the operations under the scope
``hosvd.project`` (line 2 of Algorithm 2 for every mode: the unfolding,
Omega and the mixed-precision projection) per ``rp_hosvd`` call, from a
trace of calls at the cell's arguments after the window
(``chipbench/scopes.py``).  Each operation counts its self time."""

from chipbench import scopes


def read(run):
    return scopes.read_scope(run, "hosvd.project")
