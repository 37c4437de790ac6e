"""core_ms.hosvd: device time of the operations under the scope
``hosvd.core`` (line 5 of Algorithm 2, the core G = A x_k Q_k^T) per
``rp_hosvd`` call, from a trace of calls at the cell's arguments after the
window (``chipbench/scopes.py``).  Each operation counts its self time."""

from chipbench import scopes


def read(run):
    return scopes.read_scope(run, "hosvd.core")
