"""project_roofline.hosvd: the projections of one ``rp_hosvd`` call
(``project_ms.hosvd``) against their roofline: for each mode i,
Y_i = A_(i) Omega_i is ``counts.sketch(I_i, prod_{j != i} I_j, J_i)``, A read
once and Y written once (Omega's bytes and the unfolding's transposes are
not counted).  The modes run one after another, so the least time is the
sum of each mode's."""

import math

from chipbench import counts, scopes


def read(run):
    ms = scopes.read_scope(run, "hosvd.project")
    if ms is None:
        return None
    dims, ranks = run.config["dims"], run.config["ranks"]
    total = math.prod(dims)
    return sum(counts.roofline_share(*counts.sketch(n, total // n, r),
                                     1e-3 * ms, run.peaks)
               for n, r in zip(dims, ranks))
