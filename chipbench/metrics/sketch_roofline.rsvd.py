"""sketch_roofline.rsvd: the library's sketch (``jit_sketch``) at the
cell's shape with the cell's projection, timed alone after the window by the
device trace, against its roofline: 2mnp operations and 4mn + 4mp bytes
(``counts.sketch``; Omega's bytes are not counted)."""

from chipbench import counts


def read(run):
    if run.trace is None:
        return None
    n_calls, seconds = run.trace.module_time("jit_sketch", whole_trace=True)
    if not n_calls:
        return None
    c = run.config
    p = min(c["rank"] + c["oversample"], c["n"])
    flops, nbytes = counts.sketch(c["n"], c["n"], p)
    return counts.roofline_share(flops, nbytes, seconds / n_calls, run.peaks)
