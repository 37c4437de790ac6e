"""mfu.serve: the operations the model requires for every position it
processed in the window (prompt and catch-up positions through prefill,
one per live slot in each decode step; matmuls, LM head and attention over
each position's context, ``counts.model_flops``) over the window and the
chip's bf16 peak.  Work the program does beyond that (a whole-pool forward
per prompt token, idle slots in a decode step) does not count."""


def read(run):
    d = run.driver
    if not d.flops or run.peaks is None:
        return None
    return 100.0 * d.flops / (d.window_s * run.peaks["bf16_flops_per_s"])
