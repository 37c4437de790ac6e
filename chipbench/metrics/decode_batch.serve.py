"""decode_batch.serve: slots in each batched decode step of the window
(the mask the scheduler hands ``ModelStep.decode_logits``), averaged over
the window's decode steps.  Tokens a step emits grow with it."""


def read(run):
    steps = run.driver.decode_batch
    if not steps:
        return None
    return sum(steps) / len(steps)
