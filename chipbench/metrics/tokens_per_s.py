"""tokens_per_s: output tokens emitted inside the window over the window
(host clock)."""


def read(run):
    d = run.driver
    return len(d.token_times()) / d.window_s
