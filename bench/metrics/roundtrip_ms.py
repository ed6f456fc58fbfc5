"""The window over the inverse+forward pairs completed in it: the paper's
time per transform at a fixed size."""


def read(run):
    return 1e3 * run.window_s / run.steps if run.steps else None
