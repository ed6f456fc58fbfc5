"""Set-up: process start to the first timed step (plan builds, compiles,
warm-up), on the host's clock."""


def read(run):
    return run.setup_s
