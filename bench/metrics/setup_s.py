"""Set-up: process start to window start, compilation included (host clock)."""


def read(run):
    return run.setup_s
