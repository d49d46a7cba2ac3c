"""Set-up: process start to the window's first request (host clock)."""


def read(run):
    return run.setup_s
