"""Set-up: process start until the window opens (import, build,
traffic, warm-up and any compilation), host clock."""


def read(run):
    return run.setup_s
