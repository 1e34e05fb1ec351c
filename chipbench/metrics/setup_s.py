"""Set-up: process start to window start, compiles included (s)."""


def read(r):
    return r.setup_s
