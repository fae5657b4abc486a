"""Set-up on the host clock: process start to the window's start (loading, weights, compiles or cache reads, warm-up, the first requests)."""


def read(rec):
    return rec.setup_s
