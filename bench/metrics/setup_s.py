"""Set-up time: process start to the first arrival of the traffic, on the
host clock. Loading, weights from the seed, compiles or compile-cache
loads, and one warm call per bucket."""


def read(run):
    return run.setup_s
