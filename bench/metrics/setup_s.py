"""Set-up: process start to window start. Loading, weights, compiling or
reading the compile cache, warming every shape, and the traffic's
lead-in."""


def read(run):
    return run.setup_s
