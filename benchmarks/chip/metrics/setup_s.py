"""Seconds from the start of the process to the window: loading the
program, making the weights, compiling (or reading from the persistent
cache) and warming every shape of the cell."""


def read(run):
    return run.setup_s
