"""Seconds from process start to the first measured request: imports,
building and warming the server, compiling on a first run, dialling the
clients, a closed loop's ramp."""


def read(run):
    return run.t0 - run.t_start
