"""Frames whose result reached the sink inside the window, per second."""


def read(run):
    inside = sum(1 for r in run.requests
                 if r["ok"] and run.t0 <= r["done"] < run.t1)
    return inside / run.seconds
