"""Jit exec: XLA executables built or loaded inside the window (JAX's own
``backend_compile`` events, not the engine's dictionary misses).  Warm-up
is complete only when this reads 0; a shape left out of warm-up makes
set-up shorter and stalls the window instead."""


def read(run):
    return float(len(run.counters["compiles"]))
