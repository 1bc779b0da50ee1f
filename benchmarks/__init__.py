"""The benchmark: the yardstick every performance PR is held to.

``run.py`` is the one command (``BENCHMARK.json`` names it); everything
that belongs to one configuration, one traffic mix, one serving path or
one metric is a file of its own, found by the name the manifest gives:

- ``configs/<config>.json``      sizes and element properties, as run
- ``traffic/<mix>.json``         parameters of one traffic mix
- ``drivers/<path>.py``          one serving path (``open`` a server,
                                 measure a ``window``, ``check``, ``close``)
- ``e2e_metrics/<name>.py``      ``read(run) -> float | None``
- ``layer_metrics/<name>.py``    ``read(run) -> float | None``
- ``cost/<family>.py``           operations and bytes from shapes
- ``reference/<family>.py``      plain float32 forward, independent of
                                 the program's model code (both found by
                                 the ``family`` a configuration's file
                                 names)
- ``peaks.json``                 the chip's published peaks

A later PR adds a cell by adding files and manifest entries; no file
here needs an edit for it.  The program contributes only the system
under test, its counters and its kernel names.
"""
