"""Device: share of the traced slice in which no operation ran on the
chip (1 - union of the device's operation intervals over the slice), in
percent."""


def read(run):
    if not run.trace:
        return None
    return 100.0 * run.trace["idle_share"]
